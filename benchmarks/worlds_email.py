"""The world of the configuration email-1024-1536: the adapter that wires
a ProvingService to `EmailVerify(max_header_bytes, max_body_bytes, n, k)`
the way the program's own entry point does (`ProvingService.
for_email_verify`, which `cli.cmd_service` calls), and the generator of
its requests.  Named by the configuration file as `module:function`, like
`harness/worlds.py`'s; a world of its own brings its own module.
"""

from __future__ import annotations

import random
import string
from typing import Callable, Dict

HANDLE_ALPHABET = string.ascii_letters + string.digits + "_"  # twitter_reset_regex.circom:5, [0-9A-Za-z_]+
HANDLE_CHARS = (4, 15)   # a Twitter handle has 4 to 15 characters
FILLER_BYTES = (0, 4096)  # body bytes before the line the regex matches: the midstate cut moves with them


def email_verify(config: Dict):
    from zkp2p_tpu.models.email_verify import EmailVerifyParams, build_email_verify
    from zkp2p_tpu.pipeline.service import ProvingService

    if not hasattr(ProvingService, "for_email_verify"):
        # before the circuit and the key are built: a program that predates the
        # configuration fails in seconds, not after minutes of set-up
        raise SystemExit("benchmarks: this program has no ProvingService.for_email_verify "
                         "(it predates the configuration email-1024-1536) — nothing measured")
    params = EmailVerifyParams(**{k: config[k] for k in ("max_header_bytes", "max_body_bytes", "n", "k")})
    cs, lay = build_email_verify(params)

    def make_service(dpk, vk, **kw):
        return ProvingService.for_email_verify(cs, lay, params, dpk, vk, prover_fn=None, **kw)
    return cs, make_service


def twitter_reset_email(config: Dict, cs) -> Callable[[random.Random, int], Dict]:
    """The synthetic request shape of `ProvingService.for_email_verify`: the
    @handle a password-reset email was meant for, and how many body bytes
    come before that line.  `filler_bytes` in the configuration narrows the
    filler (a toy body capacity holds less than the published one)."""
    lo, hi = config.get("filler_bytes", FILLER_BYTES)

    def payload(rng: random.Random, i: int) -> Dict:
        handle = "".join(rng.choice(HANDLE_ALPHABET) for _ in range(rng.randint(*HANDLE_CHARS)))
        return {"handle": handle, "filler": rng.randint(lo, hi)}
    return payload
