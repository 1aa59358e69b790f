"""The world of the configuration sha256-4k: the adapter that builds the
fixed-length SHA-256 preimage circuit from `message_bytes` and wires a
ProvingService to it the way the program's own entry point does
(`ProvingService.for_sha256_preimage`, which `cli.cmd_service` calls for
`--circuit sha256_preimage`), and its payload generator.  Its tie is
`reference/sha_signals.py`'s.  Named by the configuration file as
`module:function`, like `worlds_email.py`'s.
"""

from __future__ import annotations

import random
from typing import Callable, Dict


def hex_message(config: Dict, cs) -> Callable[[random.Random, int], Dict]:
    """One seeded byte per input wire of the circuit, as `msg_hex`: the
    eight callers' first requests are made and written in 6 ms on the
    chip's host, well inside the 50 ms the service lets a young burst
    settle (`BURST_SETTLE_S`); as 4,096 JSON ints a request
    (`harness/worlds.py`'s `input_bytes`) they take 21 ms on a sandbox and
    more there.  A first sweep that lists the spool inside the burst
    claims part of it, and the run scores 5-7 proofs in the time of 8
    (PERF.md §6, PR 45)."""
    n = len(cs.input_wires)

    def payload(rng: random.Random, i: int) -> Dict:
        return {"msg_hex": rng.randbytes(n).hex()}
    return payload


def preimage(config: Dict):
    from zkp2p_tpu.pipeline.service import ProvingService

    if not hasattr(ProvingService, "for_sha256_preimage"):
        # before the circuit and the key are built: a program that predates the
        # configuration fails in seconds, not after minutes of set-up
        raise SystemExit("benchmarks: this program has no ProvingService.for_sha256_preimage "
                         "(it predates the configuration sha256-4k) — nothing measured")
    from zkp2p_tpu.models.registry import build_sha256_preimage

    cs, msg_wires = build_sha256_preimage(int(config["message_bytes"]))

    def make_service(dpk, vk, **kw):
        return ProvingService.for_sha256_preimage(cs, msg_wires, dpk, vk, prover_fn=None, **kw)
    return cs, make_service
