"""The public signals an EmailVerify request must come back with, worked
out from the request alone (plain Python, nothing of the program): the
`public_tie` of the configuration email-1024-1536, beside
`public_signals.py`'s for the onramp circuit.
"""

from __future__ import annotations

from typing import Dict

MODULUS_LIMBS = 17  # RSA-2048 as k = 17 limbs of n = 121 bits: signals [0:17]
REVEAL_BYTES = 21   # TwitterResetRegex's reveal, packed seven bytes to a word: signals [17:20]


def twitter_reset(payload: Dict) -> Dict[int, int]:
    """`email.circom:15-222` with `twitter_reset_regex.circom:5` as its body
    regex: the public signals are the mail server's RSA modulus
    (`public [modulus]`, 17 limbs) and then the @handle the body names,
    zero-padded to 21 bytes and packed little-endian seven to a word.  The
    handle is the request's; the modulus needs the signing key and is left
    to the pairing, as the onramp circuit's is."""
    handle = str(payload["handle"]).encode().ljust(REVEAL_BYTES, b"\x00")
    return {MODULUS_LIMBS + k: int.from_bytes(handle[7 * k:7 * k + 7], "little")
            for k in range(REVEAL_BYTES // 7)}
