"""The public signals a SHA-256 preimage request must come back with,
worked out from the request alone (plain Python and `hashlib`, nothing of
the program): the `public_tie` of the configuration sha256-4k, beside
`public_signals.py`'s for the onramp circuit and `email_signals.py`'s.
"""

from __future__ import annotations

import hashlib
from typing import Dict


def preimage_digest(payload: Dict) -> Dict[int, int]:
    """The circuit's two public signals are the SHA-256 digest of the
    request's own bytes: [0] its first 16 bytes, [1] its last 16, each a
    big-endian 128-bit integer (32 bytes do not fit one BN254 scalar).
    Both are the request's, so the tie holds every signal there is.  The
    request carries its bytes as `msg_hex` (two hex digits a byte) or as
    `msg` (one int a byte)."""
    msg = bytes.fromhex(payload["msg_hex"]) if "msg_hex" in payload else bytes(payload["msg"])
    digest = hashlib.sha256(msg).digest()
    return {0: int.from_bytes(digest[:16], "big"), 1: int.from_bytes(digest[16:], "big")}
