"""The public signals a request must come back with, worked out from the
request alone (plain Python, nothing of the program): what ties a proof to
ITS request.  A valid proof that carries another request's signals (a
replayed witness, proofs swapped or doubled among the slots of a batch)
fails here, where the pairing alone would pass it.

Each function takes a request's payload and returns {index into the public
signals: value}; a configuration names one as `public_tie`.  A circuit with
no public signals (sha2b) has nothing to tie: its proofs say only that the
prover knows SOME satisfying input, so any valid proof answers any request,
and PERF.md says so.
"""

from __future__ import annotations

from typing import Dict

from benchmarks.reference.bn254 import R


def venmo_receipt(payload: Dict) -> Dict[int, int]:
    """Ramp.sol `_verifyAndParseOnRampProof` (contracts/Ramp.sol:253-293):
    signals [1:3] are the amount as the receipt prints it ("<dollars>."),
    21 bytes packed little-endian seven to a word; [24] is the order id and
    [25] the claim id.  [0] (a Poseidon hash of the payer's id), [4:6] (the
    nullifier, from the signature) and [7:23] (the mail server's RSA
    modulus) need the hash and the signing key and are left to the pairing."""
    amount = (str(payload["amount"]) + ".").encode().ljust(21, b"\x00")
    words = {1 + k: int.from_bytes(amount[7 * k:7 * k + 7], "little") for k in range(3)}
    return {**words, 24: int(payload["order_id"]), 25: int(payload["claim_id"])}


def differing(expected: Dict[int, int], public) -> int:
    """How many of the expected signals `public` (a list of numbers or
    decimal strings) does not carry; all of them if it cannot be read."""
    try:
        got = [int(x) for x in public]
    except (TypeError, ValueError):
        return len(expected)
    return sum(1 for i, v in expected.items() if i >= len(got) or (got[i] - v) % R)
