"""Groth16 verification, e(A,B) == e(alpha,beta) e(vk_x,gamma) e(C,delta),
from snarkjs-format JSON (what the service writes into the spool).

Everything here is plain Python integers over the copied BN254 tower; the
verifying key arrives as integers too (`vk_to_ints` in the harness strips
the program's types), so nothing the program computes is trusted except
the key itself, which every proof of a run is checked under.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from .bn254 import R
from .curve import g1_add, g1_is_on_curve, g1_mul, g1_neg, g2_is_on_curve, g2_mul
from .pairing import pairing_product_is_one
from .tower import Fq2


def g1_from_ints(v: Optional[Sequence[int]]):
    return None if v is None else (int(v[0]), int(v[1]))


def g2_from_ints(v: Optional[Sequence[int]]):
    return None if v is None else (Fq2(int(v[0]), int(v[1])), Fq2(int(v[2]), int(v[3])))


def proof_from_json(d: Dict) -> Dict:
    """snarkjs proof.json -> {"a", "b", "c"} as reference points; a point
    whose projective coordinate is not 1 is malformed (None)."""
    def g1(v):
        if len(v) != 3 or str(v[2]) != "1":
            raise ValueError(f"G1 point not affine: {v}")
        return (int(v[0]), int(v[1]))

    def g2(v):
        if len(v) != 3 or [str(x) for x in v[2]] != ["1", "0"]:
            raise ValueError(f"G2 point not affine: {v}")
        return (Fq2(int(v[0][0]), int(v[0][1])), Fq2(int(v[1][0]), int(v[1][1])))

    return {"a": g1(d["pi_a"]), "b": g2(d["pi_b"]), "c": g1(d["pi_c"])}


def verify(vk: Dict, proof: Dict, public: Sequence[int]) -> bool:
    """vk: {"alpha_1": [x, y], "beta_2": [x0, x1, y0, y1], "gamma_2", "delta_2",
    "ic": [[x, y], ...]} as integers."""
    ic = [g1_from_ints(p) for p in vk["ic"]]
    if len(public) != len(ic) - 1:
        return False
    a, b, c = proof["a"], proof["b"], proof["c"]
    if not (g1_is_on_curve(a) and g1_is_on_curve(c) and g2_is_on_curve(b)):
        return False
    if b is not None and g2_mul(b, R) is not None:  # G2's twist has a cofactor
        return False
    vk_x = ic[0]
    for i, x in enumerate(public):
        vk_x = g1_add(vk_x, g1_mul(ic[i + 1], int(x) % R))
    return pairing_product_is_one([
        (g1_neg(a), b),
        (g1_from_ints(vk["alpha_1"]), g2_from_ints(vk["beta_2"])),
        (vk_x, g2_from_ints(vk["gamma_2"])),
        (c, g2_from_ints(vk["delta_2"])),
    ])


def verify_json(vk: Dict, proof_json: Dict, public_json: List) -> bool:
    """False, never an exception, on anything malformed: a worker process
    must hand back a verdict for every proof."""
    try:
        return verify(vk, proof_from_json(proof_json), [int(x) for x in public_json])
    except (ValueError, KeyError, TypeError, IndexError):
        return False
