"""COPY for the benchmark's plain reference (benchmarks/README.md); the original is in zkp2p_tpu.

Optimal ate pairing on BN254 (host side).

The framework's native replacement for the EVM ``ecPairing`` precompile the
reference relies on (contracts/Verifier.sol:146-163 ``pairing(...)`` /
``pairingProd4``).  It lets us verify Groth16 proofs off-chain, exactly as
``snarkjs groth16 verify`` does in the reference pipeline
(dizkus-scripts/5_gen_proof.sh:15-22).

Approach: map the G2 point from the twist E'(Fq2) into E(Fq12) via the
untwist morphism psi(x, y) = (x * w^2, y * w^3), then run a plain affine
Miller loop with generic line functions in Fq12.  This trades speed for
obviousness — it is the *verification* path (a handful of pairings per
proof batch), not the prover hot loop.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

from .curve import G1Point, G2Point, g1_is_on_curve, g2_is_on_curve
from .bn254 import ATE_LOOP_COUNT, P, R
from .tower import Fq2, Fq6, Fq12

# w as an element of Fq12 = Fq6[w]
_W = Fq12(Fq6.zero(), Fq6.one())
_W2 = _W * _W
_W3 = _W2 * _W

E12Point = Optional[Tuple[Fq12, Fq12]]


def fq_to_fq12(a: int) -> Fq12:
    return Fq12(Fq6(Fq2(a, 0), Fq2.zero(), Fq2.zero()), Fq6.zero())


def fq2_to_fq12(a: Fq2) -> Fq12:
    return Fq12(Fq6(a, Fq2.zero(), Fq2.zero()), Fq6.zero())


def untwist(q: G2Point) -> E12Point:
    """E'(Fq2) -> E(Fq12): (x, y) -> (x w^2, y w^3)."""
    if q is None:
        return None
    return (fq2_to_fq12(q[0]) * _W2, fq2_to_fq12(q[1]) * _W3)


def _e12_neg(a: E12Point) -> E12Point:
    if a is None:
        return None
    return (a[0], Fq12.zero() - a[1])


def _e12_frobenius(a: E12Point) -> E12Point:
    if a is None:
        return None
    return (a[0].frobenius(), a[1].frobenius())


def _e12_add(a: E12Point, b: E12Point) -> E12Point:
    if a is None:
        return b
    if b is None:
        return a
    x1, y1 = a
    x2, y2 = b
    if x1 == x2:
        if y1 == y2:
            lam = (x1.square() * fq_to_fq12(3)) * (y1 * fq_to_fq12(2)).inv()
        else:
            return None
    else:
        lam = (y2 - y1) * (x2 - x1).inv()
    x3 = lam.square() - x1 - x2
    y3 = lam * (x1 - x3) - y1
    return (x3, y3)


def _line(t: E12Point, q: E12Point, px: Fq12, py: Fq12) -> Fq12:
    """Evaluate the line through t and q at the (embedded) G1 point P."""
    x1, y1 = t
    x2, y2 = q
    if x1 == x2 and y1 == y2:
        lam = (x1.square() * fq_to_fq12(3)) * (y1 * fq_to_fq12(2)).inv()
        return (py - y1) - lam * (px - x1)
    if x1 == x2:
        # vertical line
        return px - x1
    lam = (y2 - y1) * (x2 - x1).inv()
    return (py - y1) - lam * (px - x1)


def miller_loop(p: G1Point, q: G2Point) -> Fq12:
    """Miller loop of the optimal ate pairing (before final exponentiation)."""
    if p is None or q is None:
        return Fq12.one()
    px, py = fq_to_fq12(p[0]), fq_to_fq12(p[1])
    q12 = untwist(q)
    t = q12
    f = Fq12.one()
    for bit in bin(ATE_LOOP_COUNT)[3:]:
        f = f.square() * _line(t, t, px, py)
        t = _e12_add(t, t)
        if bit == "1":
            f = f * _line(t, q12, px, py)
            t = _e12_add(t, q12)
    # Frobenius correction steps of the optimal ate pairing.
    q1 = _e12_frobenius(q12)
    f = f * _line(t, q1, px, py)
    t = _e12_add(t, q1)
    q2 = _e12_neg(_e12_frobenius(_e12_frobenius(q12)))
    f = f * _line(t, q2, px, py)
    return f


def final_exponentiation(f: Fq12) -> Fq12:
    """f^((p^12 - 1) / r), split into easy part and (generic-pow) hard part."""
    # easy: f^((p^6 - 1)(p^2 + 1))
    f1 = f.conjugate() * f.inv()  # f^(p^6 - 1)
    f2 = f1.frobenius(2) * f1  # ^(p^2 + 1)
    # hard: ^((p^4 - p^2 + 1) / r)
    hard = (P**4 - P**2 + 1) // R
    return f2.pow(hard)


def pairing(p: G1Point, q: G2Point) -> Fq12:
    assert g1_is_on_curve(p), "G1 point not on curve"
    assert g2_is_on_curve(q), "G2 point not on twist"
    return final_exponentiation(miller_loop(p, q))


def pairing_product_is_one(
    pairs: Sequence[Tuple[G1Point, G2Point]],
) -> bool:
    """prod e(P_i, Q_i) == 1, sharing one final exponentiation.

    Mirror of Verifier.sol's pairingProd4 (contracts/Verifier.sol:116-145):
    the EVM precompile also checks a product of pairings against 1.
    """
    acc = Fq12.one()
    for p, q in pairs:
        acc = acc * miller_loop(p, q)
    return final_exponentiation(acc) == Fq12.one()
