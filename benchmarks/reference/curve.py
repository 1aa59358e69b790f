"""COPY for the benchmark's plain reference (benchmarks/README.md); the original is in zkp2p_tpu.

Host-side BN254 group arithmetic: G1 over Fq, G2 over the Fq2 twist.

Used by the trusted setup, the pairing-based verifier, serializers, and as
the oracle the vectorised TPU point kernels (zkp2p_tpu.ops) are tested
against.  The reference delegates all of this to snarkjs/rapidsnark
internals and to the EVM precompiles (contracts/Verifier.sol:42-100
ecAdd/ecMul via precompiles 6 and 7).

Points are affine tuples of ints / Fq2 (None = point at infinity); scalar
multiplication runs in Jacobian coordinates internally.
"""

from __future__ import annotations

from typing import Optional, Tuple

from .bn254 import CURVE_B, G1_GEN, G2_GEN, P
from .tower import Fq2, XI

G1Point = Optional[Tuple[int, int]]
G2Point = Optional[Tuple[Fq2, Fq2]]

# b coefficient of the D-type twist curve  y^2 = x^3 + 3/xi  over Fq2.
TWIST_B = Fq2(3, 0) * XI.inv()

G2_GENERATOR: G2Point = (Fq2(*G2_GEN[0]), Fq2(*G2_GEN[1]))
G1_GENERATOR: G1Point = G1_GEN


# ---------------------------------------------------------------- G1 (Fq)


def g1_is_on_curve(pt: G1Point) -> bool:
    if pt is None:
        return True
    x, y = pt
    return (y * y - x * x * x - CURVE_B) % P == 0


def g1_neg(pt: G1Point) -> G1Point:
    if pt is None:
        return None
    return (pt[0], (-pt[1]) % P)


def g1_add(a: G1Point, b: G1Point) -> G1Point:
    if a is None:
        return b
    if b is None:
        return a
    x1, y1 = a
    x2, y2 = b
    if x1 == x2:
        if (y1 + y2) % P == 0:
            return None
        # doubling
        lam = (3 * x1 * x1) * pow(2 * y1, P - 2, P) % P
    else:
        lam = (y2 - y1) * pow(x2 - x1, P - 2, P) % P
    x3 = (lam * lam - x1 - x2) % P
    y3 = (lam * (x1 - x3) - y1) % P
    return (x3, y3)


def g1_double(a: G1Point) -> G1Point:
    return g1_add(a, a)


def g1_mul(pt: G1Point, k: int) -> G1Point:
    """Scalar multiplication via Jacobian double-and-add."""
    if pt is None or k == 0:
        return None
    if k < 0:
        return g1_mul(g1_neg(pt), -k)
    # Jacobian (X, Y, Z); affine = (X/Z^2, Y/Z^3)
    X, Y, Z = pt[0], pt[1], 1
    RX, RY, RZ = 0, 1, 0  # infinity
    bits = bin(k)[2:]
    for bit in bits:
        if RZ != 0:
            RX, RY, RZ = _jac_double(RX, RY, RZ)
        if bit == "1":
            if RZ == 0:
                RX, RY, RZ = X, Y, Z
            else:
                RX, RY, RZ = _jac_add(RX, RY, RZ, X, Y, Z)
    if RZ == 0:
        return None
    zinv = pow(RZ, P - 2, P)
    z2 = zinv * zinv % P
    return (RX * z2 % P, RY * z2 % P * zinv % P)


def _jac_double(X1, Y1, Z1):
    A = X1 * X1 % P
    B = Y1 * Y1 % P
    C = B * B % P
    D = 2 * ((X1 + B) * (X1 + B) - A - C) % P
    E = 3 * A % P
    F = E * E % P
    X3 = (F - 2 * D) % P
    Y3 = (E * (D - X3) - 8 * C) % P
    Z3 = 2 * Y1 * Z1 % P
    return X3, Y3, Z3


def _jac_add(X1, Y1, Z1, X2, Y2, Z2):
    Z1Z1 = Z1 * Z1 % P
    Z2Z2 = Z2 * Z2 % P
    U1 = X1 * Z2Z2 % P
    U2 = X2 * Z1Z1 % P
    S1 = Y1 * Z2 * Z2Z2 % P
    S2 = Y2 * Z1 * Z1Z1 % P
    if U1 == U2:
        if S1 != S2:
            return 0, 1, 0
        return _jac_double(X1, Y1, Z1)
    H = (U2 - U1) % P
    I = (2 * H) * (2 * H) % P
    J = H * I % P
    rr = 2 * (S2 - S1) % P
    V = U1 * I % P
    X3 = (rr * rr - J - 2 * V) % P
    Y3 = (rr * (V - X3) - 2 * S1 * J) % P
    Z3 = ((Z1 + Z2) * (Z1 + Z2) - Z1Z1 - Z2Z2) * H % P
    return X3, Y3, Z3


def g1_msm(points, scalars) -> G1Point:
    """Reference MSM (naive); the TPU Pippenger kernel is tested against this."""
    acc: G1Point = None
    for pt, s in zip(points, scalars, strict=True):
        acc = g1_add(acc, g1_mul(pt, s))
    return acc


# ---------------------------------------------------------------- G2 (Fq2)


def g2_is_on_curve(pt: G2Point) -> bool:
    if pt is None:
        return True
    x, y = pt
    return (y.square() - x.square() * x - TWIST_B).is_zero()


def g2_neg(pt: G2Point) -> G2Point:
    if pt is None:
        return None
    return (pt[0], -pt[1])


def g2_add(a: G2Point, b: G2Point) -> G2Point:
    if a is None:
        return b
    if b is None:
        return a
    x1, y1 = a
    x2, y2 = b
    if x1 == x2:
        if (y1 + y2).is_zero():
            return None
        lam = (x1.square() * 3) * (y1 * 2).inv()
    else:
        lam = (y2 - y1) * (x2 - x1).inv()
    x3 = lam.square() - x1 - x2
    y3 = lam * (x1 - x3) - y1
    return (x3, y3)


def g2_double(a: G2Point) -> G2Point:
    return g2_add(a, a)


def g2_mul(pt: G2Point, k: int) -> G2Point:
    if pt is None or k == 0:
        return None
    if k < 0:
        return g2_mul(g2_neg(pt), -k)
    result: G2Point = None
    addend = pt
    while k:
        if k & 1:
            result = g2_add(result, addend)
        addend = g2_double(addend)
        k >>= 1
    return result


def g2_msm(points, scalars) -> G2Point:
    acc: G2Point = None
    for pt, s in zip(points, scalars, strict=True):
        acc = g2_add(acc, g2_mul(pt, s))
    return acc


# ------------------------------------------------- fixed-base scalar mul

from .bn254 import R as _R_SCALAR  # noqa: E402


def _g2_jac_add(X1, Y1, Z1, X2, Y2, Z2):
    """Jacobian add over Fq2 (mirrors _jac_add; Fq2 operators auto-reduce)."""
    Z1Z1 = Z1 * Z1
    Z2Z2 = Z2 * Z2
    U1 = X1 * Z2Z2
    U2 = X2 * Z1Z1
    S1 = Y1 * Z2 * Z2Z2
    S2 = Y2 * Z1 * Z1Z1
    if U1 == U2:
        if S1 != S2:
            return Fq2.zero(), Fq2.one(), Fq2.zero()
        return _g2_jac_double(X1, Y1, Z1)
    H = U2 - U1
    I = (H + H) * (H + H)
    J = H * I
    rr = (S2 - S1) + (S2 - S1)
    V = U1 * I
    X3 = rr * rr - J - V - V
    Y3 = rr * (V - X3) - (S1 * J + S1 * J)
    Z3 = ((Z1 + Z2) * (Z1 + Z2) - Z1Z1 - Z2Z2) * H
    return X3, Y3, Z3


def _g2_jac_double(X1, Y1, Z1):
    A = X1 * X1
    B = Y1 * Y1
    C = B * B
    t = (X1 + B) * (X1 + B) - A - C
    D = t + t
    E = A + A + A
    F = E * E
    X3 = F - D - D
    C8 = C + C
    C8 = C8 + C8
    C8 = C8 + C8
    Y3 = E * (D - X3) - C8
    YZ = Y1 * Z1
    Z3 = YZ + YZ
    return X3, Y3, Z3


class FixedBaseMul:
    """Windowed fixed-base scalar multiplication (host).

    Setup evaluates hundreds of thousands of scalar muls of the SAME base
    (the generators) — `[A_i(tau)]1` etc. for every wire.  A one-time
    8-bit-window affine table (32 windows x 255 entries) turns each mul
    into <= 31 Jacobian mixed additions with a single final inversion:
    ~15x over per-mul double-and-add."""

    WINDOW = 8

    def __init__(self, base, add, jac_add, to_affine):
        self._jac_add = jac_add
        self._to_affine = to_affine
        self.tables = []
        w_base = base
        for _ in range(256 // self.WINDOW):
            row = [None]
            cur = None
            for _d in range(1, 1 << self.WINDOW):
                cur = add(cur, w_base)
                row.append(cur)
            self.tables.append(row)
            for _ in range(self.WINDOW):
                w_base = add(w_base, w_base)

    def mul(self, k: int):
        k %= _R_SCALAR
        acc = None  # (X, Y, Z) jacobian
        w = 0
        while k:
            d = k & ((1 << self.WINDOW) - 1)
            k >>= self.WINDOW
            if d:
                x, y = self.tables[w][d]
                if acc is None:
                    acc = (x, y, self._one())
                else:
                    acc = self._jac_add(*acc, x, y, self._one())
            w += 1
        return None if acc is None else self._to_affine(acc)

    def _one(self):
        raise NotImplementedError


class _G1Fixed(FixedBaseMul):
    def __init__(self):
        super().__init__(G1_GENERATOR, g1_add, _jac_add, self._affine)

    def _one(self):
        return 1

    @staticmethod
    def _affine(acc):
        X, Y, Z = acc
        if Z == 0:
            return None
        zi = pow(Z, P - 2, P)
        z2 = zi * zi % P
        return (X * z2 % P, Y * z2 % P * zi % P)


class _G2Fixed(FixedBaseMul):
    def __init__(self):
        super().__init__(G2_GENERATOR, g2_add, _g2_jac_add, self._affine)

    def _one(self):
        return Fq2.one()

    @staticmethod
    def _affine(acc):
        X, Y, Z = acc
        if Z.is_zero():
            return None
        zi = Z.inv()
        z2 = zi * zi
        return (X * z2, Y * z2 * zi)


_g1_fixed: Optional[_G1Fixed] = None
_g2_fixed: Optional[_G2Fixed] = None


def g1_gen_mul(k: int) -> G1Point:
    """k*G1 via the shared fixed-base table (setup's hot path)."""
    global _g1_fixed
    if _g1_fixed is None:
        _g1_fixed = _G1Fixed()
    return _g1_fixed.mul(k)


def g2_gen_mul(k: int) -> G2Point:
    global _g2_fixed
    if _g2_fixed is None:
        _g2_fixed = _G2Fixed()
    return _g2_fixed.mul(k)
