"""COPY for the benchmark's plain reference (benchmarks/README.md); the original is in zkp2p_tpu.

Extension-field tower Fq2 -> Fq6 -> Fq12 for BN254 pairings (host side).

Tower construction (the one contracts/Verifier.sol's precompiles assume):
    Fq2  = Fq[u]  / (u^2 + 1)
    Fq6  = Fq2[v] / (v^3 - xi),  xi = u + 9
    Fq12 = Fq6[w] / (w^2 - v)

The reference never implements this itself — it calls the EVM pairing
precompiles (contracts/Verifier.sol:15-163 ``Pairing`` library).  We need it
natively to verify our own proofs without a chain, so this module is the
framework's stand-in for ecPairing (precompile 0x08).

Pure Python ints; used for verification, tests and trusted setup only — the
prover hot path never touches Fq12.
"""

from __future__ import annotations

from .bn254 import P


class Fq2:
    """a + b*u with u^2 = -1."""

    __slots__ = ("c0", "c1")
    NON_RESIDUE = (9, 1)  # xi = 9 + u

    def __init__(self, c0: int, c1: int):
        self.c0 = c0 % P
        self.c1 = c1 % P

    @classmethod
    def zero(cls) -> "Fq2":
        return cls(0, 0)

    @classmethod
    def one(cls) -> "Fq2":
        return cls(1, 0)

    def __eq__(self, other) -> bool:
        return isinstance(other, Fq2) and self.c0 == other.c0 and self.c1 == other.c1

    def __hash__(self):
        return hash((self.c0, self.c1))

    def is_zero(self) -> bool:
        return self.c0 == 0 and self.c1 == 0

    def __add__(self, other: "Fq2") -> "Fq2":
        return Fq2(self.c0 + other.c0, self.c1 + other.c1)

    def __sub__(self, other: "Fq2") -> "Fq2":
        return Fq2(self.c0 - other.c0, self.c1 - other.c1)

    def __neg__(self) -> "Fq2":
        return Fq2(-self.c0, -self.c1)

    def __mul__(self, other):
        if isinstance(other, int):
            return Fq2(self.c0 * other, self.c1 * other)
        a0, a1, b0, b1 = self.c0, self.c1, other.c0, other.c1
        t0 = a0 * b0
        t1 = a1 * b1
        # (a0+a1)(b0+b1) - t0 - t1 = a0b1 + a1b0
        return Fq2(t0 - t1, (a0 + a1) * (b0 + b1) - t0 - t1)

    __rmul__ = __mul__

    def square(self) -> "Fq2":
        a0, a1 = self.c0, self.c1
        # (a0 + a1 u)^2 = (a0+a1)(a0-a1) + 2 a0 a1 u
        return Fq2((a0 + a1) * (a0 - a1), 2 * a0 * a1)

    def mul_by_nonresidue(self) -> "Fq2":
        """Multiply by xi = 9 + u."""
        a0, a1 = self.c0, self.c1
        return Fq2(9 * a0 - a1, a0 + 9 * a1)

    def conjugate(self) -> "Fq2":
        return Fq2(self.c0, -self.c1)

    def inv(self) -> "Fq2":
        a0, a1 = self.c0, self.c1
        norm = (a0 * a0 + a1 * a1) % P
        if norm == 0:
            raise ZeroDivisionError("inverse of zero in Fq2")
        ninv = pow(norm, P - 2, P)
        return Fq2(a0 * ninv, -a1 * ninv)

    def frobenius(self) -> "Fq2":
        """x -> x^p, which for Fq2 is conjugation."""
        return self.conjugate()

    def pow(self, e: int) -> "Fq2":
        result = Fq2.one()
        base = self
        while e:
            if e & 1:
                result = result * base
            base = base.square()
            e >>= 1
        return result

    def __repr__(self):
        return f"Fq2({self.c0}, {self.c1})"


XI = Fq2(9, 1)

# Frobenius coefficients, computed (not hardcoded) at import:
#   FROB_C1[i] = xi^((p^i - 1) / 3)   acting on Fq6 v-coefficients
#   FROB_C2[i] = xi^((2 p^i - 2) / 3)
#   FROB_W[i]  = xi^((p^i - 1) / 6)   acting on Fq12 w-coefficient
def _frob_coeffs():
    # Only the p^1 coefficients are needed: frobenius(power) iterates the
    # p^1 map, so higher-power tables would be dead weight at import time.
    c1, c2, cw = [Fq2.one()], [Fq2.one()], [Fq2.one()]
    c1.append(XI.pow((P - 1) // 3))
    c2.append(XI.pow((2 * P - 2) // 3))
    cw.append(XI.pow((P - 1) // 6))
    return c1, c2, cw


FROB_C1, FROB_C2, FROB_W = _frob_coeffs()


class Fq6:
    """c0 + c1 v + c2 v^2 with v^3 = xi."""

    __slots__ = ("c0", "c1", "c2")

    def __init__(self, c0: Fq2, c1: Fq2, c2: Fq2):
        self.c0, self.c1, self.c2 = c0, c1, c2

    @classmethod
    def zero(cls) -> "Fq6":
        return cls(Fq2.zero(), Fq2.zero(), Fq2.zero())

    @classmethod
    def one(cls) -> "Fq6":
        return cls(Fq2.one(), Fq2.zero(), Fq2.zero())

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, Fq6)
            and self.c0 == other.c0
            and self.c1 == other.c1
            and self.c2 == other.c2
        )

    def is_zero(self) -> bool:
        return self.c0.is_zero() and self.c1.is_zero() and self.c2.is_zero()

    def __add__(self, other: "Fq6") -> "Fq6":
        return Fq6(self.c0 + other.c0, self.c1 + other.c1, self.c2 + other.c2)

    def __sub__(self, other: "Fq6") -> "Fq6":
        return Fq6(self.c0 - other.c0, self.c1 - other.c1, self.c2 - other.c2)

    def __neg__(self) -> "Fq6":
        return Fq6(-self.c0, -self.c1, -self.c2)

    def __mul__(self, other: "Fq6") -> "Fq6":
        a0, a1, a2 = self.c0, self.c1, self.c2
        b0, b1, b2 = other.c0, other.c1, other.c2
        t0, t1, t2 = a0 * b0, a1 * b1, a2 * b2
        c0 = ((a1 + a2) * (b1 + b2) - t1 - t2).mul_by_nonresidue() + t0
        c1 = (a0 + a1) * (b0 + b1) - t0 - t1 + t2.mul_by_nonresidue()
        c2 = (a0 + a2) * (b0 + b2) - t0 - t2 + t1
        return Fq6(c0, c1, c2)

    def mul_fq2(self, s: Fq2) -> "Fq6":
        return Fq6(self.c0 * s, self.c1 * s, self.c2 * s)

    def square(self) -> "Fq6":
        return self * self

    def mul_by_v(self) -> "Fq6":
        """Multiply by v:  (c0,c1,c2) -> (xi*c2, c0, c1)."""
        return Fq6(self.c2.mul_by_nonresidue(), self.c0, self.c1)

    def inv(self) -> "Fq6":
        a0, a1, a2 = self.c0, self.c1, self.c2
        t0 = a0.square() - (a1 * a2).mul_by_nonresidue()
        t1 = a2.square().mul_by_nonresidue() - a0 * a1
        t2 = a1.square() - a0 * a2
        denom = a0 * t0 + (a2 * t1).mul_by_nonresidue() + (a1 * t2).mul_by_nonresidue()
        dinv = denom.inv()
        return Fq6(t0 * dinv, t1 * dinv, t2 * dinv)

    def frobenius(self, power: int = 1) -> "Fq6":
        c0, c1, c2 = self.c0, self.c1, self.c2
        for _ in range(power):
            c0, c1, c2 = (
                c0.frobenius(),
                c1.frobenius() * FROB_C1[1],
                c2.frobenius() * FROB_C2[1],
            )
        return Fq6(c0, c1, c2)

    def __repr__(self):
        return f"Fq6({self.c0}, {self.c1}, {self.c2})"


class Fq12:
    """c0 + c1 w with w^2 = v."""

    __slots__ = ("c0", "c1")

    def __init__(self, c0: Fq6, c1: Fq6):
        self.c0, self.c1 = c0, c1

    @classmethod
    def one(cls) -> "Fq12":
        return cls(Fq6.one(), Fq6.zero())

    @classmethod
    def zero(cls) -> "Fq12":
        return cls(Fq6.zero(), Fq6.zero())

    def __eq__(self, other) -> bool:
        return isinstance(other, Fq12) and self.c0 == other.c0 and self.c1 == other.c1

    def __add__(self, other: "Fq12") -> "Fq12":
        return Fq12(self.c0 + other.c0, self.c1 + other.c1)

    def __sub__(self, other: "Fq12") -> "Fq12":
        return Fq12(self.c0 - other.c0, self.c1 - other.c1)

    def __mul__(self, other: "Fq12") -> "Fq12":
        a0, a1, b0, b1 = self.c0, self.c1, other.c0, other.c1
        t0 = a0 * b0
        t1 = a1 * b1
        return Fq12(t0 + t1.mul_by_v(), (a0 + a1) * (b0 + b1) - t0 - t1)

    def square(self) -> "Fq12":
        a0, a1 = self.c0, self.c1
        t = a0 * a1
        c0 = (a0 + a1) * (a0 + a1.mul_by_v()) - t - t.mul_by_v()
        return Fq12(c0, t + t)

    def conjugate(self) -> "Fq12":
        """x -> x^(p^6): negate the w coefficient."""
        return Fq12(self.c0, -self.c1)

    def inv(self) -> "Fq12":
        a0, a1 = self.c0, self.c1
        denom = a0.square() - a1.square().mul_by_v()
        dinv = denom.inv()
        return Fq12(a0 * dinv, -(a1 * dinv))

    def frobenius(self, power: int = 1) -> "Fq12":
        out = self
        for _ in range(power):
            c0 = out.c0.frobenius(1)
            c1 = out.c1.frobenius(1)
            c1 = Fq6(c1.c0 * FROB_W[1], c1.c1 * FROB_W[1], c1.c2 * FROB_W[1])
            out = Fq12(c0, c1)
        return out

    def pow(self, e: int) -> "Fq12":
        if e < 0:
            return self.inv().pow(-e)
        result = Fq12.one()
        base = self
        while e:
            if e & 1:
                result = result * base
            base = base.square()
            e >>= 1
        return result

    def __repr__(self):
        return f"Fq12({self.c0}, {self.c1})"
