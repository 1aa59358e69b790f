"""Vectorised BN254 group arithmetic on TPU (JAX): G1 over Fq, G2 over Fq2.

TPU mirror of the EVM ecAdd/ecMul precompiles the reference leans on
(``contracts/Verifier.sol:42-100``) and of rapidsnark's point kernels.
Points are homogeneous projective triples (X : Y : Z), x = X/Z, y = Y/Z,
of Montgomery limb tensors — G1: three ``(..., 16)`` uint32 arrays, G2:
three ``(..., 2, 16)`` — so every op is elementwise over leading batch
dims and `vmap`/`shard_map`-ready.

Formulas: the complete addition laws of Renes, Costello and Batina
(Eurocrypt 2016, "Complete addition formulas for prime order elliptic
curves") for a = 0: algorithm 7 (add, 12 products), 8 (mixed add, 11)
and 9 (doubling, 8), each with multiplications by the constant 3b
beside.  They have no exceptional case on a curve without a point of
order two — G1 has prime order, the twist's group order r(2q - r) is
odd — so P + P and P + (-P) are lanes like any other: one traced
program serves every lane of a batch with no data-dependent control
flow (SURVEY.md §7), and no doubling is computed beside an add.  The
formulas are shared verbatim between G1 and G2 by parameterising over
the field ops object (`JPrimeField` / `JFq2Ops` expose the same
interface) and the curve's 3b.

Infinity encoding: projective Z == 0 (the all-zero triple of
`infinity()` and of every zero pad, and the (0 : y : 0) that P + (-P)
yields); affine sentinel (0, 0) (not on either curve: 0^3 + b != 0 for
b = 3 and b = 3/xi).  The two selects that keep it cost no product:
`p` if the other operand is infinite, the other operand if Z1 == 0.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..field.bn254 import P
from ..field.jfield import FQ, FQ2, NUM_LIMBS, int_to_limbs
from ..field.tower import Fq2
from .host import TWIST_B, G1Point, G2Point

# A projective point is a (X, Y, Z) tuple of limb tensors (a JAX pytree).
ProjPoint = Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]
# An affine point is an (X, Y) tuple; (0, 0) means infinity.
AffPoint = Tuple[jnp.ndarray, jnp.ndarray]

# The addition law's name, as the prover's five `stage/msm_*` spans carry
# it (`add`).
ADD_LAW = "complete_projective"

# 3b of the twist y^2 = x^3 + 3/(9 + u), Montgomery limbs (2, 16).  G1's
# 3b is 9, multiplied by additions.
_G2_B3 = TWIST_B * Fq2(3, 0)
G2_B3_MONT = np.stack([FQ.to_mont_host(_G2_B3.c0), FQ.to_mont_host(_G2_B3.c1)])


# Curve-op implementation selector: "auto"/"pallas" (ops.pallas_curve on
# a TPU, xla elsewhere) or "xla" (force the packed-mul formulas below).
# The pallas kernels collapse the ~8 kernel launches + HBM round-trips
# per point add into one VMEM-resident kernel; the builders' isolated
# kernel timings are in docs/ROOFLINE.md.
from ..utils.config import load_config as _load_config
from ..utils.jaxcfg import on_tpu as _on_tpu

CURVE_IMPL = _load_config().curve_kernel


class JCurve:
    """Short-Weierstrass a=0 curve ops over a vectorised field; `b3` is
    the curve's 3b as Montgomery limbs, or None for G1's 9."""

    def __init__(self, field, b3=None):
        self.F = field
        self.b3 = b3

    def _pallas(self) -> bool:
        """Route through ops.pallas_curve?  Decided at trace time (static
        under jit).  TPU only: the kernels are compiled for the chip or
        not used (the differential tests call them directly with
        interpret=True instead).  Reports its arm to the execution audit
        (trace-time record: the arm is baked into the executable)."""
        from ..utils.audit import record_arm

        v = CURVE_IMPL in ("pallas", "auto") and _on_tpu()
        record_arm("curve_kernel", "pallas" if v else "xla")
        return v

    # ------------------------------------------------------------ helpers

    def infinity(self, batch_shape: Tuple[int, ...] = ()) -> ProjPoint:
        z = jnp.broadcast_to(self.F.zero_limbs, batch_shape + self.F.zero_limbs.shape)
        return (z, z, z)

    def is_inf(self, p: ProjPoint) -> jnp.ndarray:
        return self.F.is_zero(p[2])

    def is_inf_affine(self, a: AffPoint) -> jnp.ndarray:
        return self.F.is_zero(a[0]) & self.F.is_zero(a[1])

    def from_affine(self, a: AffPoint) -> ProjPoint:
        """Affine -> projective; the (0,0) sentinel maps to Z=0."""
        inf = self.is_inf_affine(a)
        one = jnp.broadcast_to(self.F.one_mont, a[0].shape)
        z = self.F.select(inf, jnp.zeros_like(one), one)
        return (a[0], a[1], z)

    def neg(self, p: ProjPoint) -> ProjPoint:
        return (p[0], self.F.neg(p[1]), p[2])

    def select(self, cond: jnp.ndarray, p: ProjPoint, q: ProjPoint) -> ProjPoint:
        F = self.F
        return (F.select(cond, p[0], q[0]), F.select(cond, p[1], q[1]), F.select(cond, p[2], q[2]))

    # --------------------------------------------------------------- core
    #
    # Field muls are PACKED: independent products are stacked on a fresh
    # leading axis and issued as ONE batched mul per dependency layer.  A
    # complete add is 12 field muls in TWO dependency layers (three on G2,
    # where 3b is a product); packing cuts both the traced graph (XLA
    # compile time scales with op count) and runtime (wider elementwise
    # kernels vectorise better on the VPU).

    def _pack(self, *xs):
        shape = jnp.broadcast_shapes(*(x.shape for x in xs))
        return jnp.stack([jnp.broadcast_to(x, shape) for x in xs])

    def _mul_b3(self, x: jnp.ndarray) -> jnp.ndarray:
        F = self.F
        if self.b3 is None:  # 9x = 8x + x
            t = F.add(x, x)
            t = F.add(t, t)
            t = F.add(t, t)
            return F.add(t, x)
        return F.mul(x, jnp.broadcast_to(self.b3, x.shape))

    def double(self, p: ProjPoint) -> ProjPoint:
        """RCB algorithm 9 in 2 packed mul layers (8 products, one by
        3b); infinity -> infinity for free (Z3 = 8 Y^3 Z = 0)."""
        F = self.F
        if self._pallas():
            from ..ops.pallas_curve import g1_double, g2_double

            if F.zero_limbs.ndim == 1:
                return g1_double(F, p)
            return g2_double(F, p)
        X, Y, Z = p
        m1 = F.mul(self._pack(Y, Y, X, Z), self._pack(Y, Z, Y, Z))  # L1
        YY, YZ, XY = m1[0], m1[1], m1[2]
        t2 = self._mul_b3(m1[3])
        Y8 = F.add(YY, YY)
        Y8 = F.add(Y8, Y8)
        Y8 = F.add(Y8, Y8)
        t0 = F.sub(YY, F.add(F.add(t2, t2), t2))
        m2 = F.mul(self._pack(t0, t2, t0, YZ), self._pack(XY, Y8, F.add(YY, t2), Y8))  # L2
        return (F.add(m2[0], m2[0]), F.add(m2[1], m2[2]), m2[3])

    def add(self, p: ProjPoint, q: ProjPoint) -> ProjPoint:
        """RCB algorithm 7: exact for every pair of lanes, equal, opposite
        and infinite operands included."""
        F = self.F
        if self._pallas():
            from ..ops.pallas_curve import g1_add, g2_add

            if F.zero_limbs.ndim == 1:
                return g1_add(F, p, q)
            return g2_add(F, p, q)
        X1, Y1, Z1 = p
        X2, Y2, Z2 = q
        m1 = F.mul(
            self._pack(X1, Y1, Z1, F.add(X1, Y1), F.add(Y1, Z1), F.add(X1, Z1)),
            self._pack(X2, Y2, Z2, F.add(X2, Y2), F.add(Y2, Z2), F.add(X2, Z2)),
        )  # L1
        t0, t1, t2 = m1[0], m1[1], m1[2]
        t3 = F.sub(m1[3], F.add(t0, t1))
        t4 = F.sub(m1[4], F.add(t1, t2))
        xz = F.sub(m1[5], F.add(t0, t2))
        return self._add_tail(p, q, t0, t1, t2, t3, t4, xz)

    def add_mixed(self, p: ProjPoint, a: AffPoint) -> ProjPoint:
        """p (projective) + a (affine, Z2=1), RCB algorithm 8: one
        product fewer than `add`.

        The workhorse of MSM bucket accumulation, where all bases are the
        affine zkey points (SURVEY.md §7 step 3)."""
        F = self.F
        if self._pallas():
            from ..ops.pallas_curve import g1_add_mixed, g2_add_mixed

            if F.zero_limbs.ndim == 1:
                return g1_add_mixed(F, p, a)
            return g2_add_mixed(F, p, a)
        X1, Y1, Z1 = p
        X2, Y2 = a
        m1 = F.mul(self._pack(X1, Y1, F.add(X1, Y1), Y2, X2), self._pack(X2, Y2, F.add(X2, Y2), Z1, Z1))  # L1
        t0, t1 = m1[0], m1[1]
        t3 = F.sub(m1[2], F.add(t0, t1))
        # _add_tail's q-select handles p==inf via from_affine(a).
        return self._add_tail(p, self.from_affine(a), t0, t1, Z1, t3, F.add(m1[3], Y1), F.add(m1[4], X1))

    def _add_tail(
        self,
        p: ProjPoint,
        q: ProjPoint,
        t0: jnp.ndarray,
        t1: jnp.ndarray,
        z: jnp.ndarray,
        t3: jnp.ndarray,
        t4: jnp.ndarray,
        xz: jnp.ndarray,
    ) -> ProjPoint:
        """The shared second half of algorithms 7 and 8, from t0 = X1 X2,
        t1 = Y1 Y2, z = Z1 Z2, t3 = X1 Y2 + X2 Y1, t4 = Y1 Z2 + Y2 Z1,
        xz = X1 Z2 + X2 Z1.  P + (-P) comes out as (0 : y : 0)."""
        F = self.F
        t0 = F.add(F.add(t0, t0), t0)
        b = self._mul_b3(self._pack(z, xz))  # a product layer on G2 only
        bz, y3 = b[0], b[1]
        z3 = F.add(t1, bz)
        t1 = F.sub(t1, bz)
        m2 = F.mul(self._pack(t3, t4, t1, y3, z3, t0), self._pack(t1, y3, z3, t0, t4, t3))  # L2
        res: ProjPoint = (F.sub(m2[0], m2[1]), F.add(m2[2], m2[3]), F.add(m2[4], m2[5]))
        res = self.select(self.is_inf(p), q, res)
        return self.select(self.is_inf(q), p, res)

    # -------------------------------------------------------- scalar mul

    def scalar_mul(self, p: ProjPoint, bits: jnp.ndarray) -> ProjPoint:
        """Branchless MSB-first double-and-add.

        `bits`: (256, *batch) uint32 bit planes (see `scalar_bit_planes`),
        batch broadcastable against p's batch shape.  One `lax.scan` of 256
        steps — static trip count, jit-stable."""
        acc0 = self.infinity(jnp.broadcast_shapes(bits.shape[1:], p[2].shape[:-self._elem_ndim()]))

        def step(acc, bit):
            acc = self.double(acc)
            return self.select(bit.astype(bool), self.add(acc, p), acc), None

        acc, _ = jax.lax.scan(step, acc0, bits)
        return acc

    def _elem_ndim(self) -> int:
        return self.F.zero_limbs.ndim


G1J = JCurve(FQ)
G2J = JCurve(FQ2, G2_B3_MONT)


# ------------------------------------------------- host <-> device bridges


def scalar_bit_planes(scalars: Sequence[int]) -> jnp.ndarray:
    """Host ints -> (256, n) uint32 bit planes, MSB first (plane 0 = bit 255)."""
    limbs = np.stack([int_to_limbs(s % (1 << 256)) for s in scalars])  # (n, 16)
    planes = np.zeros((256, len(limbs)), dtype=np.uint32)
    for j in range(256):
        planes[255 - j] = (limbs[:, j // 16] >> (j % 16)) & 1
    return jnp.asarray(planes)


def g1_to_affine_arrays(points: Sequence[G1Point]) -> AffPoint:
    """Host affine G1 -> Montgomery limb arrays; None -> (0, 0) sentinel."""
    n = len(points)
    xs = np.zeros((n, NUM_LIMBS), dtype=np.uint32)
    ys = np.zeros((n, NUM_LIMBS), dtype=np.uint32)
    for i, pt in enumerate(points):
        if pt is None:
            continue
        xs[i] = FQ.to_mont_host(pt[0])
        ys[i] = FQ.to_mont_host(pt[1])
    return jnp.asarray(xs), jnp.asarray(ys)


def g2_to_affine_arrays(points: Sequence[G2Point]) -> AffPoint:
    """Host affine G2 -> (n, 2, 16) Montgomery limb arrays."""
    n = len(points)
    xs = np.zeros((n, 2, NUM_LIMBS), dtype=np.uint32)
    ys = np.zeros((n, 2, NUM_LIMBS), dtype=np.uint32)
    for i, pt in enumerate(points):
        if pt is None:
            continue
        x, y = pt
        xs[i, 0] = FQ.to_mont_host(x.c0)
        xs[i, 1] = FQ.to_mont_host(x.c1)
        ys[i, 0] = FQ.to_mont_host(y.c0)
        ys[i, 1] = FQ.to_mont_host(y.c1)
    return jnp.asarray(xs), jnp.asarray(ys)


def _fq_from_limbs(limbs: np.ndarray) -> int:
    return FQ.from_mont_host(limbs)


def g1_jac_to_host(p: ProjPoint) -> List[G1Point]:
    """Device projective batch -> host affine points (slow; results
    only).  The name is from the coordinates the tree had before the
    complete formulas: benchmarks/tests patch it by that name."""
    X, Y, Z = (np.asarray(c) for c in p)
    X, Y, Z = X.reshape(-1, NUM_LIMBS), Y.reshape(-1, NUM_LIMBS), Z.reshape(-1, NUM_LIMBS)
    out: List[G1Point] = []
    for i in range(X.shape[0]):
        z = _fq_from_limbs(Z[i])
        if z == 0:
            out.append(None)
            continue
        zinv = pow(z, P - 2, P)
        out.append((_fq_from_limbs(X[i]) * zinv % P, _fq_from_limbs(Y[i]) * zinv % P))
    return out


def g2_jac_to_host(p: ProjPoint) -> List[G2Point]:
    X, Y, Z = (np.asarray(c) for c in p)
    X, Y, Z = (a.reshape(-1, 2, NUM_LIMBS) for a in (X, Y, Z))
    out: List[G2Point] = []
    for i in range(X.shape[0]):
        z = Fq2(_fq_from_limbs(Z[i, 0]), _fq_from_limbs(Z[i, 1]))
        if z.is_zero():
            out.append(None)
            continue
        zinv = z.inv()
        x = Fq2(_fq_from_limbs(X[i, 0]), _fq_from_limbs(X[i, 1])) * zinv
        y = Fq2(_fq_from_limbs(Y[i, 0]), _fq_from_limbs(Y[i, 1])) * zinv
        out.append((x, y))
    return out
