"""Vectorised BN254 group arithmetic on TPU (JAX): G1 over Fq, G2 over Fq2.

TPU mirror of the EVM ecAdd/ecMul precompiles the reference leans on
(``contracts/Verifier.sol:42-100``) and of rapidsnark's Jacobian point
kernels.  Points are Jacobian triples of Montgomery limb tensors — G1:
three ``(..., 16)`` uint32 arrays, G2: three ``(..., 2, 16)`` — so every
op is elementwise over leading batch dims and `vmap`/`shard_map`-ready.

All case handling (infinity, P+P, P+(-P)) is branchless via `select`, so
one traced program serves every lane of a batch: exactly what `jit` +
SPMD sharding need (no data-dependent control flow, SURVEY.md §7).

Formulas: standard a=0 Jacobian dbl (3 sq + 4 mul) and add (4 sq + 12 mul),
shared verbatim between G1 and G2 by parameterising over the field ops
object (`JPrimeField` / `JFq2Ops` expose the same interface).

Infinity encoding: Jacobian Z == 0; affine sentinel (0, 0) (not on either
curve: 0^3 + b != 0 for b = 3 and b = 3/xi).
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..field.bn254 import P
from ..field.jfield import FQ, FQ2, NUM_LIMBS, int_to_limbs
from ..field.tower import Fq2
from .host import G1Point, G2Point

# A Jacobian point is a (X, Y, Z) tuple of limb tensors (a JAX pytree).
JacPoint = Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]
# An affine point is an (X, Y) tuple; (0, 0) means infinity.
AffPoint = Tuple[jnp.ndarray, jnp.ndarray]



# Curve-op implementation selector: "auto"/"pallas" (ops.pallas_curve on
# a TPU, xla elsewhere) or "xla" (force the packed-mul formulas below).
# The pallas kernels collapse the ~8 kernel launches + HBM round-trips
# per point add into one VMEM-resident kernel; the builders' isolated
# kernel timings are in docs/ROOFLINE.md.
from ..utils.config import load_config as _load_config
from ..utils.jaxcfg import on_tpu as _on_tpu

CURVE_IMPL = _load_config().curve_kernel


class JCurve:
    """Short-Weierstrass a=0 curve ops over a vectorised field."""

    def __init__(self, field):
        self.F = field

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

    def infinity(self, batch_shape: Tuple[int, ...] = ()) -> JacPoint:
        z = jnp.broadcast_to(self.F.zero_limbs, batch_shape + self.F.zero_limbs.shape)
        return (z, z, z)

    def is_inf(self, p: JacPoint) -> jnp.ndarray:
        return self.F.is_zero(p[2])

    def is_inf_affine(self, a: AffPoint) -> jnp.ndarray:
        return self.F.is_zero(a[0]) & self.F.is_zero(a[1])

    def from_affine(self, a: AffPoint) -> JacPoint:
        """Affine -> Jacobian; the (0,0) sentinel maps to Z=0."""
        inf = self.is_inf_affine(a)
        one = jnp.broadcast_to(self.F.one_mont, a[0].shape)
        z = self.F.select(inf, jnp.zeros_like(one), one)
        return (a[0], a[1], z)

    def neg(self, p: JacPoint) -> JacPoint:
        return (p[0], self.F.neg(p[1]), p[2])

    def select(self, cond: jnp.ndarray, p: JacPoint, q: JacPoint) -> JacPoint:
        F = self.F
        return (F.select(cond, p[0], q[0]), F.select(cond, p[1], q[1]), F.select(cond, p[2], q[2]))

    # --------------------------------------------------------------- core
    #
    # Field muls are PACKED: independent products are stacked on a fresh
    # leading axis and issued as ONE batched mul per dependency layer.  A
    # Jacobian add is 16 field muls but only ~6 dependency layers; packing
    # cuts both the traced graph (XLA compile time scales with op count)
    # and runtime (wider elementwise kernels vectorise better on the VPU).

    def _pack(self, *xs):
        shape = jnp.broadcast_shapes(*(x.shape for x in xs))
        return jnp.stack([jnp.broadcast_to(x, shape) for x in xs])

    def double(self, p: JacPoint) -> JacPoint:
        """dbl-2009-l in 3 packed mul layers; infinity -> infinity for free
        (Z3 = 2YZ = 0)."""
        F = self.F
        if self._pallas():
            from ..ops.pallas_curve import g1_double, g2_double

            if F.zero_limbs.ndim == 1:
                return g1_double(F, p)
            return g2_double(F, p)
        X1, Y1, Z1 = p
        sq = F.square(self._pack(X1, Y1))  # L1
        A, B = sq[0], sq[1]
        m2 = F.mul(self._pack(B, F.add(X1, B), Y1), self._pack(B, F.add(X1, B), Z1))  # L2
        C, XB2, YZ = m2[0], m2[1], m2[2]
        t = F.sub(F.sub(XB2, A), C)
        D = F.add(t, t)
        E = F.add(F.add(A, A), A)
        Fv = F.square(E)  # L3a
        X3 = F.sub(Fv, F.add(D, D))
        C8 = F.add(C, C)
        C8 = F.add(C8, C8)
        C8 = F.add(C8, C8)
        Y3 = F.sub(F.mul(E, F.sub(D, X3)), C8)  # L3b (depends on X3)
        Z3 = F.add(YZ, YZ)
        return (X3, Y3, Z3)

    def add(self, p: JacPoint, q: JacPoint) -> JacPoint:
        """Complete Jacobian add: handles inf / equal / negated lanes."""
        F = self.F
        if self._pallas():
            from ..ops.pallas_curve import g1_add, g2_add

            if F.zero_limbs.ndim == 1:
                return g1_add(F, p, q)
            return g2_add(F, p, q)
        X1, Y1, Z1 = p
        X2, Y2, Z2 = q
        sq = F.square(self._pack(Z1, Z2))  # L1
        Z1Z1, Z2Z2 = sq[0], sq[1]
        m2 = F.mul(self._pack(X1, X2, Y1, Y2, Z1), self._pack(Z2Z2, Z1Z1, Z2, Z1, Z2))  # L2
        U1, U2, t1, t2, Z1Z2 = m2[0], m2[1], m2[2], m2[3], m2[4]
        m3 = F.mul(self._pack(t1, t2), self._pack(Z2Z2, Z1Z1))  # L3
        S1, S2 = m3[0], m3[1]
        return self._add_core(p, q, U1, U2, S1, S2, Z1Z2)

    def add_mixed(self, p: JacPoint, a: AffPoint) -> JacPoint:
        """p (Jacobian) + a (affine, Z2=1): saves 4 muls + 1 sq vs `add`.

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
        Z1Z1 = F.square(Z1)  # L1
        m2 = F.mul(self._pack(X2, Y2), self._pack(Z1Z1, F.mul(Z1, Z1Z1)))  # L2 (+Z1^3)
        U2, S2 = m2[0], m2[1]
        # _add_core's q-select handles p==inf via from_affine(a).
        return self._add_core(p, self.from_affine(a), X1, U2, Y1, S2, Z1)

    def _add_core(
        self,
        p: JacPoint,
        q: JacPoint,
        U1: jnp.ndarray,
        U2: jnp.ndarray,
        S1: jnp.ndarray,
        S2: jnp.ndarray,
        Z1Z2: jnp.ndarray,
    ) -> JacPoint:
        F = self.F
        H = F.sub(U2, U1)
        Rr = F.sub(S2, S1)
        sq = F.square(self._pack(H, Rr))  # L4
        HH, R2 = sq[0], sq[1]
        m5 = F.mul(self._pack(H, U1), self._pack(HH, HH))  # L5
        HHH, V = m5[0], m5[1]
        X3 = F.sub(F.sub(R2, HHH), F.add(V, V))
        m6 = F.mul(self._pack(Rr, S1, Z1Z2), self._pack(F.sub(V, X3), HHH, H))  # L6
        Y3 = F.sub(m6[0], m6[1])
        Z3 = m6[2]
        res: JacPoint = (X3, Y3, Z3)

        same_x = F.is_zero(H)
        same_y = F.is_zero(Rr)
        res = self.select(same_x & same_y, self.double(p), res)
        res = self.select(same_x & ~same_y, self.infinity(same_x.shape), res)
        res = self.select(self.is_inf(p), q, res)
        res = self.select(self.is_inf(q), p, res)
        return res

    # -------------------------------------------------------- scalar mul

    def scalar_mul(self, p: JacPoint, bits: jnp.ndarray) -> JacPoint:
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
G2J = JCurve(FQ2)


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


def g1_jac_to_host(p: JacPoint) -> List[G1Point]:
    """Device Jacobian batch -> host affine points (slow; results only)."""
    X, Y, Z = (np.asarray(c) for c in p)
    X, Y, Z = X.reshape(-1, NUM_LIMBS), Y.reshape(-1, NUM_LIMBS), Z.reshape(-1, NUM_LIMBS)
    out: List[G1Point] = []
    for i in range(X.shape[0]):
        z = _fq_from_limbs(Z[i])
        if z == 0:
            out.append(None)
            continue
        zinv = pow(z, P - 2, P)
        zi2 = zinv * zinv % P
        out.append((_fq_from_limbs(X[i]) * zi2 % P, _fq_from_limbs(Y[i]) * zi2 % P * zinv % P))
    return out


def g2_jac_to_host(p: JacPoint) -> List[G2Point]:
    X, Y, Z = (np.asarray(c) for c in p)
    X, Y, Z = (a.reshape(-1, 2, NUM_LIMBS) for a in (X, Y, Z))
    out: List[G2Point] = []
    for i in range(X.shape[0]):
        z = Fq2(_fq_from_limbs(Z[i, 0]), _fq_from_limbs(Z[i, 1]))
        if z.is_zero():
            out.append(None)
            continue
        zinv = z.inv()
        zi2 = zinv * zinv
        x = Fq2(_fq_from_limbs(X[i, 0]), _fq_from_limbs(X[i, 1])) * zi2
        y = Fq2(_fq_from_limbs(Y[i, 0]), _fq_from_limbs(Y[i, 1])) * zi2 * zinv
        out.append((x, y))
    return out
