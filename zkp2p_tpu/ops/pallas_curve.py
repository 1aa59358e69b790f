"""Fused BN254 G1/G2 point ops as single Pallas TPU kernels.

docs/ROOFLINE.md round-4 addendum: with the Pallas Montgomery mul
(`ops.pallas_mont`) the field layer reaches ~136 M muls/s on a v5e chip
(7.9x the XLA path), but a point add issued product by product is ~8
separate kernels/fusions — every intermediate round-trips HBM and every
launch re-pays the (B, 16) <-> (16, B) boundary transposes.  These
kernels run the WHOLE curve op (all muls, adds, carries, and the two
branchless infinity selects of `curve.jcurve`) in ONE pallas_call with
all intermediates VMEM-resident: per point-add the HBM traffic drops
from ~19 mul-kernel round-trips to one read of the operands and one
write of the result.

Formulas: the complete addition laws of Renes, Costello and Batina
(Eurocrypt 2016, "Complete addition formulas for prime order elliptic
curves"), algorithms 7 (add, 12 products), 8 (mixed add, 11) and 9
(doubling, 8) for a = 0, on homogeneous projective points (X : Y : Z),
x = X/Z, y = Y/Z.  They have no exceptional case on a curve without a
point of order two (G1 has prime order; the twist's group order
r(2q - r) is odd), so P + P and P + (-P) are lanes like any other and
no doubling is computed beside an add.  Semantics mirror
`curve.jcurve.JCurve` exactly (same formulas, same (0, 0) affine /
Z == 0 projective infinity encodings, same select ordering), and the
differential tests pin every case lane-for-lane against it
(tests/test_pallas_curve.py).  The point math is written once over a
tiny field-ops object; the G1 instance works on single (16, T) limb
tiles, the G2 instance on (c0, c1) pairs with Karatsuba Fq2 products
(u^2 = -1, mirroring field.jfield.JFq2Ops.mul).

Layout: limb-major (16, T) tiles like `pallas_mont` — limbs on the
sublane axis, batch on the 128-wide lane axis.  Field helpers are the
limb-major mirrors of `field.jfield` (same Kogge-Stone carry ladder).

Mosaic notes (learned on hardware, rounds 4-5): `.at[].add` lowers to
an unsupported scatter — limb-0 adds are built by slice-and-concat
(NOT broadcasted_iota one-hots: an iota materialised while an outer
jit trace is live becomes a captured kernel constant, which
pallas_call rejects); kernels cannot capture traced constants — the
modulus / N' / R limbs (and the twist's 3b) are passed as (16, 1)
operands and zeros are derived from tracers (`a ^ a`), never
`jnp.zeros`.

Reference analog: rapidsnark's point kernels (its G1/G2 hot loops);
this is the TPU-native equivalent.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..curve.jcurve import G2_B3_MONT
from ..field.jfield import NUM_LIMBS, int_to_limbs
from .pallas_mont import TILE, _carry_lm, _mont_mul_math, _sub_raw_lm

G2_TILE = 128  # Fq2 kernels hold ~3x the live tiles; halve the batch tile


# ----------------------------------------------------- field layer (VMEM)


def _f_cond_sub(a, n_lm):
    d, borrow = _sub_raw_lm(a, n_lm)
    return jnp.where(borrow[None, :] != 0, a, d)


@jax.jit
def _f_add(a, b, n_lm):
    return _f_cond_sub(_carry_lm(a + b, NUM_LIMBS), n_lm)


@jax.jit
def _f_sub(a, b, n_lm):
    d, borrow = _sub_raw_lm(a, b)
    dn = _carry_lm(d + n_lm, NUM_LIMBS)
    return jnp.where(borrow[None, :] != 0, dn, d)


def _f_is_zero(a):
    """(16, T) -> (1, T) bool.  Canonical limbs are < 2^16 so the sum
    cannot overflow; a sum avoids relying on Mosaic's reduce_and.  The
    sum runs in i32 — Mosaic has no unsigned reductions (found on real
    hardware; interpret mode accepted the u32 sum)."""
    return jnp.sum(a.astype(jnp.int32), axis=0, keepdims=True) == 0


class _FqOps:
    """Limb-major Fq ops closed over the (16, 1) modulus constants.
    Elements are single (16, T) tiles."""

    def __init__(self, n_lm, np_lm, one_lm):
        self.n_lm, self.np_lm, self.one = n_lm, np_lm, one_lm

    def mul(self, a, b):
        return _mont_mul_math(a, b, self.n_lm, self.np_lm)

    def add(self, a, b):
        return _f_add(a, b, self.n_lm)

    def sub(self, a, b):
        return _f_sub(a, b, self.n_lm)

    def mul_b3(self, a):
        """3b = 9 on G1: 8a + a, four additions."""
        t = self.add(a, a)
        t = self.add(t, t)
        t = self.add(t, t)
        return self.add(t, a)

    def is_zero(self, a):
        return _f_is_zero(a)

    def sel(self, cond, a, b):
        return jnp.where(cond, a, b)

    def zero_like(self, a):
        # a ^ a, not jnp.zeros_like: a zeros literal materialised while
        # an outer jit trace is live becomes a captured kernel constant,
        # which pallas_call rejects (see pallas_mont._mul_wide_lm).
        return a ^ a

    def one_bcast(self, a):
        return jnp.broadcast_to(self.one, a.shape)


class _Fq2Ops:
    """Fq2 = Fq[u]/(u^2 + 1) on (c0, c1) tile pairs; Karatsuba product —
    the exact dataflow of field.jfield.JFq2Ops.mul."""

    def __init__(self, fq: _FqOps, b3):
        self.fq, self.b3 = fq, b3  # b3: the twist's 3b = 9/(9 + u), a (16, 1) pair

    def mul_b3(self, a):
        return self.mul(a, tuple(jnp.broadcast_to(c, a[0].shape) for c in self.b3))

    def mul(self, a, b):
        f = self.fq
        v0 = f.mul(a[0], b[0])
        v1 = f.mul(a[1], b[1])
        c0 = f.sub(v0, v1)
        c1 = f.sub(f.mul(f.add(a[0], a[1]), f.add(b[0], b[1])), f.add(v0, v1))
        return (c0, c1)

    def add(self, a, b):
        return (self.fq.add(a[0], b[0]), self.fq.add(a[1], b[1]))

    def sub(self, a, b):
        return (self.fq.sub(a[0], b[0]), self.fq.sub(a[1], b[1]))

    def is_zero(self, a):
        return _f_is_zero(a[0]) & _f_is_zero(a[1])

    def sel(self, cond, a, b):
        return (jnp.where(cond, a[0], b[0]), jnp.where(cond, a[1], b[1]))

    def zero_like(self, a):
        return (self.fq.zero_like(a[0]), self.fq.zero_like(a[1]))

    def one_bcast(self, a):
        # Montgomery 1 in Fq2 = (R, 0)
        return (jnp.broadcast_to(self.fq.one, a[0].shape), self.fq.zero_like(a[1]))


# ------------------------------------------------------------ point math


def _psel(f, cond, p, q):
    return tuple(f.sel(cond, x, y) for x, y in zip(p, q))


def _double_math(f, X, Y, Z):
    """RCB algorithm 9 (a = 0), mirror of JCurve.double: 8 products and
    one by 3b; 2P for every P, the Z == 0 encodings of infinity
    included (Z3 = 8 Y^3 Z)."""
    YY = f.mul(Y, Y)
    YZ = f.mul(Y, Z)
    XY = f.mul(X, Y)
    t2 = f.mul_b3(f.mul(Z, Z))
    Y8 = f.add(YY, YY)
    Y8 = f.add(Y8, Y8)
    Y8 = f.add(Y8, Y8)
    t0 = f.sub(YY, f.add(f.add(t2, t2), t2))
    X3 = f.mul(t0, XY)
    Y3 = f.add(f.mul(t2, Y8), f.mul(t0, f.add(YY, t2)))
    return f.add(X3, X3), Y3, f.mul(YZ, Y8)


def _add_tail_math(f, p, q, t0, t1, z, t3, t4, xz):
    """Mirror of JCurve._add_tail: the shared second half of algorithms
    7 and 8 from t0 = X1 X2, t1 = Y1 Y2, z = Z1 Z2, t3 = X1 Y2 + X2 Y1,
    t4 = Y1 Z2 + Y2 Z1, xz = X1 Z2 + X2 Z1, then the two infinity
    selects in the same order.  P + P needs no case; P + (-P) comes out
    as (0 : y : 0), which the Z == 0 encoding reads as infinity."""
    t0 = f.add(f.add(t0, t0), t0)
    bz = f.mul_b3(z)
    y3 = f.mul_b3(xz)
    z3 = f.add(t1, bz)
    t1 = f.sub(t1, bz)
    X3 = f.sub(f.mul(t3, t1), f.mul(t4, y3))
    Y3 = f.add(f.mul(t1, z3), f.mul(y3, t0))
    Z3 = f.add(f.mul(z3, t4), f.mul(t0, t3))
    res = _psel(f, f.is_zero(p[2]), q, (X3, Y3, Z3))
    return _psel(f, f.is_zero(q[2]), p, res)


def _add_math(f, p, q):
    """RCB algorithm 7 (a = 0): 12 products and two by 3b."""
    X1, Y1, Z1 = p
    X2, Y2, Z2 = q
    t0 = f.mul(X1, X2)
    t1 = f.mul(Y1, Y2)
    t2 = f.mul(Z1, Z2)
    t3 = f.sub(f.mul(f.add(X1, Y1), f.add(X2, Y2)), f.add(t0, t1))
    t4 = f.sub(f.mul(f.add(Y1, Z1), f.add(Y2, Z2)), f.add(t1, t2))
    xz = f.sub(f.mul(f.add(X1, Z1), f.add(X2, Z2)), f.add(t0, t2))
    return _add_tail_math(f, p, q, t0, t1, t2, t3, t4, xz)


def _add_mixed_math(f, p, a):
    """RCB algorithm 8 (a = 0, Z2 = 1): 11 products and two by 3b."""
    X1, Y1, Z1 = p
    X2, Y2 = a
    t0 = f.mul(X1, X2)
    t1 = f.mul(Y1, Y2)
    t3 = f.sub(f.mul(f.add(X1, Y1), f.add(X2, Y2)), f.add(t0, t1))
    t4 = f.add(f.mul(Y2, Z1), Y1)
    xz = f.add(f.mul(X2, Z1), X1)
    # q = from_affine(a): (0, 0) sentinel -> Z = 0, else Z = R (Mont 1)
    a_inf = f.is_zero(X2) & f.is_zero(Y2)
    zq = f.sel(a_inf, f.zero_like(X2), f.one_bcast(X2))
    return _add_tail_math(f, p, (X2, Y2, zq), t0, t1, Z1, t3, t4, xz)


# ------------------------------------------------------- kernel factories

_OPS = {"add": _add_math, "add_mixed": _add_mixed_math, "double": _double_math}


def _g1_kernel(op):
    math_fn = _OPS[op]

    def kernel(*refs):
        ins, outs = refs[:-3], refs[-3:]
        n_lm, np_lm, one_lm = (r[:] for r in ins[-3:])
        f = _FqOps(n_lm, np_lm, one_lm)
        coords = [r[:] for r in ins[:-3]]
        if op == "add":
            r = math_fn(f, tuple(coords[:3]), tuple(coords[3:6]))
        elif op == "add_mixed":
            r = math_fn(f, tuple(coords[:3]), tuple(coords[3:5]))
        else:
            r = math_fn(f, *coords[:3])
        for o, v in zip(outs, r):
            o[:] = v

    return kernel


def _g2_kernel(op):
    math_fn = _OPS[op]

    def kernel(*refs):
        ins, outs = refs[:-6], refs[-6:]
        n_lm, np_lm, one_lm, b3_c0, b3_c1 = (r[:] for r in ins[-5:])
        f = _Fq2Ops(_FqOps(n_lm, np_lm, one_lm), (b3_c0, b3_c1))
        raw = [r[:] for r in ins[:-5]]
        pairs = [(raw[i], raw[i + 1]) for i in range(0, len(raw), 2)]
        if op == "add":
            r = math_fn(f, tuple(pairs[:3]), tuple(pairs[3:6]))
        elif op == "add_mixed":
            r = math_fn(f, tuple(pairs[:3]), tuple(pairs[3:5]))
        else:
            r = math_fn(f, *pairs[:3])
        for i, (c0, c1) in enumerate(r):
            outs[2 * i][:] = c0
            outs[2 * i + 1][:] = c1

    return kernel


_G1_KERNELS = {op: _g1_kernel(op) for op in _OPS}
_G2_KERNELS = {op: _g2_kernel(op) for op in _OPS}


# -------------------------------------------------------------- wrappers


def _consts(field):
    return (
        jnp.asarray(np.asarray(int_to_limbs(field.modulus))[:, None]),
        jnp.asarray(np.asarray(int_to_limbs(field.nprime_int))[:, None]),
        jnp.asarray(np.asarray(int_to_limbs(field.mont_r))[:, None]),
    )


def _consts_g2(fq2):
    """The base field's constants and the twist's 3b as a (c0, c1) pair."""
    return _consts(fq2.fq) + tuple(jnp.asarray(c[:, None]) for c in G2_B3_MONT)


def _run_g1(op, field, coords, interpret: bool, tile: int = TILE):
    """Flatten batch dims -> (16, B) limb-major, pad to `tile`, run the
    kernel over a 1-D grid, restore (..., 16)."""
    from jax.experimental import pallas as pl

    bshape = jnp.broadcast_shapes(*(c.shape[:-1] for c in coords))
    coords = tuple(jnp.broadcast_to(c, bshape + (NUM_LIMBS,)) for c in coords)
    B = int(np.prod(bshape)) if bshape else 1
    pad = (-B) % tile
    lm = []
    for c in coords:
        x = jnp.moveaxis(c.reshape(B, NUM_LIMBS), -1, 0)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)))
        lm.append(x)

    spec = pl.BlockSpec((NUM_LIMBS, tile), lambda i: (0, i))
    cspec = pl.BlockSpec((NUM_LIMBS, 1), lambda i: (0, 0))
    outs = pl.pallas_call(
        _G1_KERNELS[op],
        grid=((B + pad) // tile,),
        in_specs=[spec] * len(lm) + [cspec] * 3,
        out_specs=[spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((NUM_LIMBS, B + pad), jnp.uint32)] * 3,
        interpret=interpret,
    )(*lm, *_consts(field))
    return tuple(jnp.moveaxis(o[:, :B], 0, -1).reshape(bshape + (NUM_LIMBS,)) for o in outs)


def _run_g2(op, fq2, coords, interpret: bool, tile: int = G2_TILE):
    """G2 coords are (..., 2, 16); split each into (c0, c1) limb-major
    tiles, run the Fq2 kernel, restore."""
    from jax.experimental import pallas as pl

    bshape = jnp.broadcast_shapes(*(c.shape[:-2] for c in coords))
    coords = tuple(jnp.broadcast_to(c, bshape + (2, NUM_LIMBS)) for c in coords)
    B = int(np.prod(bshape)) if bshape else 1
    pad = (-B) % tile
    lm = []
    for c in coords:
        flat = c.reshape(B, 2, NUM_LIMBS)
        for k in (0, 1):
            x = jnp.moveaxis(flat[:, k, :], -1, 0)
            if pad:
                x = jnp.pad(x, ((0, 0), (0, pad)))
            lm.append(x)

    spec = pl.BlockSpec((NUM_LIMBS, tile), lambda i: (0, i))
    cspec = pl.BlockSpec((NUM_LIMBS, 1), lambda i: (0, 0))
    outs = pl.pallas_call(
        _G2_KERNELS[op],
        grid=((B + pad) // tile,),
        in_specs=[spec] * len(lm) + [cspec] * 5,
        out_specs=[spec] * 6,
        out_shape=[jax.ShapeDtypeStruct((NUM_LIMBS, B + pad), jnp.uint32)] * 6,
        interpret=interpret,
    )(*lm, *_consts_g2(fq2))
    pts = []
    for i in range(3):
        c0 = jnp.moveaxis(outs[2 * i][:, :B], 0, -1)
        c1 = jnp.moveaxis(outs[2 * i + 1][:, :B], 0, -1)
        pts.append(jnp.stack([c0, c1], axis=-2).reshape(bshape + (2, NUM_LIMBS)))
    return tuple(pts)


@partial(jax.jit, static_argnums=(0, 3))
def g1_add(field, p, q, interpret: bool = False):
    """Complete projective + projective, one fused kernel.  p, q: (X, Y, Z)
    triples of (..., 16) uint32 Montgomery limbs."""
    return _run_g1("add", field, (*p, *q), interpret)


@partial(jax.jit, static_argnums=(0, 3))
def g1_add_mixed(field, p, a, interpret: bool = False):
    """Complete projective + affine ((0,0) = infinity), one fused kernel."""
    return _run_g1("add_mixed", field, (*p, *a), interpret)


@partial(jax.jit, static_argnums=(0, 2))
def g1_double(field, p, interpret: bool = False):
    return _run_g1("double", field, p, interpret)


@partial(jax.jit, static_argnums=(0, 3))
def g2_add(fq2, p, q, interpret: bool = False):
    """G2 projective + projective over Fq2; coords (..., 2, 16)."""
    return _run_g2("add", fq2, (*p, *q), interpret)


@partial(jax.jit, static_argnums=(0, 3))
def g2_add_mixed(fq2, p, a, interpret: bool = False):
    return _run_g2("add_mixed", fq2, (*p, *a), interpret)


@partial(jax.jit, static_argnums=(0, 2))
def g2_double(fq2, p, interpret: bool = False):
    return _run_g2("double", fq2, p, interpret)
