"""Batch-affine windowed MSM: the accumulate tier in affine coordinates.

The windowed MSM (ops.msm) spends ~80% of its field muls in the
accumulate step — one complete Jacobian+Jacobian add (16 muls) per
(digit-plane, lane) slot per chunk.  rapidsnark's hot loop avoids this
with batch-affine adds: an affine+affine add is 4 muls plus a shared
inversion, and the inversion amortises to ~5 muls/lane when every lane's
denominator is inverted through ONE Montgomery batch inversion.  This
module is the TPU formulation of that trick (SURVEY.md §7 step 3):

  - The per-chunk multiples table is normalised to AFFINE once per chunk
    (Jacobian scan build -> one batched Z inversion).  Witness-
    independent, so it amortises over a vmapped proof batch.
  - Accumulators live in affine (x, y, is_inf).  Each chunk step adds
    the selected table multiple with the lambda formulas; all
    (n_digits x lanes) denominators are inverted together.
  - Batch inversion = exclusive prefix AND suffix products via
    Blelloch-style reshape-halving (work ~2 muls/element per direction
    — NOT Hillis-Steele, whose n·log n work would cost more than the
    Jacobian adds it replaces), then ONE Fermat inversion of the total,
    fused into a single kernel launch on TPU (pallas_mont.mont_pow).
  - Exceptional lanes ride branchless selects exactly like curve.jcurve:
    accumulator-at-infinity (every lane's first add), addend-at-infinity
    (digit 0 / pruned-key padding), equal-x doubling, and P + (-P).

Work per accumulate slot: 4 lambda muls + ~5 amortised inversion muls
vs 16 for the Jacobian add — ~1.45x fewer field muls on the h MSM at
the bench shape (and the h MSM is ~85% of post-classing prover adds).

Like every device tier this is pinned against the host oracle: the
differential tests compare proofs/points bit-for-bit with the Jacobian
path (tests/test_msm_affine.py), the same discipline as the reference's
pinned proof vector (``test/ramp.test.js:193-196``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..curve.jcurve import AffPoint, JacPoint, JCurve
from .msm import horner_fold_planes, tree_reduce


def _one(F, like: jnp.ndarray) -> jnp.ndarray:
    return jnp.broadcast_to(F.one_mont, like.shape)


def excl_prefix_mul(F, x: jnp.ndarray, seed: jnp.ndarray) -> jnp.ndarray:
    """Exclusive prefix products along axis 0 (power-of-2 length),
    seeded: out[i] = seed * x[0] * ... * x[i-1].

    Blelloch-style reshape-halving: each level pairs adjacent elements,
    recurses on the n/2 pair-products, then fills odd positions with one
    more mul — total work 2n muls (log-depth), vs n·log n for a
    Hillis-Steele scan."""
    n = x.shape[0]
    assert n & (n - 1) == 0, "excl_prefix_mul needs a power-of-2 length"
    if n == 1:
        return jnp.broadcast_to(seed, x.shape)
    pair = F.mul(x[0::2], x[1::2])
    pp = excl_prefix_mul(F, pair, seed)
    odd = F.mul(pp, x[0::2])
    return jnp.stack((pp, odd), axis=1).reshape(x.shape)


def batch_inverse(F, x: jnp.ndarray, fused_inv: bool = True) -> jnp.ndarray:
    """Invert every element of x (axis 0 = batch, power-of-2 length) with
    ONE field inversion: inv(x_i) = prefix_excl_i * (total^-1 *
    suffix_excl_i).  The suffix sweep is seeded with total^-1, so the
    combine is a single extra mul (~5 muls/element total).

    Fq2 (F has a .fq base field) takes the norm route instead:
    inv(a + bu) = (a - bu) * (a^2 + b^2)^-1 — the norm is never zero for
    a nonzero element (u^2 = -1 irreducible means -1 is a non-residue),
    so one Fq batch inversion of the norms serves the whole array at
    ~9 Fq muls/element vs ~15 for Fq2 prefix products.

    Zero elements are mapped to 1 inside the products so they cannot
    zero the total; their output slots are GARBAGE — callers must select
    around them (same contract as JPrimeField.inv's 0 -> 0)."""
    fq = getattr(F, "fq", None)
    if fq is not None:
        a, b = x[..., 0, :], x[..., 1, :]
        norm = fq.add(fq.square(a), fq.square(b))
        ninv = batch_inverse(fq, norm, fused_inv)
        return jnp.stack([fq.mul(a, ninv), fq.neg(fq.mul(b, ninv))], axis=-2)
    n = x.shape[0]
    if n & (n - 1):  # pad to power-of-2 with 1s (e.g. 3-plane narrow MSMs)
        pad = (1 << n.bit_length()) - n
        xp = jnp.concatenate([x, jnp.broadcast_to(F.one_mont, (pad,) + x.shape[1:])])
        return batch_inverse(F, xp, fused_inv)[:n]
    one = _one(F, x)
    safe = F.select(F.is_zero(x), one, x)
    pe = excl_prefix_mul(F, safe, F.one_mont)
    total = F.mul(pe[-1], safe[-1])
    tinv = F.inv_fused(total) if fused_inv else F.inv(total)
    sfx = jnp.flip(excl_prefix_mul(F, jnp.flip(safe, 0), tinv), 0)
    return F.mul(pe, sfx)


def jac_to_affine_batch(F, pts: JacPoint, fused_inv: bool = True) -> AffPoint:
    """Jacobian (X, Y, Z) with axis-0 batch (power-of-2) -> affine
    (x, y) = (X/Z^2, Y/Z^3); infinity (Z = 0) -> the (0, 0) sentinel.
    One batched inversion for the whole array."""
    X, Y, Z = pts
    inf = F.is_zero(Z)
    zinv = batch_inverse(F, Z, fused_inv)
    zi2 = F.square(zinv)
    x = F.mul(X, zi2)
    y = F.mul(Y, F.mul(zi2, zinv))
    zero = jnp.zeros_like(x)
    return F.select(inf, zero, x), F.select(inf, zero, y)


def _affine_add_den(F, a, b) -> tuple:
    """Phase 1 of the complete affine add: the denominator every lane
    contributes to the batch inversion, plus the case flags.  a, b are
    (x, y, is_inf) triples; exceptional lanes get denominator 1 so the
    batch product stays invertible."""
    ax, ay, ainf = a
    bx, by, binf = b
    live = ~ainf & ~binf
    x_eq = F.eq(ax, bx)
    y_eq = F.eq(ay, by)
    dbl = x_eq & y_eq & live
    # P + (-P), and doubling a 2-torsion point (y = 0): both -> infinity
    res_inf = (x_eq & ~y_eq & live) | (dbl & F.is_zero(ay))
    den = F.select(dbl, F.add(ay, ay), F.sub(bx, ax))
    den = F.select(res_inf | ~live, _one(F, den), den)
    return den, (dbl, res_inf)


def _affine_add_apply(F, a, b, dinv: jnp.ndarray, flags) -> tuple:
    """Phase 2: complete the add with the batch-inverted denominators.
    4 muls per lane (x1^2, lambda, lambda^2, y3)."""
    ax, ay, ainf = a
    bx, by, binf = b
    dbl, res_inf = flags
    axsq = F.square(ax)
    num = F.select(dbl, F.add(F.add(axsq, axsq), axsq), F.sub(by, ay))
    lam = F.mul(num, dinv)
    x3 = F.sub(F.sub(F.square(lam), ax), bx)
    y3 = F.sub(F.mul(lam, F.sub(ax, x3)), ay)
    zero = jnp.zeros_like(ax)
    rx = F.select(res_inf, zero, x3)
    ry = F.select(res_inf, zero, y3)
    rinf = res_inf
    # addend at infinity -> keep the accumulator; accumulator at
    # infinity -> take the addend (checked second so a double-infinity
    # lane stays at infinity with (0, 0) coords).
    rx = F.select(binf, ax, rx)
    ry = F.select(binf, ay, ry)
    rinf = jnp.where(binf, ainf, rinf)
    rx = F.select(ainf, bx, rx)
    ry = F.select(ainf, by, ry)
    rinf = jnp.where(ainf, binf, rinf)
    return rx, ry, rinf


def affine_add_complete(F, a, b, fused_inv: bool = True):
    """Complete affine add of two (x, y, is_inf) triples with any
    leading batch shape: phase-1 denominators are batch-inverted over
    the whole (power-of-2-padded) flattened batch, then phase 2
    completes.  The building block of the prefix-scan bucket MSM
    (ops.msm_bucket) and of ad-hoc affine folds."""
    elem = F.zero_limbs.shape
    den, flags = _affine_add_den(F, a, b)
    bshape = den.shape[: den.ndim - len(elem)]
    flat = int(np.prod(bshape)) if bshape else 1
    n_pad = (1 << (flat - 1).bit_length()) - flat if flat > 1 else 0
    d = den.reshape((flat,) + elem)
    if n_pad:
        d = jnp.concatenate([d, jnp.broadcast_to(F.one_mont, (n_pad,) + elem)])
    dinv = batch_inverse(F, d, fused_inv)[:flat].reshape(den.shape)
    return _affine_add_apply(F, a, b, dinv, flags)


def _pow2_floor(n: int) -> int:
    return 1 << (n.bit_length() - 1)


def msm_windowed_affine(
    curve: JCurve,
    bases: AffPoint,
    mags: jnp.ndarray,
    negs: jnp.ndarray,
    lanes: int = 64,
    window: int = 4,
) -> JacPoint:
    """`ops.msm.msm_windowed_signed` with the accumulate tier in batch
    affine — same signed digit planes in, bit-identical Jacobian
    accumulator out (up to Jacobian coordinate equivalence; the
    differential tests compare through the host conversion).

    Works for G1 (Fq) and G2 (Fq2 — `batch_inverse` takes the norm
    route there, so a G2 accumulate add is ~4 Fq2 muls + ~9 amortised
    Fq muls vs ~16 Fq2 muls for the Jacobian add)."""
    F = curve.F
    elem = F.zero_limbs.shape
    n_digits = mags.shape[0]
    n = bases[0].shape[0]
    # lanes must keep the flattened (n_digits * lanes) denominator and
    # (n_table * lanes) table batches power-of-2 for the halving sweeps.
    lanes = _pow2_floor(min(lanes, n))
    pad = (-n) % lanes
    if pad:
        bases = tuple(jnp.pad(c, [(0, pad)] + [(0, 0)] * (c.ndim - 1)) for c in bases)
        mags = jnp.pad(mags, [(0, 0), (0, pad)])
        negs = jnp.pad(negs, [(0, 0), (0, pad)])
    steps = (n + pad) // lanes

    pts = tuple(c.reshape((steps, lanes) + c.shape[1:]) for c in bases)
    mag_t = mags.reshape(n_digits, steps, lanes).transpose(1, 0, 2)
    neg_t = negs.reshape(n_digits, steps, lanes).transpose(1, 0, 2)

    n_table = 1 << (window - 1)  # signed digits reach 2^(w-1)

    def accumulate(acc, xs):
        pt, digits, neg = xs
        base_jac = curve.from_affine(pt)

        def table_step(prev, _):
            return curve.add_mixed(prev, pt), prev

        # multiples 1..n_table as Jacobian, then ONE batched
        # normalisation to affine (witness-independent: vmap hoists it).
        _, stacked = jax.lax.scan(table_step, base_jac, None, length=n_table)
        flat = tuple(c.reshape((n_table * lanes,) + c.shape[2:]) for c in stacked)
        tx, ty = jac_to_affine_batch(F, flat)
        tshape = (n_table + 1, lanes) + elem
        tx = jnp.concatenate([jnp.zeros_like(tx[:lanes]), tx]).reshape(tshape)
        ty = jnp.concatenate([jnp.zeros_like(ty[:lanes]), ty]).reshape(tshape)

        lane_ix = jnp.arange(lanes)[None, :]
        sx = tx[digits, lane_ix]
        sy = ty[digits, lane_ix]
        sy = F.select(neg, F.neg(sy), sy)  # -|d|*P = (x, -y); -0 = 0
        # infinity = the digit-0 row AND infinity bases (pruned-key /
        # pad lanes), both of which normalise to the (0, 0) sentinel
        sinf = F.is_zero(sx) & F.is_zero(sy)
        addend = (sx, sy, sinf)

        den, flags = _affine_add_den(F, acc, addend)
        dinv = batch_inverse(F, den.reshape((n_digits * lanes,) + elem)).reshape(den.shape)
        return _affine_add_apply(F, acc, addend, dinv, flags), None

    zero = jnp.zeros((n_digits, lanes) + F.zero_limbs.shape, dtype=jnp.uint32)
    acc0 = (zero, zero, jnp.ones((n_digits, lanes), dtype=bool))
    (ax, ay, ainf), _ = jax.lax.scan(accumulate, acc0, (pts, mag_t, neg_t))

    # inf lanes carry (0, 0) by construction -> from_affine's sentinel
    partials = curve.from_affine((ax, ay))
    per_lane = horner_fold_planes(
        curve, curve.infinity((lanes,)), tuple(c for c in partials), window
    )
    return tree_reduce(curve, per_lane, lanes)
