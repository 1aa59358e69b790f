"""Sorted-prefix bucket MSM: Pippenger-class windows with zero scatter.

The classic Pippenger bucket method (rapidsnark's MSM hot loop) routes
each point into bucket d (its current window digit) and then combines
buckets with the suffix-sum triangle — ~(256/w + 2^w/n · small) adds per
point for large windows, far below the windowed-table formulation's
digit-plane accumulate.  Its TPU blocker is the bucket FILL: a random
scatter-accumulate Mosaic/XLA cannot express efficiently (SURVEY.md §7
hard part #2).

This module reformulates the fill as sort + prefix-scan + gather, all
TPU-native primitives:

  1. Per digit plane, argsort the points by digit (XLA sort — cheap
     next to curve arithmetic) and gather points into sorted order.
  2. Take INCLUSIVE PREFIX SUMS S_i of the sorted points under curve
     addition with the batch-affine adder (ops.msm_affine): reshape-
     halving Blelloch structure, 2n adds per plane, every add 4 muls +
     ~5 amortised inversion muls.
  3. The bucket triangle telescopes against the prefixes:

         sum_i d_i P_i  =  sum_{k=0}^{K-1} (S_n - S_{c_k}),

     where c_k = #{i : d_(i) <= k} (one vectorised searchsorted per
     plane) and K = 2^(w-1) signed buckets.  Terms with c_k = n vanish
     (S_n - S_n); k below the smallest digit contribute S_n (c_k = 0,
     S_0 = identity).  This needs only K gathers + K affine subtracts +
     a K-leaf affine tree reduce — no scatter anywhere.

Work per point at w=16 (16 planes, K = 32768 on an m = 2^19 domain):
~2 adds/plane for the prefix + ~2 total for the bucket side = ~34
affine adds vs ~40 Jacobian-equivalent adds for the signed w=8 windowed
path — and with NO multiples table the cost is batch-INDEPENDENT, so
single-proof latency (the north-star p50) gains as much as throughput.

The h MSM is the intended user: its coset-quotient scalars are
full-width (width-classing cannot touch it) and it dominates the
post-classing prover profile.  Differentially pinned
against the host oracle like every device tier."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..curve.jcurve import AffPoint, JacPoint, JCurve
from .msm import horner_fold_planes
from .msm_affine import affine_add_complete


def _gather(F, triple, idx):
    x, y, inf = triple
    return x[idx], y[idx], inf[idx]


def affine_prefix_incl(F, pts):
    """Inclusive prefix sums along axis 0 (power-of-2 length) under
    complete affine addition: out[i] = pts[0] + ... + pts[i].

    Reshape-halving recursion (the curve-add twin of
    msm_affine.excl_prefix_mul): pair adjacent elements (n/2 adds),
    recurse for the odd-position prefixes, one more add layer fixes the
    even positions — 2n adds total, log depth."""
    x, y, inf = pts
    n = x.shape[0]
    assert n & (n - 1) == 0, "affine_prefix_incl needs a power-of-2 length"
    if n == 1:
        return pts
    evens = (x[0::2], y[0::2], inf[0::2])
    odds = (x[1::2], y[1::2], inf[1::2])
    pair = affine_add_complete(F, evens, odds)
    sub = affine_prefix_incl(F, pair)  # S_1, S_3, S_5, ... (odd positions)
    # S_{2k} = S_{2k-1} + x_{2k}; S_{-1} = identity
    zero = jnp.zeros_like(sub[0][:1])
    shifted = (
        jnp.concatenate([zero, sub[0][:-1]]),
        jnp.concatenate([zero, sub[1][:-1]]),
        jnp.concatenate([jnp.ones_like(sub[2][:1]), sub[2][:-1]]),
    )
    even_pref = affine_add_complete(F, shifted, evens)
    out = []
    for e, o in zip(even_pref, sub):
        out.append(jnp.stack((e, o), axis=1).reshape(x.shape if e.ndim == x.ndim else inf.shape))
    return tuple(out)


def affine_tree_reduce(F, pts):
    """Sum a power-of-2 batch of affine triples along axis 0 by pairwise
    halving (log2(n) batched affine adds)."""
    x, y, inf = pts
    n = x.shape[0]
    assert n & (n - 1) == 0
    while n > 1:
        a = (x[0 : n // 2], y[0 : n // 2], inf[0 : n // 2])
        b = (x[n // 2 : n], y[n // 2 : n], inf[n // 2 : n])
        x, y, inf = affine_add_complete(F, a, b)
        n //= 2
    return x[0], y[0], inf[0]


def msm_bucket_affine(
    curve: JCurve,
    bases: AffPoint,
    mags: jnp.ndarray,
    negs: jnp.ndarray,
    window: int = 16,
) -> JacPoint:
    """MSM over signed base-2^window digit planes via sorted prefix
    buckets.  bases: affine (x, y) with (0, 0) infinity holes; mags/negs
    from `ops.msm.signed_digit_planes_from_limbs(..., window)`.  Returns
    one Jacobian point.  G1 only (same reason as msm_windowed_affine)."""
    assert curve.F.zero_limbs.ndim == 1, "bucket MSM is G1-only"
    F = curve.F
    n_planes = mags.shape[0]
    n = bases[0].shape[0]
    npad = (1 << (n - 1).bit_length()) - n
    bx, by = bases
    if npad:
        bx = jnp.pad(bx, [(0, npad), (0, 0)])
        by = jnp.pad(by, [(0, npad), (0, 0)])
        mags = jnp.pad(mags, [(0, 0), (0, npad)])
        negs = jnp.pad(negs, [(0, 0), (0, npad)])
    base_inf = F.is_zero(bx) & F.is_zero(by)
    K = 1 << (window - 1)

    def plane(_, xs):
        mp, np_ = xs  # (n,) digits + neg mask for this plane
        order = jnp.argsort(mp)
        ds = mp[order]
        px = bx[order]
        py = by[order]
        pinf = base_inf[order] | (ds == 0)
        py = F.select(np_[order], F.neg(py), py)
        zero = jnp.zeros_like(px)
        px = F.select(pinf, zero, px)
        py = F.select(pinf, zero, py)

        Sx, Sy, Sinf = affine_prefix_incl(F, (px, py, pinf))
        # S_ext[0] = identity so a gather at c_k = 0 reads S_0 = O
        Sx = jnp.concatenate([jnp.zeros_like(Sx[:1]), Sx])
        Sy = jnp.concatenate([jnp.zeros_like(Sy[:1]), Sy])
        Sinf = jnp.concatenate([jnp.ones_like(Sinf[:1]), Sinf])

        c = jnp.searchsorted(ds, jnp.arange(K, dtype=ds.dtype), side="right")
        g = _gather(F, (Sx, Sy, Sinf), c)
        total = (
            jnp.broadcast_to(Sx[-1], g[0].shape),
            jnp.broadcast_to(Sy[-1], g[1].shape),
            jnp.broadcast_to(Sinf[-1], g[2].shape),
        )
        diff = affine_add_complete(F, total, (g[0], F.neg(g[1]), g[2]))
        gx, gy, ginf = affine_tree_reduce(F, diff)
        return None, (gx, gy, ginf)

    _, (gx, gy, ginf) = jax.lax.scan(plane, None, (mags, negs))
    # gx/gy carry (0,0) on infinity lanes only if constructed so — force
    # the sentinel before from_affine
    zero = jnp.zeros_like(gx)
    planes_jac = curve.from_affine((F.select(ginf, zero, gx), F.select(ginf, zero, gy)))
    return horner_fold_planes(curve, curve.infinity(()), planes_jac, window)
