"""Multi-scalar multiplication on TPU lanes.

The reference's MSMs live inside snarkjs `groth16 prove` (WASM) and
rapidsnark (C++ threads + x86 asm): 4 G1 MSMs + 1 G2 MSM over ~6.6M
scalars per proof (SURVEY.md §3.1 hot loop 2).  TPUs have no fast random
scatter, so bucket accumulation is reformulated as branchless dataflow
(SURVEY.md §7 hard part #2):

  1. 256 bit-plane partial sums, all planes in parallel as a 256-lane
     batch axis: plane_sums[p] = sum_i bit[p,i] * P_i.
  2. The base-point axis is consumed chunk by chunk inside ONE `lax.scan`
     (fixed chunk shape -> one compiled body reused for every chunk;
     XLA compile time scales with traced-graph size, so shape reuse is a
     design constraint here, not a nicety).  Each chunk is masked and
     pairwise tree-reduced (log2(chunk) complete adds).
  3. A second 256-step scan folds the plane sums MSB-first:
     acc = 2*acc + plane_sums[p].

Cost: ~256 point-adds per base point, fully vectorised, zero scatter /
sort / data-dependent control flow.  (Windowed Pippenger via sorted
segment scans is the planned fast path in kernels/; this is the portable
XLA formulation that the rest of the stack is tested against.)

Sharding: split the N axis across devices, run the same scan per shard,
then one `add` tree over the per-device partials (an ICI all-reduce with
the group op) — see zkp2p_tpu.parallel.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..curve.jcurve import AffPoint, ProjPoint, JCurve
from ..field.jfield import LIMB_BITS, NUM_LIMBS

SCALAR_BITS = 256


def bit_planes_from_limbs(limbs: jnp.ndarray) -> jnp.ndarray:
    """Standard-form scalar limbs (..., n, 16) uint32 -> (256, ..., n) planes,
    MSB first (plane 0 = bit 255).

    Device-side twin of `jcurve.scalar_bit_planes` so witness values produced
    on device never round-trip to the host.  Vectorised (one shift + one
    transpose), not a 256-step Python loop — trace size matters."""
    shifts = jnp.arange(LIMB_BITS, dtype=jnp.uint32)
    bits = (limbs[..., None] >> shifts) & 1  # (..., 16, 16) limb x bit
    flat = bits.reshape(*limbs.shape[:-1], SCALAR_BITS)  # LSB first
    flat = jnp.flip(flat, axis=-1)  # MSB first
    return jnp.moveaxis(flat, -1, 0)


def tree_reduce(curve: JCurve, pts: ProjPoint, axis_len: int) -> ProjPoint:
    """Sum `axis_len` projective points along the last batch axis in
    ceil(log2(n)) pairwise rounds; all other batch axes stay vectorised.

    Every round has the SAME shape: adjacent pairs are added (n/2 adds)
    and the sums are padded back to n with infinity (Z = 0), so ONE add
    graph runs inside a `lax.scan` instead of log2(n) inlined copies at
    log2(n) different widths.  On the chip each distinct width of a
    curve add is its own kernel instance to lower and compile — the
    halving tree at 4096 lanes was 12 of an MSM executable's 16 — while
    the extra adds on infinity lanes are noise next to the accumulate."""
    ax = -1 - curve.F.zero_limbs.ndim  # the reduced batch axis
    if axis_len == 1:
        return tuple(jnp.squeeze(c, axis=ax) for c in pts)
    n = 1 << (axis_len - 1).bit_length()

    def pad_to_n(c, have):
        pad_cfg = [(0, 0)] * c.ndim
        pad_cfg[ax] = (0, n - have)
        return jnp.pad(c, pad_cfg)  # zero = infinity

    def pair_round(acc, _):
        even = tuple(jax.lax.slice_in_dim(c, 0, n, stride=2, axis=ax) for c in acc)
        odd = tuple(jax.lax.slice_in_dim(c, 1, n, stride=2, axis=ax) for c in acc)
        return tuple(pad_to_n(c, n // 2) for c in curve.add(even, odd)), None

    acc, _ = jax.lax.scan(
        pair_round, tuple(pad_to_n(c, axis_len) for c in pts), None, length=n.bit_length() - 1
    )
    return tuple(jax.lax.index_in_dim(c, 0, axis=ax, keepdims=False) for c in acc)


def horner_fold_planes(curve: JCurve, init: ProjPoint, planes_stacked, window: int) -> ProjPoint:
    """MSB-first Horner fold over stacked digit-plane partials (leading
    axis = planes): acc = 2^window * acc + plane.  Shared by the
    windowed and resident-table MSMs.

    The window doublings are a nested lax.scan: ONE compiled double
    graph instead of `window` inlined copies — for G2 (Fq2 limb towers)
    the unrolled form alone pushed XLA:CPU past the driver's dryrun
    budget (>300 s compiling jit_local)."""

    def fold(acc, ps):
        def dbl(a, _):
            return curve.double(a), None

        acc, _ = jax.lax.scan(dbl, acc, None, length=window)
        return curve.add(acc, ps), None

    out, _ = jax.lax.scan(fold, init, planes_stacked)
    return out


def digit_planes_from_limbs(limbs: jnp.ndarray, window: int = 4) -> jnp.ndarray:
    """Standard-form scalar limbs (..., n, 16) -> (256/window, ..., n)
    base-2^window digit planes, most significant first.  Vectorised like
    `bit_planes_from_limbs`."""
    assert 16 % window == 0
    per_limb = 16 // window
    shifts = jnp.arange(per_limb, dtype=jnp.uint32) * window
    mask = jnp.uint32((1 << window) - 1)
    digits = (limbs[..., None] >> shifts) & mask  # (..., 16, per_limb)
    flat = digits.reshape(*limbs.shape[:-1], 16 * per_limb)  # LS digit first
    flat = jnp.flip(flat, axis=-1)
    return jnp.moveaxis(flat, -1, 0)


def signed_digit_planes_from_limbs(limbs: jnp.ndarray, window: int = 4):
    """Standard-form scalar limbs (..., n, 16) -> signed base-2^window
    digits, most significant first: (mags, negs) with
    mags (256/window, ..., n) uint32 in [0, 2^(window-1)] and negs a bool
    mask for negated digits.

    Recoding d -> d' in [-(2^(w-1) - 1), 2^(w-1)]: LSB-first, carry into
    the next digit whenever d + carry > 2^(w-1).  The multiples table
    then only needs 2^(w-1) entries — HALF the unsigned table — because
    -|d'|*P is (x, -y) for free.  The top digit cannot overflow for
    BN254 Fr scalars (< 2^254, and the final carry is absorbed by the
    unused high bits: the top base-2^w digit of an Fr scalar is at most
    0x30, so digit + carry never exceeds 2^(w-1)).

    Carry resolution is a 5-pass Kogge-Stone over the digit axis
    (generate = d > half, propagate = d == half), vectorised over the
    scalar batch — no sequential scan."""
    assert 16 % window == 0
    n_digits = 256 // window
    half = jnp.uint32(1 << (window - 1))
    full = jnp.uint32(1 << window)

    planes = digit_planes_from_limbs(limbs, window)  # (n_digits, ..., n) MSB first
    d = jnp.flip(planes, axis=0)  # LSB first for the carry recurrence
    # carry c[i+1] arrives at digit i+1 iff d[i] + c[i] > half:
    #   generate g = d > half, propagate p = (d == half)
    g = d > half
    p = d == half
    k = 1
    gg, pp = g, p
    while k < n_digits:
        shifted_g = jnp.concatenate([jnp.zeros_like(gg[:k]), gg[:-k]], axis=0)
        shifted_p = jnp.concatenate([jnp.zeros_like(pp[:k]), pp[:-k]], axis=0)
        gg = gg | (pp & shifted_g)
        pp = pp & shifted_p
        k *= 2
    carry_in = jnp.concatenate([jnp.zeros_like(gg[:1]), gg[:-1]], axis=0)
    e = d + carry_in.astype(jnp.uint32)  # in [0, 2^w]
    neg = e > half
    mag = jnp.where(neg, full - e, e)  # in [0, half]
    mags = jnp.flip(mag, axis=0)
    negs = jnp.flip(neg, axis=0)
    return mags, negs


def msm_windowed_signed(
    curve: JCurve,
    bases: AffPoint,
    mags: jnp.ndarray,
    negs: jnp.ndarray,
    lanes: int = 64,
    window: int = 4,
) -> ProjPoint:
    """`msm_windowed` on signed digits: the per-chunk multiples table is
    2^(w-1) entries instead of 2^w - 1 (built with half the adds), and a
    negated digit flips the selected point's Y (one conditional field
    subtract — negligible next to a curve add).  The table cost is the
    batch-amortised term of the windowed MSM (it is witness-independent
    under vmap), so halving it is what makes w=8 win at small batches
    too: ~63.8 adds/pt at batch=4 vs 95.5 unsigned."""
    return _msm_windowed_impl(curve, bases, mags, negs, lanes, window)


def default_lanes(n: int, cap: int = 4096) -> int:
    """Lane width for an n-point MSM: TPU ops are latency-bound until the
    per-step batch is ~10^5+ elements (measured: FR.mul at B=4096 runs at
    <5% of its B=1M throughput), so spend points on WIDE steps — subject
    to keeping enough scan steps (>=16) to amortise the windowed table."""
    return max(64, min(cap, n // 16))


def msm_windowed(curve: JCurve, bases: AffPoint, digit_planes: jnp.ndarray, lanes: int = 64, window: int = 4) -> ProjPoint:
    """Windowed MSM: ~(2^window - 2 + 256/window) adds per point instead of
    256 (window=4 -> ~78, a 3.3x work cut vs `msm`).

    Per chunk step the (lanes,) points expand into a 2^window multiples
    table (built with 2^window - 2 adds on narrow lanes); each digit plane
    then SELECTS its multiple (cheap wheres) and does one masked
    accumulate on the (n_planes, lanes) batch.  Same zero-scatter dataflow,
    same one-adder-per-scan-body compile discipline."""
    return _msm_windowed_impl(curve, bases, digit_planes, None, lanes, window)


def _msm_windowed_impl(
    curve: JCurve,
    bases: AffPoint,
    planes_in: jnp.ndarray,
    negs: Optional[jnp.ndarray],
    lanes: int,
    window: int,
) -> ProjPoint:
    """Shared body of `msm_windowed` (negs=None: unsigned 2^w - 1 table +
    masked accumulate — since PR 38 no road's: tools and tests call it)
    and `msm_windowed_signed` (half table + Y negation — the one-chip
    road's): the planes' partials, Horner over the planes lane by lane,
    then the lanes' tree."""
    partials, lanes = _window_partials(curve, bases, planes_in, negs, lanes, window)
    per_lane = horner_fold_planes(curve, curve.infinity((lanes,)), partials, window)
    return tree_reduce(curve, per_lane, lanes)


def msm_plane_sums(
    curve: JCurve,
    bases: AffPoint,
    mags: jnp.ndarray,
    negs: jnp.ndarray,
    lanes: int = 64,
    window: int = 4,
) -> ProjPoint:
    """The signed windowed MSM up to its last fold: the sum of every
    plane, (n_digits,) points, most significant first —
    `msm_windowed_signed`'s table and accumulate, then each plane's
    lanes folded (`tree_reduce`).  `horner_fold_planes` over them gives
    the point `msm_windowed_signed` gives, which folds the other way
    round (Horner lane by lane, then the lanes).  What differs is what a
    program has to lower: this Horner's double and add have a point's
    shape whatever the lanes and the planes, so a program over several
    classes of bases (the mesh road's, `parallel.mesh`: a narrow class
    at thousands of lanes beside a wide one at 64) lowers one pair for
    all of them, where a fold at each class's lanes is three kernel
    instances a class, each ~1 s of Python for G1 and ~3 s for G2 at
    every start (PERF.md, PR 38)."""
    partials, lanes = _window_partials(curve, bases, mags, negs, lanes, window)
    return tree_reduce(curve, partials, lanes)


def _window_partials(curve, bases, planes_in, negs, lanes, window):
    """The accumulate of the windowed MSMs: `(partials, lanes)`, the
    planes' partial sums lane by lane, (n_digits, lanes) points, and the
    lanes a step took (no more than there are bases)."""
    signed = negs is not None
    n_digits = planes_in.shape[0]
    n = bases[0].shape[0]
    lanes = min(lanes, n)
    pad = (-n) % lanes
    if pad:
        bases = tuple(jnp.pad(c, [(0, pad)] + [(0, 0)] * (c.ndim - 1)) for c in bases)
        planes_in = jnp.pad(planes_in, [(0, 0), (0, pad)])
        if signed:
            negs = jnp.pad(negs, [(0, 0), (0, pad)])
    steps = (n + pad) // lanes

    pts = tuple(c.reshape((steps, lanes) + c.shape[1:]) for c in bases)
    planes = planes_in.reshape(n_digits, steps, lanes).transpose(1, 0, 2)

    # table entries 1..n_table (signed digits only reach 2^(w-1))
    n_table = (1 << (window - 1)) if signed else (1 << window) - 1
    F = curve.F

    def accumulate(acc, xs):
        # the neg planes ride the scan only on the signed path
        if signed:
            pt, digits, neg = xs
        else:
            (pt, digits), neg = xs, None  # pt: (lanes, elem) affine
        base_jac = curve.from_affine(pt)

        def table_step(prev, _):
            nxt = curve.add_mixed(prev, pt)
            return nxt, prev

        # multiples 1..n_table: scan collects [1P, 2P, ...] (ys = prev)
        last, stacked = jax.lax.scan(table_step, base_jac, None, length=n_table)
        table = tuple(
            jnp.concatenate([jnp.zeros_like(c[:1]), c], axis=0) for c in stacked
        )  # index 0 = infinity (Z = 0)

        lane_ix = jnp.arange(digits.shape[-1])[None, :]
        sel = list(c[digits, lane_ix] for c in table)  # per-lane multiple -> (n_digits, lanes, elem)
        if signed:
            # negate Y where the digit is negative; F.neg keeps -0 = 0,
            # so infinity lanes (digit 0) stay (0, 0, 0).  The mask
            # broadcasts over the element dims (one for G1 limbs, two
            # for G2 Fq2 pairs).  Digit 0 selects the Z = 0 infinity
            # entry, which curve.add's infinity selects pass through — no
            # explicit mask needed.
            mask = neg.reshape(neg.shape + (1,) * (sel[1].ndim - neg.ndim))
            sel[1] = jnp.where(mask, F.neg(sel[1]), sel[1])
            return curve.add(acc, tuple(sel)), None
        nxt = curve.add(acc, tuple(sel))
        return curve.select(digits != 0, nxt, acc), None

    if signed:
        neg_t = negs.reshape(n_digits, steps, lanes).transpose(1, 0, 2)
        xs_in = (pts, planes, neg_t)
    else:
        xs_in = (pts, planes)
    partials, _ = jax.lax.scan(accumulate, curve.infinity((n_digits, lanes)), xs_in)
    return partials, lanes


# ---------------------------------------------------------------------------
# Fixed-base window multiples, resident with the key.  `_msm_windowed_impl`
# rebuilds [1P..2^(w-1)P] for every chunk of every batch, in projective, so
# its accumulate is the full add.  Bases that belong to the key (the h
# query) get the table ONCE, normalised to affine: the accumulate becomes
# select -> negate y -> `add_mixed`, and a wide window costs memory, not a
# table build a batch.
#
# Layout (chosen on the chip, PERF.md PR 25): (steps, 2^(w-1), lanes, 16)
# u32, word j = x limb j | y limb j << 16 — the limbs are 16-bit values in
# 32-bit words, so one word carries both coordinates: 64 bytes a multiple,
# one gather for the two of them, and the scan slices it by step as it
# slices the bases.  Entry k-1 holds k*P; digit 0 is masked to (0, 0).

RESIDENT_ENTRY_BYTES = 4 * NUM_LIMBS  # 16 words: x and y, 16 limbs of 16 bits each
# The build runs in this many chunks of bases, ONE kernel width.  Its
# temporaries are ~74 KB a base in flight at w=8 (2.49 GB at 2^15 bases,
# compiled for the v5e): at a sixteenth of the bases they stay under what a
# batch claims later (~7 KiB a base), so the build sets no memory peak.
RESIDENT_BUILD_CHUNKS = 16


def _affine_multiples(curve: JCurve, pt: AffPoint, n_table: int) -> AffPoint:
    """Affine bases (C, 16) -> affine k*P for k = 1..n_table, (n_table, C,
    16) a coordinate; a (0, 0) hole stays a hole in every multiple.

    One `add_mixed` scan over k (its first step is P + P: a lane like
    any other to the complete formulas), then ONE inversion a base:
    prefix products of the n_table Z's along k, `inv_fused` of the
    total, and the suffix sweep back to x = X/Z, y = Y/Z (5 products an
    entry).  Every kernel runs at
    the one width C — a tree inversion over the whole array would lower
    an instance a halving level."""
    F = curve.F

    def table_step(prev, _):
        return curve.add_mixed(prev, pt), prev

    _, (X, Y, Z) = jax.lax.scan(table_step, curve.from_affine(pt), None, length=n_table)
    inf = F.is_zero(Z)
    Zs = F.select(inf, jnp.broadcast_to(F.one_mont, Z.shape), Z)

    def prefix(run, z):
        return F.mul(run, z), run

    total, pre = jax.lax.scan(prefix, jnp.broadcast_to(F.one_mont, Z.shape[1:]), Zs)

    def suffix(run, xs):  # run = 1 / (Z_1 * ... * Z_k)
        X_k, Y_k, z, p = xs
        zinv = F.mul(run, p)
        return F.mul(run, z), (F.mul(X_k, zinv), F.mul(Y_k, zinv))

    _, (x, y) = jax.lax.scan(suffix, F.inv_fused(total), (X, Y, Zs, pre), reverse=True)
    zero = jnp.zeros_like(x)
    return F.select(inf, zero, x), F.select(inf, zero, y)


def resident_table(curve: JCurve, bases: AffPoint, window: int, lanes: int) -> jnp.ndarray:
    """The signed window multiples of `bases` in the layout above, for
    `msm_resident`, which reads the window off its shape.  Bases are padded with holes to
    whole steps of `lanes`; built chunk by chunk inside one program."""
    n = bases[0].shape[0]
    lanes = min(lanes, n)
    pad = (-n) % lanes
    if pad:
        bases = tuple(jnp.pad(c, [(0, pad)] + [(0, 0)] * (c.ndim - 1)) for c in bases)
    steps = (n + pad) // lanes
    n_table = 1 << (window - 1)
    g = max(d for d in range(1, max(1, steps // RESIDENT_BUILD_CHUNKS) + 1) if steps % d == 0)  # steps a chunk

    def chunk(pt):
        x, y = _affine_multiples(curve, pt, n_table)  # (n_table, g * lanes, 16)
        words = x | (y << LIMB_BITS)
        return words.reshape((n_table, g, lanes) + words.shape[2:]).swapaxes(0, 1)

    chunks = jax.lax.map(chunk, tuple(c.reshape((steps // g, g * lanes) + c.shape[1:]) for c in bases))
    return chunks.reshape((steps,) + chunks.shape[2:])


def msm_resident(curve: JCurve, table: jnp.ndarray, mags: jnp.ndarray, negs: jnp.ndarray) -> ProjPoint:
    """`msm_windowed_signed` over the bases a `resident_table` was built
    from, at the table's window (2^(w-1) entries a base): the same signed
    digit planes, the same Horner fold and tree reduce, the same point.
    The scan carries the step's slice of the
    table in place of the bases.  Digit 0 selects (0, 0), which
    `add_mixed` passes through; equal and opposite points are lanes like
    any other to the complete formulas, so the sum is exact for every
    input."""
    partials, lanes, window = _resident_partials(curve, table, mags, negs)
    per_lane = horner_fold_planes(curve, curve.infinity((lanes,)), tuple(c for c in partials), window)
    return tree_reduce(curve, per_lane, lanes)


def resident_plane_sums(curve: JCurve, table: jnp.ndarray, mags: jnp.ndarray, negs: jnp.ndarray) -> ProjPoint:
    """`msm_resident` up to its last fold, as `msm_plane_sums` is
    `msm_windowed_signed`'s: the sum of every plane, (n_digits,) points,
    most significant first — the table's accumulate, then each plane's
    lanes folded (`tree_reduce`).  `horner_fold_planes` over them at the
    table's window gives the point `msm_resident` gives.  The mesh road's
    h MSM (`parallel.mesh.msm_pod_resident`): its Horner has a point's
    shape, the allreduce's fold's."""
    partials, lanes, _window = _resident_partials(curve, table, mags, negs)
    return tree_reduce(curve, partials, lanes)


def _resident_partials(curve, table, mags, negs):
    """The accumulate of the resident MSMs: `(partials, lanes, window)`,
    the planes' partial sums lane by lane, (n_digits, lanes) points, and
    the step width and the window read off the table's shape."""
    steps, n_table, lanes = table.shape[:3]
    n_digits, n = mags.shape
    pad = steps * lanes - n
    if pad:
        mags = jnp.pad(mags, [(0, 0), (0, pad)])
        negs = jnp.pad(negs, [(0, 0), (0, pad)])
    digits = mags.reshape(n_digits, steps, lanes).transpose(1, 0, 2)
    neg_t = negs.reshape(n_digits, steps, lanes).transpose(1, 0, 2)
    lane_ix = jnp.arange(lanes)[None, :]
    F = curve.F
    low = jnp.uint32((1 << LIMB_BITS) - 1)

    def accumulate(acc, xs):
        t, d, neg = xs
        words = t[jnp.maximum(d, 1).astype(jnp.int32) - 1, lane_ix]  # (n_digits, lanes, 16)
        words = jnp.where((d == 0)[..., None], jnp.uint32(0), words)
        y = words >> LIMB_BITS
        y = jnp.where(neg[..., None], F.neg(y), y)  # F.neg keeps -0 = 0
        return curve.add_mixed(acc, (words & low, y)), None

    partials, _ = jax.lax.scan(accumulate, curve.infinity((n_digits, lanes)), (table, digits, neg_t))
    return partials, lanes, n_table.bit_length()


def msm(curve: JCurve, bases: AffPoint, bit_planes: jnp.ndarray, lanes: int = 64) -> ProjPoint:
    """MSM: sum_i s_i * P_i -> one projective point.

    bases: affine limb arrays, leading axis N ((0,0) lanes = infinity, e.g.
    zkey padding or public-wire holes in the c_query).
    bit_planes: (256, N) uint32 from `bit_planes_from_limbs` /
    `scalar_bit_planes`.

    Three nested scans, each with a ONE-adder body (XLA compile time scales
    with traced-graph size, so every body is exactly one curve-add graph):
      1. over N/lanes steps: masked `add_mixed` into (256, lanes) partials
      2. over 256 planes per lane: MSB-first double-and-add fold -> (lanes,)
      3. over lanes: plain add fold -> scalar point
    Work: ~256 mixed-adds per base point; step granularity (256·lanes
    lanes per step) keeps the VPU busy and loop overhead amortised."""
    n = bases[0].shape[0]
    lanes = min(lanes, n)
    pad = (-n) % lanes
    if pad:
        bases = tuple(jnp.pad(c, [(0, pad)] + [(0, 0)] * (c.ndim - 1)) for c in bases)
        bit_planes = jnp.pad(bit_planes, [(0, 0), (0, pad)])
    steps = (n + pad) // lanes

    # point i = step*lanes + lane; planes: (steps, 256, lanes)
    pts = tuple(c.reshape((steps, lanes) + c.shape[1:]) for c in bases)
    planes = bit_planes.reshape(SCALAR_BITS, steps, lanes).transpose(1, 0, 2)

    def accumulate(acc, xs):
        pt, bits = xs  # pt: (lanes, elem) affine, bits: (256, lanes)
        bcast = tuple(jnp.broadcast_to(c[None], (SCALAR_BITS,) + c.shape) for c in pt)
        nxt = curve.add_mixed(acc, bcast)
        return curve.select(bits.astype(bool), nxt, acc), None

    partials, _ = jax.lax.scan(accumulate, curve.infinity((SCALAR_BITS, lanes)), (pts, planes))

    def fold_planes(acc, ps):
        return curve.add(curve.double(acc), ps), None

    per_lane, _ = jax.lax.scan(
        fold_planes, curve.infinity((lanes,)), tuple(c for c in partials)
    )

    def fold_lanes(acc, p):
        return curve.add(acc, p), None

    total, _ = jax.lax.scan(fold_lanes, curve.infinity(()), per_lane)
    return total
