"""Radix-2 NTT / iNTT over BN254 Fr on TPU lanes.

The reference's H-polynomial FFTs run inside snarkjs/rapidsnark over the
2^23-point domain (6.6M constraints -> next pow2; SURVEY.md §2.7, §7 step 3).
Here a transform is log m stages over (..., m, 16) Montgomery limb
tensors, `vmap`-able over proof batches, and the file holds two ladders
that compute the same field elements:

- `_ntt_constant_geometry`, which `ntt` and `intt` run: Pease's constant
  geometry.  Every stage slices the vector into its two halves, runs one
  butterfly over them ((a + t*b, a - t*b): one Pallas kernel where the
  field's product is one, `ops.pallas_ntt`, else the field's three
  operations) and interleaves sum and difference, so all stages share
  one loop body and none reads through an index vector; the twiddles
  are grown a stage at a time and the bit-reversed result is put in
  order by two row permutations and a transpose.  Under the prover's
  batch axis the chunk goes through ONE kernel call a stage and shares
  the twiddles.
- `_ntt_core`, the decimation-in-time ladder whose stages are whole-
  vector gathers.  On a v5e its gathers lose to the slices at every size
  and chunk the served cells run (the table above `_transform`); nothing
  the prover runs calls it: it stays as this ladder's oracle in the tests.

Twiddle tables are generated ON DEVICE in log m doubling steps
(`_twiddle_powers`), so domain setup for 2^23 costs m Montgomery muls on
TPU instead of m Python bigint muls on host.

Differentially tested against the host oracle `snark.fft_host` (itself
exercised by the Groth16 host tests), and the two ladders against each
other (tests/test_ntt_constant_geometry.py).
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from ..field.bn254 import R, fr_domain_root, fr_inv
from ..field.jfield import FR, field_mul_impl


def _bit_reverse_perm(m: int) -> np.ndarray:
    k = m.bit_length() - 1
    idx = np.arange(m)
    rev = np.zeros(m, dtype=np.int64)
    for b in range(k):
        rev |= ((idx >> b) & 1) << (k - 1 - b)
    return rev


def _twiddle_powers(w: int, count: int) -> np.ndarray:
    """[w^0 .. w^(count-1)] in Montgomery form, built on device in
    log2(count) doubling rounds: powers[j + 2^i] = powers[j] * w^(2^i),
    and brought back to the host: a table is a constant of the programs
    that close over it, whichever device they run on, not a buffer of
    the device that happened to build it.

    Every round runs at the FULL table width inside one `fori_loop`
    (lanes outside [2^i, 2^(i+1)) keep their value), so a table is ONE
    compiled program with one field-mul instance, shared by every table
    of that width.  Grown round by round, each round had its own width
    and its own compile — ~55 of them before the first prove on a 2^19
    domain — for the sake of muls that are noise on the device."""
    n_rounds = max(1, (count - 1).bit_length())
    factors = np.stack([FR.to_mont_host(pow(w, 1 << i, R)) for i in range(n_rounds)])
    return np.asarray(_powers_by_doubling(jnp.asarray(factors), count))


@partial(jax.jit, static_argnums=1)
def _powers_by_doubling(factors: jnp.ndarray, count: int) -> jnp.ndarray:
    idx = jnp.arange(count)

    def grow(i, cur):
        half = jnp.left_shift(1, i)
        grown = FR.mul(jnp.roll(cur, half, axis=0), factors[i])  # [j] = cur[j - 2^i] * w^(2^i)
        return jnp.where(((idx >= half) & (idx < 2 * half))[:, None], grown, cur)

    ones = jnp.broadcast_to(FR.one_mont, (count,) + FR.one_mont.shape)
    return jax.lax.fori_loop(0, factors.shape[0], grow, ones)


@lru_cache(maxsize=None)
def domain(log_m: int):
    """Precomputed tables for the 2^log_m domain (cached per process),
    as host arrays: the programs of every device close over the same
    constants.

    Built under `ensure_compile_time_eval` so a first call from inside a
    traced function still computes concrete values (safe to cache)."""
    m = 1 << log_m
    w = fr_domain_root(log_m)
    with jax.ensure_compile_time_eval():
        return {
            "m": m,
            "perm": _bit_reverse_perm(m),
            "tw": _twiddle_powers(w, m // 2),
            "tw_inv": _twiddle_powers(fr_inv(w), m // 2),
            "m_inv_mont": np.asarray(FR.to_mont_host(fr_inv(m))),
        }


def _ntt_core(x: jnp.ndarray, tw: jnp.ndarray, perm: np.ndarray) -> jnp.ndarray:
    """Iterative DIT butterfly ladder on (..., m, 16) Montgomery limbs:
    the oracle of tests/test_ntt_constant_geometry.py, not a road of the
    prover's.

    ONE `fori_loop` stage body with gather-based butterflies instead of an
    unrolled per-stage reshape ladder: XLA compile time scales with traced
    graph size, and at the production domain (2^23, log m = 23 stages) the
    unrolled form made every prover compile minutes-long.  All stage
    geometry (butterfly stride, twiddle stride) is computed from the
    traced stage index with shifts, so the compiled body is shared by all
    log m iterations."""
    m = x.shape[-2]
    if m == 1:
        return x
    log_m = m.bit_length() - 1
    x = x[..., perm, :]
    half = m // 2
    j = jnp.arange(half, dtype=jnp.int32)
    k = jnp.arange(m, dtype=jnp.int32)

    def stage(s, xs):
        length = jnp.left_shift(jnp.int32(1), s)
        mask = length - 1
        pos = j & mask
        i0 = ((j >> s) << (s + 1)) | pos  # butterfly low index
        i1 = i0 | length
        twj = pos << (log_m - 1 - s)  # stage twiddle stride m/(2*length)
        a = jnp.take(xs, i0, axis=-2)
        b = FR.mul(jnp.take(xs, i1, axis=-2), jnp.take(tw, twj, axis=0))
        cat = jnp.concatenate([FR.add(a, b), FR.sub(a, b)], axis=-2)
        # Inverse permutation: output k holds sum (bit s of k clear) or
        # difference (set) of butterfly ((k>>(s+1))<<s) | (k & mask).
        jk = (((k >> (s + 1)) << s) | (k & mask)) + ((k >> s) & 1) * half
        return jnp.take(cat, jk, axis=-2)

    return jax.lax.fori_loop(0, log_m, stage, x)


def _bit_reverse_rows(x: jnp.ndarray) -> jnp.ndarray:
    """x[rev(i)] on (m, 16) without a gather of m rows: as a 2^p x 2^q
    matrix of rows, reverse the row index (2^p whole slices), transpose,
    reverse the new row index."""
    m, limbs = x.shape
    k = m.bit_length() - 1
    p = k // 2
    rows = x.reshape(1 << p, 1 << (k - p), limbs)[_bit_reverse_perm(1 << p)]
    return jnp.swapaxes(rows, 0, 1)[_bit_reverse_perm(1 << (k - p))].reshape(m, limbs)


def _butterfly(a: jnp.ndarray, b: jnp.ndarray, t: jnp.ndarray):
    """(a + t*b, a - t*b): one fused kernel where the field's product is
    one (ops.pallas_ntt), else the field's own three operations."""
    if field_mul_impl() == "pallas":
        from .pallas_ntt import butterfly

        return butterfly(FR, a, b, t)
    p = FR.mul(b, t)
    return FR.add(a, p), FR.sub(a, p)


@jax.jit
def _ntt_constant_geometry(x: jnp.ndarray, tw: jnp.ndarray) -> jnp.ndarray:
    """The same transform as `_ntt_core` on (m, 16) limbs with no gather
    (Pease's constant geometry, decimation in time): every stage
    multiplies the upper half by its twiddles, adds and subtracts the
    two halves and interleaves sum and difference, so all log m stages
    share one loop body over slices; the result comes out bit-reversed
    and `_bit_reverse_rows` puts it in order.

    The twiddle of position i at stage q is tw[rev(i mod 2^q)], periodic
    in i; it is carried and grown a stage at a time (positions whose bit
    q is set take the factor tw[m / 2^(q+2)]), one more product a stage
    instead of a table a stage.  A jit of its own with the twiddles as
    an argument, so a program that transforms six vectors lowers the
    ladder once."""
    m = x.shape[-2]
    if m == 1:
        return x
    k, half = m.bit_length() - 1, m // 2
    pos = jnp.arange(half, dtype=jnp.int32)

    def stage(q, carry):
        z, t = carry
        z = jnp.stack(_butterfly(z[:half], z[half:], t), axis=-2).reshape(x.shape)
        factor = jax.lax.dynamic_index_in_dim(tw, jnp.left_shift(1, jnp.maximum(k - 2 - q, 0)), keepdims=False)
        return z, jnp.where((((pos >> q) & 1) == 1)[:, None], FR.mul(t, factor), t)

    ones = jnp.broadcast_to(FR.one_mont, (half, x.shape[-1]))
    return _bit_reverse_rows(jax.lax.fori_loop(0, k, stage, (x, ones))[0])


# Which ladder, measured on a v5e at the shapes the served cells run (PERF.md,
# PR 27; ms, three or four runs in a row, the results bit-equal): one
# transform of a chunk through the gather ladder and through constant
# geometry, and the h stage's whole program (six transforms, two matvecs, the
# recode) on the cell's own key beside its resident h table.
#
#   points x chunk   one transform             the h program
#                    gathers      without      gathers            without
#   2^16 x 1         12.1-12.4    4.6-4.7      55.4-58.9          33.5-34.4
#   2^16 x 4         49.2-49.4    15.2-15.5    324.8-325.2        139.6-140.6
#   2^17 x 4         106.3-106.4  31.6-31.7
#   2^18 x 4         488.6-501.1  67.9-68.0
#   2^19 x 1         184.8-185.1  44.1-44.2    1,372.0-1,372.6    502.4-503.4
#   2^19 x 4         2,108-2,139  168.8-168.9  13,923.1-13,925.0  1,728.5-1,729.0
#   2^22 x 1         9,261        under 600    (PR 26)
#
# The gathers fall off a cliff as the vector they read grows, and lose under
# it too: there is no crossover to put a threshold at, so `ntt` and `intt`
# run one ladder at every size.  LADDER is its name, as the prover's
# `stage/h_planes` span carries it (`ntt`).
LADDER = "constant_geometry"


def _transform(x: jnp.ndarray, tw: jnp.ndarray) -> jnp.ndarray:
    fn = _ntt_constant_geometry
    for _ in x.shape[:-2]:
        fn = jax.vmap(fn, in_axes=(0, None))
    return fn(x, tw)


def ntt(x: jnp.ndarray, log_m: int) -> jnp.ndarray:
    """Evaluations of the coefficient vector on the 2^log_m roots domain."""
    return _transform(x, domain(log_m)["tw"])


def intt(x: jnp.ndarray, log_m: int) -> jnp.ndarray:
    d = domain(log_m)
    return FR.mul(_transform(x, d["tw_inv"]), d["m_inv_mont"])


@lru_cache(maxsize=None)
def _coset_powers(g: int, log_m: int) -> np.ndarray:
    with jax.ensure_compile_time_eval():
        return _twiddle_powers(g, 1 << log_m)


def coset_shift(coeffs: jnp.ndarray, g: int, log_m: int) -> jnp.ndarray:
    """coeff[i] *= g^i — moves evaluation onto the coset g*H (host scalar g)."""
    return FR.mul(coeffs, _coset_powers(g, log_m))
