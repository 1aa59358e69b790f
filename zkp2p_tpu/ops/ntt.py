"""Radix-2 NTT / iNTT over BN254 Fr on TPU lanes.

The reference's H-polynomial FFTs run inside snarkjs/rapidsnark over the
2^23-point domain (6.6M constraints -> next pow2; SURVEY.md §2.7, §7 step 3).
Here each stage is a reshape + one batched Montgomery mul + add/sub —
pure elementwise dataflow on (..., m, 16) limb tensors, `vmap`-able over
proof batches and shardable over the coefficient axis (all-to-all at the
stage boundary where the butterfly stride crosses the shard width).

Twiddle tables are generated ON DEVICE in log m doubling steps
(`_twiddle_powers`), so domain setup for 2^23 costs m Montgomery muls on
TPU instead of m Python bigint muls on host.

Differentially tested against the host oracle `snark.fft_host` (itself
exercised by the Groth16 host tests).
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from ..field.bn254 import R, fr_domain_root, fr_inv
from ..field.jfield import FR


def _bit_reverse_perm(m: int) -> np.ndarray:
    k = m.bit_length() - 1
    idx = np.arange(m)
    rev = np.zeros(m, dtype=np.int64)
    for b in range(k):
        rev |= ((idx >> b) & 1) << (k - 1 - b)
    return rev


def _twiddle_powers(w: int, count: int) -> jnp.ndarray:
    """[w^0 .. w^(count-1)] in Montgomery form, built on device in
    log2(count) doubling rounds: powers[j + 2^i] = powers[j] * w^(2^i).

    Every round runs at the FULL table width inside one `fori_loop`
    (lanes outside [2^i, 2^(i+1)) keep their value), so a table is ONE
    compiled program with one field-mul instance, shared by every table
    of that width.  Grown round by round, each round had its own width
    and its own compile — ~55 of them before the first prove on a 2^19
    domain — for the sake of muls that are noise on the device."""
    n_rounds = max(1, (count - 1).bit_length())
    factors = np.stack([FR.to_mont_host(pow(w, 1 << i, R)) for i in range(n_rounds)])
    return _powers_by_doubling(jnp.asarray(factors), count)


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
    """Precomputed tables for the 2^log_m domain (cached per process).

    Built under `ensure_compile_time_eval` so a first call from inside a
    traced function still produces concrete device arrays (safe to cache)."""
    m = 1 << log_m
    w = fr_domain_root(log_m)
    with jax.ensure_compile_time_eval():
        return {
            "m": m,
            "perm": _bit_reverse_perm(m),
            "tw": _twiddle_powers(w, m // 2),
            "tw_inv": _twiddle_powers(fr_inv(w), m // 2),
            "m_inv_mont": jnp.asarray(FR.to_mont_host(fr_inv(m))),
        }


def _ntt_core(x: jnp.ndarray, tw: jnp.ndarray, perm: np.ndarray) -> jnp.ndarray:
    """Iterative DIT butterfly ladder on (..., m, 16) Montgomery limbs.

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


def ntt(x: jnp.ndarray, log_m: int) -> jnp.ndarray:
    """Evaluations of the coefficient vector on the 2^log_m roots domain."""
    d = domain(log_m)
    return _ntt_core(x, d["tw"], d["perm"])


def intt(x: jnp.ndarray, log_m: int) -> jnp.ndarray:
    d = domain(log_m)
    y = _ntt_core(x, d["tw_inv"], d["perm"])
    return FR.mul(y, d["m_inv_mont"])


@lru_cache(maxsize=None)
def _coset_powers(g: int, log_m: int) -> jnp.ndarray:
    with jax.ensure_compile_time_eval():
        return _twiddle_powers(g, 1 << log_m)


def coset_shift(coeffs: jnp.ndarray, g: int, log_m: int) -> jnp.ndarray:
    """coeff[i] *= g^i — moves evaluation onto the coset g*H (host scalar g)."""
    return FR.mul(coeffs, _coset_powers(g, log_m))
