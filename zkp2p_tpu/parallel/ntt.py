"""Sharded NTT over a device mesh: four-step (Bailey) factorisation with
an ICI all-to-all transpose at the stage boundary.

The reference runs its H-polynomial FFTs inside rapidsnark on one
machine's threads (SURVEY.md §2.7); at the production domain (2^23 for
the 6.6M-constraint Venmo circuit, README.md:79) a single chip's HBM
cannot hold the six full-domain transform intermediates, so the domain
is factored m = r·c and sharded:

Index bookkeeping (j = c·j1 + j2, k = k1 + r·k2, w_r = w^c, w_c = w^r):

  X[k1 + r·k2] = Σ_{j2} w^(j2·k1) · w_c^(j2·k2) · [Σ_{j1} w_r^(j1·k1) x[c·j1 + j2]]

so the pipeline per shard is
    1. all-to-all transpose (r,c) -> (c,r): rows become the j1 axis
    2. local length-r NTT along j1                  -> A[j2, k1]
    3. cross twiddle w^(j2·k1)                      (elementwise)
    4. all-to-all transpose (c,r) -> (r,c)          -> B[k1, j2]
    5. local length-c NTT along j2                  -> X_mat[k1, k2]
    6. all-to-all transpose (r,c) -> (c,r)          -> X_t[k2, k1]
  row-major flatten of X_t is exactly natural order (k = r·k2 + k1), so
  callers hand in the natural-order sharded vector and get the
  natural-order sharded transform back — three ICI all-to-alls total.
  (The transposed-FFT trick — DIF forward + DIT inverse with fused
  orderings — can drop two of them; kept simple until profiling says so.)

Differentially tested against ops.ntt (single device) in
tests/test_parallel.py.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..field.bn254 import fr_domain_root, fr_inv
from ..field.jfield import FR, NUM_LIMBS
from ..ops.ntt import _ntt_core, domain


@lru_cache(maxsize=None)
def _factor(log_m: int):
    """m = r * c with r = 2^(log_m//2) (rows), c the co-factor."""
    log_r = log_m // 2
    return 1 << log_r, 1 << (log_m - log_r), log_r, log_m - log_r


@lru_cache(maxsize=None)
def _cross_twiddles(log_m: int, inverse: bool) -> jnp.ndarray:
    """(c, r) matrix W[j2, k1] = w^(±j2*k1) in Montgomery form."""
    r, c, _, _ = _factor(log_m)
    m = r * c
    w = fr_domain_root(log_m)
    if inverse:
        w = fr_inv(w)
    d = domain(log_m)
    tw = d["tw"] if not inverse else d["tw_inv"]  # (m/2,) powers of w
    # full power table: extend to m entries (tw holds m/2; w^(m/2) = -1)
    idx = (np.outer(np.arange(c, dtype=np.int64), np.arange(r, dtype=np.int64))) % m
    lo = idx % (m // 2)
    flip = idx >= (m // 2)
    with jax.ensure_compile_time_eval():
        base = jnp.asarray(tw)[lo]  # (c, r, 16)
        return jnp.where(jnp.asarray(flip)[..., None], FR.neg(base), base)


def _local_ntt(x: jnp.ndarray, log_n: int) -> jnp.ndarray:
    """Batched NTT along axis -2 of (..., n, 16)."""
    d = domain(log_n)
    return _ntt_core(x, d["tw"], d["perm"])


def _local_intt_unscaled(x: jnp.ndarray, log_n: int) -> jnp.ndarray:
    d = domain(log_n)
    return _ntt_core(x, d["tw_inv"], d["perm"])


def _transpose_all_to_all(x: jnp.ndarray, axis: str, rows: int, cols: int, n_dev: int) -> jnp.ndarray:
    """Local block (rows/d, cols, 16) of a row-sharded (rows, cols) matrix
    -> local block (cols/d, rows, 16) of the col-sharded transpose."""
    lr = rows // n_dev
    lc = cols // n_dev
    # split columns into d groups -> (lr, d, lc, 16); all_to_all swaps the
    # device axis with the named mesh axis.
    blocks = x.reshape(lr, n_dev, lc, NUM_LIMBS)
    swapped = jax.lax.all_to_all(blocks, axis, split_axis=1, concat_axis=0, tiled=False)
    # swapped: (d, lr, lc, 16) where dim 0 indexes the source device (row
    # block) — transpose local dims to (lc, d, lr) = (lc, rows) layout.
    return swapped.transpose(2, 0, 1, 3).reshape(lc, rows, NUM_LIMBS)


@lru_cache(maxsize=None)
def _ntt_sharded_fn(log_m: int, mesh: Mesh, axis: str, inverse: bool):
    """Cached jitted shard_map executable per (domain, mesh, direction).

    Without this every `ntt_sharded` call built a fresh shard_map closure,
    so the six transforms of one H-evaluation compiled six separate
    executables (~7 min of XLA on a 1-core host, and 6x the work on TPU
    too).  Cached, a prove compiles exactly two NTT executables (forward +
    inverse) shared by the a/b/c ladders and all later proves."""
    r, c, log_r, log_c = _factor(log_m)
    n_dev = mesh.shape[axis]
    assert c % n_dev == 0 and r % n_dev == 0, "mesh must divide both factors"
    d = domain(log_m)

    def local(xs: jnp.ndarray, cross_blk: jnp.ndarray) -> jnp.ndarray:
        # xs: (m/d, 16) natural order = (r, c) row-major x[j1, j2], the j1
        # row axis sharded.  The inner transforms run over j1 (stride c),
        # so transpose first.
        blk = xs.reshape(r // n_dev, c, NUM_LIMBS)
        blk = _transpose_all_to_all(blk, axis, r, c, n_dev)  # (c/d, r): y[j2, j1]
        if inverse:
            blk = _local_intt_unscaled(blk, log_r)  # A[j2, k1]
        else:
            blk = _local_ntt(blk, log_r)
        blk = FR.mul(blk, cross_blk)  # cross_blk = W[j2, k1] slice (c/d, r)
        blk = _transpose_all_to_all(blk, axis, c, r, n_dev)  # (r/d, c): B[k1, j2]
        if inverse:
            blk = _local_intt_unscaled(blk, log_c)  # X_mat[k1, k2]
        else:
            blk = _local_ntt(blk, log_c)
        blk = _transpose_all_to_all(blk, axis, r, c, n_dev)  # (c/d, r): X_t[k2, k1]
        out = blk.reshape(r * c // n_dev, NUM_LIMBS)  # k = r*k2 + k1: natural
        if inverse:
            out = FR.mul(out, d["m_inv_mont"])
        return out

    return jax.jit(
        jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis, None), P(axis, None, None)),
            out_specs=P(axis, None),
            check_vma=False,
        )
    )


def ntt_sharded(
    x: jnp.ndarray,
    log_m: int,
    mesh: Mesh,
    axis: str = "shard",
    inverse: bool = False,
) -> jnp.ndarray:
    """NTT/iNTT of a natural-order (m, 16) Montgomery vector, sharded on
    its leading axis over `mesh`'s `axis`.  Returns the natural-order
    result with the same sharding.  Exactly equal to ops.ntt / ops.intt.
    """
    return _ntt_sharded_fn(log_m, mesh, axis, inverse)(x, _cross_twiddles(log_m, inverse))
