"""The NTT butterfly as one Pallas TPU kernel: (a + t*b, a - t*b).

On the XLA path a stage of `ops.ntt._ntt_constant_geometry` is one fused
Montgomery product and then a modular add and a modular sub, each a
chain of some seventeen elementwise fusions over (m/2, 17) limb tensors
that go through HBM: on a v5e three quarters of a stage at 2^22
(PERF.md, PR 26).  Here product, sum and difference stay in VMEM: the
same limb-major math the field and curve kernels run
(`pallas_mont._mont_mul_math`, `pallas_curve._f_add`/`_f_sub`), tiled
like `pallas_mont.mont_mul`.

The math is pinned with `interpret=True` on the CPU
(tests/test_ntt_constant_geometry.py); on the chip the transform is held
to the gather ladder's values and every proof to the C++ prover's bytes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..field.jfield import NUM_LIMBS, int_to_limbs
from .pallas_curve import _f_add, _f_sub
from .pallas_mont import TILE, _mont_mul_math, _to_limb_major


def _kernel(a_ref, b_ref, t_ref, n_ref, np_ref, sum_ref, diff_ref):
    n_lm = n_ref[:]
    a = a_ref[:]
    p = _mont_mul_math(b_ref[:], t_ref[:], n_lm, np_ref[:])
    sum_ref[:] = _f_add(a, p, n_lm)
    diff_ref[:] = _f_sub(a, p, n_lm)


@partial(jax.jit, static_argnums=(0, 4))
def butterfly(field, a: jnp.ndarray, b: jnp.ndarray, t: jnp.ndarray, interpret: bool = False):
    """(a + t*b, a - t*b) mod N on (..., 16) Montgomery limbs of one
    shape; `interpret=True` runs the Pallas interpreter (CPU tests)."""
    from jax.experimental import pallas as pl

    bshape = a.shape[:-1]
    B = int(np.prod(bshape)) if bshape else 1
    pad = (-B) % TILE
    n_lm = jnp.asarray(np.asarray(int_to_limbs(field.modulus))[:, None])
    np_lm = jnp.asarray(np.asarray(int_to_limbs(field.nprime_int))[:, None])
    spec = pl.BlockSpec((NUM_LIMBS, TILE), lambda i: (0, i))
    cspec = pl.BlockSpec((NUM_LIMBS, 1), lambda i: (0, 0))
    outs = pl.pallas_call(
        _kernel,
        grid=((B + pad) // TILE,),
        in_specs=[spec] * 3 + [cspec] * 2,
        out_specs=[spec] * 2,
        out_shape=[jax.ShapeDtypeStruct((NUM_LIMBS, B + pad), jnp.uint32)] * 2,
        interpret=interpret,
    )(*(_to_limb_major(x, B, pad) for x in (a, b, t)), n_lm, np_lm)
    return tuple(jnp.moveaxis(o[:, :B], 0, -1).reshape(bshape + (NUM_LIMBS,)) for o in outs)
