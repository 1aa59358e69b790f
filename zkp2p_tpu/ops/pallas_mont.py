"""Fused Montgomery multiplication as a Pallas TPU kernel.

docs/ROOFLINE.md: the XLA field-mul path materialises its (B, 512) f32
partial-product planes in HBM between the outer product and the one-hot
fold, capping FR.mul at ~14 M muls/s (~1-2% of VPU) — the measured
ceiling of the whole MSM stack.  This kernel runs the complete SOS
Montgomery product (3 limb convolutions + carry ladders + conditional
subtract) inside ONE kernel with every intermediate resident in VMEM.

Layout: limbs live on the SUBLANE axis and the batch on the 128-wide
LANE axis — (16, T) tiles — so every elementwise op fills the vector
unit (the batch-major (B, 16) layout uses 16/128 lanes).  The wrapper
transposes at the boundary; inside, the dataflow is identical
arithmetic to field.jfield (same 16x16-bit limbs, same Kogge-Stone
carry ladder), differentially tested against it.

The math is pinned with `interpret=True` on the CPU
(tests/test_pallas_mont.py) and the Mosaic lowering by the compiled
differential `chip_smoke.py` runs first on the chip; JPrimeField.mul
takes the kernel on a TPU unless ZKP2P_FIELD_MUL=xla.

Reference analog: rapidsnark's x86-assembly Montgomery mul
(its fastest-path field layer); this is the TPU-native equivalent.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..field.jfield import LIMB_BITS, MASK, NUM_LIMBS, int_to_limbs

TILE = 256  # batch elements per grid step; VMEM high-water ~ (16,16,TILE) u32


def _up(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """Limb-axis (axis 0) shift up by k, zero-filled."""
    return jnp.pad(x, ((k, 0), (0, 0)))[: x.shape[0]]


def _carry_lm(x: jnp.ndarray, out_limbs: int) -> jnp.ndarray:
    """Kogge-Stone carry resolution, limbs on axis 0 (mirror of
    field.jfield._carry_ladder)."""
    L = x.shape[0]
    if L < out_limbs:
        x = jnp.pad(x, ((0, out_limbs - L), (0, 0)))
    else:
        x = x[:out_limbs]
    for _ in range(2):
        x = (x & MASK) + _up(x >> LIMB_BITS, 1)
    g = x >> LIMB_BITS
    r = x & MASK
    p = (r == MASK).astype(jnp.uint32)
    k = 1
    while k < out_limbs:
        g = g | (p & _up(g, k))
        p = p & _up(p, k)
        k *= 2
    return (r + _up(g, 1)) & MASK


def _mul_wide_lm(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(La, T) x (Lb, T or 1) -> (La+Lb, T) canonical limbs; schoolbook
    accumulation is exact in u32 (sums of < 2*16 values < 2^16).

    The accumulator starts from the i=0 partial product instead of a
    `jnp.zeros` array: a zeros literal created inside the kernel body
    while an outer jit trace is live becomes a CAPTURED CONSTANT of the
    kernel jaxpr, which pallas_call rejects ("captures constants ...
    pass them as inputs") — first seen on the round-5 driver box's JAX
    when ntt.domain() built twiddles mid-trace."""
    La = a.shape[0]
    Lb = b.shape[0]
    out_len = La + Lb + 1
    p0 = a[0][None, :] * b  # (Lb, T)
    acc = jnp.pad(p0 & MASK, ((0, out_len - Lb), (0, 0)))
    acc = acc + jnp.pad(p0 >> LIMB_BITS, ((1, out_len - Lb - 1), (0, 0)))
    for i in range(1, La):
        p = a[i][None, :] * b  # (Lb, T)
        acc = acc + jnp.pad(p & MASK, ((i, out_len - Lb - i), (0, 0)))
        acc = acc + jnp.pad(p >> LIMB_BITS, ((i + 1, out_len - Lb - i - 1), (0, 0)))
    return _carry_lm(acc, La + Lb)


def _sub_raw_lm(a: jnp.ndarray, b: jnp.ndarray):
    """(a - b) mod 2^(16*L) + borrow flag, limb-major."""
    L = a.shape[0]
    x = a + (MASK - b)
    # +1 on limb 0 by slicing and re-concatenating: `.at[0].add` lowers
    # to scatter-add, which Mosaic TPU cannot lower (found on real
    # hardware; interpret mode accepted it), and a broadcasted_iota
    # one-hot becomes a captured kernel constant under a live outer
    # trace (same failure mode as the zeros in _mul_wide_lm).
    x = jnp.concatenate([x[0:1] + 1, x[1:]], axis=0)
    y = _carry_lm(x, L + 1)
    borrow = 1 - y[L]
    return y[:L], borrow


@jax.jit
def _mont_mul_math(a, b, n_lm, np_lm):
    """The full Montgomery product, limb-major: shared by the Pallas
    kernel bodies (here and in ops.pallas_curve).

    jit-wrapped so a kernel body that multiplies many times (a G2 add
    is 72 of these) holds one `jit` equation per product over ONE cached
    jaxpr, instead of re-tracing ~800 jnp calls each time: tracing the
    curve kernels was the larger part of the prover's cold start on the
    chip (PERF.md, PR 21).  Mosaic inlines the calls, so the compiled
    kernel is unchanged."""
    t = _mul_wide_lm(a, b)  # (32, T)
    m = _mul_wide_lm(t[:NUM_LIMBS], np_lm)[:NUM_LIMBS]
    u = _mul_wide_lm(m, n_lm)  # (32, T)
    s = _carry_lm(t + u, 2 * NUM_LIMBS + 1)
    hi = s[NUM_LIMBS : 2 * NUM_LIMBS + 1]
    red = _carry_lm(hi, NUM_LIMBS + 1)[:NUM_LIMBS]
    d, borrow = _sub_raw_lm(red, n_lm)
    return jnp.where(borrow[None, :] != 0, red, d)


def _kernel(a_ref, b_ref, n_ref, np_ref, out_ref):
    out_ref[:] = _mont_mul_math(a_ref[:], b_ref[:], n_ref[:], np_ref[:])


def _pow_kernel(nbits: int):
    """Fused square-and-multiply for a COMPILE-TIME exponent: the whole
    254-step ladder runs inside one kernel (fori_loop, all state in
    VMEM).  The XLA-level `JPrimeField.pow_const` scan issues 2 mul
    dispatches per exponent bit — ~508 kernel launches per inversion —
    which makes the resident h table's build (ops.msm `_affine_multiples`:
    one inversion a base) latency-bound; this kernel is one launch.

    The exponent bits ride as a (nbits, 1) u32 operand (LSB first) —
    kernels cannot capture traced constants (Mosaic note above) and a
    Python-unrolled ladder would inline ~500 mul graphs."""

    def kernel(a_ref, bits_ref, n_ref, np_ref, one_ref, out_ref):
        from jax.experimental import pallas as pl

        n_lm = n_ref[:]
        np_lm = np_ref[:]
        base0 = a_ref[:]
        acc0 = jnp.broadcast_to(one_ref[:], base0.shape)

        def body(i, carry):
            acc, base = carry
            bit = bits_ref[pl.ds(i, 1), :][0, 0]
            nacc = _mont_mul_math(acc, base, n_lm, np_lm)
            acc = jnp.where(bit != 0, nacc, acc)
            base = _mont_mul_math(base, base, n_lm, np_lm)
            return (acc, base)

        acc, _ = jax.lax.fori_loop(0, nbits, body, (acc0, base0))
        out_ref[:] = acc

    return kernel


@partial(jax.jit, static_argnums=(0, 2, 3))
def mont_pow(field, a: jnp.ndarray, e: int, interpret: bool = False) -> jnp.ndarray:
    """a^e (Montgomery in, Montgomery out) via the fused ladder kernel.

    Montgomery mul is a ring isomorphism, so mont(x)^e mont-wise =
    mont(x^e): callers use e = modulus - 2 for batched Fermat inversion
    (0 maps to 0 like JPrimeField.inv — select around it)."""
    assert e >= 1
    nbits = e.bit_length()
    bits = jnp.asarray(
        np.array([(e >> i) & 1 for i in range(nbits)], dtype=np.uint32)[:, None]
    )
    n_lm = jnp.asarray(np.asarray(int_to_limbs(field.modulus))[:, None])
    np_lm = jnp.asarray(np.asarray(int_to_limbs(field.nprime_int))[:, None])
    one_lm = jnp.asarray(np.asarray(int_to_limbs(field.mont_r))[:, None])
    return _run_tiled(
        _pow_kernel(nbits), (a,), (bits, n_lm, np_lm, one_lm), a.shape[:-1], interpret
    )


def _to_limb_major(x: jnp.ndarray, B: int, pad: int) -> jnp.ndarray:
    """(..., 16) batch-major -> (16, B+pad) limb-major tile input."""
    lm = jnp.moveaxis(x.reshape(B, NUM_LIMBS), -1, 0)
    return jnp.pad(lm, ((0, 0), (0, pad))) if pad else lm


def _run_tiled(kernel, batch_ins, const_ins, bshape, interpret: bool):
    """Shared pallas_call wrapper: flatten batch dims to the 128-lane
    axis, pad to TILE, run a 1-D grid, restore (..., 16)."""
    from jax.experimental import pallas as pl

    B = int(np.prod(bshape)) if bshape else 1
    pad = (-B) % TILE
    spec = pl.BlockSpec((NUM_LIMBS, TILE), lambda i: (0, i))
    out = pl.pallas_call(
        kernel,
        grid=((B + pad) // TILE,),
        in_specs=[spec] * len(batch_ins)
        + [pl.BlockSpec(c.shape, lambda i: (0, 0)) for c in const_ins],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((NUM_LIMBS, B + pad), jnp.uint32),
        interpret=interpret,
    )(*(_to_limb_major(x, B, pad) for x in batch_ins), *const_ins)
    return jnp.moveaxis(out[:, :B], 0, -1).reshape(bshape + (NUM_LIMBS,))


@partial(jax.jit, static_argnums=(0, 3))
def mont_mul(field, a: jnp.ndarray, b: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    """Montgomery product (a*b*R^-1 mod N) via the fused kernel.

    a, b: (..., 16) uint32 Montgomery limbs (broadcastable batch dims).
    field: a JPrimeField (supplies modulus / N' limb constants).
    interpret=True runs the Pallas interpreter (CPU differential tests).
    """
    n_lm = jnp.asarray(np.asarray(int_to_limbs(field.modulus))[:, None])
    np_lm = jnp.asarray(np.asarray(int_to_limbs(field.nprime_int))[:, None])

    bshape = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
    a = jnp.broadcast_to(a, bshape + (NUM_LIMBS,))
    b = jnp.broadcast_to(b, bshape + (NUM_LIMBS,))
    return _run_tiled(_kernel, (a, b), (n_lm, np_lm), bshape, interpret)
