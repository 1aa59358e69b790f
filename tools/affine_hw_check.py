#!/usr/bin/env python
"""On-chip correctness + A/B timing for the batch-affine MSM tier.

Run on the chip BEFORE arming ZKP2P_MSM_AFFINE by default:
Mosaic lowering has twice accepted interpret-mode semantics it could not
run on real hardware (scatter-add, u32 reductions — see ops/pallas_curve
docstring), so the affine tier's fused-pow inversion kernel and its
select-heavy add dataflow must be diffed ON THE CHIP against the
Jacobian path before any default flips.

Phases:
  1. correctness: msm_windowed_affine vs msm_windowed_signed, n=4096,
     w=4 and w=8 — host-compared point equality.
  2. timing: both paths at n=2^17 (the bench-shape chunk regime),
     steady-state over 3 runs.
"""

import sys
import time

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    from zkp2p_tpu.utils.jaxcfg import enable_cache

    enable_cache()
    print("devices:", jax.devices(), flush=True)

    import random

    from zkp2p_tpu.curve.host import G1_GENERATOR, g1_mul
    from zkp2p_tpu.curve.jcurve import G1J, g1_jac_to_host, g1_to_affine_arrays
    from zkp2p_tpu.field.bn254 import R
    from zkp2p_tpu.field.jfield import FR
    from zkp2p_tpu.ops import msm as jmsm
    from zkp2p_tpu.ops.msm_affine import msm_windowed_affine

    rng = random.Random(9)

    def limbs(scalars):
        return jnp.asarray(np.stack([FR.to_std_host(s) for s in scalars]))

    # -------------------------------------------------- 1. correctness
    n = 4096
    base_pts = [g1_mul(G1_GENERATOR, rng.randrange(1, R)) for _ in range(64)]
    pts = [base_pts[i % 64] for i in range(n)]  # repeats force doubling lanes
    pts[5] = None
    scalars = [rng.randrange(R) for _ in range(n)]
    scalars[9] = 0
    bases = g1_to_affine_arrays(pts)
    for w in (4, 8):
        mags, negs = jmsm.signed_digit_planes_from_limbs(limbs(scalars), w)
        t0 = time.perf_counter()
        got = g1_jac_to_host(
            jax.jit(lambda b, m, s, w=w: msm_windowed_affine(G1J, b, m, s, lanes=512, window=w))(
                bases, mags, negs
            )
        )[0]
        want = g1_jac_to_host(
            jax.jit(lambda b, m, s, w=w: jmsm.msm_windowed_signed(G1J, b, m, s, lanes=512, window=w))(
                bases, mags, negs
            )
        )[0]
        ok = got == want
        print(f"correctness w={w}: {'OK' if ok else 'MISMATCH'} ({time.perf_counter()-t0:.1f}s incl compile)", flush=True)
        if not ok:
            print("AFFINE TIER MISCOMPARES ON HARDWARE — do not arm", flush=True)
            return 1

    # ------------------------------------- 1b. vmapped (the prover path)
    # The batched prover runs jit(vmap(msm)) — a different Mosaic
    # lowering combination (fused-pow inside a scan under vmap) that the
    # unbatched phase cannot vouch for.
    Bv = 2
    sc_b = [[rng.randrange(R) for _ in range(4096)] for _ in range(Bv)]
    mags_b, negs_b = zip(*(jmsm.signed_digit_planes_from_limbs(limbs(s), 8) for s in sc_b))
    mags_b, negs_b = jnp.stack(mags_b), jnp.stack(negs_b)
    vfn = jax.jit(
        jax.vmap(
            lambda m, s: msm_windowed_affine(G1J, bases, m, s, lanes=512, window=8)
        )
    )
    vref = jax.jit(
        jax.vmap(
            lambda m, s: jmsm.msm_windowed_signed(G1J, bases, m, s, lanes=512, window=8)
        )
    )
    got_b = g1_jac_to_host(vfn(mags_b, negs_b))
    want_b = g1_jac_to_host(vref(mags_b, negs_b))
    ok = got_b == want_b
    print(f"correctness vmap B={Bv}: {'OK' if ok else 'MISMATCH'}", flush=True)
    if not ok:
        print("AFFINE TIER MISCOMPARES UNDER VMAP — do not arm", flush=True)
        return 1

    # -------------------------------------------------- 2. timing A/B
    n = 1 << 17
    pts = [base_pts[i % 64] for i in range(n)]
    scalars = [rng.randrange(R) for _ in range(n)]
    bases = g1_to_affine_arrays(pts)
    w = 8
    mags, negs = jmsm.signed_digit_planes_from_limbs(limbs(scalars), w)
    aff = jax.jit(lambda b, m, s: msm_windowed_affine(G1J, b, m, s, lanes=4096, window=w))
    jac = jax.jit(lambda b, m, s: jmsm.msm_windowed_signed(G1J, b, m, s, lanes=4096, window=w))
    for name, fn in (("jacobian", jac), ("affine", aff)):
        t0 = time.perf_counter()
        r = fn(bases, mags, negs)
        jax.block_until_ready(r)
        compile_s = time.perf_counter() - t0
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(bases, mags, negs))
            ts.append(time.perf_counter() - t0)
        best = min(ts)
        print(
            f"{name}: first={compile_s:.1f}s steady={best:.3f}s -> {n/best/1e6:.3f} M pts/s",
            flush=True,
        )

    # ------------------------- 3. bucket MSM (lever 2): correctness + A/B
    from zkp2p_tpu.ops.msm_bucket import msm_bucket_affine

    nb = 4096
    pts_b = [base_pts[i % 64] for i in range(nb)]
    pts_b[3] = None
    sc_b = [rng.randrange(R) for _ in range(nb)]
    sc_b[7] = 0
    bases_b = g1_to_affine_arrays(pts_b)
    mags8, negs8 = jmsm.signed_digit_planes_from_limbs(limbs(sc_b), 8)
    t0 = time.perf_counter()
    got = g1_jac_to_host(
        jax.jit(lambda b, m, s: msm_bucket_affine(G1J, b, m, s, window=8))(bases_b, mags8, negs8)
    )[0]
    want = g1_jac_to_host(
        jax.jit(lambda b, m, s: jmsm.msm_windowed_signed(G1J, b, m, s, lanes=512, window=8))(
            bases_b, mags8, negs8
        )
    )[0]
    ok = got == want
    print(f"bucket correctness w=8: {'OK' if ok else 'MISMATCH'} ({time.perf_counter()-t0:.1f}s incl compile)", flush=True)
    if not ok:
        print("BUCKET TIER MISCOMPARES ON HARDWARE — do not arm", flush=True)
        return 1

    mags16, negs16 = jmsm.signed_digit_planes_from_limbs(limbs(scalars), 16)
    bkt = jax.jit(lambda b, m, s: msm_bucket_affine(G1J, b, m, s, window=16))
    t0 = time.perf_counter()
    jax.block_until_ready(bkt(bases, mags16, negs16))
    compile_s = time.perf_counter() - t0
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(bkt(bases, mags16, negs16))
        ts.append(time.perf_counter() - t0)
    best = min(ts)
    print(
        f"bucket w=16: first={compile_s:.1f}s steady={best:.3f}s -> {n/best/1e6:.3f} M pts/s",
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
