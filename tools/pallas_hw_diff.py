"""Kernel differential: the fused Pallas kernels (G1 + G2 add /
add_mixed / double on every special-case lane, mont_mul, mont_pow)
against the host bigint oracles (curve.host, Python ints).

The interpret-mode tests (tests/test_pallas_curve.py) pin the MATH
against the XLA formulas; compiled, this pins the MOSAIC LOWERING — the
layer that has already produced two behaviours interpret mode accepted
and the chip rejected (scatter-add, u32 reductions).  On a TPU
`curve.jcurve` routes to these same kernels, so the reference here is
the host oracle, which shares no code with them.  `chip_smoke.py` runs
it first, with interpret OFF, so a Mosaic refusal is told apart from a
prover fault; `interpret` is always the caller's explicit choice.
"""

from __future__ import annotations


def kernel_differential(interpret: bool, log=print) -> None:
    """Raise AssertionError on the first kernel that disagrees with the
    host oracle.  Projective operands with Z != 1 are produced by the
    kernels themselves (the doubled points feed the adds)."""
    import jax.numpy as jnp
    import numpy as np

    from zkp2p_tpu.curve import host
    from zkp2p_tpu.curve.jcurve import (
        G1J,
        G2J,
        g1_jac_to_host,
        g1_to_affine_arrays,
        g2_jac_to_host,
        g2_to_affine_arrays,
    )
    from zkp2p_tpu.field.bn254 import P as PMOD
    from zkp2p_tpu.field.jfield import FQ, FQ2, MONT_R, int_to_limbs, limbs_to_int
    from zkp2p_tpu.ops import pallas_curve as pc
    from zkp2p_tpu.ops.pallas_mont import mont_mul, mont_pow

    rng = np.random.default_rng(11)

    def check(name, got, want):
        assert got == want, f"{name}: kernel != host oracle at lanes {[i for i, (g, w) in enumerate(zip(got, want)) if g != w]}"
        log(f"kernel differential: {name} OK")

    def cases(gen, mul, neg):
        """(p, q) host lanes: [0] inf+Q, [1] P+P, [2] P+(-P), [3] P+inf,
        [4:] generic."""
        pts = [mul(gen, int(k)) for k in rng.integers(1, 2**60, 16)]
        p = [None] + pts[:7]
        q = pts[8:16]
        q[1], q[2], q[3] = p[1], neg(p[2]), None
        return p, q

    for tag, curve, field, to_arrays, to_host, add, dbl, neg, k_add, k_mixed, k_dbl, (p, q) in (
        ("g1", G1J, FQ, g1_to_affine_arrays, g1_jac_to_host, host.g1_add, host.g1_double, host.g1_neg,
         pc.g1_add, pc.g1_add_mixed, pc.g1_double, cases(host.G1_GENERATOR, host.g1_mul, host.g1_neg)),
        ("g2", G2J, FQ2, g2_to_affine_arrays, g2_jac_to_host, host.g2_add, host.g2_double, host.g2_neg,
         pc.g2_add, pc.g2_add_mixed, pc.g2_double, cases(host.G2_GENERATOR, host.g2_mul, host.g2_neg)),
    ):
        aff_q = to_arrays(q)
        jp, jq = curve.from_affine(to_arrays(p)), curve.from_affine(aff_q)
        dp = k_dbl(field, jp, interpret)
        check(f"{tag}_double", to_host(dp), [dbl(a) for a in p])
        check(f"{tag}_add", to_host(k_add(field, jp, jq, interpret)), [add(a, b) for a, b in zip(p, q)])
        check(f"{tag}_add_mixed", to_host(k_mixed(field, jp, aff_q, interpret)), [add(a, b) for a, b in zip(p, q)])
        # Z != 1 on the left (2P from the kernel): equal and opposite
        # operands in different representations — [4] 2P + 2P,
        # [5] 2P + (-2P) — projective and mixed
        q[4], q[5] = dbl(p[4]), neg(dbl(p[5]))
        aff_q = to_arrays(q)
        want = [add(dbl(a), b) for a, b in zip(p, q)]
        check(f"{tag}_add (Z != 1)", to_host(k_add(field, dp, curve.from_affine(aff_q), interpret)), want)
        check(f"{tag}_add_mixed (Z != 1)", to_host(k_mixed(field, dp, aff_q, interpret)), want)

    n = 300  # not a multiple of the kernel tile: the pad lanes run too
    ints_a = [int.from_bytes(rng.bytes(32), "little") % PMOD for _ in range(n)]
    ints_b = [int.from_bytes(rng.bytes(32), "little") % PMOD for _ in range(n)]
    a = jnp.asarray(np.stack([int_to_limbs(x) for x in ints_a]))
    b = jnp.asarray(np.stack([int_to_limbs(x) for x in ints_b]))
    rinv = pow(MONT_R, -1, PMOD)
    got = np.asarray(mont_mul(FQ, a, b, interpret))
    check("mont_mul", [limbs_to_int(g) for g in got], [x * y * rinv % PMOD for x, y in zip(ints_a, ints_b)])
    # the 254-step fused ladder: Fermat inverse, Montgomery in and out
    a_mont = jnp.asarray(np.stack([FQ.to_mont_host(x) for x in ints_a]))
    got = np.asarray(mont_pow(FQ, a_mont, PMOD - 2, interpret))
    check("mont_pow", [FQ.from_mont_host(g) for g in got], [pow(x, PMOD - 2, PMOD) for x in ints_a])
