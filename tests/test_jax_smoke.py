"""Fast JAX-path smoke checks for the default suite.

The heavy differential files (test_jfield/test_jcurve/test_ops/
test_prover_tpu) are ZKP2P_RUN_SLOW-gated because each
costs minutes of XLA compile on a 1-core host.  This file keeps one tiny
representative of each layer in the default run: a field mul, a curve
add, and an NTT round trip — enough to catch gross breakage (wrong
Montgomery constants, broken carry ladder, bad butterfly indexing)
within seconds on a warm cache.
"""

import numpy as np

from zkp2p_tpu.field.bn254 import P, R, fr_domain_root
from zkp2p_tpu.field.jfield import FQ, FR


def test_field_mul_smoke():
    rng = np.random.default_rng(5)
    a = int.from_bytes(rng.bytes(31), "big") % R
    b = int.from_bytes(rng.bytes(31), "big") % R
    got = FR.mul(FR.to_mont_host(a)[None], FR.to_mont_host(b)[None])
    assert FR.from_mont_host(np.asarray(got)[0]) == a * b % R


def test_curve_add_smoke():
    from zkp2p_tpu.curve.host import G1_GENERATOR, g1_add, g1_mul
    from zkp2p_tpu.curve.jcurve import G1J, g1_jac_to_host, g1_to_affine_arrays

    p1 = g1_mul(G1_GENERATOR, 7)
    p2 = g1_mul(G1_GENERATOR, 11)
    a1 = G1J.from_affine(g1_to_affine_arrays([p1]))
    a2 = G1J.from_affine(g1_to_affine_arrays([p2]))
    got = g1_jac_to_host(G1J.add(a1, a2))[0]
    assert got == g1_add(p1, p2)


def test_ntt_roundtrip_smoke():
    from zkp2p_tpu.ops.ntt import intt, ntt
    from zkp2p_tpu.snark import fft_host

    log_m = 3
    m = 1 << log_m
    rng = np.random.default_rng(6)
    vals = [int.from_bytes(rng.bytes(31), "big") % R for _ in range(m)]
    x = np.stack([FR.to_mont_host(v) for v in vals])
    got = ntt(np.asarray(x), log_m)
    want = fft_host.ntt(vals)
    assert [FR.from_mont_host(r) for r in np.asarray(got)] == want
    back = intt(got, log_m)
    assert [FR.from_mont_host(r) for r in np.asarray(back)] == vals


def test_limb_major_conv_matches_matmul_path():
    """Both _mul_wide layouts are bit-exact vs the host oracle and each
    other (CONV_LAYOUT is a pure perf knob)."""
    from zkp2p_tpu.field import jfield

    rng = np.random.default_rng(9)
    vals = [(int.from_bytes(rng.bytes(31), "big") % R, int.from_bytes(rng.bytes(31), "big") % R) for _ in range(8)]
    a = np.stack([FR.to_mont_host(x) for x, _ in vals])
    b = np.stack([FR.to_mont_host(y) for _, y in vals])
    saved = jfield.CONV_LAYOUT
    try:
        jfield.CONV_LAYOUT = "matmul"
        got_m = np.asarray(FR.mul(a, b))
        jfield.CONV_LAYOUT = "limb_major"
        got_l = np.asarray(FR.mul(a, b))
    finally:
        jfield.CONV_LAYOUT = saved
    np.testing.assert_array_equal(got_m, got_l)
    for i, (x, y) in enumerate(vals):
        assert FR.from_mont_host(got_l[i]) == x * y % R


def test_limb_major_reduce_wide_and_addsub():
    """The non-mul users of _mul_wide (Montgomery reduction, sub borrow
    chains) also agree across layouts."""
    from zkp2p_tpu.field import jfield
    from zkp2p_tpu.field.jfield import reduce_wide

    rng = np.random.default_rng(11)
    wide_vals = [int.from_bytes(rng.bytes(60), "big") for _ in range(4)]
    arr = np.stack(
        [np.array([(v >> (16 * i)) & 0xFFFF for i in range(30)], dtype=np.uint32) for v in wide_vals]
    )
    from zkp2p_tpu.field.jfield import limbs_to_int

    saved = jfield.CONV_LAYOUT
    try:
        jfield.CONV_LAYOUT = "limb_major"
        got = np.asarray(reduce_wide(FR, arr))
    finally:
        jfield.CONV_LAYOUT = saved
    for i, v in enumerate(wide_vals):
        assert limbs_to_int(got[i]) == v % R
