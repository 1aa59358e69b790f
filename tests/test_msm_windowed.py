"""Windowed MSM fast path vs host oracle (one compiled shape)."""

import random

import jax
import numpy as np
import pytest

from zkp2p_tpu.curve.host import G1_GENERATOR, G2_GENERATOR, g1_msm, g1_mul, g2_msm, g2_mul
from zkp2p_tpu.curve.jcurve import (
    G1J,
    G2J,
    g1_jac_to_host,
    g1_to_affine_arrays,
    g2_jac_to_host,
    g2_to_affine_arrays,
)
from zkp2p_tpu.field.bn254 import R
from zkp2p_tpu.field.jfield import FR
from zkp2p_tpu.ops import msm as jmsm

# XLA-compile-heavy: opt-in via ZKP2P_RUN_SLOW=1 (default suite must stay
# minutes on a 1-core host; the dryrun/bench paths exercise this code too)
pytestmark = pytest.mark.slow

rng = random.Random(21)


def _limbs(scalars):
    import jax.numpy as jnp

    return jnp.asarray(np.stack([FR.to_std_host(s) for s in scalars]))


def test_msm_windowed_g1_vs_host():
    n = 29
    pts = [g1_mul(G1_GENERATOR, rng.randrange(1, R)) for _ in range(n)]
    scalars = [rng.randrange(R) for _ in range(n)]
    pts[1] = None
    scalars[2] = 0
    pts[4] = pts[3]
    planes = jmsm.digit_planes_from_limbs(_limbs(scalars))
    got = g1_jac_to_host(
        jax.jit(lambda b, p: jmsm.msm_windowed(G1J, b, p, lanes=8))(g1_to_affine_arrays(pts), planes)
    )[0]
    assert got == g1_msm(pts, scalars)


def test_msm_windowed_g2_vs_host():
    n = 6
    pts = [g2_mul(G2_GENERATOR, rng.randrange(1, R)) for _ in range(n)]
    scalars = [rng.randrange(R) for _ in range(n)]
    planes = jmsm.digit_planes_from_limbs(_limbs(scalars))
    got = g2_jac_to_host(jmsm.msm_windowed(G2J, g2_to_affine_arrays(pts), planes, lanes=8))[0]
    assert got == g2_msm(pts, scalars)


def test_digit_planes_shape_and_values():
    s = 0x1234567890ABCDEF
    planes = np.asarray(jmsm.digit_planes_from_limbs(_limbs([s])))
    assert planes.shape == (64, 1)
    # digit k (MSB-first) = nibble (63-k) of the scalar
    for k in range(64):
        assert planes[k, 0] == (s >> (4 * (63 - k))) & 0xF


def test_msm_windowed_g1_w8_vs_host():
    """window=8 (the mesh road's unsigned formulation at the wider
    window): the halved digit-plane count and 255-entry table must stay
    bit-exact."""
    n = 21
    pts = [g1_mul(G1_GENERATOR, rng.randrange(1, R)) for _ in range(n)]
    scalars = [rng.randrange(R) for _ in range(n)]
    pts[0] = None
    scalars[5] = 0
    planes = jmsm.digit_planes_from_limbs(_limbs(scalars), window=8)
    assert planes.shape[0] == 32
    got = g1_jac_to_host(
        jax.jit(lambda b, p: jmsm.msm_windowed(G1J, b, p, lanes=8, window=8))(
            g1_to_affine_arrays(pts), planes
        )
    )[0]
    assert got == g1_msm(pts, scalars)


def test_digit_planes_w8_values():
    s = 0x1234567890ABCDEF
    planes = np.asarray(jmsm.digit_planes_from_limbs(_limbs([s]), window=8))
    assert planes.shape == (32, 1)
    for k in range(32):
        assert planes[k, 0] == (s >> (8 * (31 - k))) & 0xFF


def test_msm_windowed_signed_g1_vs_host():
    """Signed digit recoding (the default prover path): half-size table,
    Y-negation on negative digits — must stay bit-exact vs the host MSM."""
    n = 23
    pts = [g1_mul(G1_GENERATOR, rng.randrange(1, R)) for _ in range(n)]
    scalars = [rng.randrange(R) for _ in range(n)]
    pts[2] = None
    scalars[3] = 0
    for w in (4, 8):
        mags, negs = jmsm.signed_digit_planes_from_limbs(_limbs(scalars), w)
        got = g1_jac_to_host(
            jax.jit(lambda b, m, s, w=w: jmsm.msm_windowed_signed(G1J, b, m, s, lanes=8, window=w))(
                g1_to_affine_arrays(pts), mags, negs
            )
        )[0]
        assert got == g1_msm(pts, scalars), f"window {w}"


def test_msm_windowed_signed_g2_vs_host():
    n = 5
    pts = [g2_mul(G2_GENERATOR, rng.randrange(1, R)) for _ in range(n)]
    scalars = [rng.randrange(R) for _ in range(n)]
    mags, negs = jmsm.signed_digit_planes_from_limbs(_limbs(scalars), 4)
    got = g2_jac_to_host(jmsm.msm_windowed_signed(G2J, g2_to_affine_arrays(pts), mags, negs, lanes=8, window=4))[0]
    assert got == g2_msm(pts, scalars)
