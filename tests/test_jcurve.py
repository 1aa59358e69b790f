"""Vectorised G1/G2 projective ops vs the host curve oracle.

Differential testing mirrors the reference's trust chain: snarkjs point ops
are checked against the EVM precompiles on-chain; here the TPU lanes are
checked against `zkp2p_tpu.curve.host` (itself pairing-tested)."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zkp2p_tpu.curve import host
from zkp2p_tpu.curve.host import (
    G1_GENERATOR,
    G2_GENERATOR,
    g1_add,
    g1_double,
    g1_mul,
    g1_neg,
    g2_add,
    g2_double,
    g2_mul,
    g2_neg,
)
from zkp2p_tpu.curve.jcurve import (
    G1J,
    G2J,
    g1_jac_to_host,
    g1_to_affine_arrays,
    g2_jac_to_host,
    g2_to_affine_arrays,
    scalar_bit_planes,
)
from zkp2p_tpu.field.bn254 import R

# XLA-compile-heavy: opt-in via ZKP2P_RUN_SLOW=1 (default suite must stay
# minutes on a 1-core host; the dryrun/bench paths exercise this code too)
pytestmark = pytest.mark.slow

rng = random.Random(99)


def rand_g1(n):
    return [g1_mul(G1_GENERATOR, rng.randrange(1, R)) for _ in range(n)]


def rand_g2(n):
    return [g2_mul(G2_GENERATOR, rng.randrange(1, R)) for _ in range(n)]


CASES = [
    ("g1", G1J, rand_g1, g1_to_affine_arrays, g1_jac_to_host, g1_add, g1_double, g1_mul, g1_neg),
    ("g2", G2J, rand_g2, g2_to_affine_arrays, g2_jac_to_host, g2_add, g2_double, g2_mul, g2_neg),
]


@pytest.mark.parametrize(
    "curve,to_arrays,to_host,h_add,h_double,h_mul,h_neg,mk",
    [(c[1], c[3], c[4], c[5], c[6], c[7], c[8], c[2]) for c in CASES],
    ids=[c[0] for c in CASES],
)
def test_add_double_cases(curve, to_arrays, to_host, h_add, h_double, h_mul, h_neg, mk):
    pts = mk(4)
    # Lane layout exercises every branch of the complete adder:
    # random+random, P+P, P+(-P) (infinity), inf+Q, P+inf, inf+inf.
    a_pts = [pts[0], pts[1], pts[2], None, pts[3], None]
    b_pts = [pts[1], pts[1], h_neg(pts[2]), pts[0], None, None]
    a = curve.from_affine(to_arrays(a_pts))
    b = curve.from_affine(to_arrays(b_pts))

    got = to_host(jax.jit(curve.add)(a, b))
    want = [h_add(x, y) for x, y in zip(a_pts, b_pts)]
    assert got == want

    got_mixed = to_host(jax.jit(curve.add_mixed)(a, to_arrays(b_pts)))
    assert got_mixed == want

    got_dbl = to_host(jax.jit(curve.double)(a))
    assert got_dbl == [h_double(x) for x in a_pts]


@pytest.mark.parametrize(
    "curve,to_arrays,to_host,h_mul,mk",
    [(c[1], c[3], c[4], c[7], c[2]) for c in CASES],
    ids=[c[0] for c in CASES],
)
def test_scalar_mul_batch(curve, to_arrays, to_host, h_mul, mk):
    n = 4
    pts = mk(n)
    scalars = [rng.randrange(R) for _ in range(n - 2)] + [0, 1]
    p = curve.from_affine(to_arrays(pts))
    bits = scalar_bit_planes(scalars)
    got = to_host(jax.jit(curve.scalar_mul)(p, bits))
    assert got == [h_mul(pt, k) for pt, k in zip(pts, scalars)]


def test_g1_add_associativity_device_only():
    """(A+B)+C == A+(B+C) computed entirely on device."""
    pts = rand_g1(3)
    arrs = [G1J.from_affine(g1_to_affine_arrays([p])) for p in pts]
    lhs = G1J.add(G1J.add(arrs[0], arrs[1]), arrs[2])
    rhs = G1J.add(arrs[0], G1J.add(arrs[1], arrs[2]))
    assert g1_jac_to_host(lhs) == g1_jac_to_host(rhs)
