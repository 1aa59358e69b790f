"""Differential test: the fused Pallas Montgomery-mul kernel vs the XLA
field layer and the host bigint oracle (interpret mode — no TPU needed).
"""

import random

import jax.numpy as jnp
import numpy as np
import pytest

from zkp2p_tpu.field.bn254 import P, R
from zkp2p_tpu.field.jfield import FQ, FR, limbs_to_int
from zkp2p_tpu.ops.pallas_mont import mont_mul

rng = random.Random(777)


@pytest.mark.parametrize("field,mod", [(FR, R), (FQ, P)], ids=["fr", "fq"])
def test_pallas_mont_matches_xla_and_host(field, mod):
    xs = [rng.randrange(mod) for _ in range(9)] + [0, 1, mod - 1]
    ys = [rng.randrange(mod) for _ in range(9)] + [mod - 1, 0, 1]
    a = jnp.asarray(np.stack([field.to_mont_host(x) for x in xs]))
    b = jnp.asarray(np.stack([field.to_mont_host(y) for y in ys]))
    got = mont_mul(field, a, b, interpret=True)
    want = field.mul(a, b)
    assert jnp.array_equal(got, want), "pallas kernel != XLA field layer"
    for i, (x, y) in enumerate(zip(xs, ys)):
        assert field.from_mont_host(np.asarray(got[i])) == x * y % mod


def test_pallas_mont_padding_and_batch_dims():
    # A batch size that is not a TILE multiple exercises the pad/unpad
    # boundary; 2D batch dims exercise the reshape path.
    xs = [rng.randrange(R) for _ in range(6)]
    ys = [rng.randrange(R) for _ in range(6)]
    a = jnp.asarray(np.stack([FR.to_mont_host(x) for x in xs])).reshape(2, 3, 16)
    b = jnp.asarray(np.stack([FR.to_mont_host(y) for y in ys])).reshape(2, 3, 16)
    got = mont_mul(FR, a, b, interpret=True)
    assert got.shape == (2, 3, 16)
    assert jnp.array_equal(got, FR.mul(a, b))


def test_pallas_mont_pow_inverse():
    """The fused square-and-multiply ladder (one kernel launch) vs the
    host Fermat inverse — the inversion primitive of the resident h
    table's build (ops.msm `_affine_multiples`, through `inv_fused`)."""
    from zkp2p_tpu.ops.pallas_mont import mont_pow

    xs = [rng.randrange(1, P) for _ in range(5)] + [1, P - 1]
    a = jnp.asarray(np.stack([FQ.to_mont_host(x) for x in xs]))
    got = mont_pow(FQ, a, P - 2, interpret=True)
    for i, x in enumerate(xs):
        assert FQ.from_mont_host(np.asarray(got[i])) == pow(x, P - 2, P)


def test_pallas_mont_pow_small_exponent():
    xs = [rng.randrange(R) for _ in range(4)]
    a = jnp.asarray(np.stack([FR.to_mont_host(x) for x in xs]))
    from zkp2p_tpu.ops.pallas_mont import mont_pow

    got = mont_pow(FR, a, 5, interpret=True)
    for i, x in enumerate(xs):
        assert FR.from_mont_host(np.asarray(got[i])) == pow(x, 5, R)


def test_pallas_mont_pow_under_vmap():
    """Exercise the pallas batching rule for the pow kernel in interpret
    mode, so a caller that vmaps `inv_fused` is not TPU-only."""
    import jax

    from zkp2p_tpu.ops.pallas_mont import mont_pow

    xs = [[rng.randrange(1, P) for _ in range(3)] for _ in range(2)]
    a = jnp.asarray(
        np.stack([np.stack([FQ.to_mont_host(x) for x in row]) for row in xs])
    )
    got = jax.vmap(lambda v: mont_pow(FQ, v, P - 2, True))(a)
    for i, row in enumerate(xs):
        for j, x in enumerate(row):
            assert FQ.from_mont_host(np.asarray(got[i, j])) == pow(x, P - 2, P)
