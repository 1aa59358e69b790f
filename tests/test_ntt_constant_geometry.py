"""The transform above 2^NTT_GATHER_LOG points (ops.ntt._ntt_constant_geometry):
the same field elements as the gather ladder and as the host's FFT, at sizes
a CPU compiles in seconds."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zkp2p_tpu.field.bn254 import R
from zkp2p_tpu.field.jfield import FR
from zkp2p_tpu.ops import ntt as jntt
from zkp2p_tpu.snark import fft_host


def _mont(xs):
    return jnp.asarray(FR.array_to_mont_host_fast(xs))


@pytest.mark.parametrize("log_m", [1, 2, 3, 4, 7])
def test_constant_geometry_is_the_gather_ladder(log_m):
    rng = random.Random(log_m)
    x = _mont([rng.randrange(R) for _ in range(1 << log_m)])
    d = jntt.domain(log_m)
    for tw in (d["tw"], d["tw_inv"]):
        want = np.asarray(jntt._ntt_core(x, tw, d["perm"]))
        assert (np.asarray(jntt._ntt_constant_geometry(x, tw)) == want).all()


@pytest.mark.parametrize("log_m", [1, 4, 5])
def test_bit_reverse_rows_is_the_permutation(log_m):
    x = jnp.arange(16 << log_m, dtype=jnp.uint32).reshape(1 << log_m, 16)
    assert (np.asarray(jntt._bit_reverse_rows(x)) == np.asarray(x)[jntt._bit_reverse_perm(1 << log_m)]).all()


@pytest.mark.parametrize("log_m", [3, 6])
def test_ntt_and_intt_above_the_gather_size_match_the_host(monkeypatch, log_m):
    """`ntt`/`intt` with the threshold under the domain: a batch axis,
    the host FFT's values, and the round trip."""
    monkeypatch.setattr(jntt, "NTT_GATHER_LOG", 2)
    rng = random.Random(log_m)
    rows = [[rng.randrange(R) for _ in range(1 << log_m)] for _ in range(2)]
    x = jnp.stack([_mont(r) for r in rows])
    got = jntt.ntt(x, log_m)
    for i, row in enumerate(rows):
        assert (np.asarray(got[i]) == FR.array_to_mont_host_fast(fft_host.ntt(row))).all()
    assert (np.asarray(jntt.intt(got, log_m)) == np.asarray(x)).all()
    assert (np.asarray(jax.vmap(lambda v: jntt.intt(v, log_m))(got)) == np.asarray(x)).all()


def test_at_or_under_the_gather_size_the_ladder_is_kept(monkeypatch):
    """The cells that were there run the program they ran: `ntt` reaches
    the gather-free transform only above NTT_GATHER_LOG (2^19)."""
    assert jntt.NTT_GATHER_LOG == 19
    monkeypatch.setattr(jntt, "NTT_GATHER_LOG", 4)
    called = []
    monkeypatch.setattr(jntt, "_ntt_constant_geometry", lambda x, tw: called.append(x.shape) or x)
    x = _mont(list(range(16)))
    jntt.ntt(x, 4)
    assert not called
    jntt.ntt(_mont(list(range(32))), 5)
    assert called == [(32, 16)]


def test_the_h_stage_above_the_gather_size_is_the_host_quotient(monkeypatch):
    """`h_evals` (matvec, three iNTT, three coset NTT) over the gather-free
    transform gives the host oracle's coset evaluations for a toy circuit."""
    from zkp2p_tpu.prover import groth16_tpu as G
    from zkp2p_tpu.snark.groth16 import coset_quotient_evals, setup
    from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem

    cs = ConstraintSystem("chain")
    out = cs.new_public("out")
    wires = [cs.new_wire() for _ in range(6)]
    for a, b, c in zip(wires, wires[1:], wires[2:]):
        cs.enforce(LC.of(a), LC.of(b), LC.of(c))
        cs.compute(c, lambda u, v: u * v % R, [a, b])
    cs.enforce(LC.of(wires[-1]), LC.of(wires[-1]), LC.of(out))
    vals = [3, 5]
    for _ in range(4):
        vals.append(vals[-2] * vals[-1] % R)
    witness = cs.witness([vals[-1] * vals[-1] % R], {wires[0]: 3, wires[1]: 5})
    pk, _vk = setup(cs)
    dpk = G.device_pk(pk, cs)
    assert dpk.log_m == 3
    monkeypatch.setattr(jntt, "NTT_GATHER_LOG", 1)
    got = G.h_evals(dpk, G.witness_to_device(witness))
    assert [FR.from_mont_host(v) for v in np.asarray(got)] == coset_quotient_evals(cs, witness)


@pytest.mark.parametrize("rows", [1, 300])
def test_the_butterfly_kernel_is_the_field_s_three_operations(rows):
    """ops.pallas_ntt.butterfly under the Pallas interpreter: a + t*b and
    a - t*b as `FR` computes them, on a row count the tile does not
    divide, with the operands that wrap (0, R - 1)."""
    from zkp2p_tpu.ops.pallas_ntt import butterfly

    rng = random.Random(rows)
    edge = [0, R - 1, 1]
    a, b, t = (_mont([edge[(i + j) % 3] if i < 3 else rng.randrange(R) for i in range(rows)]) for j in range(3))
    got_sum, got_diff = butterfly(FR, a, b, t, True)
    p = FR.mul(b, t)
    assert (np.asarray(got_sum) == np.asarray(FR.add(a, p))).all()
    assert (np.asarray(got_diff) == np.asarray(FR.sub(a, p))).all()


def test_the_butterfly_kernel_lowers_for_the_tpu():
    """Mosaic takes the kernel (no chip needed to lower), alone and under
    the batch axis the prover's vmap gives it."""
    from zkp2p_tpu.ops.pallas_ntt import butterfly

    a = jax.ShapeDtypeStruct((600, 16), jnp.uint32)
    text = butterfly.trace(FR, a, a, a, False).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 1
    batch = jax.ShapeDtypeStruct((2, 600, 16), jnp.uint32)
    batched = jax.jit(jax.vmap(lambda u, v, w: butterfly(FR, u, v, w, False), in_axes=(0, 0, None)))
    assert "tpu_custom_call" in batched.trace(batch, batch, a).lower(lowering_platforms=("tpu",)).as_text()
