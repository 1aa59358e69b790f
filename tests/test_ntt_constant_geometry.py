"""The ladder without gathers (ops.ntt._ntt_constant_geometry), which `ntt`
and `intt` run at every size: the same field elements as the gather ladder
and as the host's FFT, alone and under the prover's batch axis, at sizes a
CPU compiles in seconds."""

import functools
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
def test_ntt_and_intt_match_the_host_under_a_batch_axis(log_m):
    """`ntt`/`intt` as the prover calls them: a batch axis, the host FFT's
    values, and the round trip."""
    rng = random.Random(log_m)
    rows = [[rng.randrange(R) for _ in range(1 << log_m)] for _ in range(2)]
    x = jnp.stack([_mont(r) for r in rows])
    got = jntt.ntt(x, log_m)
    for i, row in enumerate(rows):
        assert (np.asarray(got[i]) == FR.array_to_mont_host_fast(fft_host.ntt(row))).all()
    assert (np.asarray(jntt.intt(got, log_m)) == np.asarray(x)).all()
    assert (np.asarray(jax.vmap(lambda v: jntt.intt(v, log_m))(got)) == np.asarray(x)).all()


@pytest.mark.parametrize("chunk", [1, 4])
@pytest.mark.parametrize("log_m", [16, 19, 22])
def test_the_served_sizes_take_the_ladder_the_chip_chose(monkeypatch, log_m, chunk):
    """sha2b (2^16), venmo-256-192 (2^19) and email-1024-1536 (2^22), a
    proof at a time and a chunk of four: the A/B on the chip read the
    gather-free ladder faster at every one (PERF.md, PR 27), so the
    transform reaches it alone, once for the whole chunk, and LADDER, which
    the `h_planes` span carries, names it.  Shapes only: nothing runs."""
    ran = []
    monkeypatch.setattr(jntt, "_ntt_core", lambda *a: pytest.fail("the transform took the gather ladder"))
    monkeypatch.setattr(jntt, "_ntt_constant_geometry", lambda x, tw: ran.append(x.shape) or x)
    m, u32 = 1 << log_m, jnp.uint32
    out = jax.eval_shape(jntt._transform, jax.ShapeDtypeStruct((chunk, m, 16), u32), jax.ShapeDtypeStruct((m // 2, 16), u32))
    assert out.shape == (chunk, m, 16) and ran == [(m, 16)]  # vmapped over the chunk: one trace
    assert jntt.LADDER == "constant_geometry"


def test_a_domain_of_one_point_is_left_as_it_is():
    """Both ladders, as `ntt` at log_m = 0 needs of whichever it runs."""
    x = _mont([R - 2])
    assert (np.asarray(jntt._ntt_constant_geometry(x, jnp.zeros((0, 16), jnp.uint32))) == np.asarray(x)).all()
    assert (np.asarray(jntt._ntt_core(x, jnp.zeros((0, 16), jnp.uint32), np.zeros(1, np.int64))) == np.asarray(x)).all()


@functools.lru_cache(maxsize=None)
def _chain_circuit():
    from zkp2p_tpu.prover import groth16_tpu as G
    from zkp2p_tpu.snark.groth16 import setup
    from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem

    cs = ConstraintSystem("chain")
    out = cs.new_public("out")
    wires = [cs.new_wire() for _ in range(6)]
    for a, b, c in zip(wires, wires[1:], wires[2:]):
        cs.enforce(LC.of(a), LC.of(b), LC.of(c))
        cs.compute(c, lambda u, v: u * v % R, [a, b])
    cs.enforce(LC.of(wires[-1]), LC.of(wires[-1]), LC.of(out))
    pk, _vk = setup(cs)
    dpk = G.device_pk(pk, cs)
    assert dpk.log_m == 3
    return cs, wires, dpk


def _chain_world(starts):
    """A chain of products over a 2^3 domain: (cs, device key, one witness
    a pair of starting values)."""
    cs, wires, dpk = _chain_circuit()
    witnesses = []
    for u, v in starts:
        vals = [u, v]
        for _ in range(4):
            vals.append(vals[-2] * vals[-1] % R)
        witnesses.append(cs.witness([vals[-1] * vals[-1] % R], {wires[0]: u, wires[1]: v}))
    return cs, dpk, witnesses


def test_the_h_stage_is_the_host_quotient():
    """`h_evals` (matvec, three iNTT, three coset NTT) over the gather-free
    transform gives the host oracle's coset evaluations for a toy circuit."""
    from zkp2p_tpu.prover import groth16_tpu as G
    from zkp2p_tpu.snark.groth16 import coset_quotient_evals

    cs, dpk, (witness,) = _chain_world([(3, 5)])
    got = G.h_evals(dpk, G.witness_to_device(witness))
    assert [FR.from_mont_host(v) for v in np.asarray(got)] == coset_quotient_evals(cs, witness)


def test_the_h_stage_under_the_batch_axis_is_the_host_quotient_of_each_witness():
    """The prover's own batching, `jax.vmap(h_evals, in_axes=(None, 0))`
    (`_h_and_planes`' vmap, `_h_pod_fn`), over the gather-free
    ladder: every witness of a chunk of four gets its own quotient."""
    from zkp2p_tpu.prover import groth16_tpu as G
    from zkp2p_tpu.snark.groth16 import coset_quotient_evals

    cs, dpk, witnesses = _chain_world([(3, 5), (2, 7), (R - 1, 11), (1, 1)])
    w = jnp.stack([G.witness_to_device(wit) for wit in witnesses])
    got = np.asarray(jax.vmap(G.h_evals, in_axes=(None, 0))(dpk, w))
    for row, wit in zip(got, witnesses):
        assert [FR.from_mont_host(v) for v in row] == coset_quotient_evals(cs, wit)


@pytest.mark.parametrize("butterfly", ["xla", "interpret"])
@pytest.mark.parametrize("log_m", [2, 5])
def test_the_transform_vmapped_by_four_is_four_single_ones_and_the_host_s(monkeypatch, log_m, butterfly):
    """A chunk of four through one ladder (the batch axis JAX's rules give
    the slices, the interleave and the kernel call) against the same
    ladder a vector at a time and the host's FFT; with the field's XLA
    operations and with the Pallas butterfly under the interpreter."""
    if butterfly == "interpret":
        from zkp2p_tpu.ops.pallas_ntt import butterfly as kernel

        monkeypatch.setattr(jntt, "_butterfly", lambda a, b, t: kernel(FR, a, b, t, True))
    ladder = jntt._ntt_constant_geometry.__wrapped__  # traced afresh: the jit's cache holds the XLA butterfly
    rng = random.Random(4 * log_m)
    rows = [[rng.randrange(R) for _ in range(1 << log_m)] for _ in range(4)]
    rows[0][:2] = [0, R - 1]
    x = jnp.stack([_mont(r) for r in rows])
    tw = jntt.domain(log_m)["tw"]
    got = np.asarray(jax.jit(jax.vmap(ladder, in_axes=(0, None)))(x, tw))
    single = jax.jit(ladder)
    for i, row in enumerate(rows):
        assert (got[i] == np.asarray(single(x[i], tw))).all()
        assert (got[i] == FR.array_to_mont_host_fast(fft_host.ntt(row))).all()


def _spy_on_the_ladders(monkeypatch):
    ran = []
    core, gather_free = jntt._ntt_core, jntt._ntt_constant_geometry
    monkeypatch.setattr(jntt, "_ntt_core", lambda *a: ran.append("gather") or core(*a))
    monkeypatch.setattr(jntt, "_ntt_constant_geometry", lambda *a: ran.append("constant_geometry") or gather_free(*a))
    return ran


def test_the_h_planes_span_names_the_ladder_that_ran(monkeypatch):
    """`tpu/prove_batch/stage/h_planes` carries `ntt`: the real h program of
    a batch of two runs its six transforms through the ladder the span
    names (the MSMs stood in for: they compile for minutes on XLA:CPU)."""
    import dataclasses

    from zkp2p_tpu.prover import groth16_tpu as G
    from zkp2p_tpu.utils import trace as tr

    _cs, dpk, witnesses = _chain_world([(3, 5), (2, 7)])
    none = jnp.zeros((0,), jnp.int32)  # no narrow class: one MSM a query, no curve add to compile
    dpk = dataclasses.replace(
        dpk, a_nsel=none, b_nsel=none, c_nsel=none, a_wsel=jnp.arange(dpk.n_wires, dtype=jnp.int32),
        b_wsel=jnp.arange(dpk.b_sel.shape[0], dtype=jnp.int32), c_wsel=jnp.arange(dpk.c_sel.shape[0], dtype=jnp.int32))
    ran = _spy_on_the_ladders(monkeypatch)

    def infinity(limbs):
        return lambda bases, planes: tuple(
            np.zeros((jax.tree_util.tree_leaves(planes)[0].shape[0],) + limbs, np.uint32) for _ in range(3))

    monkeypatch.setenv("ZKP2P_TPU_SHARD", "off")
    monkeypatch.setattr(G, "BATCH_CHUNK", "0")
    monkeypatch.setattr(G, "_h_table_window", lambda log_m, device=None, mesh=None: None)
    monkeypatch.setattr(G, "_jit_h_planes", jax.jit(jax.vmap(G._h_and_planes, in_axes=(None, 0, None)), static_argnums=2))
    monkeypatch.setattr(G, "_jit_msm_g1", infinity((16,)))
    monkeypatch.setattr(G, "_jit_msm_g2", infinity((2, 16)))
    monkeypatch.setattr(G, "_assemble", lambda dpk_, acc, r, s: acc)
    tr.reset()
    assert len(G.prove_tpu_batch(dpk, witnesses, rs=[1, 2], ss=[3, 4])) == 2
    (h_stage,) = [r for r in tr.records() if r["stage"].endswith("/stage/h_planes")]
    assert h_stage["ntt"] == jntt.LADDER
    assert ran == [jntt.LADDER] * 6  # three iNTTs and three coset NTTs, traced once for the chunk
    assert all("ntt" not in r for r in tr.records() if not r["stage"].endswith("/stage/h_planes"))
    tr.reset()


def test_the_mesh_road_s_h_planes_span_names_the_ladder_too(monkeypatch):
    """`_prove_batch_sharded` on the 1x4 virtual mesh: its h stage is
    `h_evals` vmapped inside a shard_map (`_h_pod_fn`), the same
    transforms, and its span says so (the pod MSMs stood in for)."""
    from zkp2p_tpu.curve.jcurve import G2J
    from zkp2p_tpu.parallel import mesh as pmesh
    from zkp2p_tpu.prover import groth16_tpu as G
    from zkp2p_tpu.utils import trace as tr

    _cs, dpk, witnesses = _chain_world([(3, 5), (2, 7), (R - 1, 11), (1, 1)])
    ran = _spy_on_the_ladders(monkeypatch)

    def infinity(curve, bases, planes, mesh, **kw):
        return tuple(np.zeros((planes[0][0].shape[0],) + ((2, 16) if curve is G2J else (16,)), np.uint32) for _ in range(3))

    monkeypatch.setenv("ZKP2P_TPU_SHARD", "on")
    monkeypatch.setenv("ZKP2P_TPU_MESH", "1x4")
    monkeypatch.setattr(G, "BATCH_CHUNK", "0")
    monkeypatch.setattr(pmesh, "msm_pod_batched", infinity)
    monkeypatch.setattr(G, "_assemble", lambda dpk_, acc, r, s: acc)
    G._h_pod_fn.cache_clear()  # traced here, under the spies
    tr.reset()
    try:
        assert len(G.prove_tpu_batch(dpk, witnesses, rs=[1, 2, 3, 4], ss=[5, 6, 7, 8])) == 4
    finally:
        G._h_pod_fn.cache_clear()
    (h_stage,) = [r for r in tr.records() if r["stage"].endswith("/stage/h_planes")]
    assert h_stage["ntt"] == jntt.LADDER and ran == [jntt.LADDER] * 6
    tr.reset()


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
