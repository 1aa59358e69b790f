"""One proof's h stage shared by the chips of its group
(`prover.groth16_tpu._h_shard_fn`, `parallel/ntt.py`): on the CPU's
virtual devices, bit-equal to `h_evals` on one device and to the tests'
oracles — the host's coset quotient (`snark.groth16.coset_quotient_evals`,
Python integers) at 2^10 and 2^12 and `h_evals` through `_ntt_core`'s
gather ladder at 2^10, on a chain of products — and to `h_evals` at 2^16
on `sha2b`; then a chunk
of one on 1x4 through `prove_tpu_batch`, the h stage and the exchange the
real programs and the pod MSMs the host's, byte-equal to `prove_host` and
to `prove_native` under pinned (r, s)."""

import dataclasses
import functools
import random
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zkp2p_tpu.field.bn254 import R
from zkp2p_tpu.field.jfield import FR
from zkp2p_tpu.ops import ntt as jntt
from zkp2p_tpu.prover import groth16_tpu as G
from zkp2p_tpu.snark.groth16 import coset_quotient_evals, domain_size_for, qap_rows
from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem


def _mesh(b, s):
    from zkp2p_tpu.parallel.mesh import make_pod_mesh

    return make_pod_mesh(b, s, names=("batch", "shard"))


def _rows_key(cs):
    """What the h stage reads of a key: the domain and the QAP rows."""
    rows, m = qap_rows(cs), domain_size_for(cs)
    arrays = G._rows_to_arrays([t[0] for t in rows], m) + G._rows_to_arrays([t[1] for t in rows], m)
    return SimpleNamespace(log_m=m.bit_length() - 1, **dict(zip(G._QAP_ROWS, arrays)))


@functools.lru_cache(maxsize=None)
def _chain(n):
    """c = (a + b) * b down a chain of `n` wires, squared into the public
    output: `n` - 1 constraints, two entries in every A row."""
    cs = ConstraintSystem("chain")
    out = cs.new_public("out")
    wires = [cs.new_wire() for _ in range(n)]
    for a, b, c in zip(wires, wires[1:], wires[2:]):
        cs.enforce(LC.of(a) + LC.of(b), LC.of(b), LC.of(c))
        cs.compute(c, lambda u, v: (u + v) * v % R, [a, b])
    cs.enforce(LC.of(wires[-1]), LC.of(wires[-1]), LC.of(out))
    return cs, wires


def _chain_witness(n, u, v):
    cs, wires = _chain(n)
    vals = [u, v]
    for _ in range(n - 2):
        vals.append((vals[-2] + vals[-1]) * vals[-1] % R)
    return cs.witness([vals[-1] * vals[-1] % R], {wires[0]: u, wires[1]: v})


def _sha2b_world(n_wits):
    from zkp2p_tpu.models.registry import build_sha2b

    cs, _ = build_sha2b()
    wires, rng = sorted(cs.input_wires), random.Random(37)
    return cs, [cs.witness([], dict(zip(wires, (rng.randrange(256) for _ in wires)))) for _ in range(n_wits)]


def _shared_h(key, mesh, limbs):
    n_ici = mesh.shape["shard"]
    (a_starts, a_most), (b_starts, b_most) = G._row_blocks(key, n_ici)
    rows = tuple(getattr(key, f) for f in G._QAP_ROWS)
    h, done = G._h_shard_fn(mesh, key.log_m, (a_most, b_most))(rows, (a_starts, b_starts), limbs)
    assert done.shape == (limbs.shape[0], n_ici)
    for shard in h.addressable_shards:  # a chip its block of columns of each of its group's proofs
        assert shard.data.shape == (limbs.shape[0] // mesh.shape["batch"], (1 << key.log_m) // n_ici, 16)
    return np.asarray(h)


def _one_device_h(key, limbs):
    return np.asarray(FR.from_mont(jax.vmap(G.h_evals, in_axes=(None, 0))(key, FR.to_mont(jnp.asarray(limbs)))))


def _std_limbs(values):
    from zkp2p_tpu.native.lib import _scalars_to_u64, _u64_to_limbs16

    return _u64_to_limbs16(_scalars_to_u64(values))


@pytest.mark.parametrize("world,mesh_shape,n_wits", [
    ("chain-2^10", (1, 4), 1),   # the cell's shape: a batch of one on 1x4
    ("chain-2^12", (2, 2), 2),   # a proof a group, two chips a group
    ("chain-2^10", (1, 8), 3),   # three proofs eight chips do not divide
    ("sha2b", (1, 4), 1),        # 2^16, the SHA gadget's rows
])
def test_the_shared_h_stage_is_h_evals_bit_for_bit_and_the_oracles(monkeypatch, world, mesh_shape, n_wits):
    if world == "sha2b":
        cs, wits = _sha2b_world(n_wits)
    else:
        n = {"chain-2^10": 1000, "chain-2^12": 4000}[world]
        cs, wits = _chain(n)[0], [_chain_witness(n, 3 + i, R - 5 - i) for i in range(n_wits)]
    key = _rows_key(cs)
    assert key.log_m == {"chain-2^10": 10, "chain-2^12": 12, "sha2b": 16}[world]
    limbs = np.stack([G._witness_std_limbs(w) for w in wits])
    got = _shared_h(key, _mesh(*mesh_shape), limbs)
    assert got.shape == (n_wits, 1 << key.log_m, 16) and got.any()
    assert (got == _one_device_h(key, limbs)).all()
    if world != "sha2b":  # the Python quotient takes minutes at 2^16
        for h, wit in zip(got, wits):
            assert (h == _std_limbs(coset_quotient_evals(cs, wit))).all()
    if world == "chain-2^10":  # and `h_evals` through the gather ladder, the constant-geometry ladder's own oracle
        d = jntt.domain(key.log_m)
        monkeypatch.setattr(jntt, "_transform", lambda x, tw: jax.vmap(
            lambda v: jntt._ntt_core(v, tw, d["perm"]))(x.reshape((-1,) + x.shape[-2:])).reshape(x.shape))
        assert (got == _one_device_h(key, limbs)).all()


def test_a_block_s_matvec_drops_its_neighbours_entries():
    """`_matvec_block` slices `most` entries from a block's first; at the
    arrays' end the slice is clamped back into the block before, whose
    entries are dropped by their row ids: the last block, and an empty
    one, still sum exactly their own rows."""
    rng = random.Random(5)
    m, n_wires, n_ici = 32, 7, 4
    row = np.sort(np.array([rng.randrange(0, 20) for _ in range(40)], dtype=np.int32))  # rows 20..31 are empty
    wire = np.array([rng.randrange(n_wires) for _ in row], dtype=np.int32)
    coeff, w = [rng.randrange(R) for _ in row], [rng.randrange(R) for _ in range(n_wires)]
    key = SimpleNamespace(log_m=5, a_row=row, b_row=row)
    (starts, most), _ = G._row_blocks(key, n_ici)
    assert list(starts) == [int(np.searchsorted(row, c * 8)) for c in range(n_ici)] and most == max(np.bincount(row // 8))
    want = [0] * m
    for c, j, r in zip(coeff, wire, row):
        want[r] = (want[r] + c * w[j]) % R
    co, w_mont = jnp.asarray(FR.array_to_mont_host_fast(coeff)), jnp.asarray(FR.array_to_mont_host_fast(w))
    for c in range(n_ici):
        got = G._matvec_block(co, jnp.asarray(wire), jnp.asarray(row), int(starts[c]), most, w_mont, c * 8, 8)
        assert [FR.from_mont_host(v) for v in np.asarray(got)] == want[c * 8:(c + 1) * 8]


@pytest.mark.parametrize("n_proofs", [1, 4], ids=["a-batch-of-one", "a-batch-of-four"])
def test_a_chunk_of_one_on_1x4_proves_the_bytes_of_the_host_and_the_native_prover(monkeypatch, n_proofs):
    """`prove_tpu_batch` with ZKP2P_TPU_MESH=1x4 and one witness: the key
    placed on the mesh in its classes, the shared h stage and the exchange
    the real programs, each pod MSM answered on the host from the signed
    digit planes the exchange left on the chips and the placed key's
    classes of bases (the programs of the curve compile for minutes on
    XLA:CPU).  The proof is `prove_host`'s and `prove_native`'s for the
    same (witness, r, s), the `h_planes` span says four chips shared it,
    and the chunk was not exchanged.  And a batch of four: one proof's h
    stage a chip (`_h_pod_fn`, the real program), exchanged over the
    chips, the same bytes."""
    from test_mesh_exchange import host_pod_msm

    from zkp2p_tpu.parallel import mesh as pmesh
    from zkp2p_tpu.prover.native_prove import prove_native
    from zkp2p_tpu.snark.groth16 import prove_host, setup
    from zkp2p_tpu.utils import trace as tr
    from zkp2p_tpu.utils.audit import gate_arms

    n = 40
    cs, _wires = _chain(n)
    pk, _vk = setup(cs)
    dpk = G.device_pk(pk, cs)
    assert dpk.log_m == 6 and int(dpk.a_nsel.shape[0]) > 0  # a key with a narrow class
    wits = [_chain_witness(n, 7 + 3 * i, R - 11 - i) for i in range(n_proofs)]
    monkeypatch.setenv("ZKP2P_TPU_SHARD", "on")
    monkeypatch.setenv("ZKP2P_TPU_MESH", "1x4")
    monkeypatch.setattr(G, "BATCH_CHUNK", "0")
    monkeypatch.setattr(pmesh, "msm_pod_batched", host_pod_msm)
    other = "_h_pod_fn" if n_proofs == 1 else "_h_shard_fn"
    monkeypatch.setattr(G, other, lambda *a: pytest.fail(f"a chunk of {n_proofs} took the other form of the h stage"))
    rs, ss = [1234567 + i for i in range(n_proofs)], [R - 7654321 - i for i in range(n_proofs)]
    tr.reset()
    got = G.prove_tpu_batch(dataclasses.replace(dpk), wits, rs=rs, ss=ss)
    assert gate_arms()["tpu_shard"] == "1x4"
    assert got == [prove_host(pk, cs, w, r=r, s=s) for w, r, s in zip(wits, rs, ss)]
    native = prove_native(dpk, wits[0], rs[0], ss[0])
    assert native is None or got[0] == native  # None: the native library did not build
    (h_stage,) = [rec for rec in tr.records() if rec["stage"].endswith("/stage/h_planes")]
    (exchange,) = [rec for rec in tr.records() if rec["stage"].endswith("/stage/exchange")]
    if n_proofs == 1:
        assert h_stage["h_shards"] == 4 and h_stage["proofs_a_chip"] == 1
        assert h_stage["ici_bytes"] == G.h_ici_bytes(G._shard_mesh(), 1, dpk.log_m) == 6 * 3 * (64 << 6)
        assert exchange["bytes"] == 0
    else:
        assert "h_shards" not in h_stage and h_stage["proofs_a_chip"] == 1 and exchange["bytes"] > 0
    tr.reset()


GIB = 1 << 30


@pytest.mark.parametrize("limit", [16 * GIB, int(15.75 * GIB)])
@pytest.mark.parametrize("log_m,one_chip,on_1x4", [(16, 4, 4), (19, 4, 4), (22, 1, 4), (23, None, 1)])
def test_the_chunk_rule_plans_the_fullest_chip_of_a_placement(log_m, one_chip, on_1x4, limit):
    """`batch_chunk_for` for one shard and for four: the one-chip answers
    are what they were (4, 4, 1), a 2^23 key fits no single chip and says
    so with the bytes, and on 1x4 it takes a chunk of one, whose h stage
    the chips share; what the rule plans fits under HBM_PLAN_FRACTION and
    the next chunk up does not."""
    if one_chip is None:
        with pytest.raises(G.KeyDoesNotFit, match=r"2\^23 domain points fits no chunk on 1x1 devices.*plans 21474836480 B"):
            G.batch_chunk_for(log_m, limit)
        assert G.key_arrays_home(log_m) is np.asarray  # such a key waits on the host for a mesh
    else:
        assert G.batch_chunk_for(log_m, limit) == G.batch_chunk_for(log_m, limit, 1, 1) == one_chip
        assert G.key_arrays_home(log_m) is jnp.asarray
    assert G.batch_chunk_for(log_m, limit, 1, 4) == on_1x4
    budget = G.HBM_PLAN_FRACTION * limit
    assert G.chip_bytes_a_point(on_1x4, 1, 4) * (1 << log_m) <= budget
    assert on_1x4 == G.BATCH_CHUNK_MAX or G.chip_bytes_a_point(2 * on_1x4, 1, 4) * (1 << log_m) > budget
    assert G.chip_bytes_a_point(4) == G.work_bytes_a_point(4) == 7 << 10  # one chip: the rule PR 26 wrote


def test_a_key_that_fits_no_placement_is_an_error_that_names_the_bytes(monkeypatch):
    with pytest.raises(G.KeyDoesNotFit, match=r"2\^24 domain points fits no chunk on 1x4 devices of 17179869184 B: a chunk of 1 plans"):
        G.batch_chunk_for(24, 16 * GIB, 1, 4)
    with pytest.raises(G.KeyDoesNotFit, match="2x2 devices"):  # a proof a group is the least: 2x2 has no chunk of one
        G.batch_chunk_for(23, 16 * GIB, 2, 2)
    # the gate takes the mesh's rule where the batch takes the mesh road, and records its answer
    from zkp2p_tpu.utils import audit

    monkeypatch.setattr(G, "_on_tpu", lambda: True)
    monkeypatch.setattr(G, "BATCH_CHUNK", "auto")
    assert G._batch_chunk_size(23, None, _mesh(1, 4)) == 1 and audit.gate_arms()["batch_chunk"] == "1"
    assert G._batch_chunk_size(22, None, _mesh(1, 4)) == 4 and G._batch_chunk_size(22) == 1
    with pytest.raises(G.KeyDoesNotFit):
        G._batch_chunk_size(23)


def test_a_key_on_the_host_reaches_the_mesh_a_shard_a_chip_and_round_trips(tmp_path, monkeypatch):
    """A key no single chip can prove from is numpy on the host as
    `load_dpk` hands it over (`key_arrays_home`; steered here by the
    limit the rule reads, for a toy key): it is pinned nowhere, placed on
    a mesh it is, shard for shard, what the same key placed from a device
    is, and `prove_native` reads it as it is."""
    from test_tpu_shard import build_toy

    from zkp2p_tpu.prover.keycache import load_dpk, save_dpk
    from zkp2p_tpu.prover.native_prove import prove_native
    from zkp2p_tpu.snark.groth16 import setup

    cs, _out, x, y = build_toy()
    pk, vk = setup(cs)
    dpk = G.device_pk(pk, cs)
    path = str(tmp_path / "key.npz")
    save_dpk(path, dpk, vk)
    on_device, _ = load_dpk(path)
    assert isinstance(on_device.a_coeff, jax.Array)
    monkeypatch.setattr(G, "_hbm_bytes_limit", lambda device=None: 1 << 10)  # a chip this key does not fit
    assert G.key_arrays_home(dpk.log_m) is np.asarray
    on_host, _ = load_dpk(path)
    leaves = jax.tree_util.tree_leaves([getattr(on_host, f) for f in G._DPK_ARRAY_FIELDS])
    assert leaves and all(isinstance(a, np.ndarray) for a in leaves)
    assert G.key_device(on_host) is None and G.key_mesh(on_host) is None
    mesh = _mesh(1, 4)
    a, b = G.place_key(on_host, mesh), G.place_key(on_device, mesh)
    assert G.key_mesh(a) == mesh
    for f in G._DPK_ARRAY_FIELDS:
        for got, want in zip(jax.tree_util.tree_leaves(getattr(a, f)), jax.tree_util.tree_leaves(getattr(b, f))):
            assert got.sharding == want.sharding
            assert all((np.asarray(g.data) == np.asarray(w.data)).all() for g, w in zip(got.addressable_shards, want.addressable_shards))
    wit = cs.witness([pow(15, 2, R)], {x: 3, y: 5})
    native = prove_native(on_host, wit, 5, 7)
    assert native is None or native == prove_native(on_device, wit, 5, 7)


class _OneIsEnough(Exception):
    pass


@pytest.mark.parametrize("a_group,lanes", [(4, 64), (3, 64), (2, 128), (1, 256), (8, 64)])
def test_a_smaller_chunk_takes_wider_pod_msm_steps(monkeypatch, a_group, lanes):
    """`pod_lanes`: a chunk of four proofs a group steps 64 bases of a
    wide class (and of h) at a time, as the key is padded for; a chunk of
    one 256, a quarter of the steps, because a step's table of multiples
    costs the same whatever the chunk.  `pod_narrow_lanes`: a narrow class
    steps a sixteenth of a chip's share, under its curve's cap, and no
    narrower than makes its three planes' accumulate the wide class's
    16,384 adds.  `_prove_batch_sharded` hands every pod MSM those widths."""
    from zkp2p_tpu.parallel import mesh as pmesh

    assert G.pod_lanes(1 << 23, 4, a_group) == lanes and G.pod_lanes(1 << 23, 4) == 64
    assert G.pod_lanes(6, 4, a_group) == 2 * max(1, 4 // a_group)  # a toy's whole share, as many times over
    floor = -(-64 * lanes // 3)  # three planes against 64: 1,366 lanes for a chunk of four, 5,462 for one
    assert G.pod_narrow_lanes(1 << 23, 4, a_group) == 16384 and G.pod_narrow_lanes(1 << 23, 4, a_group, cap=4096) == 4096
    assert G.pod_narrow_lanes(491361, 4, a_group) == max(7678, floor)  # venmo-256-192's a: sixteen steps of its quarter
    assert G.pod_narrow_lanes(53617, 4, a_group) == floor and G.pod_narrow_lanes(4 * 1000, 4, a_group) == 1000  # sha2b's; a whole share
    n_to = 491361 + (-491361) % (4 * 7678)
    assert G.pod_narrow_lanes(n_to, 4) == 7678 == G.pod_narrow_lanes(491361, 4)  # a padded class answers the same
    if a_group > 4:
        return
    seen = []

    def first_msm(curve, bases, planes, mesh, **kw):
        seen.append(kw["lanes"])
        raise _OneIsEnough  # the five are handed the same rule

    monkeypatch.setattr(pmesh, "msm_pod_batched", first_msm)
    monkeypatch.setattr(G, "_h_pod_fn", lambda mesh, log_m: lambda rows, w: (np.zeros((a_group, 8, 16), np.uint32), np.zeros((a_group,), np.uint32)))
    monkeypatch.setattr(G, "_h_shard_fn", lambda mesh, log_m, most: lambda rows, starts, w: (np.zeros((a_group, 8, 16), np.uint32), None))
    cs, _wires = _chain(6)
    from zkp2p_tpu.snark.groth16 import setup

    placed = G.place_key(G.device_pk(setup(cs)[0], cs), _mesh(1, 4))
    limbs = np.stack([G._witness_std_limbs(_chain_witness(6, 2 + i, 3)) for i in range(a_group)])
    with pytest.raises(_OneIsEnough):
        G._prove_batch_sharded(placed, limbs, _mesh(1, 4))
    n_narrow, n_wide = (cls[0].shape[0] for cls in placed.a_bases)
    assert n_narrow and seen == [(G.pod_narrow_lanes(n_narrow, 4, a_group), G.pod_lanes(n_wide, 4, a_group))]
