"""The mesh road's h MSM against a resident table in shards
(`parallel.mesh.resident_table_pod` / `msm_pod_resident`;
`prover.groth16_tpu._h_table` on a placed key): each chip's table is
`resident_table` of the shard of the bases it holds, the pod MSM over it
gives the point `msm_pod_batched` gives over the raw bases, and the window
follows the mesh chip's memory.  The CPU's virtual 1x4 mesh; w=4 tables of
the real curve (a program is ~20 s of XLA:CPU compile, so one case of it)
and tests/test_mesh_exchange.py's stand-in group for the shapes, which
compiles in seconds.  The whole road with its table beside the one-chip
road's is there (`test_the_mesh_road_s_accumulators_are_the_one_chip_road_s`)."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from test_mesh_exchange import P_LIN, _LinCurve, _mesh
from test_msm_resident import _limbs, _points

from zkp2p_tpu.curve.host import g1_msm
from zkp2p_tpu.curve.jcurve import G1J, g1_jac_to_host, g1_to_affine_arrays
from zkp2p_tpu.field.bn254 import R
from zkp2p_tpu.field.jfield import FQ
from zkp2p_tpu.ops import msm as jmsm
from zkp2p_tpu.parallel import mesh as pmesh

GIB = 1 << 30
V5E = int(15.75 * GIB)  # what a v5e chip reports as `bytes_limit`


def _on_mesh(mesh, bases, planes):
    """Bases and (mags, negs) laid out as the placed key and the exchange
    leave them: bases in shards, planes (B, planes, N) in the same."""
    cols = NamedSharding(mesh, P("batch", None, "shard"))
    return (tuple(jax.device_put(c, NamedSharding(mesh, P("shard"))) for c in bases),
            tuple(jax.device_put(p, cols) for p in planes))


def _planes(scalars, window):
    """A batch of scalar rows -> (mags, negs), each (B, 256 / window, N)."""
    per = [jmsm.signed_digit_planes_from_limbs(_limbs(row), window) for row in scalars]
    return tuple(jnp.stack([p[i] for p in per]) for i in (0, 1))


def test_each_chip_s_table_is_the_resident_table_of_its_shard_and_the_pod_msm_is_the_scan_form_s_point():
    """The real curve on 1x4, w=4, two proofs: 40 bases, ten a chip, in
    steps of four lanes: three steps a chip, the last padded with two
    holes (a base count that is no whole number of steps).  Every entry
    of a chip's slice of the table is k times the base it stands for
    (host curve), a hole a hole; `msm_pod_resident` gives `g1_msm` of the
    whole query, and the point `msm_pod_batched` gives over the raw
    bases and the same planes."""
    window, lanes, n, s = 4, 4, 40, 4
    rng = random.Random(43)
    pts = _points(rng, n)
    pts[13] = None
    scalars = [[rng.randrange(R) for _ in range(n)], [rng.randrange(1 << 20) for _ in range(n)]]
    mesh = _mesh(1, s)
    bases, planes = _on_mesh(mesh, g1_to_affine_arrays(pts), _planes(scalars, window))
    table = pmesh.resident_table_pod(G1J, bases, mesh, window, lanes)
    share, steps = n // s, 3
    assert table.shape == (s * steps, 1 << (window - 1), lanes, 16)
    assert sorted(sh.index[0].start for sh in table.addressable_shards) == [c * steps for c in range(s)]
    from zkp2p_tpu.curve.host import g1_mul

    for sh in table.addressable_shards:
        chip, words = sh.index[0].start // steps, np.asarray(sh.data)
        assert not words[-1, :, share - 2 * lanes:].any()  # the two lanes past the chip's ten bases: holes
        for i in range(share):
            for k in (1, 2, 8):
                w = words[i // lanes, k - 1, i % lanes]
                x, y = FQ.from_mont_host(w & 0xFFFF), FQ.from_mont_host(w >> 16)
                base = pts[chip * share + i]
                assert (None if x == 0 and y == 0 else (x, y)) == (None if base is None else g1_mul(base, k))
    got = g1_jac_to_host(pmesh.msm_pod_resident(G1J, table, planes, mesh, dcn_axis="batch", ici_axis="shard"))
    live = [i for i, p in enumerate(pts) if p is not None]
    assert got == [g1_msm([pts[i] for i in live], [row[i] for i in live]) for row in scalars]
    assert got == g1_jac_to_host(pmesh.msm_pod_batched(
        G1J, (bases,), (planes,), mesh, dcn_axis="batch", ici_axis="shard", lanes=(lanes,), window=window))


@pytest.mark.parametrize("window", [4, 8])
@pytest.mark.parametrize("b,s,n_proofs,n,lanes", [
    (1, 4, 4, 64, 4),   # a chunk of four, whole steps: sixteen bases a chip in four steps
    (1, 4, 1, 64, 4),   # a batch of one: the planes of one proof on every chip
    (1, 4, 4, 40, 4),   # ten bases a chip: no whole number of steps
    (2, 2, 4, 44, 8),   # two groups: a table a group, two proofs each; 22 bases a chip in three steps
    (1, 4, 2, 12, 64),  # fewer bases a chip than lanes: one step of three
], ids=["split", "one-proof", "ragged", "2x2", "narrower-than-a-step"])
def test_the_pod_resident_msm_is_the_pod_scan_msm_over_the_stand_in_group(b, s, n_proofs, n, lanes, window):
    """`msm_pod_resident` over `resident_table_pod`'s table against
    `msm_pod_batched` over the raw bases, the programs' own shards,
    folds and allreduce, over the group that compiles in seconds: equal
    element for element, and the sum of scalar x point."""
    curve, rng = _LinCurve((1,)), np.random.default_rng(100 * n + window)
    y = rng.integers(1, P_LIN, (n, 1), dtype=np.uint32)
    y[rng.random(n) < 0.15] = 0  # holes
    scalars = [[int(v) for v in rng.integers(0, 1 << 62, n)] for _ in range(n_proofs)]
    scalars[-1][:3] = [0, R - 1, 1]
    mesh = _mesh(b, s)
    bases, planes = _on_mesh(mesh, (np.zeros_like(y), y), _planes(scalars, window))
    table = pmesh.resident_table_pod(curve, bases, mesh, window, lanes)
    share = n // s
    assert table.shape == (s * -(-share // min(lanes, share)), 1 << (window - 1), min(lanes, share), 1)
    got = pmesh.msm_pod_resident(curve, table, planes, mesh, dcn_axis="batch", ici_axis="shard")
    # the scan form at ITS window: the same scalars, recoded at four bits
    want = pmesh.msm_pod_batched(curve, (bases,), (_on_mesh(mesh, bases, _planes(scalars, 4))[1],), mesh,
                                 dcn_axis="batch", ici_axis="shard", lanes=(lanes,), window=4)
    assert got[1].shape == want[1].shape == (n_proofs, 1) and (np.asarray(got[1]) == np.asarray(want[1])).all()
    assert [int(v) for v in np.asarray(got[1])[:, 0]] == [
        sum(k * int(v) for k, v in zip(row, y[:, 0])) % P_LIN for row in scalars]


# ------------------------------------------------------------ the window rule, by the placement's shape


@pytest.mark.parametrize("log_m,chunk,b,s,limit,want,planned", [
    (16, 4, 1, 4, V5E, 8, 4128 << 16),       # sha2b-mesh4: 2,080 B of key and proofs + 2,048 of table a point
    (19, 4, 1, 4, V5E, 8, 4128 << 19),       # venmo-256-192-mesh4: 2.16 GB planned, the table 1.07 GB a chip
    (23, 1, 1, 4, V5E, 4, 1568 << 23),       # venmo-full-mesh4: w=8 would be 17 GB a chip; 1,440 + 128 B a point
    (23, 1, 1, 4, 16 * 10**9, None, None),   # a chip 0.9 GB smaller: neither fits, the in-scan form
    (23, 1, 1, 8, V5E, 4, 1232 << 23),       # eight shards: an eighth of the table a chip, w=8 still 17.9 GB
    (19, 4, 2, 2, V5E, 8, 6336 << 19),       # two groups of two: half the table a chip
    (19, 4, 1, 4, 2 * GIB, 4, 2208 << 19),   # a limit that admits only w=4
])
def test_the_window_follows_the_mesh_chip_s_memory(log_m, chunk, b, s, limit, want, planned):
    from zkp2p_tpu.prover import groth16_tpu as G

    if want is not None or s == 4:
        assert G.batch_chunk_for(log_m, limit, b, s) == chunk  # the chunk the same memory gives that placement
    assert G.h_table_window(log_m, jmsm.RESIDENT_ENTRY_BYTES, limit, chunk, b, s) == want
    if want is not None:
        a_point = (jmsm.RESIDENT_ENTRY_BYTES << (want - 1)) / s + G.chip_bytes_a_point(chunk, b, s)
        assert a_point * (1 << log_m) == planned <= G.HBM_PLAN_FRACTION * limit
        wider = {4: 8}.get(want)  # and the next window up would not fit
        assert wider is None or ((jmsm.RESIDENT_ENTRY_BYTES << (wider - 1)) / s + G.chip_bytes_a_point(chunk, b, s)) * (
            1 << log_m) > G.HBM_PLAN_FRACTION * limit


@pytest.mark.parametrize("log_m", [4, 16, 19, 20, 21, 22, 23])
@pytest.mark.parametrize("limit", [16 * GIB, V5E, 8 * GIB])
def test_one_chip_is_the_placement_of_one_by_one(log_m, limit):
    """1x1 answers what `h_table_window` has answered since PR 25: the
    table beside `work_bytes_a_point(chunk)`, whole."""
    from zkp2p_tpu.prover import groth16_tpu as G

    for chunk in (1, 2, 4):
        want = next((w for w in (8, 4) if ((jmsm.RESIDENT_ENTRY_BYTES << (w - 1)) + G.work_bytes_a_point(chunk)) << log_m
                     <= G.HBM_PLAN_FRACTION * limit), None)
        assert G.h_table_window(log_m, jmsm.RESIDENT_ENTRY_BYTES, limit, chunk) == want
        assert G.h_table_window(log_m, jmsm.RESIDENT_ENTRY_BYTES, limit, chunk, 1, 1) == want
        assert G.chip_bytes_a_point(chunk) == G.work_bytes_a_point(chunk)


@pytest.mark.parametrize("mesh_shape,log_m,want", [((1, 4), 16, 8), ((1, 4), 19, 8), ((1, 4), 23, 4), (None, 19, 8), (None, 23, None)])
def test_the_process_reads_the_placement_off_the_mesh(monkeypatch, mesh_shape, log_m, want):
    """`_h_table_window(log_m, device, mesh)` is the rule at the device's
    memory (XLA:CPU: the nominal chip), the mesh's shape and the chunk
    the same rule plans for it."""
    from zkp2p_tpu.prover import groth16_tpu as G

    monkeypatch.setattr(G, "BATCH_CHUNK", "auto")
    mesh = None if mesh_shape is None else _mesh(*mesh_shape)
    if mesh is None and want is None:
        with pytest.raises(G.KeyDoesNotFit):  # no chunk fits one chip: such a key is placed on a mesh
            G._h_table_window(log_m, None, mesh)
        return
    assert G._h_table_window(log_m, None, mesh) == want


@pytest.mark.parametrize("n,s,want", [(1 << 16, 4, 256), (1 << 19, 4, 256), (1 << 23, 4, 256), (1 << 19, 1, 256),
                                      (1 << 9, 4, 128), (8, 4, 2), (4, 4, 1), (40, 4, 10)])
def test_the_table_s_steps_are_a_chip_s_share_up_to_256_lanes(n, s, want):
    from zkp2p_tpu.prover import groth16_tpu as G

    assert G.pod_table_lanes(n, s) == want
