"""The sharded TPU arm (`make tpu-shard-smoke`; docs/TPU.md).

Tier-1 resident: pjit batch-axis prove parity on the 8-virtual-device
CPU mesh (toy circuit, byte-identical to the host oracle under pinned
(r, s)), the `tpu_shard` gate grammar + fallback arming, the
ZKP2P_TPU_* knob registry, the warm-start compile-cache round-trip
(>=10x second-run compile span, asserted via the jax.monitoring
backend_compile listener in subprocess pairs), and the heterogeneous
worker-tier routing units + the mixed-tier toy fleet A/B under the
chaos zero-lost/zero-duplicate invariant.

The parity tests dispatch REAL pod-mesh executables: cold, one
shard_map MSM compiles for minutes on a 1-core host, so they ride the
persistent .jax_cache (tests/conftest.py points every test at it) and
SKIP with a pointer at `make warm-cache` when the pod entries are
absent — the budget rule that keeps tier-1 minutes, not hours.  The
per-device bucket partial-sum check lives in the slow tier
(ZKP2P_RUN_SLOW=1) for the same reason: its diagnostic program is a
different executable than the prover's, so it can never be pre-warmed
by a production warm-cache run.
"""

import importlib.util
import json
import os
import subprocess
import sys
import time

import pytest

from zkp2p_tpu.field.bn254 import R
from zkp2p_tpu.pipeline.sched import (
    AmortModel,
    BatchController,
    DEFAULT_SHARDED_AMORT_POINTS,
    SchedRequest,
    normalize_tier,
    worker_tier_arm,
)
from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHAOS = os.path.join(REPO, "tools", "chaos.py")


# ------------------------------------------------------------ mesh grammar


def test_mesh_spec_grammar():
    from zkp2p_tpu.prover.groth16_tpu import _parse_mesh_spec

    assert _parse_mesh_spec("", 8) == (1, 8)  # auto: all devices on the shard axis
    assert _parse_mesh_spec("4", 8) == (1, 4)  # bare int = 1xN
    assert _parse_mesh_spec("2x4", 8) == (2, 4)
    assert _parse_mesh_spec(" 2X4 ", 8) == (2, 4)  # case/space tolerant
    # malformed or non-positive fails CLOSED (None -> vmap arm)
    assert _parse_mesh_spec("0x4", 8) is None
    assert _parse_mesh_spec("2x-1", 8) is None
    assert _parse_mesh_spec("ax2", 8) is None
    assert _parse_mesh_spec("2x", 8) is None


def test_shard_mesh_gate_grammar_and_digest(monkeypatch):
    from zkp2p_tpu.prover import groth16_tpu as G
    from zkp2p_tpu.utils.audit import execution_digest, gate_arms

    monkeypatch.delenv("ZKP2P_TPU_SHARD", raising=False)
    monkeypatch.delenv("ZKP2P_TPU_MESH", raising=False)
    assert G._shard_mesh() is None
    assert gate_arms()["tpu_shard"] == "off"
    d_off = execution_digest()

    # anything but the literal "on" fails closed
    monkeypatch.setenv("ZKP2P_TPU_SHARD", "yes")
    assert G._shard_mesh() is None and gate_arms()["tpu_shard"] == "off"

    # on + unsatisfiable/malformed mesh: an on-record disarm
    monkeypatch.setenv("ZKP2P_TPU_SHARD", "on")
    monkeypatch.setenv("ZKP2P_TPU_MESH", "junk")
    assert G._shard_mesh() is None and gate_arms()["tpu_shard"] == "off"
    monkeypatch.setenv("ZKP2P_TPU_MESH", "4x4")  # 16 > the 8 virtual devices
    assert G._shard_mesh() is None and gate_arms()["tpu_shard"] == "off"

    monkeypatch.setenv("ZKP2P_TPU_MESH", "2x4")
    mesh = G._shard_mesh()
    assert mesh is not None
    assert dict(mesh.shape) == {"batch": 2, "shard": 4}
    assert gate_arms()["tpu_shard"] == "2x4"
    # a sharded prove must never share a digest with the vmap arm
    assert execution_digest() != d_off
    # mesh instances are memoised per shape (the shard_map executable
    # cache keys on the instance)
    assert G._shard_mesh() is mesh

    # restore the off arm for later tests in this process
    monkeypatch.setenv("ZKP2P_TPU_SHARD", "off")
    assert G._shard_mesh() is None


# --------------------------------------------------- arm selection (stubbed)


def build_toy():
    cs = ConstraintSystem("toy")
    out = cs.new_public("out")
    x = cs.new_wire("x")
    y = cs.new_wire("y")
    z = cs.new_wire("z")
    cs.enforce(LC.of(x), LC.of(y), LC.of(z), "mul")
    cs.enforce(LC.of(z), LC.of(z), LC.of(out), "sq")
    cs.compute(z, lambda a, b: a * b % R, [x, y])
    return cs, out, x, y


def _toy_wits(cs, x, y, cases):
    wits, pubs = [], []
    for a, b in cases:
        o = pow(a * b % R, 2, R)
        wits.append(cs.witness([o], {x: a, y: b}))
        pubs.append([o])
    return wits, pubs


@pytest.fixture(scope="module")
def toy_keys():
    from zkp2p_tpu.prover import device_pk
    from zkp2p_tpu.snark.groth16 import setup

    cs, out, x, y = build_toy()
    pk, vk = setup(cs)
    return cs, pk, vk, device_pk(pk, cs), x, y


class _ArmTaken(Exception):
    def __init__(self, arm):
        self.arm = arm


def test_batch_arm_selection_and_fallback(toy_keys, monkeypatch):
    """The per-call arm decision WITHOUT paying a compile: both prove
    arms stubbed to raise, so the test observes which one prove_tpu_batch
    dispatched and which `tpu_shard` arm it recorded."""
    from zkp2p_tpu.prover import groth16_tpu as G
    from zkp2p_tpu.utils.audit import gate_arms

    cs, _pk, _vk, dpk, x, y = toy_keys
    wits, _ = _toy_wits(cs, x, y, [(3, 5), (2, 7), (10, 11), (1, 1)])

    monkeypatch.setattr(
        G, "_prove_batch_sharded", lambda *a, **k: (_ for _ in ()).throw(_ArmTaken("sharded"))
    )
    monkeypatch.setattr(
        G, "_prove_device", lambda *a, **k: (_ for _ in ()).throw(_ArmTaken("vmap"))
    )

    def arm_for(n_wits, shard, mesh_spec):
        monkeypatch.setenv("ZKP2P_TPU_SHARD", shard)
        monkeypatch.setenv("ZKP2P_TPU_MESH", mesh_spec)
        with pytest.raises(_ArmTaken) as e:
            G.prove_tpu_batch(dpk, wits[:n_wits])
        return e.value.arm, gate_arms()["tpu_shard"]

    # knob off: the vmap arm, digest-visible as "off"
    assert arm_for(4, "off", "2x4") == ("vmap", "off")
    # on + divisible batch: the sharded arm with the resolved shape
    assert arm_for(4, "on", "2x4") == ("sharded", "2x4")
    assert arm_for(3, "on", "1x4") == ("sharded", "1x4")  # B=1 divides anything
    # on + indivisible batch (3 % 2): fallback recorded, vmap dispatched
    assert arm_for(3, "on", "2x4") == ("vmap", "fallback")
    # on + a chunk eight chips would share over the toy's domain of four points: no blocks to give them
    assert arm_for(4, "on", "1x8") == ("vmap", "fallback")
    assert arm_for(4, "on", "1x4") == ("sharded", "1x4")  # a proof a chip: nothing shared, any domain


# ------------------------------------------- the batch's spans (stubbed)


@pytest.mark.parametrize("chunk,n_chunks,h_road", [("0", 1, "resident"), ("2", 2, "resident"), ("0", 1, "scan")])
def test_prove_batch_writes_phases_that_partition_it_and_six_stages_inside_device(
        toy_keys, monkeypatch, chunk, n_chunks, h_road):
    """prove_tpu_batch on the XLA road with the six stage executables
    stood in for (each compiles for minutes on XLA:CPU): the real
    `_prove_device` enqueues them and the real read loop writes the
    spans.  `prep` + `device` + `finish` partition `tpu/prove_batch`;
    `dispatch` and six stages a chunk lie in `device`, the stages in the
    order this road enqueues them (no narrow class: the h MSM first),
    abutting, from `device`'s start to its end.  The h MSM reads the
    key's resident table where the window rule gives one (`h_road`),
    built once a key under `tpu/prove_batch/h_table`, and its stage span
    says which road it took."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from zkp2p_tpu.prover import groth16_tpu as G
    from zkp2p_tpu.utils import trace as tr

    cs, _pk, _vk, dpk, x, y = toy_keys
    wits, _ = _toy_wits(cs, x, y, [(3, 5), (2, 7), (10, 11)])  # two chunks: the second padded
    # no narrow class (as an imported zkey without width inference): one MSM a query, no curve add to compile
    none = jnp.zeros((0,), jnp.int32)
    dpk = dataclasses.replace(
        dpk, a_nsel=none, b_nsel=none, c_nsel=none, a_wsel=jnp.arange(dpk.n_wires, dtype=jnp.int32),
        b_wsel=jnp.arange(dpk.b_sel.shape[0], dtype=jnp.int32), c_wsel=jnp.arange(dpk.c_sel.shape[0], dtype=jnp.int32))
    monkeypatch.setenv("ZKP2P_TPU_SHARD", "off")
    monkeypatch.setattr(G, "BATCH_CHUNK", chunk)
    order = []

    def fake_h_planes(dpk_, w_mont, h_window):
        time.sleep(0.03)
        b, n = w_mont.shape[0], w_mont.shape[1]
        planes = (np.zeros((b, 4, n), np.uint32), np.zeros((b, 4, n), bool))
        m = 1 << dpk_.log_m
        return (planes, ()), (np.zeros((b, 4, m), np.uint32), np.zeros((b, 4, m), bool))

    def fake_msm(limbs):
        def run(bases, planes):
            order.append(int(bases[0].shape[0]))
            time.sleep(0.01)
            b = planes[0].shape[0]
            return tuple(np.zeros((b,) + limbs, np.uint32) for _ in range(3))  # Z = 0: infinity
        return run

    monkeypatch.setattr(G, "_jit_h_planes", fake_h_planes)
    monkeypatch.setattr(G, "_jit_msm_g1", fake_msm((16,)))
    m = 1 << dpk.log_m
    built = []

    def fake_h_table(bases, window):
        built.append(window)
        return np.zeros((1, 1 << (window - 1), int(bases[0].shape[0]), 16), np.uint32)

    def fake_msm_resident(table, planes):
        return fake_msm((16,))((np.zeros((table.shape[0] * table.shape[2], 16)),), planes)

    monkeypatch.setattr(G, "_jit_h_table", fake_h_table)
    monkeypatch.setattr(G, "_jit_msm_h_resident", fake_msm_resident)
    if h_road == "scan":
        monkeypatch.setattr(G, "_h_table_window", lambda log_m, device=None, mesh=None: None)
    monkeypatch.setattr(G, "_jit_msm_g2", fake_msm((2, 16)))
    monkeypatch.setattr(G, "_assemble", lambda dpk_, acc, r, s: acc)
    tr.reset()
    out = G.prove_tpu_batch(dpk, wits, rs=[1, 2, 3], ss=[4, 5, 6])
    assert len(out) == 3 and all(len(acc) == 5 for acc in out)  # five accumulators a proof, per witness
    # this road enqueues the h MSM first, and the stage spans say so
    assert order[0::5] == [m] * n_chunks
    from zkp2p_tpu.utils.metrics import REGISTRY

    assert built == ([8] if h_road == "resident" else [])  # once a key, whatever the chunks
    assert REGISTRY.gauge("zkp2p_msm_h_table_bytes").value == (m * 128 * 64 if h_road == "resident" else 0)
    enqueued = ["h_planes", "msm_h", "msm_a", "msm_b1", "msm_b2", "msm_c"]

    recs = tr.records()
    by = {}
    for r in recs:
        by.setdefault(r["stage"], []).append(r)
    (batch,), (prep,), (device,), (finish,), (dispatch,) = (
        by["tpu/prove_batch" + s] for s in ("", "/prep", "/device", "/finish", "/dispatch"))
    assert batch["n"] == 3 and prep["parent"] == device["parent"] == finish["parent"] == batch["id"]
    assert prep["ms"] + device["ms"] + finish["ms"] == pytest.approx(batch["ms"], rel=0.01, abs=1.0)
    assert dispatch["parent"] == device["id"] and dispatch["ms"] <= device["ms"]
    stages = sorted((r for r in recs if "/stage/" in r["stage"]), key=lambda r: r["id"])
    assert [(r["window"], r["table"]) for r in stages if r["stage"].endswith("/msm_h")] == [
        (8, "resident") if h_road == "resident" else (4, "scan")] * n_chunks
    assert all("table" not in r for r in stages if not r["stage"].endswith("/msm_h"))
    # the five MSM stages carry the curve's addition law, and only they
    assert [r.get("add") for r in stages] == [None if r["stage"].endswith("/h_planes") else "complete_projective" for r in stages]
    tables = [r for r in recs if r["stage"].endswith("/h_table")]
    assert [(r["stage"], r["parent"]) for r in tables] == (
        [("tpu/prove_batch/h_table", device["id"])] if h_road == "resident" else [])
    assert sorted(enqueued) == sorted(G.STAGES)
    assert [r["stage"].rsplit("/", 1)[1] for r in stages] == enqueued * n_chunks
    assert [r["chunk"] for r in stages] == [c for c in range(n_chunks) for _ in G.STAGES]
    assert all(r["tid"] != device["tid"] for r in stages)  # read by the watching thread, as each result is ready
    assert all(r["parent"] == device["id"] and r["stage"].startswith("tpu/prove_batch/stage/") for r in stages)
    assert stages[0]["t0"] == pytest.approx(device["t0"], abs=1e-3)
    for a, b in zip(stages, stages[1:]):
        assert b["t0"] == pytest.approx(a["t0"] + a["ms"] / 1e3, abs=1e-5)  # abutting: they partition `device`
    t_last, t_device = stages[-1]["t0"] + stages[-1]["ms"] / 1e3, device["t0"] + device["ms"] / 1e3
    if n_chunks == 1:  # `device` ends with its last stage
        assert t_last == pytest.approx(t_device, abs=2e-2)
        assert sum(r["ms"] for r in stages) == pytest.approx(device["ms"], rel=0.01, abs=20.0)
    else:  # the chunks' accumulators are concatenated on the device after the last stage (here that compiles)
        assert t_last <= t_device + 1e-3
    tr.reset()


def _stand_in_for_the_mesh_programs(monkeypatch, sleep_s=0.0):
    """The mesh road's compiled programs stood in for (the h stage, the
    h table's build and each pod MSM compile for minutes on XLA:CPU):
    zero h, a table of zeros in the real one's shape and shards,
    infinity accumulators, and `_assemble` handing the accumulators
    back.  The key's placement, the window rule, the upload, the
    exchange program and the read loop that writes the spans are the
    real ones.  Returns the windows of the tables built, in order."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    import numpy as np

    from zkp2p_tpu.curve.jcurve import G2J
    from zkp2p_tpu.parallel import mesh as pmesh
    from zkp2p_tpu.prover import groth16_tpu as G

    def fake_h_pod(mesh, log_m):
        def run(rows, w_std):
            time.sleep(sleep_s)
            b = w_std.shape[0]
            return np.zeros((b, 1 << log_m, 16), np.uint32), np.zeros((b,), np.uint32)
        return run

    def fake_h_shard(mesh, log_m, most):
        return lambda rows, starts, w_std: fake_h_pod(mesh, log_m)(rows, w_std)

    def fake_msm_pod(curve, bases, planes, mesh, **kw):
        time.sleep(sleep_s)
        limbs = (2, 16) if curve is G2J else (16,)
        return tuple(np.zeros((planes[0][0].shape[0],) + limbs, np.uint32) for _ in range(3))  # Z = 0: infinity

    built = []

    def fake_table_pod(curve, bases, mesh, window, lanes, **kw):
        built.append(window)
        share = bases[0].shape[0] // mesh.shape["shard"]
        lanes = min(lanes, share)
        steps = mesh.shape["shard"] * -(-share // lanes)
        return jax.device_put(np.zeros((steps, 1 << (window - 1), lanes, 16), np.uint32), NamedSharding(mesh, P("shard")))

    def fake_msm_pod_resident(curve, table, planes, mesh, **kw):
        assert planes[0].shape[1] == 256 // int(table.shape[1]).bit_length()  # h came at the table's window
        return fake_msm_pod(curve, None, (planes,), mesh)

    monkeypatch.setattr(G, "_h_pod_fn", fake_h_pod)
    monkeypatch.setattr(G, "_h_shard_fn", fake_h_shard)
    monkeypatch.setattr(pmesh, "msm_pod_batched", fake_msm_pod)
    monkeypatch.setattr(pmesh, "resident_table_pod", fake_table_pod)
    monkeypatch.setattr(pmesh, "msm_pod_resident", fake_msm_pod_resident)
    monkeypatch.setattr(G, "_assemble", lambda dpk_, acc, r, s: acc)
    return built


@pytest.mark.parametrize("mesh_spec,n_wits,window", [("1x4", 4, 8), ("1x4", 1, 8), ("2x2", 4, 8), ("1x4", 4, None)],
                         ids=["1x4-split", "1x4-shared", "2x2-split", "no-window"])
def test_the_mesh_road_builds_its_h_table_once_a_placed_key(toy_keys, monkeypatch, mesh_spec, n_wits, window):
    """`_prove_batch_sharded` reads a resident h table in shards: the
    first batch of a placed key builds it (`resident_table_pod`, each
    chip its own shard's), under one `tpu/prove_batch/h_table` span
    inside `device` with `mesh`, `window` and `bytes` (every chip's), and
    memoises it on the PLACED key, never on the key handed in; the next
    batch builds nothing.  `zkp2p_msm_h_table_bytes` reads the table's
    bytes, and the `msm_h` stage's span says `window`, `table` =
    "resident" and `add` = "mixed" (the accumulate is `add_mixed`).
    Where the rule gives no window the road keeps the in-scan form:
    nothing built, the gauge 0, h at MSM_WINDOW through
    `msm_pod_batched`, the span `table` = "scan" with the curve's law.
    The real road on the virtual mesh, its programs stood in for."""
    import dataclasses

    from zkp2p_tpu.parallel import mesh as pmesh
    from zkp2p_tpu.prover import groth16_tpu as G
    from zkp2p_tpu.utils import trace as tr
    from zkp2p_tpu.utils.audit import gate_arms
    from zkp2p_tpu.utils.metrics import REGISTRY

    cs, _pk, _vk, dpk, x, y = toy_keys
    dpk = dataclasses.replace(dpk)  # a key instance of its own: nothing memoised on it yet
    wits, _ = _toy_wits(cs, x, y, [(3, 5), (2, 7), (10, 11), (1, 1)][:n_wits])
    monkeypatch.setenv("ZKP2P_TPU_SHARD", "on")
    monkeypatch.setenv("ZKP2P_TPU_MESH", mesh_spec)
    monkeypatch.setattr(G, "BATCH_CHUNK", "0")
    built = _stand_in_for_the_mesh_programs(monkeypatch)
    monkeypatch.setattr(G, "_jit_h_table", lambda *a, **k: pytest.fail("the mesh road built a one-chip table"))
    if window is None:
        monkeypatch.setattr(G, "_h_table_window", lambda log_m, device=None, mesh=None: None)
    fake, scanned = pmesh.msm_pod_batched, []
    monkeypatch.setattr(pmesh, "msm_pod_batched", lambda curve, bases, planes, mesh, **kw: (
        scanned.append(planes[0][0].shape[1]), fake(curve, bases, planes, mesh, **kw))[1])
    gauge = REGISTRY.gauge("zkp2p_msm_h_table_bytes")
    gauge.set(-1)
    pinned = list(range(1, n_wits + 1))
    for batch in (0, 1):
        tr.reset()
        out = G.prove_tpu_batch(dpk, wits, rs=pinned, ss=pinned)
        assert len(out) == n_wits and gate_arms()["tpu_shard"] == mesh_spec
        recs = tr.records()
        (device,) = [r for r in recs if r["stage"] == "tpu/prove_batch/device"]
        (dispatch,) = [r for r in recs if r["stage"] == "tpu/prove_batch/dispatch"]
        tables = [r for r in recs if r["stage"].endswith("/h_table")]
        (h_stage,) = [r for r in recs if r["stage"].endswith("/stage/msm_h")]
        placed = G._key_on_mesh(dpk, G._shard_mesh())
        assert not hasattr(dpk, "_h_table_cache")
        if window is None:
            assert not tables and not built and gauge.value == 0 and not hasattr(placed, "_h_table_cache")
            assert (h_stage["window"], h_stage["table"], h_stage["add"]) == (G.MSM_WINDOW, "scan", "complete_projective")
            assert scanned[4::5] == [64] * (batch + 1)  # the fifth pod MSM of a batch: h, at MSM_WINDOW's planes
            continue
        table = placed._h_table_cache
        n_chips = len(table.addressable_shards)
        m_pad = placed.h_bases[0].shape[0]
        want_bytes = m_pad * 128 * 64 * (n_chips // int(mesh_spec[-1]))  # 2^(w-1) entries of 64 B a base, a replica a group
        assert built == [window] and gauge.value == want_bytes and len(scanned) == 4 * (batch + 1)
        assert (h_stage["window"], h_stage["table"], h_stage["add"], h_stage["digits"], h_stage["mesh"]) == (
            window, "resident", "mixed", "signed", mesh_spec)
        if batch:
            assert not tables  # memoised: the second batch builds nothing
        else:
            (built_span,) = tables
            assert built_span["stage"] == "tpu/prove_batch/h_table" and built_span["parent"] == device["id"]
            assert (built_span["window"], built_span["mesh"], built_span["bytes"]) == (window, mesh_spec, want_bytes)
            assert built_span["id"] < dispatch["id"]  # before the batch is enqueued: no stage waits for it
    tr.reset()


@pytest.mark.parametrize("mesh_spec,n_wits,chunk,proofs_a_chip,h_shards,crossed", [
    ("1x4", 4, "0", 1, None, True),    # a chunk of four on 1x4: one proof's h a chip
    ("2x2", 4, "0", 1, None, True),    # two groups of two chips: two proofs a group, one a chip
    ("4x1", 4, "0", 1, None, False),   # a chip a group: nothing to exchange
    ("1x4", 3, "0", 3, 4, False),      # the mesh does not divide the chunk: the four chips share each proof's h stage
    ("1x4", 1, "0", 1, 4, False),      # a batch of one, the same rule: the form a 2^23 key takes
    ("2x2", 2, "0", 1, 2, False),      # a proof a group, shared by the group's two chips
    ("1x4", 6, "4", 1, None, True),    # two chunks, the second padded to four
])
def test_the_mesh_road_writes_seven_stages_that_partition_device_and_places_its_key_once(
        toy_keys, monkeypatch, mesh_spec, n_wits, chunk, proofs_a_chip, h_shards, crossed):
    """prove_tpu_batch on the mesh road, its h program and pod MSMs
    stood in for: `prep`, `device` and `finish` lie in that order inside
    `tpu/prove_batch`, and seven stages a chunk partition `device`, in
    the order the road enqueues them, `exchange` between `h_planes` and
    `msm_a`, each with `mesh`.  `h_planes` says how many proofs a chip
    took part in (the chunk over the whole mesh where the group's chips
    divide its share, each chip whole proofs of its own; else every
    proof of its group, an `h_shards`-th of each, with the `ici_bytes`
    the shared transforms moved, which `zkp2p_h_ici_bytes_total` grows
    by), `exchange` the bytes that crossed.  The key is placed by the
    first batch, under a `tpu/place_key` span outside the batch's own,
    and a second batch on the placed key grows
    `zkp2p_key_placed_bytes_total` by 0.

    The partition is held to the spans' own ends (ids, parents, a stage
    starting where the last one ended), not to a loaded machine's clock:
    a worker beside five others waits milliseconds between two spans."""
    import dataclasses

    from zkp2p_tpu.prover import groth16_tpu as G
    from zkp2p_tpu.utils import trace as tr
    from zkp2p_tpu.utils.audit import gate_arms
    from zkp2p_tpu.utils.metrics import REGISTRY

    cs, _pk, _vk, dpk, x, y = toy_keys
    dpk = dataclasses.replace(dpk)  # placed by this test's first batch
    wits, _ = _toy_wits(cs, x, y, [(3, 5), (2, 7), (10, 11), (1, 1), (4, 9), (6, 6)][:n_wits])
    monkeypatch.setenv("ZKP2P_TPU_SHARD", "on")
    monkeypatch.setenv("ZKP2P_TPU_MESH", mesh_spec)
    monkeypatch.setattr(G, "BATCH_CHUNK", chunk)
    _stand_in_for_the_mesh_programs(monkeypatch, sleep_s=0.01)
    placed_total, ici_total = REGISTRY.counter("zkp2p_key_placed_bytes_total"), REGISTRY.counter("zkp2p_h_ici_bytes_total")
    before, ici_before = placed_total.value, ici_total.value
    tr.reset()
    pinned = list(range(1, n_wits + 1))
    out = G.prove_tpu_batch(dpk, wits, rs=pinned, ss=pinned)
    assert len(out) == n_wits and gate_arms()["tpu_shard"] == mesh_spec
    recs = tr.records()
    (placing,) = [r for r in recs if r["stage"].endswith("place_key")]
    assert placing["stage"] == "tpu/place_key" and placing["parent"] is None and placing["mesh"] == mesh_spec
    assert placing["bytes"] == placed_total.value - before > 0
    by = {}
    for r in recs:
        by.setdefault(r["stage"], []).append(r)
    (batch,), (prep,), (device,), (finish,) = (by["tpu/prove_batch" + s] for s in ("", "/prep", "/device", "/finish"))
    end = lambda r: r["t0"] + r["ms"] / 1e3  # noqa: E731
    eps = 2e-6  # `ms` is written to the microsecond
    assert placing["id"] < batch["id"] and end(placing) <= batch["t0"] + eps  # before the batch's span, not in it
    assert all(r["parent"] == batch["id"] for r in (prep, device, finish))
    assert batch["t0"] <= prep["t0"] and end(prep) <= device["t0"] + eps
    assert end(device) <= finish["t0"] + eps and end(finish) <= end(batch) + eps
    assert batch["chunk"] == int(chunk)
    n_chunks = batch["n_chunks"]
    stages = sorted((r for r in recs if "/stage/" in r["stage"]), key=lambda r: r["id"])
    assert G.MESH_STAGES == ("h_planes", "exchange", "msm_a", "msm_b1", "msm_b2", "msm_c", "msm_h")
    assert [r["stage"].rsplit("/", 1)[1] for r in stages] == list(G.MESH_STAGES) * n_chunks
    assert [r["chunk"] for r in stages] == [c for c in range(n_chunks) for _ in G.MESH_STAGES]
    assert all(r["mesh"] == mesh_spec and r["parent"] == device["id"] for r in stages)
    h_stages = [r for r in stages if r["stage"].endswith("/h_planes")]
    assert [r["proofs_a_chip"] for r in h_stages] == [proofs_a_chip] * n_chunks
    assert [r.get("h_shards") for r in h_stages] == [h_shards] * n_chunks
    shared = 6 * n_wits * (h_shards - 1) * (64 << dpk.log_m) if h_shards else 0  # six all_gathers a proof
    assert [r.get("ici_bytes") for r in h_stages] == [shared if h_shards else None] * n_chunks
    assert ici_total.value - ici_before == shared * n_chunks
    assert all(k not in r for k in ("proofs_a_chip", "h_shards", "ici_bytes") for r in stages if r not in h_stages)
    assert all((r["bytes"] > 0) == crossed for r in stages if r["stage"].endswith("/exchange"))
    assert all("bytes" not in r for r in stages if not r["stage"].endswith("/exchange"))
    assert stages[0]["t0"] == device["t0"]  # the first stage starts where `device` does
    for a, b in zip(stages, stages[1:]):
        assert abs(b["t0"] - end(a)) <= eps  # abutting: they partition `device`
    assert end(stages[-1]) <= end(device) + eps  # and end inside it: what is left is the host's, reading the accumulators
    # the placed key is the one the next batch reads: nothing placed, no span, no bytes
    at = placed_total.value
    tr.reset()
    assert len(G.prove_tpu_batch(dpk, wits, rs=pinned, ss=pinned)) == n_wits
    assert placed_total.value == at and not [r for r in tr.records() if r["stage"].endswith("place_key")]
    # and a key handed in already placed is read as it is
    placed = G._key_on_mesh(dpk, G._shard_mesh())
    assert G.key_mesh(placed) is not None and G.key_device(placed) is None and G.key_mesh(dpk) is None
    assert len(G.prove_tpu_batch(placed, wits, rs=pinned, ss=pinned)) == n_wits and placed_total.value == at
    tr.reset()


@pytest.mark.parametrize("classed", [True, False], ids=["classed", "no-widths"])
def test_the_mesh_road_s_query_spans_say_their_classes_and_digits(toy_keys, monkeypatch, classed):
    """The counter that says the narrow class engaged: on the mesh road
    `stage/msm_a`, `msm_b1`, `msm_b2` and `msm_c` carry `narrow` and
    `wide`, the bases a chip holds in each class, padding included (a, b1
    and c the same: they are padded to one count and share a program),
    `narrow` 0 for a key without widths; all five MSM stages carry
    `digits` = "signed"; `exchange` and `h_planes` neither.  What
    `msm_pod_batched` is handed agrees: a class a span counts 0 bases in
    takes no part, a narrow class comes with its low planes alone."""
    import dataclasses

    from test_msm_resident import _no_narrow_class

    from zkp2p_tpu.parallel import mesh as pmesh
    from zkp2p_tpu.prover import groth16_tpu as G
    from zkp2p_tpu.utils import trace as tr

    cs, _pk, _vk, dpk, x, y = toy_keys
    dpk = dataclasses.replace(dpk) if classed else _no_narrow_class(dpk)
    wits, _ = _toy_wits(cs, x, y, [(3, 5), (2, 7), (10, 11), (1, 1)])
    monkeypatch.setenv("ZKP2P_TPU_SHARD", "on")
    monkeypatch.setenv("ZKP2P_TPU_MESH", "1x4")
    monkeypatch.setattr(G, "BATCH_CHUNK", "0")
    _stand_in_for_the_mesh_programs(monkeypatch)
    fake, handed = pmesh.msm_pod_batched, []

    def spy(curve, bases, planes, mesh, **kw):
        handed.append([(cls[0].shape[0], mags.shape[1], lanes) for cls, (mags, _negs), lanes in zip(bases, planes, kw["lanes"])])
        return fake(curve, bases, planes, mesh, **kw)

    monkeypatch.setattr(pmesh, "msm_pod_batched", spy)
    tr.reset()
    assert len(G.prove_tpu_batch(dpk, wits, rs=[1, 2, 3, 4], ss=[5, 6, 7, 8])) == 4
    stages = {r["stage"].rsplit("/", 1)[1]: r for r in tr.records() if "/stage/" in r["stage"]}
    placed = G._key_on_mesh(dpk, G._shard_mesh())
    for (name, q), classes in zip((("msm_a", "a"), ("msm_b1", "b1"), ("msm_b2", "b2"), ("msm_c", "c")), handed):
        n_narrow, n_wide = (cls[0].shape[0] for cls in getattr(placed, q + "_bases"))
        span = stages[name]
        assert (span["narrow"], span["wide"], span["digits"]) == (n_narrow // 4, n_wide // 4, "signed") and span["wide"] > 0
        assert bool(span["narrow"]) == (classed and q != "b2")  # the toy's B rows name no narrow wire
        want = ([(n_narrow, G.NARROW_PLANES, G.pod_narrow_lanes(n_narrow, 4, 4, 4096 if q == "b2" else 16384))] if n_narrow else []) + [
            (n_wide, 64, G.pod_lanes(n_wide, 4, 4))]
        assert classes == want
    assert stages["msm_a"]["narrow"] == stages["msm_b1"]["narrow"] == stages["msm_c"]["narrow"]
    assert stages["msm_a"]["wide"] == stages["msm_b1"]["wide"] == stages["msm_c"]["wide"]
    assert stages["msm_h"]["digits"] == "signed" and len(handed) == 4  # h reads its resident table: `msm_pod_resident`
    assert all(k not in stages[name] for k in ("narrow", "wide") for name in ("msm_h", "exchange", "h_planes"))
    assert all("digits" not in stages[name] for name in ("exchange", "h_planes"))
    tr.reset()


@pytest.mark.parametrize("classed", [True, False])
def test_prove_tpu_is_a_batch_of_one(monkeypatch, classed):
    """`prove_tpu(dpk, w, r, s)` is `prove_tpu_batch(dpk, [w], [r],
    [s])[0]`: the bytes of the batch and of `prove_host`, and what it
    writes is the batch's — one `tpu/prove_batch` span with n=1 and six
    `stage/*` spans under its `device`.  The h stage, the table's build
    and the resident h MSM are the real programs at B=1; the witness
    MSMs are test_msm_resident's host stand-ins.  Over a key with a
    narrow class and one without."""
    from test_msm_resident import _no_narrow_class, _toy_world

    from zkp2p_tpu.prover import groth16_tpu as G
    from zkp2p_tpu.snark.groth16 import prove_host
    from zkp2p_tpu.utils import trace as tr

    cs, pk, dpk, wits = _toy_world(monkeypatch)
    if not classed:
        dpk = _no_narrow_class(dpk)
    r, s = 1234567, 7654321
    want = G.prove_tpu_batch(dpk, [wits[1]], [r], [s])[0]
    tr.reset()
    got = G.prove_tpu(dpk, wits[1], r, s)
    assert got == want == prove_host(pk, cs, wits[1], r=r, s=s)
    recs = tr.records()
    (batch,) = [rec for rec in recs if rec["stage"] == "tpu/prove_batch"]
    (device,) = [rec for rec in recs if rec["stage"] == "tpu/prove_batch/device"]
    assert batch["n"] == 1 and device["parent"] == batch["id"]
    stages = [rec for rec in recs if "/stage/" in rec["stage"]]
    assert sorted(rec["stage"] for rec in stages) == sorted("tpu/prove_batch/stage/" + name for name in G.STAGES)
    assert all(rec["parent"] == device["id"] for rec in stages)
    assert not [rec for rec in recs if rec["stage"].startswith("tpu/prove/") or rec["stage"] == "tpu/prove"]
    # unpinned blinding still proves: a fresh (r, s) a call
    assert G.prove_tpu(dpk, wits[1]) != G.prove_tpu(dpk, wits[1])
    tr.reset()


# ----------------------------------------------------------- byte parity

_POD_CACHE_HINTS = ("jit_local", "jit_msm_pod", "shard_map")


def _pod_cache_ready() -> bool:
    """True when the persistent cache holds the pod-mesh executables (a
    `make warm-cache` ran on this checkout) — the gate that keeps the
    parity tests out of a COLD tier-1 run, where one shard_map MSM
    compiles for minutes on a 1-core host."""
    if os.environ.get("ZKP2P_NO_CACHE") == "1":
        return False
    from zkp2p_tpu.utils.jaxcfg import cache_dir

    try:
        names = os.listdir(cache_dir())
    except OSError:
        return False
    return any(n.startswith(_POD_CACHE_HINTS) and n.endswith("-cache") for n in names)


needs_warm_cache = pytest.mark.skipif(
    not _pod_cache_ready(),
    reason="pod-mesh executables not in the persistent cache — run `make warm-cache` "
    "(cold shard_map compiles take minutes; docs/TPU.md §warm-start)",
)


@needs_warm_cache
@pytest.mark.parametrize("mesh_spec", ["2x4", "1x4"])
def test_sharded_batch_matches_host_oracle(toy_keys, monkeypatch, mesh_spec):
    """THE acceptance: ZKP2P_TPU_SHARD=on on a virtual pod mesh, batch
    of 4 -> every proof byte-identical to prove_host under the same
    (witness, r, s), and pairing-verified.  On 2x4 a group's two proofs
    are computed on each of its four chips; on 1x4 (the benchmark's
    shape) the chunk is split one proof a chip and exchanged.  A single witness rides a (1x4) mesh, below."""
    from zkp2p_tpu.prover import groth16_tpu as G
    from zkp2p_tpu.snark.groth16 import prove_host, verify
    from zkp2p_tpu.utils.audit import gate_arms

    cs, pk, vk, dpk, x, y = toy_keys
    cases = [(3, 5), (2, 7), (10, 11), (1, 1)]
    wits, pubs = _toy_wits(cs, x, y, cases)

    monkeypatch.setenv("ZKP2P_TPU_SHARD", "on")
    monkeypatch.setenv("ZKP2P_TPU_MESH", mesh_spec)
    rs = [1000 + 2 * i for i in range(len(wits))]
    ss = [1001 + 2 * i for i in range(len(wits))]
    proofs = G.prove_tpu_batch(dpk, wits, rs=rs, ss=ss)
    assert gate_arms()["tpu_shard"] == mesh_spec
    for i, (proof, pub) in enumerate(zip(proofs, pubs)):
        assert proof == prove_host(pk, cs, wits[i], r=rs[i], s=ss[i]), f"proof {i} != oracle"
        assert verify(vk, proof, pub)


@pytest.mark.slow
@needs_warm_cache
def test_sharded_single_matches_host_oracle(toy_keys, monkeypatch):
    """Single-witness parity on a base-axis-only (1x4) mesh.

    Slow tier: ~217 s even warm-cache on the 1-core host (virtual-device
    execution), and the tier-1 sharded-parity guarantee is carried by
    test_sharded_batch_matches_host_oracle above — this adds only the
    (1x4) mesh shape.  Runs under `make test-slow`."""
    from zkp2p_tpu.prover import groth16_tpu as G
    from zkp2p_tpu.snark.groth16 import prove_host, verify
    from zkp2p_tpu.utils.audit import gate_arms

    cs, pk, vk, dpk, x, y = toy_keys
    wits, pubs = _toy_wits(cs, x, y, [(6, 7)])
    monkeypatch.setenv("ZKP2P_TPU_SHARD", "on")
    monkeypatch.setenv("ZKP2P_TPU_MESH", "1x4")
    (proof,) = G.prove_tpu_batch(dpk, wits, rs=[1000], ss=[1001])
    assert gate_arms()["tpu_shard"] == "1x4"
    assert proof == prove_host(pk, cs, wits[0], r=1000, s=1001)
    assert verify(vk, proof, pubs[0])


@pytest.mark.slow
@pytest.mark.xslow
def test_per_device_bucket_partials_match_unsharded():
    """The allreduce layout claim (docs/TPU.md): each shard-axis
    device's bucket accumulation covers ONLY its base slice, and the
    psum fold is a pure group-op combine — so per-slice host MSMs over
    the same slicing, group-added, must equal both the unsharded host
    oracle and the pod-mesh device result.  Slow tier with the rest of
    the mesh tests (XLA-compile-heavy on a 1-core host)."""
    import jax.numpy as jnp
    import numpy as np

    from zkp2p_tpu.curve.host import G1_GENERATOR, g1_add, g1_msm, g1_mul
    from zkp2p_tpu.curve.jcurve import G1J, g1_jac_to_host, g1_to_affine_arrays
    from zkp2p_tpu.field.jfield import int_to_limbs
    from zkp2p_tpu.ops import msm as jmsm
    from zkp2p_tpu.parallel.mesh import make_pod_mesh, msm_pod_batched

    n_ici, lanes, window = 4, 2, 4
    rng = np.random.default_rng(11)
    n = 16  # a multiple of n_ici * lanes: slice boundaries == device slices
    pts = [g1_mul(G1_GENERATOR, int(k)) for k in rng.integers(1, 2**62, n)]
    batch_scalars = [[int(s) for s in rng.integers(1, 2**62, n)] for _ in range(2)]

    # per-device partial sums, host-computed over each device's base
    # slice, folded with plain group addition
    per = n // n_ici
    for row in batch_scalars:
        partials = [
            g1_msm(pts[d * per : (d + 1) * per], row[d * per : (d + 1) * per])
            for d in range(n_ici)
        ]
        folded = None
        for p in partials:
            folded = g1_add(folded, p) if folded is not None else p
        assert folded == g1_msm(pts, row)

    # the pod-mesh executable agrees with the same oracle
    mesh = make_pod_mesh(2, n_ici)
    # one class: signed digits (planes, B, n) -> (B, planes, n)
    planes = tuple(jnp.moveaxis(p, 0, 1) for p in jmsm.signed_digit_planes_from_limbs(
        jnp.asarray(np.stack([[int_to_limbs(s) for s in row] for row in batch_scalars])), window))
    bases = g1_to_affine_arrays(pts)  # n is a multiple of n_ici * lanes: nothing to pad
    acc = msm_pod_batched(G1J, (bases,), (planes,), mesh, lanes=(lanes,), window=window)
    got = g1_jac_to_host(acc)
    for i, row in enumerate(batch_scalars):
        assert got[i] == g1_msm(pts, row), f"batch element {i}"


@pytest.mark.slow
@pytest.mark.xslow
def test_make_mesh_shapes():
    import jax

    from zkp2p_tpu.parallel.mesh import make_mesh

    assert make_mesh(8).shape["shard"] == 8
    assert make_mesh(2).shape["shard"] == 2
    assert make_mesh().size == len(jax.devices())


@pytest.mark.slow
@pytest.mark.xslow
def test_msm_pod_batched_dcn_axis():
    """A REAL collective over the dcn axis: proof batch data-parallel
    over dcn, base axis sharded over ici, one proof point per batch element crossing
    DCN — each batched result must equal the host oracle."""
    import jax
    import numpy as np

    from zkp2p_tpu.curve.host import G1_GENERATOR, g1_msm, g1_mul
    from zkp2p_tpu.curve.jcurve import G1J, g1_jac_to_host, g1_to_affine_arrays
    from zkp2p_tpu.field.jfield import int_to_limbs
    from zkp2p_tpu.ops import msm as jmsm
    from zkp2p_tpu.parallel.mesh import make_pod_mesh, msm_pod_batched

    n = 11  # deliberately not a multiple of any mesh size (exercises padding)
    mesh = make_pod_mesh(2, 4)  # 2 slices x 4-wide ICI on the 8 vdevs
    rng = np.random.default_rng(42)
    pts = [g1_mul(G1_GENERATOR, int(k)) for k in rng.integers(1, 2**62, n)]
    rng = np.random.default_rng(7)
    batch_scalars = [[int(s) for s in rng.integers(1, 2**62, n)] for _ in range(4)]
    # infinity bases and zero digit columns up to a multiple of the mesh width, as `place_key` pads a key
    pad = (-n) % 8
    planes = tuple(
        jax.numpy.pad(jax.numpy.moveaxis(p, 0, 1), [(0, 0), (0, 0), (0, pad)])
        for p in jmsm.signed_digit_planes_from_limbs(
            jax.numpy.asarray(np.stack([[int_to_limbs(s) for s in sc] for sc in batch_scalars])), 4))
    bases = tuple(jax.numpy.pad(c, [(0, pad), (0, 0)]) for c in g1_to_affine_arrays(pts))
    acc = msm_pod_batched(G1J, (bases,), (planes,), mesh, lanes=(8,), window=4)
    got = g1_jac_to_host(acc)
    for i, sc in enumerate(batch_scalars):
        assert got[i] == g1_msm(pts, sc), f"batch element {i}"


# ------------------------------------------- real-mesh lowering (no chip)

_MESH_LOWERING = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from zkp2p_tpu.curve import jcurve
from zkp2p_tpu.field import jfield
from zkp2p_tpu.ops import ntt
from zkp2p_tpu.parallel.mesh import make_pod_mesh
from zkp2p_tpu.prover import groth16_tpu as G
for mod in (jfield, jcurve, G):
    mod._on_tpu = lambda: True  # resolve the gates as the chip does: Pallas kernels
log_m, nw, nnz = 4, 9, 20
z = lambda n: np.zeros((n, 16), np.uint32)  # domain tables are eager device work: stubbed
tables = {"m": 1 << log_m, "perm": ntt._bit_reverse_perm(1 << log_m), "tw": z(8), "tw_inv": z(8),
          "m_inv_mont": np.zeros(16, np.uint32)}
ntt.domain = lambda _log_m: tables
ntt._coset_powers = lambda _g, _log_m: z(1 << log_m)
S, u32, i32 = jax.ShapeDtypeStruct, jnp.uint32, jnp.int32
g1 = lambda n: (S((n, 16), u32), S((n, 16), u32))
g2 = lambda n: (S((n, 2, 16), u32), S((n, 2, 16), u32))
sel = S((1,), i32)
dpk = G.DeviceProvingKey(
    n_public=1, n_wires=nw, log_m=log_m,
    a_coeff=S((nnz, 16), u32), a_wire=S((nnz,), i32), a_row=S((nnz,), i32),
    b_coeff=S((nnz, 16), u32), b_wire=S((nnz,), i32), b_row=S((nnz,), i32),
    a_bases=g1(nw), b1_bases=g1(nw), b2_bases=g2(nw), c_bases=g1(nw), h_bases=g1(1 << log_m),
    b_sel=S((nw,), i32), c_sel=S((nw,), i32),
    a_nsel=sel, a_wsel=sel, b_nsel=sel, b_wsel=sel, c_nsel=sel, c_wsel=sel,
    alpha_1=None, beta_1=None, beta_2=None, delta_1=None, delta_2=None)
mesh = make_pod_mesh(1, 4, names=("batch", "shard"))
w = S((4, nw, 16), u32, sharding=NamedSharding(mesh, P(("batch", "shard"))))  # one proof a chip
rows = tuple(getattr(dpk, f) for f in G._QAP_ROWS)
text = G._h_pod_fn(mesh, log_m).trace(rows, w).lower(lowering_platforms=("tpu",)).as_text()
print("KERNELS", text.count("tpu_custom_call"))
from zkp2p_tpu.parallel.ntt import TABLE_SPECS
one = S((1, nw, 16), u32, sharding=NamedSharding(mesh, P("batch")))  # a batch of one: the four chips share its h stage
tabs = {k: S(((1 << log_m) // (1 if spec == P("shard") else 8), 16), u32, sharding=NamedSharding(mesh, spec)) for k, spec in TABLE_SPECS.items()}
starts = (S((4,), i32), S((4,), i32))
shared = G._h_shard_fn(mesh, log_m, (nnz, nnz)).program.trace(rows, starts, tabs, one).lower(lowering_platforms=("tpu",)).as_text()
print("SHARED_KERNELS", shared.count("tpu_custom_call"), "ALL_GATHERS", shared.count("stablehlo.all_gather"))
try:
    jax.jit(jax.vmap(G.h_evals, in_axes=(None, 0))).trace(dpk, w).lower(lowering_platforms=("tpu",))
    print("PLAIN_JIT lowered")
except NotImplementedError as e:
    print("PLAIN_JIT", e)
"""


def test_sharded_h_stage_lowers_for_a_real_mesh():
    """Lowered for the TPU platform on four virtual CPU devices, with
    the gates resolved as the chip resolves them: the sharded arm's h
    stage must reach Mosaic inside a shard_map.  A plain jit over
    mesh-sharded inputs is what the four-chip host refused ("Mosaic
    kernels cannot be automatically partitioned") — the virtual CPU
    mesh never sees it, because off a TPU no Pallas kernel arms."""
    out = subprocess.run(
        [sys.executable, "-c", _MESH_LOWERING, REPO],
        capture_output=True, text=True, timeout=300, check=True,
    ).stdout
    kernels = [ln for ln in out.splitlines() if ln.startswith("KERNELS")][0]
    assert int(kernels.split()[1]) > 0, out
    # and the form in which a group's chips share a proof's h stage: kernels inside the shard_map, two all_gathers
    shared = [ln for ln in out.splitlines() if ln.startswith("SHARED_KERNELS")][0].split()
    assert int(shared[1]) > 0 and int(shared[3]) == 2, out
    assert "cannot be automatically partitioned" in out, out


# ------------------------------------------------- warm-start compile cache

_PROBE = r"""
import os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("ZKP2P_NO_CACHE", None)
os.environ["JAX_COMPILATION_CACHE_DIR"] = sys.argv[1]
sys.path.insert(0, sys.argv[2])
from zkp2p_tpu.utils.jaxcfg import cache_dir, enable_cache
enable_cache(min_compile_s=0.0)
assert cache_dir() == sys.argv[1]  # exactly the directory the variable names
import jax, jax.numpy as jnp
import collections
seen = collections.Counter()
jax.monitoring.register_event_listener(lambda name, **kw: seen.update([name]))
def ladder(x):
    for i in range(4):
        x = jnp.sin(x + i) * jnp.cos(x * 0.5) + jnp.tanh(x)
    return x.sum()
jax.jit(ladder)(jnp.ones((256, 256))).block_until_ready()
print("PROGRAMS", seen["/jax/compilation_cache/compile_requests_use_cache"], seen["/jax/compilation_cache/cache_hits"])
"""


def _probe_programs(cache_root: str) -> tuple:
    """(programs asked of the cache, programs it answered) in a process of its own."""
    env = {k: v for k, v in os.environ.items() if k != "ZKP2P_NO_CACHE"}
    out = subprocess.run(
        [sys.executable, "-c", _PROBE, cache_root, REPO],
        capture_output=True, text=True, timeout=300, env=env, check=True,
    ).stdout
    line = [ln for ln in out.splitlines() if ln.startswith("PROGRAMS")][0]
    _tag, asked, hits = line.split()
    assert int(asked) > 0  # the listener saw the compile either way
    return int(asked), int(hits)


def _cache_entries(root: str) -> set:
    return {fn for _r, _d, fns in os.walk(root) for fn in fns if fn.endswith("-cache")}


def test_warm_cache_roundtrip_10x(tmp_path):
    """Cold subprocess compiles + persists into a fresh
    JAX_COMPILATION_CACHE_DIR; a second subprocess on the same directory
    compiles fewer programs and persists nothing new: the warm-start
    contract the warm-cache command exists to establish, read from the
    cache's own events and its directory, not from a ratio of seconds
    (a loaded runner's clocks decide that one)."""
    root = str(tmp_path / "cache")
    asked, hits = _probe_programs(root)
    cold_compiled = asked - hits
    entries = _cache_entries(root)
    assert hits == 0 and entries, "cold run persisted no cache entries"
    asked, hits = _probe_programs(root)
    assert hits > 0 and asked - hits < cold_compiled
    assert _cache_entries(root) == entries


# --------------------------------------------------- heterogeneous tiers

AMORT = "1:0.9,2:1.2,4:1.8,8:3.0"


def _ctl(tier="native", objective=8.0):
    c = BatchController(AmortModel.from_spec(AMORT), objective_s=objective, tier=tier)
    c.observe_batch(1, 0.9)  # end warm-up: predictions run on the curve
    return c


def _mixed_reqs(now, n_bulk=4, n_int=2):
    reqs = [
        SchedRequest(rid=f"b{i:02d}", t_submit=now - 1.0 + i * 1e-3,
                     deadline=now + 8.0, interactive=False)
        for i in range(n_bulk)
    ]
    reqs += [
        SchedRequest(rid=f"i{i:02d}", t_submit=now - 0.5 + i * 1e-3,
                     deadline=now + 8.0, interactive=True)
        for i in range(n_int)
    ]
    return reqs


def test_normalize_tier_fails_closed():
    assert normalize_tier("sharded") == "sharded"
    for junk in ("", "native", "SHARDED", "tpu", "mesh"):
        assert normalize_tier(junk) == "native"


def test_worker_tier_arm_digest_visible(monkeypatch):
    from zkp2p_tpu.utils.audit import execution_digest

    monkeypatch.delenv("ZKP2P_WORKER_TIER", raising=False)
    assert worker_tier_arm() == "native"
    d_native = execution_digest()
    monkeypatch.setenv("ZKP2P_WORKER_TIER", "sharded")
    assert worker_tier_arm() == "sharded"
    assert execution_digest() != d_native
    monkeypatch.setenv("ZKP2P_WORKER_TIER", "native")
    worker_tier_arm()
    assert execution_digest() == d_native


def test_native_defers_bulk_to_sharded_peer():
    """Bulk-lane wide batches prefer the sharded tier: with a live
    sharded peer the native worker's plan serves ONLY interactive; the
    bulk lane stays in the spool (deferred, never shed)."""
    c = _ctl("native")
    now = 1000.0
    plan = c.plan(now, _mixed_reqs(now), cap=8, peer_tiers=["sharded"])
    assert plan.tier == "native"
    assert plan.deferred == {"bulk": 4}
    served = [r.rid for b in plan.batches for r in b]
    assert served == ["i00", "i01"]
    assert plan.shed == []  # deferred bulk is the peer's, never shed here
    assert plan.lanes.get("bulk", 0) == 0


def test_deferred_bulk_never_shed_even_when_hopeless():
    """A doomed bulk request next to a live sharded peer is DEFERRED,
    not shed: the peer's own shed walk owns its deadline."""
    c = _ctl("native")
    now = 1000.0
    reqs = [SchedRequest(rid="doomed", t_submit=now - 50.0, deadline=now - 1.0,
                         interactive=False)]
    plan = c.plan(now, reqs, cap=8, peer_tiers=["sharded"])
    assert plan.shed == [] and plan.deferred == {"bulk": 1}
    # without the peer the same request IS shed (the baseline behavior)
    c2 = _ctl("native")
    plan2 = c2.plan(now, list(reqs), cap=8, peer_tiers=[])
    assert [r.rid for r, _why in plan2.shed] == ["doomed"]


def test_sharded_defers_interactive_to_native_peer():
    """The interactive lane never waits on a sharded-tier dispatch: with
    a live native peer the sharded worker's plan serves ONLY bulk."""
    c = _ctl("sharded")
    now = 1000.0
    plan = c.plan(now, _mixed_reqs(now), cap=8, peer_tiers=["native"])
    assert plan.tier == "sharded"
    assert plan.deferred == {"interactive": 2}
    served = [r.rid for b in plan.batches for r in b]
    assert served == ["b00", "b01", "b02", "b03"]
    assert plan.lanes.get("interactive", 0) == 0


def test_solo_worker_serves_both_lanes():
    """No starvation when the fleet degrades to one tier: without a
    peer of the other tier, either tier serves everything."""
    now = 1000.0
    for tier, peers in (("native", []), ("native", ["native"]),
                        ("sharded", []), ("sharded", ["sharded"]), ("native", None)):
        c = _ctl(tier)
        plan = c.plan(now, _mixed_reqs(now), cap=8, peer_tiers=peers)
        assert plan.deferred == {}, (tier, peers)
        assert sum(len(b) for b in plan.batches) == 6, (tier, peers)


def test_tier_loss_degrades_to_native_with_counted_event():
    """A sharded peer vanishing while bulk is queued fires tier_fallback
    exactly ONCE per loss; the native worker resumes the bulk lane."""
    c = _ctl("native")
    now = 1000.0
    plan = c.plan(now, _mixed_reqs(now), cap=8, peer_tiers=["sharded"])
    assert plan.deferred == {"bulk": 4} and not plan.tier_fallback
    # peer gone, bulk queued: fallback flagged, bulk served again
    plan2 = c.plan(now + 5.0, _mixed_reqs(now + 5.0), cap=8, peer_tiers=[])
    assert plan2.tier_fallback
    assert plan2.deferred == {}
    assert sum(len(b) for b in plan2.batches) == 6
    # once per loss, not once per sweep
    plan3 = c.plan(now + 10.0, _mixed_reqs(now + 10.0), cap=8, peer_tiers=[])
    assert not plan3.tier_fallback
    # peer back then lost again during IDLE: the edge must not fire a
    # stale fallback on the next busy sweep
    c.plan(now + 15.0, [], cap=8, peer_tiers=["sharded"])
    c.plan(now + 20.0, [], cap=8, peer_tiers=[])
    plan4 = c.plan(now + 25.0, _mixed_reqs(now + 25.0), cap=8, peer_tiers=[])
    assert not plan4.tier_fallback


def test_build_controller_resolves_per_tier_amort(monkeypatch):
    """ZKP2P_WORKER_TIER=sharded + no explicit spec + no profile ->
    DEFAULT_SHARDED_AMORT_POINTS (heavy dispatch floor, hard wide-batch
    amortization); native keeps the venmo default; an explicit
    ZKP2P_SCHED_AMORT wins for either tier."""
    from zkp2p_tpu.pipeline.sched import DEFAULT_AMORT_POINTS, build_controller
    from zkp2p_tpu.utils.config import load_config

    # a REAL host profile on this box would seed the curve — isolate it
    monkeypatch.setenv("ZKP2P_PROFILE_PATH", "/nonexistent/no-profile.json")

    cfg = load_config(environ={"ZKP2P_WORKER_TIER": "sharded"})
    monkeypatch.setenv("ZKP2P_WORKER_TIER", "sharded")  # worker_tier_arm fresh-reads
    ctl = build_controller(cfg)
    assert ctl.tier == "sharded"
    for s, cost in DEFAULT_SHARDED_AMORT_POINTS.items():
        assert ctl.amort.batch_s(s) == pytest.approx(cost)
    # the sharded curve amortizes wide batches harder than native
    nat = AmortModel(DEFAULT_AMORT_POINTS)
    assert ctl.amort.per_proof_s(16) / ctl.amort.per_proof_s(1) < \
        nat.per_proof_s(16) / nat.per_proof_s(1)

    monkeypatch.setenv("ZKP2P_WORKER_TIER", "native")
    ctl_n = build_controller(load_config(environ={}))
    assert ctl_n.tier == "native"
    for s, cost in DEFAULT_AMORT_POINTS.items():
        assert ctl_n.amort.batch_s(s) == pytest.approx(cost)

    monkeypatch.setenv("ZKP2P_WORKER_TIER", "sharded")
    ctl_s = build_controller(
        load_config(environ={"ZKP2P_WORKER_TIER": "sharded", "ZKP2P_SCHED_AMORT": AMORT})
    )
    assert ctl_s.amort.batch_s(8) == pytest.approx(3.0)  # explicit spec wins


# ------------------------------------------- mixed-tier toy fleet A/B


def _chaos_mod():
    spec = importlib.util.spec_from_file_location("zkp2p_chaos_for_shard", CHAOS)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def toy_world():
    from zkp2p_tpu.native.lib import get_lib

    if get_lib() is None:
        pytest.skip("native toolchain unavailable")
    return _chaos_mod()._build_world()


def _toy_service(world, **kw):
    from zkp2p_tpu.pipeline.service import ProvingService
    from zkp2p_tpu.prover.native_prove import prove_native_batch

    cs, dpk, vk, witness_fn = world
    kw.setdefault("batch_size", 8)
    kw.setdefault("prover_fn", prove_native_batch)
    return ProvingService(cs, dpk, vk, witness_fn, public_fn=lambda w: [w[1]], **kw)


def _drop(spool, rid, payload):
    os.makedirs(spool, exist_ok=True)
    with open(os.path.join(spool, rid + ".req.json"), "w") as f:
        json.dump(payload, f)


def _fake_peer_hb(fleet_dir, wid, tier):
    os.makedirs(fleet_dir, exist_ok=True)
    with open(os.path.join(fleet_dir, wid + ".hb"), "w") as f:
        json.dump({"pid": 0, "ts": round(time.time(), 3), "worker": wid,
                   "state": "up", "tier": tier}, f)


def test_mixed_tier_fleet_routes_bulk_to_sharded(toy_world, tmp_path, monkeypatch):
    """The mixed-tier A/B on one spool: a native worker with a live
    sharded peer proves ONLY the interactive lane (bulk deferred, sched
    line + heartbeat say so); the sharded worker then proves the bulk
    lane; the chaos checker holds — every request exactly one terminal,
    zero lost, zero duplicated."""
    monkeypatch.setenv("ZKP2P_SCHED", "adaptive")
    monkeypatch.setenv("ZKP2P_SCHED_AMORT", "1:0.05,8:0.1")
    monkeypatch.setenv("ZKP2P_DEADLINE_S", "30")
    spool = str(tmp_path / "spool")
    fleet_dir = str(tmp_path / "fleet")
    monkeypatch.setenv("ZKP2P_FLEET_DIR", fleet_dir)
    for i in range(4):
        _drop(spool, f"b{i}", {"x": 3 + i, "y": 4})
    for i in range(2):
        _drop(spool, f"i{i}", {"x": 5 + i, "y": 6, "priority": "interactive"})

    # --- the native worker, with a live sharded peer advertised
    monkeypatch.setenv("ZKP2P_WORKER_TIER", "native")
    monkeypatch.setenv("ZKP2P_WORKER_ID", "w-native")
    _fake_peer_hb(fleet_dir, "w-sharded", "sharded")
    svc_n = _toy_service(toy_world)
    stats_n = svc_n.process_dir(spool)
    assert stats_n["done"] == 2  # the interactive pair only
    assert svc_n._sched_hb["tier"] == "native"
    assert svc_n._sched_hb["deferred"] == {"bulk": 4}
    # the bulk lane is still OPEN in the spool — no terminal artifact,
    # no claim (deferral is claim-free) — while interactive is proved
    names = set(os.listdir(spool))
    for i in range(4):
        assert f"b{i}.proof.json" not in names and f"b{i}.error.json" not in names
        assert f"b{i}.claim" not in names
    for i in range(2):
        assert f"i{i}.proof.json" in names

    # --- the sharded worker sweeps next (native peer still fresh)
    monkeypatch.setenv("ZKP2P_WORKER_TIER", "sharded")
    monkeypatch.setenv("ZKP2P_WORKER_ID", "w-sharded")
    _fake_peer_hb(fleet_dir, "w-native", "native")
    svc_s = _toy_service(toy_world)
    stats_s = svc_s.process_dir(spool)
    assert stats_s["done"] == 4  # the whole bulk lane
    assert svc_s._sched_hb["tier"] == "sharded"

    # --- global invariant: zero lost, zero duplicated, all verified
    chaos = _chaos_mod()
    report = chaos.check_invariants(spool, vk=toy_world[2])
    assert report["violations"] == [], report
    assert report["proofs_verified"] == 6 and report["states"] == {"done": 6}

    # the decision telemetry: one sched line per worker, defer recorded
    with open(spool + ".metrics.jsonl") as f:
        recs = [json.loads(line) for line in f]
    sched_lines = [r for r in recs if r.get("type") == "sched"]
    by_tier = {ln["tier"]: ln for ln in sched_lines}
    assert by_tier["native"]["deferred"] == {"bulk": 4}
    assert by_tier["native"]["peer_tiers"] == ["sharded"]
    assert "deferred" not in by_tier["sharded"]
    # bulk records attribute to the sharded worker, interactive to native
    reqs = {r["request_id"]: r for r in recs if r.get("type") == "request"}
    assert all(reqs[f"b{i}"]["worker"] == "w-sharded" for i in range(4))
    assert all(reqs[f"i{i}"]["worker"] == "w-native" for i in range(2))
