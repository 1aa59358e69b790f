"""A replica set (pipeline/replicas.py): n one-chip services of one
process on one spool, on the eight virtual CPU devices conftest gives
every test.  The served tests drive the REAL native prover on a
2-constraint circuit through four `ProvingService.run` loops, once wired
with a `witness_fn` and once as `ProvingService.for_venmo` wires the
flagship (`_from_inputs_fn`: whole batches through `cs.witness_batch`); the
device tests drive the real `prove_tpu_batch` on the toy world of
test_msm_resident.py (its h stage, table and resident h MSM are the real
programs) with the key on another device than the first.  The checker of
a spool's outcome is the benchmark's plain one
(benchmarks/reference/exactly_once.py, which imports nothing of the
program)."""

import json
import os
import shutil
import threading
import time

import jax
import numpy as np
import pytest

from benchmarks.harness.check import check_window, verify_many, vk_to_ints
from benchmarks.reference import exactly_once
from zkp2p_tpu.field.bn254 import R
from zkp2p_tpu.native.lib import get_lib
from zkp2p_tpu.pipeline.replicas import ReplicaSet
from zkp2p_tpu.pipeline.service import ProvingService
from zkp2p_tpu.utils import audit
from zkp2p_tpu.utils.metrics import REGISTRY

needs_native = pytest.mark.skipif(get_lib() is None, reason="native toolchain unavailable")


@pytest.fixture(scope="module")
def world():
    from zkp2p_tpu.prover.groth16_tpu import device_pk
    from zkp2p_tpu.snark.groth16 import setup
    from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem

    cs = ConstraintSystem("replicas")
    out = cs.new_public("out")
    x, y, z = cs.new_wire("x"), cs.new_wire("y"), cs.new_wire("z")
    cs.enforce(LC.of(x), LC.of(y), LC.of(z), "mul")
    cs.enforce(LC.of(z), LC.of(z), LC.of(out), "sq")
    cs.compute(z, lambda a, b: a * b % R, [x, y])
    pk, vk = setup(cs, seed="replicas")

    def inputs_fn(payload):
        xv, yv = int(payload["x"]), int(payload["y"])
        return [pow(xv * yv, 2, R)], {x: xv, y: yv}

    return cs, device_pk(pk, cs), vk, lambda payload: cs.witness(*inputs_fn(payload)), inputs_fn


@pytest.fixture(params=["witness_fn", "inputs_fn"])
def tier(request):
    """How a set's services come by a witness: a request at a time, or the
    tier of the flagship's service (`inputs_fn` + `cs.witness_batch`)."""
    return request.param


def _prover(pause_s):
    def prove(dpk, wits):
        from zkp2p_tpu.prover.native_prove import prove_native

        time.sleep(pause_s)  # a device step: the peers get their turn at the spool
        return [prove_native(dpk, w) for w in wits]
    return prove


def _set(world, n=4, pause_s=0.05, tier="witness_fn", **kw):
    cs, dpk, vk, witness_fn, inputs_fn = world
    kw = dict({"batch_size": 4}, prover_fn=_prover(pause_s), retry_backoff_s=0.0, **kw)
    if tier == "inputs_fn":
        return ReplicaSet(lambda key: ProvingService._from_inputs_fn(cs, key, vk, inputs_fn, **kw), dpk, n=n)
    return ReplicaSet(lambda key: ProvingService(cs, key, vk, witness_fn, public_fn=lambda w: [w[1]], **kw), dpk, n=n)


def _payload(i):
    return {"x": 2 + i, "y": 3 + 2 * i}


def _write_reqs(spool, n, prefix="r"):
    for i in range(n):
        tmp = os.path.join(spool, f"{prefix}{i:03d}.tmp")
        with open(tmp, "w") as f:
            json.dump(_payload(i), f)
        os.replace(tmp, os.path.join(spool, f"{prefix}{i:03d}.req.json"))


def _serve(rset, spool, poll_s=0.02):
    """The set's `run` on a thread; returns it once `last_preflight` says every loop is up."""
    t_started = time.time()
    out = {}
    th = threading.Thread(target=lambda: out.setdefault("why", rset.run(spool, poll_s=poll_s)), daemon=True)
    th.start()
    while (audit.last_preflight() or {}).get("ts", 0) < t_started and th.is_alive():
        time.sleep(0.01)
    assert th.is_alive() and rset.live() == len(rset.replicas)
    return th, out


def _ended(spool):
    return sum(1 for n in os.listdir(spool) if n.endswith((".proof.json", ".error.json")))


def _wait(cond, timeout_s=120.0):
    t_give_up = time.time() + timeout_s
    while not cond():
        assert time.time() < t_give_up, "timed out"
        time.sleep(0.01)


def _sink(spool):
    with open(spool.rstrip("/") + ".metrics.jsonl") as f:
        return [json.loads(line) for line in f if line.strip()]


@needs_native
def test_four_replicas_serve_forty_requests_each_exactly_once(world, tier, tmp_path):
    from zkp2p_tpu.utils import trace

    trace.reset()  # the ring is the process's: a span an earlier test's solo service left there is not this set's
    spool = str(tmp_path / "spool")
    os.makedirs(spool)
    rset = _set(world, tier=tier)
    from zkp2p_tpu.prover.groth16_tpu import key_device

    assert [key_device(s.dpk) for s in rset.replicas] == jax.local_devices()[:4]
    assert rset.replicas[0].dpk is world[1]  # the loaded key itself, pinned where it lives
    lost0 = REGISTRY.counter("zkp2p_service_claim_lost_total").value
    th, out = _serve(rset, spool)
    assert audit.gate_arms()["replicas"] == "4" and REGISTRY.gauge("zkp2p_replicas_live").value == 4
    _write_reqs(spool, 40)
    _wait(lambda: _ended(spool) == 40)
    rset.request_drain()
    th.join(60)
    assert not th.is_alive() and out["why"] == "drained" and rset.live() == 0

    res = exactly_once.check(spool)
    assert res["ok"], res
    assert res["requests"] == res["ended"] == 40 and sum(res["served"].values()) == 40
    assert sorted(res["served"]) == ["0", "1", "2", "3"]  # every replica served: none holds more than two batches
    # every proof passes the benchmark's own pairing check under the key's vk
    paths = [(os.path.join(spool, f"r{i:03d}.proof.json"), os.path.join(spool, f"r{i:03d}.public.json")) for i in range(40)]
    vk_ints = vk_to_ints(world[2])
    assert verify_many(vk_ints, paths, workers=4) == [True] * 40
    # and carries its own request's signal, worked out from the request alone; a pair of proofs
    # that changed places still passes the pairing and reads as two failures
    asked = [{"rid": f"r{i:03d}", "state": "done", "payload": _payload(i)} for i in range(40)]
    tie = lambda p: {0: pow(p["x"] * p["y"], 2, R)}  # noqa: E731
    assert check_window(vk_ints, spool, asked, 4, tie) == 0 and all(r["valid"] for r in asked)
    swapped = str(tmp_path / "swapped")
    shutil.copytree(spool, swapped)
    for kind in ("proof", "public"):
        a, b = (os.path.join(swapped, f"r{i:03d}.{kind}.json") for i in (7, 8))
        os.replace(a, a + ".tmp")
        os.replace(b, a)
        os.replace(a + ".tmp", b)
    assert check_window(vk_ints, swapped, asked, 4, tie) == 2
    assert [r["rid"] for r in asked if not r["valid"]] == ["r007", "r008"]

    recs = _sink(spool)
    requests = [r for r in recs if r.get("type") == "request"]
    assert len(requests) == 40 and all(r["state"] == "done" and r["replica"] in (0, 1, 2, 3) for r in requests)
    spans = [r for r in recs if r.get("type") == "stage"]
    witness = "service/witness_batch" if tier == "inputs_fn" else "service/witness"  # the tier that ran
    for name in ("service/sweep", "service/starved", witness, "service/prove", "service/verify", "service/emit"):
        under = [s for s in spans if s["stage"].endswith(name)]
        assert under and all(s.get("replica") in (0, 1, 2, 3) for s in under), name
    # the members of a set take turns at the batched tier: no two of them inside `cs.witness_batch` at once
    turns = [s for s in spans if s["stage"].endswith("service/witness_turn")]
    inside = sorted((s["t0"], s["t0"] + s["ms"] / 1e3) for s in spans if s["stage"].endswith("service/witness_batch"))
    assert len(turns) == len(inside) and all(s["replica"] in (0, 1, 2, 3) and s["n"] >= 1 for s in turns)
    assert all(later[0] >= earlier[1] - 2e-3 for earlier, later in zip(inside, inside[1:]))
    # the set's bring-up is in the sink too: a placement a replica, of which the first copies nothing
    placed = sorted((s for s in spans if s["stage"] == "replicas/place"), key=lambda s: s["replica"])
    assert [s["replica"] for s in placed] == [0, 1, 2, 3] and placed[0]["bytes"] == 0
    assert all(s["bytes"] == placed[1]["bytes"] > 0 for s in placed[1:])
    idle = sorted((s for s in spans if s["stage"] == "replicas/idle"), key=lambda s: s["replica"])
    assert [s["replica"] for s in idle] == [0, 1, 2, 3] and sum(s["n"] for s in idle) == 40
    assert all(s["n"] > 0 and 0 <= s["ms"] <= s["span_s"] * 1e3 + 1 for s in idle)
    by_replica = {str(s["replica"]): s["n"] for s in idle}
    assert by_replica == res["served"]
    batches = sum(REGISTRY.counter("zkp2p_replica_batches_total", {"replica": str(i)}).value for i in range(4))
    assert batches >= 10  # 40 requests in batches of at most four
    assert REGISTRY.counter("zkp2p_service_claim_lost_total").value > lost0  # four scans of one backlog


@needs_native
def test_drain_with_batches_in_flight_on_all_four_loses_and_duplicates_nothing(world, tier, tmp_path):
    spool = str(tmp_path / "spool")
    os.makedirs(spool)
    rset = _set(world, pause_s=0.4, tier=tier)
    th, out = _serve(rset, spool)
    _write_reqs(spool, 64)
    _wait(lambda: all(s.n_batches >= 1 for s in rset.replicas))  # every replica has a batch in its prover or past it
    claimed = sum(1 for n in os.listdir(spool) if n.endswith(".claim"))
    assert claimed >= 8
    rset.request_drain()
    th.join(60)
    assert not th.is_alive() and out["why"] == "drained"
    res = exactly_once.check(spool)
    assert res["ok"], res
    assert 16 <= res["ended"] < 64  # what was claimed ended; what was not is free, with no claim left on it
    # a solo service takes the rest: still every request once, under one name each
    cs, dpk, vk, witness_fn, _ = world
    solo = ProvingService(cs, dpk, vk, witness_fn, public_fn=lambda w: [w[1]], prover_fn=_prover(0.0), batch_size=4)
    while _ended(spool) < 64:
        solo.process_dir(spool)
    res = exactly_once.check(spool)
    assert res["ok"] and res["ended"] == 64 and sum(res["served"].values()) == 64 and res["served"]["solo"] > 0, res


@needs_native
def test_the_replicas_of_a_set_count_each_other_as_peers(world, tier, tmp_path, monkeypatch):
    """`_live_peers` is the adaptive scheduler's `parallelism`: inside a set
    of four it is four (plus the other processes of a fleet), not one."""
    spool = str(tmp_path / "spool")
    os.makedirs(spool)
    cs, dpk, vk, witness_fn, _ = world
    solo = ProvingService(cs, dpk, vk, witness_fn, public_fn=lambda w: [w[1]], prover_fn=_prover(0.0))
    solo._resolve_policy()
    assert solo._live_peers() == 1
    rset = _set(world, tier=tier)
    assert [s._live_peers() for s in rset.replicas] == [1] * 4  # no loop is up yet
    th, out = _serve(rset, spool)
    assert [s._live_peers() for s in rset.replicas] == [4] * 4
    # the scheduler is handed that number
    seen = []
    from zkp2p_tpu.pipeline import sched

    real_plan = sched.BatchController.plan
    monkeypatch.setattr(sched.BatchController, "plan",
                        lambda self, *a, **kw: (seen.append(kw.get("parallelism")), real_plan(self, *a, **kw))[1])
    monkeypatch.setenv("ZKP2P_SCHED", "adaptive")
    _write_reqs(spool, 8)
    _wait(lambda: _ended(spool) == 8)
    assert seen and set(seen) == {4}
    # two more processes of a fleet on the same spool: their heartbeat files, and this process's own
    fleet = tmp_path / "fleet"
    fleet.mkdir()
    for wid in ("me", "w1", "w2"):
        (fleet / f"{wid}.hb").write_text("{}")
    rset.replicas[2]._fleet_dir = str(fleet)
    assert rset.replicas[2]._live_peers() == 6
    rset.request_drain()
    th.join(60)
    assert not th.is_alive() and [s._live_peers() for s in rset.replicas[:2]] == [1, 1]
    assert audit.gate_arms()["replicas"] == "4"
    solo.run(spool, poll_s=0.01, max_sweeps=1)
    assert audit.gate_arms()["replicas"] == "off"


def test_a_short_batch_proves_at_the_size_it_was_claimed_for(world, tmp_path, monkeypatch):
    """The device prover compiles a program a batch shape: three requests
    under a batch size of four go to it as four witnesses, and come back as
    three proofs.  A stand-in `prover_fn` is handed the three."""
    from zkp2p_tpu.prover import groth16_tpu

    cs, dpk, vk, witness_fn, _ = world
    sizes = []

    def device_prover(dpk_, wits):
        sizes.append(len(wits))
        assert wits[-1] is wits[2]  # the last witness repeated
        return _prover(0.0)(dpk_, wits)

    if get_lib() is None:
        pytest.skip("native toolchain unavailable")
    monkeypatch.setattr(groth16_tpu, "prove_tpu_batch", device_prover)
    spool = str(tmp_path / "spool")
    os.makedirs(spool)
    _write_reqs(spool, 3)
    svc = ProvingService(cs, dpk, vk, witness_fn, public_fn=lambda w: [w[1]], prover_fn=None, batch_size=4)
    assert svc.process_dir(spool)["done"] == 3 and sizes == [4]
    _write_reqs(spool, 3, prefix="s")
    handed = []
    svc = ProvingService(cs, dpk, vk, witness_fn, public_fn=lambda w: [w[1]], batch_size=4,
                         prover_fn=lambda d, w: (handed.append(len(w)), _prover(0.0)(d, w))[1])
    assert svc.process_dir(spool)["done"] == 3 and handed == [3]
    assert exactly_once.check(spool)["ok"]


# ------------------------------------------------- the device prover, where its key lives


def _toy(monkeypatch):
    from test_msm_resident import _toy_world

    return _toy_world(monkeypatch)


def test_a_pinned_batch_is_the_same_bytes_on_four_devices_from_prove_host_and_from_prove_native(monkeypatch):
    """What the replica cells' pinned warm-up batch is held to, at a toy size:
    one batch with (r, s) pinned, proved where each replica's key lives."""
    from zkp2p_tpu.formats.proof_json import proof_to_json
    from zkp2p_tpu.prover import groth16_tpu as G
    from zkp2p_tpu.prover.native_prove import prove_native
    from zkp2p_tpu.snark.groth16 import prove_host

    cs, pk, dpk, wits = _toy(monkeypatch)
    batch, rs, ss = [wits[1], wits[2]], [0x1234567, 0x89abcde], [0x7654321, 0xedcba98]
    want = [proof_to_json(prove_host(pk, cs, w, r=r, s=s)) for w, r, s in zip(batch, rs, ss)]
    if get_lib() is not None:
        assert [proof_to_json(prove_native(dpk, w, r, s)) for w, r, s in zip(batch, rs, ss)] == want
    assert G.key_device(dpk) is None  # as loaded: pinned nowhere
    for dev in jax.local_devices()[:4]:
        key = G.place_key(dpk, dev)
        assert G.key_device(key) == dev and (key is dpk) == (dev == jax.local_devices()[0])
        assert [proof_to_json(p) for p in G.prove_tpu_batch(key, batch, rs=rs, ss=ss)] == want, dev
        assert key._h_table_cache.devices() == {dev}


def test_a_batch_runs_where_its_key_lives_and_touches_no_buffer_of_the_first_device(monkeypatch):
    """Key on device 2: what the real stage programs are handed and what
    they return, the resident table and the key's class splits are all on
    device 2 and on no other (the toy world's four witness MSMs are host
    stand-ins: what they are HANDED is checked, not what they make up)."""
    from zkp2p_tpu.prover import groth16_tpu as G

    cs, pk, dpk, wits = _toy(monkeypatch)
    dev0, dev2 = jax.local_devices()[0], jax.local_devices()[2]
    from zkp2p_tpu.ops import ntt

    assert all(isinstance(v, (int, np.ndarray)) for v in ntt.domain(dpk.log_m).values())  # host constants, no device's
    key = G.place_key(dpk, dev2)
    outputs = {}
    for name in ("_jit_h_planes", "_jit_h_table", "_jit_msm_h_resident"):
        real = getattr(G, name)

        def spy(*a, _real=real, _name=name, **kw):
            out = _real(*a, **kw)
            outputs.setdefault(_name, []).extend(jax.tree_util.tree_leaves(out))
            return out
        monkeypatch.setattr(G, name, spy)
    real_host = G._jit_msm_g1

    def msm_inputs(bases, planes, _real=real_host):
        outputs.setdefault("msm_inputs", []).extend(jax.tree_util.tree_leaves((bases, planes)))
        return _real(bases, planes)
    monkeypatch.setattr(G, "_jit_msm_g1", msm_inputs)
    proofs = G.prove_tpu_batch(key, wits[:2], rs=[1, 2], ss=[3, 4])
    assert len(proofs) == 2
    assert set(outputs) == {"_jit_h_planes", "_jit_h_table", "_jit_msm_h_resident", "msm_inputs"}
    for name, leaves in outputs.items():
        assert leaves and all(a.devices() == {dev2} for a in leaves), name
    assert key._h_table_cache.devices() == {dev2}
    assert all(a.devices() == {dev2} for a in jax.tree_util.tree_leaves(key._split_cache))
    assert G._hbm_bytes_limit(dev2) == G._hbm_bytes_limit(dev0) == G.NOMINAL_HBM_BYTES
