"""The device's idle time as the program accounts for it (PR 35): one
`tpu/prove_batch/device_idle` span a batch, from the last stage of the
feeding thread's last batch on the same key placement to this batch's
`device`, split into eight causes by what that thread was doing
(`utils.trace.thread_tally`), with `offcpu` beside them; `upload` beside
`stage/h_planes`; and the service's `handover` and `poll` spans between
sweeps.  The real `prove_tpu_batch` and the real service write the spans
around a stood-in device, as benchmarks/tests/test_stage_metrics.py does."""

import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from test_replicas import _ended, _wait, _write_reqs, needs_native, world  # noqa: F401 — `world` is a fixture

from zkp2p_tpu.pipeline.replicas import ReplicaSet
from zkp2p_tpu.pipeline.service import ProvingService
from zkp2p_tpu.prover import groth16_tpu as G
from zkp2p_tpu.utils import audit
from zkp2p_tpu.utils import trace as tr

CAUSES = tuple(G.IDLE_CAUSES) + ("other",)


@pytest.fixture
def stood_in_device(monkeypatch):
    """`prove_tpu_batch` itself around a device stood in for: six stages
    of 2 ms whose values are ready at once, accumulators that are nothing,
    and `_assemble` the C++ prover's proof of the batch's next witness."""
    from zkp2p_tpu.prover.native_prove import prove_native

    real, state = G.prove_tpu_batch, threading.local()

    def entry(dpk, witnesses, rs=None, ss=None):
        state.witnesses = iter(list(witnesses))
        return real(dpk, witnesses, rs=rs, ss=ss)

    def device(dpk, w_mont, batched=False, watch=None):
        done = np.zeros(w_mont.shape[0], np.uint32)
        for name in G.STAGES:
            time.sleep(0.002)
            watch.enqueued(name, done)
        return (done,) * 5

    monkeypatch.setattr(G, "prove_tpu_batch", entry)
    monkeypatch.setattr(G, "_prove_device", device)
    monkeypatch.setattr(G, "_h_table", lambda dpk: None)
    monkeypatch.setattr(G, "g1_jac_to_host", lambda acc: [None] * len(acc))
    monkeypatch.setattr(G, "g2_jac_to_host", lambda acc: [None] * len(acc))
    monkeypatch.setattr(G, "_assemble", lambda dpk, acc, r, s: prove_native(dpk, next(state.witnesses), r, s))
    monkeypatch.setattr(G, "_fed_last", {})  # no placement has been fed: an earlier test's batch is not this one's last
    monkeypatch.setenv("ZKP2P_TPU_SHARD", "off")
    monkeypatch.setattr(G, "BATCH_CHUNK", "0")
    tr.reset()
    tr.clear_context()
    return entry


def _wits(world, n=2):
    return [world[3]({"x": 2 + i, "y": 3 + i}) for i in range(n)]


def _by_end(records, end):
    return [r for r in records if r["stage"] == end or r["stage"].endswith("/" + end)]


def _gaps(records):
    """[(the device_idle span, {cause: its span})] in the order written."""
    by_parent = {}
    for r in records:
        if "/device_idle/" in r["stage"]:
            by_parent.setdefault(r["parent"], {})[r["stage"].rsplit("/", 1)[1]] = r
    return [(g, by_parent[g["id"]]) for g in _by_end(records, "prove_batch/device_idle")]


@needs_native
def test_two_batches_of_one_thread_give_one_gap_whose_eight_causes_sum_to_it(world, stood_in_device):
    dpk, wits = world[1], _wits(world)
    stood_in_device(dpk, wits)
    t_verify = time.perf_counter()
    with tr.trace("service/verify"):
        time.sleep(0.03)
    verify_wall_ms = (time.perf_counter() - t_verify) * 1e3  # the span lies inside it, however late the sleep woke
    with tr.trace("service/emit"):
        end = time.thread_time() + 0.01
        while time.thread_time() < end:
            pass
    time.sleep(0.02)  # in no span
    t_wait = time.time()
    time.sleep(0.015)
    t_woke = time.time()
    tr.record("service/starved", t_wait, t_woke)
    stood_in_device(dpk, wits)

    recs = tr.records()
    ((gap, parts),) = _gaps(recs)
    first, second = sorted(_by_end(recs, "prove_batch/device"), key=lambda r: r["t0"])
    last_stage = max(r["t0"] + r["ms"] / 1e3 for r in _by_end(recs, "stage/msm_h") if r["parent"] == first["id"])
    # from the first batch's last stage being ready to the second's `device` opening, as a span of the second
    assert gap["t0"] == pytest.approx(last_stage, abs=2e-6) and gap["t0"] + gap["ms"] / 1e3 == pytest.approx(second["t0"], abs=2e-6)
    assert gap["parent"] == second["id"] and gap["n"] == 2 and gap["stage"] == "tpu/prove_batch/device_idle"
    assert set(parts) == set(CAUSES) | {"offcpu"}
    assert sum(parts[c]["ms"] for c in CAUSES) == pytest.approx(gap["ms"], abs=1.0)
    assert sum(parts[c]["cpu_ms"] for c in CAUSES) == pytest.approx(gap["cpu_ms"], abs=1.0)
    # each named span's self time under its cause; the unnamed sleep is `other`
    finish = min(_by_end(recs, "prove_batch/finish"), key=lambda r: r["t0"])
    prep = max(_by_end(recs, "prove_batch/prep"), key=lambda r: r["t0"])
    # the stand-in's C++ prover writes spans of its own: those this thread closed are no part of `finish`'s self time
    under_finish = sum(r["ms"] for r in recs if r["parent"] == finish["id"] and r["tid"] == finish["tid"])
    assert parts["finish"]["ms"] == pytest.approx(finish["ms"] - under_finish, abs=0.5)
    assert parts["prep"]["ms"] == pytest.approx(prep["ms"], abs=0.5)
    # From above, only what this test measured around its own sleeps: a sleep on a loaded host (six workers)
    # wakes late by any amount, and a thread that is runnable and not run is off a CPU too.
    assert 30.0 <= parts["verify"]["ms"] <= verify_wall_ms + 0.5 and parts["verify"]["cpu_ms"] < 10.0
    assert 10.0 <= parts["emit"]["cpu_ms"] <= parts["emit"]["ms"] + 0.5
    assert 15.0 <= parts["starved"]["ms"] == pytest.approx((t_woke - t_wait) * 1e3, abs=0.5) and parts["starved"]["cpu_ms"] == 0.0
    assert parts["handover"]["ms"] == parts["poll"]["ms"] == 0.0  # zero is written: a mean over spans is one over batches
    assert 20.0 <= parts["other"]["ms"]  # the remainder: the sum above holds it from above, every other cause held from below
    # off the CPU in the causes that are work: the sleep inside `verify`, not the one the thread chose (`starved`)
    work_off_cpu = sum(parts[c]["ms"] - parts[c]["cpu_ms"] for c in G._IDLE_WORK)
    assert 25.0 <= parts["offcpu"]["ms"] == pytest.approx(work_off_cpu, abs=1.0) and "cpu_ms" not in parts["offcpu"]
    # laid end to end from the gap's start, an account and no more
    assert parts["finish"]["t0"] == gap["t0"] and parts["other"]["t0"] > parts["prep"]["t0"] >= parts["starved"]["t0"]


@needs_native
def test_no_gap_for_a_placement_s_first_batch_nor_across_two_feeding_threads(world, stood_in_device):
    dpk, wits = world[1], _wits(world)
    pinned = G.place_key(dpk, jax.local_devices()[1])
    stood_in_device(dpk, wits)
    assert not _gaps(tr.records())  # the default device's first
    stood_in_device(pinned, wits)
    assert not _gaps(tr.records())  # another placement's first, on the same thread
    th = threading.Thread(target=stood_in_device, args=(dpk, wits))  # a warm-up's thread
    th.start()
    th.join()
    assert not _gaps(tr.records())
    stood_in_device(dpk, wits)  # this thread again: the other fed the placement last
    assert not _gaps(tr.records())
    stood_in_device(dpk, wits)
    stood_in_device(pinned, wits)
    gaps = _gaps(tr.records())
    assert len(gaps) == 2
    # the pinned placement's gap reaches back over the default one's batches to ITS last batch
    devices = sorted(_by_end(tr.records(), "prove_batch/device"), key=lambda r: r["t0"])
    assert gaps[1][0]["parent"] == devices[-1]["id"] and gaps[1][0]["t0"] < devices[2]["t0"]
    assert gaps[0][0]["parent"] == devices[-2]["id"] and gaps[0][0]["t0"] > devices[-3]["t0"]


@needs_native
def test_the_upload_span_lies_beside_h_planes_and_moves_no_stage(world, stood_in_device, monkeypatch):
    """The chunk's limbs take 20 ms to arrive: `upload` says so, `stage/
    h_planes` still starts where `device` does and covers it, and the six
    stages still partition `device`."""
    ready = jax.block_until_ready

    def slow_limbs(value):
        if getattr(value, "ndim", 0) == 3:  # (B, n_wires, 16): the witnesses
            time.sleep(0.02)
        return ready(value)

    monkeypatch.setattr(G.jax, "block_until_ready", slow_limbs)
    dpk, wits = world[1], _wits(world)
    stood_in_device(dpk, wits)
    recs = tr.records()
    (device,), (upload,), (h_planes,) = (_by_end(recs, e) for e in ("prove_batch/device", "prove_batch/upload", "stage/h_planes"))
    assert upload["stage"] == "tpu/prove_batch/upload" and upload["parent"] == device["id"]
    assert upload["bytes"] == len(wits) * dpk.n_wires * 16 * 4 and upload["chunk"] == 0 and upload["ms"] >= 20.0
    assert upload["t0"] == device["t0"] == h_planes["t0"]
    assert h_planes["ms"] >= upload["ms"]
    stages = [r for r in recs if "/stage/" in r["stage"]]
    assert len(stages) == len(G.STAGES)
    assert sum(r["ms"] for r in stages) == pytest.approx(device["ms"], abs=5.0)


def _service(world, prove, **kw):
    cs, dpk, vk, witness_fn, _ = world
    return ProvingService(cs, dpk, vk, witness_fn, public_fn=lambda w: [w[1]], prover_fn=prove,
                          retry_backoff_s=0.0, batch_size=2, **kw)


def _sink(spool):
    with open(spool.rstrip("/") + ".metrics.jsonl") as f:
        return [r for r in map(json.loads, f) if r.get("type") == "stage"]


@needs_native
def test_two_sweeps_write_handover_and_poll_beside_the_sweep_and_idle_passes_write_none(world, stood_in_device, tmp_path):
    spool = str(tmp_path / "spool")
    os.makedirs(spool)
    svc = _service(world, lambda dpk, wits: stood_in_device(dpk, wits))
    _write_reqs(spool, 4, "a")  # one sweep of two batches: all four are there when the service first looks
    th = threading.Thread(target=svc.run, args=(spool,), kwargs={"poll_s": 0.03}, daemon=True)
    th.start()
    _wait(lambda: _ended(spool) == 4)
    time.sleep(0.4)  # a dozen passes that find nothing
    n_idle = len(_sink(spool))
    time.sleep(0.2)
    assert len(_sink(spool)) == n_idle  # ... and write nothing
    _write_reqs(spool, 1, "b")  # a second sweep, of one batch
    _wait(lambda: _ended(spool) == 5)
    svc.request_drain()
    th.join(timeout=60)
    assert not th.is_alive()

    recs = _sink(spool)
    sweeps = sorted(_by_end(recs, "service/sweep"), key=lambda r: r["t0"])
    handovers = sorted(_by_end(recs, "service/handover"), key=lambda r: r["t0"])
    polls = sorted(_by_end(recs, "service/poll"), key=lambda r: r["t0"])
    assert len(sweeps) == 2 and len(handovers) == 2 and 1 <= len(polls) <= 2
    # beside the sweep, never under it (nor under anything)
    assert all(r["parent"] is None and r["stage"] in ("service/handover", "service/poll") for r in handovers + polls)
    assert all("cpu_ms" in r for r in handovers + polls)
    # the idle passes' sleeps are folded into the poll the second sweep's pass wrote
    assert polls[-1]["ms"] >= 300.0 and polls[-1]["cpu_ms"] < 0.5 * polls[-1]["ms"]
    # the first batch of the second sweep waited for the polls, and says so
    gaps = _gaps(recs)
    assert len(gaps) == 2  # three batches of one thread: the first has none
    assert all(sum(p[c]["ms"] for c in CAUSES) == pytest.approx(g["ms"], abs=1.0) for g, p in gaps)
    across = gaps[-1][1]
    assert across["poll"]["ms"] == pytest.approx(polls[-1]["ms"], abs=0.5)
    sweep_self = handovers[-1]["ms"]  # and the scan, the flush, the sweep's close: `handover`
    assert across["handover"]["ms"] >= sweep_self and across["other"]["ms"] < 0.25 * gaps[-1][0]["ms"]
    within = gaps[0][1]  # both batches of the first sweep: no pass between them
    assert within["poll"]["ms"] == 0.0 and within["verify"]["ms"] > 0.0 and within["emit"]["ms"] > 0.0
    # what a batch adds to the sink: a gap, its eight causes, offcpu, an upload; a serving pass, two more
    n_batches = len(_by_end(recs, "prove_batch/device"))
    added = [r for r in recs if "/device_idle" in r["stage"] or r["stage"].endswith(("/upload", "service/handover", "service/poll"))]
    assert n_batches == 3 and len(added) <= 11 * n_batches + 2 * len(sweeps)


@needs_native
def test_under_a_replica_set_each_gap_carries_its_replica_and_none_mixes_two(world, stood_in_device, tmp_path):
    spool = str(tmp_path / "spool")
    os.makedirs(spool)
    cs, dpk, vk, witness_fn, _ = world
    rset = ReplicaSet(lambda key: ProvingService(cs, key, vk, witness_fn, public_fn=lambda w: [w[1]], retry_backoff_s=0.0,
                                                 prover_fn=lambda d, w: stood_in_device(d, w), batch_size=2), dpk, n=2)
    assert [G.key_device(s.dpk) for s in rset.replicas] == jax.local_devices()[:2]
    t_started = time.time()
    th = threading.Thread(target=rset.run, args=(spool,), kwargs={"poll_s": 0.02}, daemon=True)
    th.start()
    _wait(lambda: (audit.last_preflight() or {}).get("ts", 0) >= t_started or not th.is_alive())
    for wave in range(3):
        _write_reqs(spool, 8, f"w{wave}")
        _wait(lambda: _ended(spool) == 8 * (wave + 1))
    rset.request_drain()
    th.join(timeout=60)
    assert not th.is_alive()

    recs = _sink(spool)
    gaps = _gaps(recs)
    served = {r["replica"] for r in _by_end(recs, "prove_batch/device")}
    assert served == {0, 1} and {g["replica"] for g, _ in gaps} == served
    for gap, parts in gaps:
        mine = sorted((r for r in _by_end(recs, "prove_batch/device") if r["replica"] == gap["replica"]), key=lambda r: r["t0"])
        assert all(p["replica"] == gap["replica"] for p in parts.values())
        # it closes at a `device` of its own replica and opens at the end of that replica's batch before it
        i = next(i for i, d in enumerate(mine) if d["id"] == gap["parent"])
        assert i > 0 and gap["t0"] + gap["ms"] / 1e3 == pytest.approx(mine[i]["t0"], abs=2e-6)
        before = mine[i - 1]
        assert before["t0"] < gap["t0"] <= before["t0"] + before["ms"] / 1e3 + 1e-3
        assert sum(parts[c]["ms"] for c in CAUSES) == pytest.approx(gap["ms"], abs=1.0)
    # a gap a batch but each replica's first
    assert len(gaps) == len(_by_end(recs, "prove_batch/device")) - len(served)
