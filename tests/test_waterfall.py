"""Request waterfalls (PR 8 tentpole): per-request lifecycle spans and
queue_wait_s on every service record (deferred sweeps included), the
Chrome-trace export (valid JSON, monotonic timestamps, one pid per
worker / one tid per request), the takeover and batch-fill meters, and
the time-series sampler line schema — tier-1 resident."""

import json
import os
import subprocess
import sys
import time

import pytest

from zkp2p_tpu.field.bn254 import R
from zkp2p_tpu.native import lib as native
from zkp2p_tpu.pipeline.service import ProvingService, TimeseriesSampler
from zkp2p_tpu.utils import faults
from zkp2p_tpu.utils.metrics import REGISTRY

pytestmark = pytest.mark.skipif(native.get_lib() is None, reason="native toolchain unavailable")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def world():
    from zkp2p_tpu.prover.groth16_tpu import device_pk
    from zkp2p_tpu.snark.groth16 import setup
    from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem

    cs = ConstraintSystem("waterfall")
    out = cs.new_public("out")
    x = cs.new_wire("x")
    y = cs.new_wire("y")
    z = cs.new_wire("z")
    cs.enforce(LC.of(x), LC.of(y), LC.of(z), "mul")
    cs.enforce(LC.of(z), LC.of(z), LC.of(out), "sq")
    cs.compute(z, lambda a, b: a * b % R, [x, y])
    pk, vk = setup(cs, seed="waterfall")
    dpk = device_pk(pk, cs)

    def witness_fn(payload):
        xv, yv = int(payload["x"]), int(payload["y"])
        return cs.witness([pow(xv * yv, 2, R)], {x: xv, y: yv})

    return cs, dpk, vk, witness_fn


def _mk(world, **kw):
    from zkp2p_tpu.prover.native_prove import prove_native_batch

    cs, dpk, vk, witness_fn = world
    kw.setdefault("batch_size", 2)
    kw.setdefault("prover_fn", prove_native_batch)
    return ProvingService(cs, dpk, vk, witness_fn, public_fn=lambda w: [w[1]], **kw)


def _write_reqs(spool, pairs, prefix="r"):
    for i, (xv, yv) in enumerate(pairs):
        with open(os.path.join(spool, f"{prefix}{i}.req.json"), "w") as f:
            json.dump({"x": xv, "y": yv}, f)


def _records(spool):
    path = str(spool).rstrip("/") + ".metrics.jsonl"
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(ln) for ln in f if json.loads(ln).get("type") == "request"]


def _counter(name, **labels):
    return REGISTRY.counter(name, labels or None).value


# ------------------------------------------------------- record schema


def test_done_records_carry_full_waterfall(world, tmp_path, monkeypatch):
    """Every done record: t_submit/t_claim/queue_wait_s plus the
    witness -> prove -> verify -> emit span chain, with the prove span
    SHARED across the batch (one interval, every member)."""
    monkeypatch.delenv("ZKP2P_METRICS_SINK", raising=False)
    monkeypatch.delenv("ZKP2P_FAULTS", raising=False)
    faults.reset()
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5), (2, 7)])
    t_before = time.time()
    assert _mk(world).process_dir(spool)["done"] == 2
    recs = {r["request_id"]: r for r in _records(spool)}
    assert set(recs) == {"r0", "r1"}
    for r in recs.values():
        assert r["state"] == "done"
        assert r["t_submit"] <= r["t_claim"] <= time.time()
        assert r["t_submit"] <= t_before + 1.0  # mtime-anchored, not claim-time
        assert r["queue_wait_s"] == pytest.approx(r["t_claim"] - r["t_submit"], abs=1e-3)
        names = [s["name"] for s in r["spans"]]
        assert names.index("witness") < names.index("prove") < names.index("emit")
        assert "verify" in names
        for s in r["spans"]:
            assert s["ms"] >= 0 and s["t0"] >= r["t_submit"] - 1.0
    # the batch prove is ONE shared interval: same t0/ms on both members
    p0 = [s for s in recs["r0"]["spans"] if s["name"] == "prove"][0]
    p1 = [s for s in recs["r1"]["spans"] if s["name"] == "prove"][0]
    assert p0["t0"] == p1["t0"] and p0["ms"] == p1["ms"] and p0["n"] == 2


def test_retry_attempts_and_rungs_appear_as_spans(world, tmp_path, monkeypatch):
    """A transient prove fault retried once leaves attempt-0 AND
    attempt-1 prove spans (plus the backoff) on the terminal record —
    failed attempts are part of the waterfall, not invisible."""
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5)])
    monkeypatch.setenv("ZKP2P_FAULTS", "prove:raise:once")
    faults.reset()
    svc = _mk(world, retry_backoff_s=0.01)
    assert svc.process_dir(spool)["done"] == 1
    (rec,) = _records(spool)
    proves = [s for s in rec["spans"] if s["name"] == "prove"]
    assert len(proves) == 2
    assert "attempt" not in proves[0] and proves[1]["attempt"] == 1
    assert any(s["name"] == "retry_backoff" for s in rec["spans"])


def test_deferred_sweep_keeps_history(world, tmp_path, monkeypatch):
    """A transient witness failure defers: the sweep emits a
    state='deferred' record (reason + spans + queue_wait), the next
    sweep terminals — cumulative queue_wait_s grows across the cycle
    because it is anchored to the spool arrival mtime."""
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5)])
    monkeypatch.setenv("ZKP2P_FAULTS", "witness:raise:once")
    faults.reset()
    svc = _mk(world)
    d0 = _counter("zkp2p_service_deferred_total")
    assert not any(svc.process_dir(spool).values())
    assert _counter("zkp2p_service_deferred_total") - d0 == 1
    time.sleep(0.05)
    assert svc.process_dir(spool)["done"] == 1
    recs = _records(spool)
    assert [r["state"] for r in recs] == ["deferred", "done"]
    deferred, done = recs
    assert deferred["deferred_reason"].startswith("transient witness failure")
    assert any(s["name"] == "witness" for s in deferred["spans"])
    # cumulative: the terminal's queue wait includes the deferred cycle
    assert done["queue_wait_s"] > deferred["queue_wait_s"]


# ------------------------------------------------------------- meters


def test_takeover_counter_ticks_on_stale_claim_steal(world, tmp_path, monkeypatch):
    monkeypatch.delenv("ZKP2P_FAULTS", raising=False)
    faults.reset()
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5)])
    claim = os.path.join(spool, "r0.claim")
    with open(claim, "w") as f:
        f.write(json.dumps({"pid": 99999, "ts": time.time() - 3600}))
    os.utime(claim, (time.time() - 3600, time.time() - 3600))  # provably stale
    w0 = _counter("zkp2p_service_takeovers_total", result="won")
    svc = _mk(world, stale_claim_s=5.0)
    assert svc.process_dir(spool)["done"] == 1
    assert _counter("zkp2p_service_takeovers_total", result="won") - w0 == 1


def test_batch_fill_histogram_observes_live_batches(world, tmp_path, monkeypatch):
    monkeypatch.delenv("ZKP2P_FAULTS", raising=False)
    faults.reset()
    h = REGISTRY.histogram("zkp2p_service_batch_fill")
    n0, s0 = h.count, h.sum
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5), (2, 7), (4, 4)])  # batch_size=2 -> fills 2, 1
    assert _mk(world).process_dir(spool)["done"] == 3
    assert h.count - n0 == 2
    assert h.sum - s0 == 3  # 2 + 1


# ---------------------------------------------------------- timeseries


def test_timeseries_line_schema(world, tmp_path, monkeypatch):
    """Forced sampler tick: the zkp2p_timeseries line carries the queue
    state (arrivals/backlog/claimable/in_flight), rescue counters, and
    the SLO snapshot."""
    monkeypatch.delenv("ZKP2P_FAULTS", raising=False)
    faults.reset()
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5), (2, 7)])
    svc = _mk(world)
    sampler = TimeseriesSampler(interval_s=3600.0, stale_claim_s=300.0)
    rec = sampler.maybe_sample(spool, svc._sink(spool), force=True)
    assert rec is not None and rec["type"] == "timeseries"
    for key in ("ts", "run_id", "pid", "window_s", "arrivals", "arrival_rate_hz",
                "backlog", "claimable", "in_flight", "batch_fill_last", "counters", "slo"):
        assert key in rec, key
    assert rec["backlog"] == 2 and rec["claimable"] == 2 and rec["in_flight"] == 0
    assert rec["arrivals"] == 2  # both mtimes inside the first window
    assert "attainment" in rec["slo"]
    # not due again until the interval elapses
    assert sampler.maybe_sample(spool, svc._sink(spool)) is None
    # the line landed in the sink and terminal artifacts change the scan
    assert svc.process_dir(spool)["done"] == 2
    rec2 = sampler.maybe_sample(spool, svc._sink(spool), force=True)
    assert rec2["backlog"] == 0 and rec2["batch_fill_last"] == 0
    with open(str(spool).rstrip("/") + ".metrics.jsonl") as f:
        ts_lines = [json.loads(ln) for ln in f if json.loads(ln).get("type") == "timeseries"]
    assert len(ts_lines) == 2


# -------------------------------------------------------- chrome trace


def test_chrome_trace_export_loads_and_is_monotonic(world, tmp_path, monkeypatch):
    """trace_report --chrome-trace: valid JSON, X-event timestamps
    monotonic and non-negative, one pid (this process), one tid per
    request (thread_name metadata maps them), queue_wait + prove slices
    present."""
    monkeypatch.delenv("ZKP2P_FAULTS", raising=False)
    monkeypatch.delenv("ZKP2P_METRICS_SINK", raising=False)
    faults.reset()
    spool = str(tmp_path / "spool")
    os.makedirs(spool)
    _write_reqs(spool, [(3, 5), (2, 7), (4, 4)])
    assert _mk(world).process_dir(spool)["done"] == 3
    sink = spool.rstrip("/") + ".metrics.jsonl"
    out = str(tmp_path / "trace.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"), sink,
         "--chrome-trace", out],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    with open(out) as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    xs = [e for e in events if e.get("ph") == "X"]
    assert xs, events[:3]
    # monotonic, normalized timestamps
    ts = [e["ts"] for e in xs]
    assert ts == sorted(ts) and min(ts) == 0
    assert all(e["dur"] >= 0 for e in xs)
    # one pid per worker process: this test ran one worker
    assert {e["pid"] for e in xs} == {os.getpid()}
    # one tid per request, named by thread_name metadata
    names = {e["args"]["name"]: (e["pid"], e["tid"])
             for e in events if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert set(names) == {"r0", "r1", "r2"}
    assert len(set(names.values())) == 3  # distinct tids
    by_name = {}
    for e in xs:
        by_name.setdefault(e["name"], set()).add((e["pid"], e["tid"]))
    # queue_wait and prove slices present; each request's own tid
    assert set(by_name) >= {"queue_wait", "witness", "prove", "verify", "emit"}
    assert by_name["queue_wait"] == set(names.values())
    # the terminal instant markers carry the state
    marks = [e for e in events if e.get("ph") == "i"]
    assert len(marks) == 3 and all(m["name"] == "done" for m in marks)


# ------------------------------------------- the service's own spans (PR 24)


def test_a_served_sweep_writes_sweep_and_starved_and_leaves_request_spans_as_they_were(
        world, tmp_path, monkeypatch):
    """`ProvingService.run` over one sweep of three requests (two
    batches): `service/sweep` around it, `service/starved` per batch
    fetched — as stage records only.  The request records' `spans` keep
    exactly the four names they had (every entry there labels a device
    gap in the benchmark), and the wait in the spool stays where it was:
    `queue_wait_s` on each record."""
    import importlib.util

    from zkp2p_tpu.utils import trace as tr

    monkeypatch.delenv("ZKP2P_FAULTS", raising=False)
    monkeypatch.delenv("ZKP2P_METRICS_SINK", raising=False)
    faults.reset()
    tr.reset()  # sweeps driven through process_dir alone left their spans in the ring
    spool = str(tmp_path / "spool")
    os.makedirs(spool)
    _write_reqs(spool, [(3, 5), (2, 7), (4, 4)])
    assert _mk(world).run(spool, poll_s=0.05, exit_when_spool_terminal=True) == "terminal"
    sink = spool + ".metrics.jsonl"
    with open(sink) as f:
        lines = [json.loads(ln) for ln in f]
    stages = [r for r in lines if r.get("type") == "stage"]
    by = {}
    for r in stages:
        by.setdefault(r["stage"], []).append(r)

    (sweep,) = by["service/sweep"]
    assert (sweep["n_pending"], sweep["n_batches"], sweep["parent"]) == (3, 2, None)
    assert sorted(r["n"] for r in by["service/starved"]) == [1, 2]
    reqs = {r["request_id"]: r for r in _records(spool)}
    assert sorted(reqs) == ["r0", "r1", "r2"] and all(r["queue_wait_s"] >= 0 for r in reqs.values())
    # every span of the pass is the sweep's descendant, under the path it always had
    for name in ("service/witness", "service/prove", "service/verify", "service/emit", "service/starved"):
        assert by[name] and all(r["parent"] == sweep["id"] for r in by[name]), name
    # ... but what the loop did before it opened it, which is written beside it (PR 35)
    beside = [r for r in stages if r["stage"] in ("service/handover", "service/poll")]
    assert beside and all(r["parent"] is None and r["t0"] <= sweep["t0"] for r in beside)
    assert all(sweep["t0"] <= r["t0"] for r in stages if r is not sweep and r not in beside)
    # the producer's spans are on another thread than the proving thread's
    assert {r["tid"] for r in by["service/witness"]} != {r["tid"] for r in by["service/prove"]}
    # exactly today's entries in the request records
    assert all([s["name"] for s in r["spans"]] == ["witness", "prove", "verify", "emit"] for r in reqs.values())
    prove = next(r for r in by["service/prove"] if r["n"] == 2)
    shared = [s for s in reqs["r0"]["spans"] if s["name"] == "prove"][0]
    assert (shared["t0"], shared["ms"], shared["n"]) == (prove["t0"], prove["ms"], 2) and "request_ids" not in shared
    assert prove["request_ids"] == ["r0", "r1"]

    # the Perfetto view: the stage spans on a row per thread under the worker, beside the request rows
    spec = importlib.util.spec_from_file_location("trace_report", os.path.join(REPO, "tools", "trace_report.py"))
    trace_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_report)
    st, rq, _m, _t = trace_report.load_records([sink])
    events = trace_report.chrome_trace(rq, stages=st)["traceEvents"]
    drawn = [e for e in events if e.get("cat") == "stage"]
    assert {"service/sweep", "service/starved", "service/prove", "service/witness"} <= {e["name"] for e in drawn}
    assert "queue_wait" in {e["name"] for e in events if e.get("cat") == "request"}  # the request rows keep it
    rows = {e["tid"] for e in drawn}
    assert all(t > trace_report.STAGE_TID_BASE for t in rows) and {e["pid"] for e in drawn} == {os.getpid()}
    # the service's own spans: the proving thread's row and the producer's (the C++ prover's pool adds its own)
    assert len({e["tid"] for e in drawn if e["name"].count("/") == 1}) == 2
    named = {e["tid"] for e in events if e.get("ph") == "M" and e["name"] == "thread_name" and e["tid"] in rows}
    assert named == rows
    # slices on one row nest or follow each other: what a trace viewer needs of a thread
    for row in rows:
        open_until = []
        for e in sorted((e for e in drawn if e["tid"] == row), key=lambda e: (e["ts"], -e["dur"])):
            while open_until and open_until[-1] <= e["ts"] + 1e-3:
                open_until.pop()
            assert not open_until or e["ts"] + e["dur"] <= open_until[-1] + 1.0, e
            open_until.append(e["ts"] + e["dur"])
