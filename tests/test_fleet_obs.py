"""The fleet observability plane (pipeline.fleet_obs + utils.alerts +
the mergeable SLO windows in utils.slo), tier-1 (`make fleet-obs-smoke`):

  * federation aggregation rules — counters SUM across workers, gauges
    get per-worker labels, histograms bucket-merge, and a bucket-layout
    mismatch is REFUSED (skipped + counted), never mis-binned;
  * mergeable SLO — merged-sample percentiles pinned EXACTLY against a
    pooled oracle tracker (never averaged snapshots), fleet sample
    count = sum of worker windows, fast/slow multi-window burn split;
  * alert engine — fires only after `for_s`, one FIRE per episode under
    a flapping signal (hysteresis), clears only after `clear_s` clean,
    missing signals hold state, breaker park fires restart_storm
    immediately;
  * fleet `/status` fail-closed — 503-shaped (ok=False) while any live
    worker is unreachable or unarmed, ready only when every live worker
    has armed its gates;
  * cross-worker forensics — chrome-trace FLOW events stitch a
    defer→takeover across worker pids, `--request` renders the hop
    timeline, `--fleet-dir` discovers a fleet run's sinks;
  * the 2-worker plane smoke — real supervisor + toy workers: fleet
    /metrics + /status scrape 200, merged request counters equal the
    per-worker sums AND the proof artifacts, merged SLO n equals the
    sum of worker windows, `--fleet-dir` trace renders valid JSON.
"""

import importlib.util
import json
import os
import re
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from zkp2p_tpu.pipeline.fleet_obs import FleetPlane, merge_worker_metrics, render_top
from zkp2p_tpu.utils.alerts import AlertEngine, TrendTracker, fleet_rules
from zkp2p_tpu.utils.config import load_config
from zkp2p_tpu.utils.metrics import Registry
from zkp2p_tpu.utils.slo import SloTracker, merge_window_states

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHAOS = os.path.join(REPO, "tools", "chaos.py")


def _trace_report():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    return trace_report


# ------------------------------------------------- federation merge rules


def _worker_registry(done: int, backlog: float, fills) -> Registry:
    r = Registry()
    r.counter("zkp2p_service_requests_total", {"state": "done"}).inc(done)
    r.gauge("zkp2p_service_backlog").set(backlog)
    h = r.histogram("zkp2p_service_batch_fill", buckets=(1, 2, 4, 8))
    for f in fills:
        h.observe(f)
    return r


def test_merge_counter_sum_gauge_label_histogram_buckets():
    fleet = Registry()
    merge_worker_metrics(fleet, _worker_registry(3, 4, [1, 2]).snapshot(), "w0")
    merge_worker_metrics(fleet, _worker_registry(5, 7, [2, 8]).snapshot(), "w1")
    snap = {(m["name"], tuple(sorted(m["labels"].items()))): m for m in fleet.snapshot()}
    # counters SUM (labels preserved, no worker label — fleet totals)
    c = snap[("zkp2p_service_requests_total", (("state", "done"),))]
    assert c["kind"] == "counter" and c["value"] == 8
    # gauges get per-worker labels (attribution, never summed/maxed)
    g0 = snap[("zkp2p_service_backlog", (("worker", "w0"),))]
    g1 = snap[("zkp2p_service_backlog", (("worker", "w1"),))]
    assert g0["value"] == 4 and g1["value"] == 7
    # histograms bucket-merge: counts add positionally
    h = snap[("zkp2p_service_batch_fill", ())]
    assert h["count"] == 4 and h["sum"] == 13
    assert h["counts"][0] == 1 and h["counts"][1] == 2 and h["counts"][3] == 1


def test_merge_refuses_histogram_bucket_mismatch():
    fleet = Registry()
    merge_worker_metrics(fleet, _worker_registry(1, 0, [1]).snapshot(), "w0")
    bad = Registry()
    bad.histogram("zkp2p_service_batch_fill", buckets=(10, 20)).observe(15)
    bad.counter("zkp2p_service_requests_total", {"state": "done"}).inc(2)
    refused = []
    merge_worker_metrics(fleet, bad.snapshot(), "w1", refused=refused.append)
    # the mismatched family was refused, the rest of the snapshot merged
    assert refused == ["zkp2p_service_batch_fill"]
    snap = {(m["name"], tuple(sorted(m["labels"].items()))): m for m in fleet.snapshot()}
    assert snap[("zkp2p_service_requests_total", (("state", "done"),))]["value"] == 3
    assert snap[("zkp2p_service_batch_fill", ())]["count"] == 1  # w0's, untouched


def test_registry_merge_raises_on_bucket_mismatch():
    """The underlying Registry.merge path REFUSES loudly — the fleet
    layer's counted skip is built on this refusal, not instead of it."""
    a = Registry()
    a.histogram("h", buckets=(1, 2)).observe(1)
    b = Registry()
    b.histogram("h", buckets=(3, 4)).observe(3)
    with pytest.raises(ValueError, match="bucket layout mismatch"):
        a.merge(b.snapshot())


# ------------------------------------------------------- mergeable SLO


def test_merged_window_equals_pooled_oracle():
    """THE merge contract: merging N serialized windows reproduces what
    ONE tracker observing every worker's traffic would report — exact
    attainment and exact percentiles, not averaged snapshots."""
    import random

    rng = random.Random(7)
    oracle = SloTracker(objective_s=2.0, target=0.9, window_s=300.0, clock=lambda: 100.0)
    workers = [
        SloTracker(objective_s=2.0, target=0.9, window_s=300.0, clock=lambda: 100.0)
        for _ in range(3)
    ]
    for i in range(200):
        w = workers[i % 3]
        lat = rng.uniform(0.1, 4.0)
        ok = rng.random() < 0.9
        t = rng.uniform(0.0, 100.0)
        w.observe(lat, ok=ok, now=t)
        oracle.observe(lat, ok=ok, now=t)
    merged = merge_window_states([w.window_state(now=100.0) for w in workers])
    want = oracle.snapshot(now=100.0)
    assert merged["n"] == want["n"] == 200
    assert merged["good"] == want["good"]
    assert abs(merged["attainment"] - want["attainment"]) < 1e-9
    assert merged["p50_s"] == want["p50_s"]
    assert merged["p95_s"] == want["p95_s"]
    assert merged["max_s"] == want["max_s"]
    assert abs(merged["burn_slow"] - want["burn_rate"]) < 1e-6


def test_merged_is_not_an_average_of_snapshots():
    """An idle worker (empty window, vacuous attainment 1.0) must not
    dilute a drowning worker's attainment — the classic averaged-
    snapshot bug the pooled merge exists to prevent."""
    idle = SloTracker(objective_s=1.0, clock=lambda: 0.0)
    busy = SloTracker(objective_s=1.0, clock=lambda: 0.0)
    for _ in range(10):
        busy.observe(5.0, ok=True, now=0.0)  # all over objective: misses
    merged = merge_window_states(
        [idle.window_state(now=0.0), busy.window_state(now=0.0)]
    )
    assert merged["attainment"] == 0.0  # not (1.0 + 0.0) / 2
    assert merged["workers"] == 2 and merged["n"] == 10


def test_window_state_cap_keeps_true_n():
    t = SloTracker(objective_s=0.0, clock=lambda: 50.0)
    for i in range(100):
        t.observe(0.1, ok=True, now=float(i % 50))
    st = t.window_state(max_samples=30, now=50.0)
    assert st["n"] == 100 and len(st["samples"]) == 30 and st["dropped"] == 70
    merged = merge_window_states([st])
    assert merged["n"] == 100 and merged["n_merged"] == 30


def test_fast_slow_burn_split():
    """Old samples good, trailing `fast_window_s` all bad: burn_fast
    maxes out while burn_slow stays diluted — the multi-window pair."""
    t = SloTracker(objective_s=1.0, target=0.95, window_s=300.0, clock=lambda: 200.0)
    for i in range(90):
        t.observe(0.2, ok=True, now=float(i))       # ages 110..200: good
    for i in range(10):
        t.observe(5.0, ok=True, now=195.0 + i / 10)  # ages < 60: misses
    merged = merge_window_states([t.window_state(now=200.0)], fast_window_s=60.0)
    assert merged["n_fast"] == 10
    assert merged["burn_fast"] == pytest.approx((1.0 - 0.0) / 0.05)
    assert merged["burn_slow"] == pytest.approx((10 / 100) / 0.05)


# ----------------------------------------------------------- alert engine


def _engine(rules, **cfg_env):
    env = {
        "ZKP2P_ALERT_FOR_S": "5", "ZKP2P_ALERT_CLEAR_S": "10",
        "ZKP2P_ALERT_BURN_RATE": "2", "ZKP2P_ALERT_RESTARTS": "3",
        "ZKP2P_ALERT_HB_GAP_S": "15",
    }
    env.update({k: str(v) for k, v in cfg_env.items()})
    cfg = load_config(environ=env)
    reg = Registry()
    log = []
    eng = AlertEngine(rules if rules is not None else fleet_rules(cfg),
                      registry=reg, log=log.append)
    return eng, reg, log


def _alert_count(reg, rule):
    for m in reg.snapshot():
        if m["name"] == "zkp2p_fleet_alerts_total" and m["labels"].get("rule") == rule:
            return m["value"]
    return 0


def test_alert_fires_after_for_s_not_before():
    eng, reg, log = _engine(None)
    sig = {"burn_fast": 5.0, "burn_slow": 5.0, "slo_n": 100}
    assert eng.evaluate(sig, now=0.0) == []          # pending, not firing
    assert eng.active() == []
    assert eng.evaluate(sig, now=4.0) == []          # still inside for_s
    trs = eng.evaluate(sig, now=5.0)                 # held 5 s: fires
    assert [t["event"] for t in trs] == ["fired"] and trs[0]["rule"] == "slo_burn"
    assert [a["rule"] for a in eng.active()] == ["slo_burn"]
    assert _alert_count(reg, "slo_burn") == 1
    assert any("FIRED" in m for m in log)


def test_alert_hysteresis_flapping_raises_one_alert():
    """A signal crossing its threshold every tick: ONE fire, no clear —
    the stream-of-pages failure mode the hysteresis exists to stop."""
    eng, reg, _ = _engine(None)
    on = {"burn_fast": 5.0, "burn_slow": 5.0, "slo_n": 100}
    off = {"burn_fast": 0.0, "burn_slow": 0.0, "slo_n": 100}
    eng.evaluate(on, now=0.0)
    eng.evaluate(on, now=5.0)                        # fires
    assert _alert_count(reg, "slo_burn") == 1
    transitions = []
    for i in range(20):                              # flap every second
        t = 6.0 + i
        transitions += eng.evaluate(on if i % 2 else off, now=t)
    assert transitions == []                         # still the SAME episode
    assert _alert_count(reg, "slo_burn") == 1
    assert eng.active()                              # never cleared mid-flap


def test_alert_clears_only_after_clear_s_clean():
    eng, reg, log = _engine(None)
    on = {"burn_fast": 5.0, "burn_slow": 5.0, "slo_n": 100}
    off = {"burn_fast": 0.0, "burn_slow": 0.0, "slo_n": 100}
    eng.evaluate(on, now=0.0)
    eng.evaluate(on, now=5.0)
    assert eng.evaluate(off, now=6.0) == []          # clean, but < clear_s
    assert eng.active()
    trs = eng.evaluate(off, now=16.0)                # clean for 10 s: clears
    assert [t["event"] for t in trs] == ["cleared"]
    assert eng.active() == []
    # a fresh episode after the clear fires AGAIN (new counter inc)
    eng.evaluate(on, now=20.0)
    eng.evaluate(on, now=25.0)
    assert _alert_count(reg, "slo_burn") == 2
    assert eng.state()["slo_burn"]["fired_count"] == 2


def test_missing_signal_holds_state():
    eng, reg, _ = _engine(None)
    on = {"burn_fast": 5.0, "burn_slow": 5.0, "slo_n": 100}
    eng.evaluate(on, now=0.0)
    eng.evaluate(on, now=5.0)
    assert eng.active()
    # scrape gap: no burn data at all — the alert must neither clear
    # nor re-fire on absence of evidence
    for i in range(50):
        assert eng.evaluate({}, now=6.0 + i) == []
    assert eng.active() and _alert_count(reg, "slo_burn") == 1


def test_empty_slo_window_never_burns():
    eng, _, _ = _engine(None)
    # burn 20 on an EMPTY window is vacuous (no traffic != outage)
    sig = {"burn_fast": 20.0, "burn_slow": 20.0, "slo_n": 0}
    for t in range(20):
        eng.evaluate(sig, now=float(t))
    assert eng.active() == []


def test_restart_storm_fires_immediately_on_park():
    eng, reg, _ = _engine(None)
    trs = eng.evaluate({"parked": 1, "restarts_recent": 0}, now=0.0)
    assert [t["rule"] for t in trs] == ["restart_storm"]
    assert _alert_count(reg, "restart_storm") == 1
    # and on restarts over threshold without a park
    eng2, reg2, _ = _engine(None)
    assert eng2.evaluate({"parked": 0, "restarts_recent": 2}, now=0.0) == []
    assert [t["rule"] for t in eng2.evaluate({"parked": 0, "restarts_recent": 3}, now=1.0)] \
        == ["restart_storm"]


def test_heartbeat_gap_and_governor_rules():
    eng, _, _ = _engine(None)
    assert [t["rule"] for t in eng.evaluate({"hb_gap_s": 20.0}, now=0.0)] == ["heartbeat_gap"]
    eng2, _, _ = _engine(None)
    sig = {"degraded": 1}
    assert eng2.evaluate(sig, now=0.0) == []         # lingering = held for_s
    assert [t["rule"] for t in eng2.evaluate(sig, now=5.0)] == ["governor_degrade"]


def test_trend_tracker_growth_and_delta():
    tr = TrendTracker(keep_s=100.0)
    assert tr.growing(10.0, now=0.0) is None         # no history: hold
    tr.update(0.0, 2.0)
    assert tr.growing(10.0, now=0.0) is None         # span too short, value > 0
    for t in range(1, 12):
        tr.update(float(t), 2.0 + t)
    assert tr.growing(10.0, now=11.0) is True
    assert tr.delta(10.0, now=11.0) == pytest.approx(10.0)
    flat = TrendTracker(keep_s=100.0)
    for t in range(12):
        flat.update(float(t), 5.0)
    assert flat.growing(10.0, now=11.0) is False
    empty = TrendTracker(keep_s=100.0)
    empty.update(0.0, 0.0)
    assert empty.growing(10.0, now=0.0) is False     # zero is a confident no


# ------------------------------------------------- fail-closed fleet status


class _FakeProc:
    def __init__(self, alive=True):
        self._alive = alive
        self.pid = 4242

    def poll(self):
        return None if self._alive else 1


class _FakeSlot:
    def __init__(self, wid, state="up", alive=True, restarts=0):
        self.wid = wid
        self.state = state
        self.proc = _FakeProc(alive) if state not in ("parked",) else None
        self.restarts = restarts
        self.last_rc = None


class _FakeSup:
    def __init__(self, spool, slots, hbs=None):
        self.spool = spool
        self.slots = {s.wid: s for s in slots}
        self.hbs = hbs or {}
        self.log = lambda m: None

    def _hb(self, slot):
        return self.hbs.get(slot.wid)

    def _hb_age_s(self, slot):
        hb = self.hbs.get(slot.wid)
        return 0.1 if hb else None

    def status(self):
        return {"type": "fleet_status", "fleet_id": "ftest", "workers": {}, "draining": False}


def _plane(sup, monkeypatch=None, snapshots=None):
    plane = FleetPlane(sup, port=0, scrape_s=0.5, clock=time.time)
    if snapshots is not None:
        plane._fetch_snapshot = lambda port: snapshots.get(port)
    return plane


def test_status_fails_closed_until_every_live_worker_armed(tmp_path):
    spool = str(tmp_path / "spool")
    os.makedirs(spool)
    hbs = {"w0": {"port": 1001}, "w1": {"port": 1002}}
    sup = _FakeSup(spool, [_FakeSlot("w0"), _FakeSlot("w1")], hbs)
    armed = {"armed": True, "metrics": [], "slo_window": None}
    unarmed = {"armed": False, "metrics": [], "slo_window": None}

    # one worker unreachable -> NOT ready (and the failure is counted)
    plane = _plane(sup, snapshots={1001: dict(armed)})
    view = plane.scrape_once()
    assert view["ready"] is False and "unreachable" in view["reason"]
    body = plane.status_payload()
    assert body["ok"] is False and body["reason"]

    # reachable but unarmed -> NOT ready (the PR-8 fail-closed rule,
    # fleet-wide: nobody preflighted that worker's gates)
    plane = _plane(sup, snapshots={1001: dict(armed), 1002: dict(unarmed)})
    view = plane.scrape_once()
    assert view["ready"] is False and "armed" in view["reason"]

    # every live worker armed -> ready, /status would be 200
    plane = _plane(sup, snapshots={1001: dict(armed), 1002: dict(armed)})
    view = plane.scrape_once()
    assert view["ready"] is True
    assert plane.status_payload()["ok"] is True

    # no live workers at all -> fail closed again
    sup_dead = _FakeSup(spool, [_FakeSlot("w0", state="done", alive=False)])
    plane = _plane(sup_dead, snapshots={})
    view = plane.scrape_once()
    assert view["ready"] is False and view["reason"] == "no live workers"


def test_scrape_merges_heartbeat_slo_fallback(tmp_path):
    """A worker whose /snapshot scrape fails still contributes its
    heartbeat-carried SLO window — fleet attainment degrades to
    slightly-stale, not to a worker-shaped hole."""
    spool = str(tmp_path / "spool")
    os.makedirs(spool)
    t = SloTracker(objective_s=1.0, clock=time.monotonic)
    for _ in range(4):
        t.observe(0.5, ok=True)
    hbs = {"w0": {"port": 1001, "slo_window": t.window_state()}}
    sup = _FakeSup(spool, [_FakeSlot("w0")], hbs)
    plane = _plane(sup, snapshots={})  # scrape always fails
    view = plane.scrape_once()
    assert view["ready"] is False              # unreachable: NOT ready...
    assert view["slo"]["n"] == 4               # ...but the window merged


# --------------------------------------------------- forensics (synthetic)


def _two_attempt_records():
    return [
        {"type": "request", "request_id": "q1", "state": "deferred", "pid": 100,
         "worker": "w0", "ts": 1010.0, "t_submit": 1000.0, "t_claim": 1001.0,
         "queue_wait_s": 1.0, "deferred_reason": "transient emit failure",
         "spans": [{"name": "witness", "t0": 1001.0, "ms": 50.0},
                   {"name": "prove", "t0": 1002.0, "ms": 800.0}]},
        {"type": "request", "request_id": "q1", "state": "done", "pid": 200,
         "worker": "w1", "ts": 1020.0, "t_submit": 1000.0, "t_claim": 1015.0,
         "queue_wait_s": 15.0,
         "spans": [{"name": "prove", "t0": 1015.5, "ms": 700.0}]},
        {"type": "request", "request_id": "q2", "state": "done", "pid": 100,
         "worker": "w0", "ts": 1005.0, "t_submit": 1000.0, "t_claim": 1001.0,
         "queue_wait_s": 1.0, "spans": [{"name": "prove", "t0": 1001.5, "ms": 100.0}]},
    ]


def test_chrome_trace_flow_events_stitch_attempts_across_pids():
    tr = _trace_report()
    trace = tr.chrome_trace(_two_attempt_records())
    flows = [e for e in trace["traceEvents"] if e.get("ph") in ("s", "f")]
    assert len(flows) == 2                      # one hop = one s/f pair
    s, f = sorted(flows, key=lambda e: e["ph"], reverse=True)  # s then f
    assert s["ph"] == "s" and f["ph"] == "f" and f.get("bp") == "e"
    assert s["id"] == f["id"]
    assert s["pid"] == 100 and f["pid"] == 200  # across worker processes
    assert "takeover" in s["name"]
    assert f["ts"] > s["ts"] >= 0
    json.loads(json.dumps(trace))               # valid, serializable
    # single-attempt requests get no flow events
    only_q2 = tr.chrome_trace([r for r in _two_attempt_records() if r["request_id"] == "q2"])
    assert not [e for e in only_q2["traceEvents"] if e.get("ph") in ("s", "f")]


def test_request_timeline_shows_takeover_and_queue_wait():
    tr = _trace_report()
    out = tr.request_timeline(_two_attempt_records(), "q1")
    assert "2 attempt(s)" in out
    assert "TAKEOVER" in out
    assert "queue_wait 15.000s" in out
    assert "w0 (pid 100)" in out and "w1 (pid 200)" in out
    assert "deferred (transient emit failure)" in out and "-> done" in out
    assert "(no records" in tr.request_timeline([], "nope")


def test_fleet_dir_sink_discovery(tmp_path):
    tr = _trace_report()
    spool = tmp_path / "spool"
    fleet_dir = spool / ".fleet"
    os.makedirs(fleet_dir)
    sink = str(spool) + ".metrics.jsonl"
    for p in (sink, sink + ".1"):
        with open(p, "w") as f:
            f.write("")
    with open(fleet_dir / "status.json", "w") as f:
        json.dump({"spool": str(spool)}, f)
    with open(fleet_dir / "extra.jsonl", "w") as f:
        f.write("")
    found = tr.fleet_sinks(str(fleet_dir))
    assert sink in found and sink + ".1" in found
    assert str(fleet_dir / "extra.jsonl") in found
    # no status.json: falls back to the directory layout
    os.unlink(fleet_dir / "status.json")
    assert sink in tr.fleet_sinks(str(fleet_dir))


def test_render_top_frame():
    body = {
        "ok": True, "fleet_id": "f1", "draining": False,
        "slo": {"attainment": 0.97, "burn_fast": 0.5, "burn_slow": 0.2,
                "p95_s": 1.25, "objective_p95_s": 2.0, "n": 42, "workers": 2},
        "signals": {"backlog": 3, "restarts_recent": 0, "parked": 0, "degraded": 0},
        "alerts": [{"rule": "slo_burn", "detail": "burning", "since": 1.0}],
        "workers": {"w0": {"state": "up", "pid": 1, "port": 1001, "restarts": 0,
                           "rss_mb": 100.0, "hb_age_s": 0.2, "degraded": False}},
        "scrape": {"cycles": 9, "interval_s": 2.0, "last_ts": 123.0},
    }
    out = render_top(body)
    assert "READY" in out and "attainment 0.9700" in out
    assert "ALERT slo_burn" in out and "w0" in out and "9 cycle(s)" in out
    assert "NOT READY" in render_top({"ok": False, "reason": "no live workers"})


# --------------------------------------------- the 2-worker plane smoke


@pytest.mark.skipif(
    importlib.util.find_spec("numpy") is None, reason="needs the toy prover"
)
def test_fleet_obs_smoke_two_worker_plane(tmp_path, monkeypatch):
    """`make fleet-obs-smoke` acceptance: a REAL supervisor + 2 toy
    workers with the plane on an auto port — /status fails closed
    before the workers arm, then 200; fleet /metrics request counters
    equal the per-worker /snapshot sums AND the proof artifacts; merged
    SLO sample count equals the sum of worker windows; trace_report
    --fleet-dir renders valid chrome-trace JSON."""
    from zkp2p_tpu.native.lib import get_lib

    if get_lib() is None:
        pytest.skip("native toolchain unavailable")
    from zkp2p_tpu.pipeline.fleet import FleetSupervisor
    from zkp2p_tpu.pipeline.service import spool_terminal

    monkeypatch.setenv("ZKP2P_FLEET_SCRAPE_S", "0.3")
    spool = str(tmp_path / "spool")
    os.makedirs(spool)
    n_req = 6
    for i in range(n_req):
        with open(os.path.join(spool, f"q{i:03d}.req.json"), "w") as f:
            json.dump({"x": 3 + i, "y": 5 + i}, f)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("ZKP2P_FAULTS", None)
    env.pop("ZKP2P_METRICS_SINK", None)
    worker_cmd = lambda wid: [  # noqa: E731
        sys.executable, CHAOS, "--worker", "--linger", "--spool", spool,
        "--batch", "2", "--prove-s", "0.1", "--max-seconds", "150", "--poll-s", "0.05",
    ]
    sup = FleetSupervisor(
        spool, worker_cmd, workers=2, worker_env=env,
        fleet_metrics_port=0, restart_backoff_s=0.1, drain_timeout_s=20.0,
        fleet_dir=str(tmp_path / "fleet"), log=lambda m: None,
    )
    out = {}
    t = threading.Thread(
        target=lambda: out.update(rc=sup.run(poll_s=0.05, max_seconds=150, install_signals=False))
    )
    t.start()
    try:
        deadline = time.time() + 120
        while time.time() < deadline and (sup.plane is None or sup.plane.bound_port is None):
            time.sleep(0.02)
        port = sup.plane.bound_port
        assert port, "plane never bound its endpoint"

        # fail-closed first: workers need seconds of imports before
        # preflight arms them — the immediate answer must be 503
        saw_503 = saw_200 = False
        status = None
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(f"http://127.0.0.1:{port}/status", timeout=3) as r:
                    saw_200 = True
                    status = json.loads(r.read())
                    break
            except urllib.error.HTTPError as e:
                if e.code == 503:
                    saw_503 = True
                    body = json.loads(e.read())
                    assert body["ok"] is False and body["reason"]
            time.sleep(0.1)
        assert saw_200, "fleet /status never reached 200"
        assert saw_503, "fleet /status never failed closed before the workers armed"
        assert status["ok"] is True and status["metrics_port"] == port
        healthz = json.loads(
            urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz", timeout=3).read()
        )
        assert healthz["ok"] is True

        # serve to terminal, then give the scrape loop 2 intervals
        while time.time() < deadline and not spool_terminal(spool):
            time.sleep(0.1)
        assert spool_terminal(spool), "spool never went terminal"
        time.sleep(1.0)

        status = json.loads(
            urllib.request.urlopen(f"http://127.0.0.1:{port}/status", timeout=3).read()
        )
        met = urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=3).read().decode()

        # merged counters == per-worker sums == artifacts
        fleet_done = 0.0
        for line in met.splitlines():
            m = re.match(r'zkp2p_service_requests_total\{state="done"\} (\d+(?:\.\d+)?)', line)
            if m:
                fleet_done = float(m.group(1))
        worker_done = 0.0
        slo_sum = 0
        ports = []
        for wid, w in status["workers"].items():
            if w["state"] != "up":
                continue
            ports.append(w["port"])
            snap = json.loads(
                urllib.request.urlopen(f"http://127.0.0.1:{w['port']}/snapshot", timeout=3).read()
            )
            assert snap["armed"] is True and snap["worker"] == wid
            for m in snap["metrics"]:
                if m["name"] == "zkp2p_service_requests_total" and m["labels"].get("state") == "done":
                    worker_done += m["value"]
            slo_sum += snap["slo_window"]["n"]
        assert len(ports) == 2
        assert fleet_done == worker_done == n_req
        # merged SLO sample count = sum of the worker windows
        assert status["slo"]["n"] == slo_sum == n_req
        assert status["slo"]["attainment"] == 1.0
        # per-worker labelled gauges made it to the fleet exposition
        assert re.search(r'zkp2p_slo_attainment\{worker="w[01]"\}', met)
        assert "zkp2p_fleet_slo_attainment 1" in met
        assert status["alerts"] == []
    finally:
        sup.stop()
        t.join(timeout=120)
    assert not t.is_alive()
    assert out.get("rc") == 0

    # forensics over the run the fleet just produced: --fleet-dir
    # discovers the sink, the chrome trace renders valid JSON
    tr = _trace_report()
    sinks = tr.fleet_sinks(sup.fleet_dir)
    assert sinks, "fleet sink discovery found nothing"
    out_json = str(tmp_path / "trace.json")
    rc = tr.main(["--fleet-dir", sup.fleet_dir, "--chrome-trace", out_json])
    assert rc == 0
    with open(out_json) as f:
        trace = json.load(f)
    assert sum(1 for e in trace["traceEvents"] if e.get("ph") == "X") >= n_req
    # final status.json carries the plane view (alert history included)
    with open(os.path.join(sup.fleet_dir, "status.json")) as f:
        st = json.load(f)
    assert "alerts_state" in st and "slo" in st and st["metrics_port"] == port
