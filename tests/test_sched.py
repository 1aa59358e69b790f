"""The adaptive scheduler (pipeline.sched), tier-1 (`make sched-smoke`):

  * AmortModel — interpolation/extrapolation, spec parsing, the
    strictly-increasing validation, loud malformed-spec failure;
  * BatchController — deterministic over injected clocks + synthetic
    arrival streams: EWMA arrival rate, batch size monotone in load and
    clamped to backlog/cap, small at low load, interactive-first lane
    ordering with the bounded latency-lane width, expected-deadline-miss
    shedding (hopeless shed, feasible NEVER shed), admission-cap shed by
    least slack, no shedding while draining;
  * AutoscalePolicy — hysteresis: fires only after a sustained window,
    a boundary-oscillating signal never flaps (zero decisions), bounds
    clamp, missing signals hold state, every decision resets the clock;
  * the service integration smoke — a toy-circuit mini-trace through
    the REAL service: ZKP2P_SCHED=adaptive sheds the hopeless request,
    proves the interactive lane first, stamps batch_size_target on
    records, writes {"type": "sched"} decision lines; the off arm keeps
    the static slicing; the two arms are digest-distinguishable
    (service_sched gate);
  * the fleet autoscale demo — a 1->2->1 worker fleet under a backlog
    spike: scale events in status.json + the sched block, zero lost /
    zero duplicated proofs (the PR-7 invariant via chaos
    check_invariants).
"""

import importlib.util
import json
import os
import time

import pytest

from zkp2p_tpu.pipeline.sched import (
    AmortModel,
    AutoscalePolicy,
    BatchController,
    INTERACTIVE_LANE_CAP,
    SchedRequest,
    sched_mode,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHAOS = os.path.join(REPO, "tools", "chaos.py")


def _chaos_mod():
    spec = importlib.util.spec_from_file_location("zkp2p_chaos_for_sched", CHAOS)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ AmortModel


def test_amort_interpolation_and_extrapolation():
    m = AmortModel({1: 0.9, 4: 1.8, 8: 3.0})
    assert m.batch_s(1) == pytest.approx(0.9)
    assert m.batch_s(4) == pytest.approx(1.8)
    assert m.batch_s(2) == pytest.approx(0.9 + (1.8 - 0.9) / 3)  # linear between points
    assert m.batch_s(8) == pytest.approx(3.0)
    # above the last point: the last segment's slope, not a flat line
    assert m.batch_s(12) == pytest.approx(3.0 + 4 * (3.0 - 1.8) / 4)
    # a single point scales proportionally in both directions
    m1 = AmortModel({4: 2.0})
    assert m1.batch_s(2) == pytest.approx(1.0)
    assert m1.batch_s(8) == pytest.approx(4.0)
    assert m1.batch_s(0) == 0.0
    # per-proof cost + the throughput argmin (tie breaks small)
    assert m.per_proof_s(8) == pytest.approx(3.0 / 8)
    assert m.best_throughput_size(8) == 8
    flat = AmortModel({1: 1.0, 2: 2.0})  # perfectly linear: no amortization
    assert flat.best_throughput_size(8) == 1


def test_amort_spec_parsing_and_validation():
    m = AmortModel.from_spec("1:0.5, 4:1.1")
    assert m.batch_s(4) == pytest.approx(1.1)
    # "" = the built-in conservative default
    d = AmortModel.from_spec("")
    assert d.batch_s(1) > 0
    with pytest.raises(ValueError):
        AmortModel.from_spec("junk")
    with pytest.raises(ValueError):
        AmortModel.from_spec("1:2,1:3")  # duplicate / non-increasing S
    with pytest.raises(ValueError):
        AmortModel({1: 2.0, 4: 1.0})  # cost must increase with S
    with pytest.raises(ValueError):
        AmortModel({})


# ------------------------------------------------------- BatchController

AMORT = "1:0.9,2:1.2,4:1.8,8:3.0"  # overhead 0.6 + 0.3/request


def _ctl(objective=8.0, fill=0.8, confirmed=True):
    c = BatchController(AmortModel.from_spec(AMORT), objective_s=objective, target_fill=fill)
    if confirmed:
        # one on-model observation (ratio 1.0) ends the warm-up: sizing
        # and predictive shedding run on the confirmed curve
        c.observe_batch(1, 0.9)
    return c


def _reqs(now, n, wait=0.5, deadline_s=8.0, interactive=False, prefix="r"):
    return [
        SchedRequest(
            rid=f"{prefix}{i:03d}", t_submit=now - wait - i * 1e-3,
            deadline=(now - wait - i * 1e-3 + deadline_s) if deadline_s else None,
            interactive=interactive,
        )
        for i in range(n)
    ]


def test_ewma_arrival_rate_deterministic():
    c = _ctl()
    now = 1000.0
    # seed: 20 arrivals inside the 10 s tau window -> 2 Hz
    subs = [now - 0.1 - i * 0.4 for i in range(20)]
    assert c.observe_arrivals(now, subs) == pytest.approx(2.0)
    # 10 more arrivals over the next 5 s pulls the EWMA toward 2.0 (same
    # instantaneous rate: stays put)
    subs2 = subs + [now + 0.25 + i * 0.5 for i in range(10)]
    r = c.observe_arrivals(now + 5.0, subs2)
    assert r == pytest.approx(2.0, abs=1e-6)
    # silence decays toward zero, never negative
    r2 = c.observe_arrivals(now + 30.0, [])
    assert 0.0 <= r2 < 0.2


def test_batch_size_monotone_in_load_and_clamped():
    c = _ctl()
    now = 50.0
    sizes = []
    # generous budgets: sizing is the pure load dial (the clamp), and
    # must be monotone — more backlog never shrinks the batch
    for n in (1, 2, 3, 5, 8, 20):
        plan = c.plan(now, _reqs(now, n, deadline_s=60.0), cap=8)
        got = plan.batch_target
        sizes.append(got)
        assert got <= min(8, n)  # clamped to cap and live backlog
    assert sizes == sorted(sizes)
    assert sizes[0] == 1 and sizes[-1] == 8
    # low load = small batch (latency), full budget would admit 8
    assert c.plan(now, _reqs(now, 2), cap=8).batch_target == 2
    # overload with tight budgets: the count-maximizing rule must HOLD
    # throughput (wide-ish batches), not collapse to tiny batches
    # chasing the oldest straggler (head-of-line inversion)
    plan = c.plan(now, _reqs(now, 20, wait=0.5), cap=8)
    assert plan.batch_target >= 4 and plan.batch_reason == "slo"


def test_batch_size_tracks_remaining_budget():
    c = _ctl()
    now = 50.0
    # fresh queue: wide (batch_s(8)=3.0 <= 0.8 * 8)
    assert c.plan(now, _reqs(now, 16, wait=0.1), cap=8).batch_target == 8
    # aged queue (objective pressure, no hard deadline): budget ~2 s ->
    # only batch_s(S) <= 0.8*2 = 1.6 fits -> S=3 (batch_s(3)=1.5)
    plan = c.plan(now, _reqs(now, 16, wait=6.0, deadline_s=0), cap=8)
    assert plan.shed == []  # objective-only work is never predictively shed
    assert plan.batch_target == 3 and plan.batch_reason == "slo"
    # no deadline and no objective: pure throughput, the cap
    c2 = _ctl(objective=0.0)
    plan2 = c2.plan(now, _reqs(now, 16, deadline_s=0), cap=8)
    assert plan2.batch_target == 8 and plan2.batch_reason == "backlog"


def test_interactive_lane_first_and_bounded():
    c = _ctl()
    now = 50.0
    bulk = _reqs(now, 6, prefix="b")
    inter = _reqs(now, 3, wait=0.1, interactive=True, prefix="i")
    plan = c.plan(now, bulk + inter, cap=8)
    assert plan.lanes == {"interactive": 3, "bulk": 6}
    # interactive batches first, never wider than the lane cap, never
    # mixed with bulk
    first = plan.batches[0]
    assert all(r.interactive for r in first)
    assert len(first) <= INTERACTIVE_LANE_CAP
    n_int_batches = sum(1 for b in plan.batches if b[0].interactive)
    assert all(all(r.interactive for r in b) for b in plan.batches[:n_int_batches])
    assert all(not r.interactive for b in plan.batches[n_int_batches:] for r in b)
    assert plan.interactive_target <= INTERACTIVE_LANE_CAP


def test_shed_by_predicted_miss_never_the_feasible():
    c = _ctl()
    c.observe_batch(1, 0.9)  # confirmed model: predictive shed engages
    now = 100.0
    fresh = _reqs(now, 8, wait=0.5)                       # easily feasible
    hopeless = _reqs(now, 3, wait=30.0, prefix="old")     # deadline long gone
    plan = c.plan(now, fresh + hopeless, cap=8)
    shed_rids = {r.rid for r, _why in plan.shed}
    assert shed_rids == {"old000", "old001", "old002"}
    kept = [r.rid for b in plan.batches for r in b]
    assert sorted(kept) == sorted(r.rid for r in fresh)
    # every verdict names the prediction
    assert all("deadline" in why for _r, why in plan.shed)
    # with NOTHING hopeless, nothing is shed — a feasible request is
    # never shed outside the admission cap (16 requests fit the 8 s
    # deadline as two 8-wide batches: 6.0 s optimistic)
    assert c.plan(now + 1, _reqs(now + 1, 16, wait=0.2), cap=8).shed == []


def test_shed_walk_saves_requests_behind_the_hopeless():
    """Removing a hopeless request frees its virtual slot: the walk
    must not count shed requests against the queue positions behind
    them."""
    c = _ctl()
    c.observe_batch(1, 0.9)
    now = 100.0
    # 3 expired + exactly 8 feasible: if the walk charged the expired
    # ones as positions, the tail of the feasible would be mis-shed
    expired = _reqs(now, 3, wait=20.0, prefix="old")
    feasible = _reqs(now, 8, wait=0.3)
    plan = c.plan(now, expired + feasible, cap=8)
    assert {r.rid for r, _ in plan.shed} == {r.rid for r in expired}


def test_admission_cap_sheds_by_least_slack():
    c = _ctl(objective=0.0)  # no objective: slack is inf for everyone
    now = 100.0
    reqs = _reqs(now, 10, deadline_s=0)
    plan = c.plan(now, reqs, cap=8, spool_cap=6)
    assert len(plan.shed) == 4
    kept = [r.rid for b in plan.batches for r in b]
    assert len(kept) == 6
    # all-inf slack: the LAST service positions go (the newest — the
    # static arm's newest-first cap semantics for unbounded work).
    # Service order is oldest-first, and rid index here DESCENDS with
    # age, so the oldest six (r004..r009) survive.
    assert set(kept) == {f"r{i:03d}" for i in range(4, 10)}
    assert all("cap" in why for _r, why in plan.shed)


def test_no_shedding_while_draining():
    c = _ctl()
    now = 100.0
    hopeless = _reqs(now, 3, wait=30.0, prefix="old")
    plan = c.plan(now, hopeless, cap=8, spool_cap=1, allow_shed=False)
    assert plan.shed == []
    assert sum(len(b) for b in plan.batches) == 3


# ------------------------------------------------------- AutoscalePolicy


def test_autoscale_fires_after_sustained_window_only():
    p = AutoscalePolicy(1, 3, scale_up_s=5.0, scale_down_s=10.0)
    growing = {"backlog_growing": True, "backlog": 9}
    assert p.update(0.0, 1, growing) is None
    assert p.update(4.9, 1, growing) is None
    d = p.update(5.0, 1, growing)
    assert d == {"direction": "up", "reason": "backlog_growth"}
    # cooldown: the clock restarted — the next step needs a FULL window
    assert p.update(5.1, 2, growing) is None
    assert p.update(10.2, 2, growing)["direction"] == "up"
    # at the ceiling: condition may persist, no decision
    assert p.update(20.0, 3, growing) is None


def test_autoscale_never_flaps_on_boundary_oscillation():
    p = AutoscalePolicy(1, 3, scale_up_s=2.0, scale_down_s=2.0)
    decisions = []
    for t in range(200):
        on = bool(t % 2)
        decisions.append(p.update(float(t), 2, {
            "backlog_growing": on, "backlog": 5 if on else 0,
        }))
    assert [d for d in decisions if d] == []


def test_autoscale_down_on_sustained_idle_and_floor():
    p = AutoscalePolicy(1, 3, scale_up_s=2.0, scale_down_s=4.0)
    idle = {"backlog_growing": False, "backlog": 0}
    assert p.update(0.0, 2, idle) is None
    d = p.update(4.0, 2, idle)
    assert d == {"direction": "down", "reason": "idle"}
    # at the floor: stays put forever
    p2 = AutoscalePolicy(1, 3, scale_down_s=1.0)
    assert p2.update(0.0, 1, idle) is None
    assert p2.update(50.0, 1, idle) is None


def test_autoscale_burn_condition_and_missing_signals_hold():
    p = AutoscalePolicy(1, 3, scale_up_s=2.0, scale_down_s=10.0, burn_threshold=2.0)
    burn = {"burn_fast": 3.0, "burn_slow": 2.5, "slo_n": 40, "backlog": 3}
    assert p.update(0.0, 1, burn) is None
    assert p.update(2.0, 1, burn) == {"direction": "up", "reason": "slo_burn"}
    # an empty merged window is NOT a burn (no traffic != outage)
    p2 = AutoscalePolicy(1, 3, scale_up_s=1.0)
    empty = {"burn_fast": 5.0, "burn_slow": 5.0, "slo_n": 0, "backlog": 0}
    assert p2.update(0.0, 1, empty) is None
    assert p2.update(5.0, 1, empty) is None
    # missing signals HOLD the pending clock instead of resetting it
    p3 = AutoscalePolicy(1, 3, scale_up_s=4.0, scale_down_s=10.0)
    grow = {"backlog_growing": True, "backlog": 5}
    assert p3.update(0.0, 1, grow) is None
    assert p3.update(2.0, 1, {}) is None          # no data: hold
    assert p3.update(4.0, 1, grow)["direction"] == "up"  # window spans the gap


# ------------------------------------------------ gate + service smoke


def test_sched_gate_fails_closed_and_is_digest_visible(monkeypatch):
    from zkp2p_tpu.utils.audit import execution_digest

    monkeypatch.delenv("ZKP2P_SCHED", raising=False)
    assert sched_mode() == "off"
    monkeypatch.setenv("ZKP2P_SCHED", "junk")
    assert sched_mode() == "off"  # anything unrecognized = the oracle arm
    d_off = execution_digest()
    monkeypatch.setenv("ZKP2P_SCHED", "adaptive")
    assert sched_mode() == "adaptive"
    d_on = execution_digest()
    assert d_off != d_on  # adaptive-vs-off A/Bs are digest-distinguishable
    monkeypatch.setenv("ZKP2P_SCHED", "off")
    sched_mode()
    assert execution_digest() == d_off


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


def _drop(spool, rid, payload, age_s=0.0):
    os.makedirs(spool, exist_ok=True)
    p = os.path.join(spool, rid + ".req.json")
    with open(p, "w") as f:
        json.dump(payload, f)
    if age_s:
        t = time.time() - age_s
        os.utime(p, (t, t))
    return p


def _sink_records(spool):
    with open(spool + ".metrics.jsonl") as f:
        return [json.loads(line) for line in f]


def test_adaptive_sweep_sheds_lanes_and_stamps_targets(toy_world, tmp_path, monkeypatch):
    """The sched-smoke heart: a mini-trace through the REAL service —
    hopeless request shed by prediction, interactive proved in the
    first (small) batch, bulk behind it, batch_size_target + decision
    line recorded."""
    monkeypatch.setenv("ZKP2P_SCHED", "adaptive")
    monkeypatch.setenv("ZKP2P_SCHED_AMORT", "1:0.05,8:0.1")
    monkeypatch.setenv("ZKP2P_SLO_P95_S", "10")
    monkeypatch.setenv("ZKP2P_DEADLINE_S", "10")
    spool = str(tmp_path / "spool")
    for i in range(6):
        _drop(spool, f"b{i}", {"x": 3 + i, "y": 4})
    _drop(spool, "int0", {"x": 5, "y": 6, "priority": "interactive"})
    _drop(spool, "old0", {"x": 7, "y": 8}, age_s=100.0)  # expired long ago
    svc = _toy_service(toy_world)
    stats = svc.process_dir(spool)
    assert stats["done"] == 7 and stats["error-shed"] == 1
    recs = _sink_records(spool)
    reqs = {r["request_id"]: r for r in recs if r.get("type") == "request"}
    assert reqs["old0"]["state"] == "error-shed"
    assert "sched" in reqs["old0"]["error"]
    # interactive lane: a batch of its own, ahead of bulk
    assert reqs["int0"]["state"] == "done"
    assert reqs["int0"]["batch_n"] == 1
    assert reqs["int0"]["batch_size_target"] == 1
    # bulk rode one controller-sized batch of 6
    assert reqs["b0"]["batch_n"] == 6
    assert reqs["b0"]["batch_size_target"] == 6
    # one decision line with the plan's fields
    sched_lines = [r for r in recs if r.get("type") == "sched"]
    assert len(sched_lines) == 1
    line = sched_lines[0]
    assert line["backlog"] == 8 and line["shed"] == 1
    assert line["lanes"] == {"interactive": 1, "bulk": 6}
    assert line["batch_target"] == 6 and line["interactive_target"] == 1
    # heartbeat block for fleet /status + top
    assert svc._sched_hb["mode"] == "adaptive"
    assert svc._sched_hb["lane_interactive"] == 1


def test_off_arm_keeps_static_slicing_and_records_cap_target(toy_world, tmp_path, monkeypatch):
    monkeypatch.setenv("ZKP2P_SCHED", "off")
    monkeypatch.delenv("ZKP2P_DEADLINE_S", raising=False)
    spool = str(tmp_path / "spool")
    for i in range(5):
        _drop(spool, f"b{i}", {"x": 3 + i, "y": 4})
    # priority is IGNORED by the static arm: scan order only
    _drop(spool, "zint", {"x": 5, "y": 6, "priority": "interactive"})
    svc = _toy_service(toy_world, batch_size=4)
    stats = svc.process_dir(spool)
    assert stats["done"] == 6
    recs = _sink_records(spool)
    reqs = {r["request_id"]: r for r in recs if r.get("type") == "request"}
    # static slicing: sorted scan order, batches of 4 then 2
    assert reqs["b0"]["batch_n"] == 4 and reqs["zint"]["batch_n"] == 2
    # the target is the CAP on every record (fill < target = low load)
    assert all(r["batch_size_target"] == 4 for r in reqs.values())
    # no decision lines on the oracle arm
    assert [r for r in recs if r.get("type") == "sched"] == []
    assert svc._sched_hb == {"mode": "off", "batch_target": 4}


def test_adaptive_cap_shed_orders_by_miss_not_newest(toy_world, tmp_path, monkeypatch):
    """Under the admission cap the adaptive arm sheds the requests the
    model predicts cannot finish — the aged ones — where the static arm
    sheds newest-first."""
    monkeypatch.setenv("ZKP2P_SCHED", "adaptive")
    monkeypatch.setenv("ZKP2P_SCHED_AMORT", "1:1.0,8:2.0")
    monkeypatch.setenv("ZKP2P_DEADLINE_S", "6")
    spool = str(tmp_path / "spool")
    for i in range(4):
        _drop(spool, f"fresh{i}", {"x": 3 + i, "y": 4})
    for i in range(2):
        _drop(spool, f"aged{i}", {"x": 9, "y": 4 + i}, age_s=5.5)  # ~0.5 s budget left
    svc = _toy_service(toy_world, batch_size=4, spool_cap=3)
    stats = svc.process_dir(spool)
    recs = _sink_records(spool)
    reqs = {r["request_id"]: r for r in recs if r.get("type") == "request"}
    shed = {rid for rid, r in reqs.items() if r["state"] == "error-shed"}
    # the aged pair is hopeless (predicted completion past deadline) and
    # the cap trims ONE more by least slack — never a fresh one ahead of
    # a doomed one
    assert {"aged0", "aged1"} <= shed
    assert len(shed) == 3
    assert stats["done"] == 3


def test_timeseries_line_carries_batch_size_target(toy_world, tmp_path, monkeypatch):
    from zkp2p_tpu.pipeline.service import TimeseriesSampler

    monkeypatch.setenv("ZKP2P_SCHED", "adaptive")
    monkeypatch.setenv("ZKP2P_SCHED_AMORT", "1:0.05,8:0.1")
    spool = str(tmp_path / "spool")
    for i in range(3):
        _drop(spool, f"b{i}", {"x": 3 + i, "y": 4})
    svc = _toy_service(toy_world)
    svc._sampler = TimeseriesSampler(interval_s=1000.0)
    svc.process_dir(spool)
    rec = svc._sampler.maybe_sample(spool, svc._sink(spool), force=True)
    assert rec is not None and rec["batch_size_target"] == 3


# ---------------------------------------------------- fleet autoscale demo


def test_fleet_autoscale_grows_on_spike_and_drains_back(tmp_path, monkeypatch):
    """The acceptance demo: a 1-worker toy fleet under a backlog spike
    scales to 2 (backlog_growth sustained), drains back to 1 on idle,
    with zero lost / zero duplicated proofs and the events on record."""
    import sys as _sys

    from zkp2p_tpu.native.lib import get_lib

    if get_lib() is None:
        pytest.skip("native toolchain unavailable")
    from zkp2p_tpu.pipeline.fleet import FleetSupervisor
    from zkp2p_tpu.utils.metrics import REGISTRY

    chaos = _chaos_mod()
    spool = str(tmp_path / "spool")
    fleet_dir = str(tmp_path / "fleet")
    os.makedirs(spool, exist_ok=True)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    # fast trend + scrape windows so the demo fits a test budget
    monkeypatch.setenv("ZKP2P_FLEET_SCRAPE_S", "0.3")
    monkeypatch.setenv("ZKP2P_ALERT_FOR_S", "0.9")
    worker_argv = [
        _sys.executable, CHAOS, "--worker", "--linger",
        "--spool", spool, "--batch", "2", "--prove-s", "0.35",
        "--max-seconds", "120", "--poll-s", "0.05",
    ]
    sup = FleetSupervisor(
        spool, lambda wid: list(worker_argv),
        workers=1, fleet_dir=fleet_dir,
        workers_min=1, workers_max=2,
        scale_up_s=0.8, scale_down_s=2.5,
        drain_timeout_s=30.0,
        fleet_metrics_port=0,
        log=lambda m: None,
    )
    rng_reqs = []
    try:
        sup.start()
        t_end = time.time() + 60.0
        i = 0
        scaled_up = False
        # feed a spike until the supervisor scales up (or time out)
        while time.time() < t_end:
            if i < 30:
                with open(os.path.join(spool, f"s{i:03d}.req.json"), "w") as f:
                    json.dump({"x": 3 + (i % 40), "y": 5}, f)
                rng_reqs.append(f"s{i:03d}")
                i += 1
            sup.tick()
            if len(sup.slots) > 1:
                scaled_up = True
                break
            time.sleep(0.1)
        assert scaled_up, "fleet never scaled up under a growing backlog"
        up_events = [e for e in sup._scale_events if e["direction"] == "up"]
        assert up_events and up_events[0]["reason"] in ("backlog_growth", "slo_burn")
        # let the spike drain, then idle long enough for a scale-down
        t_end = time.time() + 90.0
        scaled_down = False
        while time.time() < t_end:
            sup.tick()
            live = sup._live_workers()
            if any(e["direction"] == "down" for e in sup._scale_events) and len(live) == 1:
                scaled_down = True
                break
            time.sleep(0.1)
        assert scaled_down, "fleet never drained back down on sustained idle"
        # status.json carries the sched block + events
        with open(os.path.join(fleet_dir, "status.json")) as f:
            status = json.load(f)
        assert status["sched"]["autoscale"] is True
        assert status["sched"]["scale_events"] >= 2
        assert status["sched"]["last_scale"]["direction"] == "down"
        # decisions visible in metrics
        kinds = {
            (m["labels"].get("kind")): m["value"]
            for m in REGISTRY.snapshot()
            if m["name"] == "zkp2p_sched_decisions_total"
        }
        assert kinds.get("scale_up", 0) >= 1 and kinds.get("scale_down", 0) >= 1
    finally:
        sup.drain()
        if sup.plane is not None:
            sup.plane.stop()
    # zero lost, zero duplicated: every request exactly one terminal,
    # every proof pairing-verifies (the PR-7 invariant)
    deadline = time.time() + 30.0
    from zkp2p_tpu.pipeline.service import spool_terminal

    while time.time() < deadline and not spool_terminal(spool):
        time.sleep(0.2)
    report = chaos.check_invariants(spool)
    assert report["violations"] == [], report["violations"]
    assert report["states"].get("done", 0) == len(rng_reqs)


def test_top_renders_sched_block():
    """`zkp2p-tpu top` renders per-worker batch targets + lane depths
    and the autoscale state out of the fleet /status payload."""
    from zkp2p_tpu.pipeline.fleet_obs import render_top

    body = {
        "ok": True, "fleet_id": "fdemo",
        "workers": {
            "w0": {"state": "up", "sched": {
                "mode": "adaptive", "batch_target": 4,
                "lane_interactive": 1, "lane_bulk": 7,
            }},
            "w1": {"state": "up", "sched": {"mode": "off", "batch_target": 8}},
        },
        "sched": {
            "autoscale": True, "workers_min": 1, "workers_max": 4,
            "workers_live": 2, "scale_events": 3,
            "last_scale": {"direction": "up", "reason": "backlog_growth",
                           "workers": 2, "ts": 123.0},
        },
    }
    frame = render_top(body)
    assert "w0[adaptive] tgt=4 lanes i1/b7" in frame
    assert "w1[off] tgt=8" in frame
    assert "autoscale: 2 live in [1..4]" in frame
    assert "last up (backlog_growth) -> 2" in frame
    # no sched data = no sched lines, not a crash
    assert "sched:" not in render_top({"ok": False, "workers": {}})


def test_fleet_parallelism_scales_predictions():
    """N workers pull ONE queue: with parallelism=N the shed walk and
    sizing divide positions by N — a worker must never shed (or
    undersize for) requests its peers could still serve."""
    c = _ctl()
    c.observe_batch(1, 0.9)  # confirm the model so predictive shed engages
    now = 100.0
    reqs = _reqs(now, 20, wait=0.2)
    solo = c.plan(now, reqs, cap=8)
    c2 = _ctl()
    c2.observe_batch(1, 0.9)
    fleet = c2.plan(now + 0.001, reqs, cap=8, parallelism=4)
    # solo: the tail of 20 cannot finish alone; 4 peers: everything fits
    assert len(solo.shed) >= 1
    assert fleet.shed == []
    # sizing under pressure: positions /4 relax the count constraint so
    # the chosen batch is at least as wide
    aged = _reqs(now, 16, wait=5.0)
    ca, cb = _ctl(), _ctl()
    ca.observe_batch(1, 0.9)
    cb.observe_batch(1, 0.9)
    s_solo = ca.plan(now, aged, cap=8)
    s_fleet = cb.plan(now, aged, cap=8, parallelism=4)
    assert s_fleet.batch_target >= s_solo.batch_target
    assert len(s_fleet.shed) <= len(s_solo.shed)


def test_online_calibration_and_warmup_guard():
    """The static curve can be arbitrarily wrong for this circuit/host:
    before any real batch is observed, predictive shedding trusts only
    the model-free truth (deadline already passed); after observation,
    the EWMA scale pulls predictions toward measured reality."""
    c = _ctl(confirmed=False)
    now = 100.0
    fresh = _reqs(now, 20, wait=0.2)  # tail predicted-infeasible IF the model is right
    # uncalibrated: NOT expired -> never shed, however wrong the curve
    assert c.plan(now, fresh, cap=8).shed == []
    # already-expired requests shed even uncalibrated (now >= deadline)
    expired = _reqs(now, 2, wait=30.0, prefix="old")
    assert len(c.plan(now + 0.001, expired + fresh, cap=8).shed) == 2
    # observe a batch 10x CHEAPER than the model: scale drops, the
    # 20-request tail becomes feasible and stays unshed after
    # calibration too
    c.observe_batch(4, 0.18)  # model says 1.8 s -> ratio 0.1
    assert c.calibrated and c.model_scale == pytest.approx(0.1)
    assert c.plan(now + 0.002, fresh, cap=8).shed == []
    # observe a batch 2x the model: scale climbs toward it (EWMA)
    c.observe_batch(4, 3.6)
    assert 0.1 < c.model_scale < 2.0
    # a wildly slow outlier is clamped, not adopted verbatim
    c2 = _ctl(confirmed=False)
    c2.observe_batch(1, 9999.0)
    assert c2.model_scale <= 50.0
    # warm-up SIZING acts like the static arm (take the cap), never the
    # distrusted model's per-proof argmin
    c3 = _ctl(confirmed=False)
    warm = c3.plan(now + 1.0, _reqs(now + 1.0, 12, wait=0.1), cap=8)
    assert warm.batch_target == 8 and warm.batch_reason == "warmup"
