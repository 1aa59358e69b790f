"""The perf-regression sentry (utils.perfledger + the service budget
checks + the `perf_regression` alert + `make perf-gate`), tier-1
(`make perf-smoke`):

  * ledger round-trip — signed entries append atomically (one O_APPEND
    write per line) and load back in order; a torn line is counted,
    never fatal;
  * trust model — foreign-fingerprint, digest-tampered and
    schema-drifted lines are REFUSED and counted, exactly like a
    tampered host profile: never blended into budgets;
  * budget derivation — trailing-window slice, head-digest arm filter
    (mixed-arm history never blends into one budget), UPPER median on
    even windows, tolerance multiplier;
  * gating — ZKP2P_PERF_LEDGER=0 silences every producer through the
    single record() entry point and empties every BudgetBook, and a
    ledger-on run is digest-distinguishable from a ledger-off one on
    exactly the perf_ledger gate;
  * drift gate — rc 0 within band, rc 1 on head drift, rc 2 FAIL
    CLOSED on missing baseline / empty ledger / schema drift; new
    stages never fail the gate;
  * bench backfill — committed BENCH_r*.json tails import once
    (idempotent), failed rounds skipped, steady-rep stage paths
    normalized;
  * alert plumbing — perf_regression fires only after for_s of
    persistent overruns, HOLDs (never pages) on a fresh host with no
    budgets, clears after clear_s clean;
  * the acceptance end-to-end — a REAL service sweep with a seeded
    `prove:hang` fault trips zkp2p_stage_budget_overruns_total against
    ledger-derived budgets while an identical clean sweep stays quiet.
"""

import json
import os
import sys

import pytest

from zkp2p_tpu.utils import audit, faults
from zkp2p_tpu.utils import perfledger as pl
from zkp2p_tpu.utils.alerts import AlertEngine, fleet_rules
from zkp2p_tpu.utils.config import load_config
from zkp2p_tpu.utils.metrics import REGISTRY

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    """Hermetic gate state: no perf/fault env leaks between tests, and
    the budget memo never carries a previous test's ledger."""
    for var in ("ZKP2P_PERF_LEDGER", "ZKP2P_PERF_TOLERANCE", "ZKP2P_PERF_WINDOW",
                "ZKP2P_FAULTS", "ZKP2P_MSM_PRECOMP_CACHE"):
        monkeypatch.delenv(var, raising=False)
    faults.reset()
    pl.reset()
    yield
    faults.reset()
    pl.reset()


def _entry(circuit="toy", stages=None, digest="d1", **kw):
    return pl.make_entry(
        "bench", circuit, stages or {"prove": {"p50_ms": 100.0, "p95_ms": 120.0, "n": 4}},
        execution_digest=digest, **kw,
    )


def _counter(name, **labels):
    return REGISTRY.counter(name, labels or None).value


# ------------------------------------------------------------ round-trip


def test_append_load_roundtrip_preserves_order(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    for i in range(3):
        e = _entry(stages={"prove": {"p50_ms": 10.0 * (i + 1), "p95_ms": 11.0, "n": 1}})
        assert pl.append_entry(e, path=path) == path
    entries, refused = pl.load_entries(path)
    assert [e["stages"]["prove"]["p50_ms"] for e in entries] == [10.0, 20.0, 30.0]
    assert refused == {"unparseable": 0, "schema": 0, "foreign": 0, "tampered": 0}
    # every line is intact standalone JSON (the single-write append
    # discipline: concurrent workers interleave whole lines, never torn)
    with open(path) as f:
        assert all(json.loads(ln) for ln in f)


def test_torn_line_is_counted_not_fatal(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    pl.append_entry(_entry(), path=path)
    with open(path, "a") as f:
        f.write('{"schema": 1, "trunc\n')  # a torn line from a crash
    pl.append_entry(_entry(), path=path)
    entries, refused = pl.load_entries(path)
    assert len(entries) == 2 and refused["unparseable"] == 1


def test_missing_or_disabled_ledger_is_empty_not_error(tmp_path, monkeypatch):
    entries, refused = pl.load_entries(str(tmp_path / "nope.jsonl"))
    assert entries == [] and sum(refused.values()) == 0
    # persistence off (ZKP2P_MSM_PRECOMP_CACHE=0): no default path at all
    monkeypatch.setenv("ZKP2P_MSM_PRECOMP_CACHE", "0")
    assert pl.default_ledger_path() is None
    assert pl.append_entry(_entry()) is None


# ------------------------------------------------------------ trust model


def test_tampered_entry_refused(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    e = _entry()
    e["stages"]["prove"]["p50_ms"] = 1.0  # edited AFTER signing
    pl.append_entry(e, path=path)
    entries, refused = pl.load_entries(path)
    assert entries == [] and refused["tampered"] == 1


def test_foreign_fingerprint_refused(tmp_path):
    """A ledger copied from another box: the fingerprint key differs,
    and budgets derived from someone else's hardware would page on
    every healthy request here."""
    path = str(tmp_path / "ledger.jsonl")
    e = _entry()
    e["fingerprint_key"] = "0" * 16
    e["entry_digest"] = pl._entry_digest(e)  # re-signed: digest VALID
    pl.append_entry(e, path=path)
    entries, refused = pl.load_entries(path)
    assert entries == [] and refused["foreign"] == 1 and refused["tampered"] == 0


def test_schema_drift_refused(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    e = _entry()
    e["schema"] = pl.SCHEMA_VERSION + 1
    e["entry_digest"] = pl._entry_digest(e)
    pl.append_entry(e, path=path)
    entries, refused = pl.load_entries(path)
    assert entries == [] and refused["schema"] == 1


# ------------------------------------------------------------ stage stats


def test_stage_stats_nearest_rank():
    st = pl.stage_stats([5.0, 1.0, 3.0, 2.0, 4.0])
    assert st == {"p50_ms": 3.0, "p95_ms": 5.0, "n": 5}
    assert pl.stage_stats([7.0]) == {"p50_ms": 7.0, "p95_ms": 7.0, "n": 1}
    assert pl.stage_stats([]) is None


# ------------------------------------------------------- budget derivation


def test_budget_trailing_window_and_tolerance():
    entries = [
        _entry(stages={"prove": {"p50_ms": float(i), "p95_ms": float(i), "n": 1}})
        for i in range(1, 11)
    ]
    b = pl.derive_budgets(entries, window=4, tolerance=2.0)["toy"]["prove"]
    # tail [7,8,9,10]: upper median 9, budget 9*2
    assert b["median_ms"] == 9.0 and b["budget_ms"] == 18.0
    assert b["n"] == 4 and b["arm_skipped"] == 0 and b["tolerance"] == 2.0


def test_budget_upper_median_on_two_entry_window():
    """A 2-entry window must take the HIGHER middle: a lower median
    would flag the slower-but-valid of the two rounds that produced
    it — the gate would fail on its own history."""
    entries = [
        _entry(stages={"prove": {"p50_ms": ms, "p95_ms": ms, "n": 1}})
        for ms in (100.0, 200.0)
    ]
    b = pl.derive_budgets(entries, window=8, tolerance=1.5)["toy"]["prove"]
    assert b["median_ms"] == 200.0 and b["budget_ms"] == 300.0
    # and the head entry itself is within its own budget (no self-flag)
    assert 200.0 <= b["budget_ms"]


def test_budget_filters_to_head_digest():
    """Mixed-arm history: only entries sharing the HEAD entry's
    execution digest may shape the budget — blending two code paths'
    cost distributions into one band would mis-page both."""
    entries = (
        [_entry(digest="old", stages={"prove": {"p50_ms": 5.0, "p95_ms": 5.0, "n": 1}})] * 2
        + [_entry(digest="new", stages={"prove": {"p50_ms": 50.0, "p95_ms": 50.0, "n": 1}})] * 2
    )
    b = pl.derive_budgets(entries, window=4, tolerance=1.5)["toy"]["prove"]
    assert b["median_ms"] == 50.0  # the 5ms old-arm rows never blended in
    assert b["n"] == 2 and b["arm_skipped"] == 2


def test_budget_book_over_within_and_unknown(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    pl.append_entry(_entry(), path=path)  # prove p50 100 -> budget 150
    book = pl.BudgetBook.load("toy", path=path)
    assert len(book) == 1 and book.budget_ms("prove") == 150.0
    assert book.over("prove", 151.0) is True
    assert book.over("prove", 149.0) is False
    assert book.over("witness", 1e9) is None   # no budget: never counts
    assert book.over("prove", None) is None
    # a circuit with no entries gets an EMPTY book, not someone else's
    assert len(pl.BudgetBook.load("other-circuit", path=path)) == 0


def test_budget_book_empty_when_gate_off(tmp_path, monkeypatch):
    path = str(tmp_path / "ledger.jsonl")
    pl.append_entry(_entry(), path=path)
    monkeypatch.setenv("ZKP2P_PERF_LEDGER", "0")
    assert len(pl.BudgetBook.load("toy", path=path)) == 0


# ------------------------------------------------------------------ gating


def test_record_gate_off_silences_producers(tmp_path, monkeypatch):
    path = str(tmp_path / "ledger.jsonl")
    monkeypatch.setenv("ZKP2P_PERF_LEDGER", "0")
    assert pl.record("bench", "toy", {"prove": {"p50_ms": 1.0}}, path=path) is None
    assert not os.path.exists(path)
    monkeypatch.delenv("ZKP2P_PERF_LEDGER")
    assert pl.record("bench", "toy", {"prove": {"p50_ms": 1.0}}, path=path) == path
    entries, _ = pl.load_entries(path)
    assert len(entries) == 1 and entries[0]["source"] == "bench"
    # an empty stage map records nothing (a sweep that measured nothing)
    assert pl.record("bench", "toy", {}, path=path) is None


def test_ledger_on_off_is_digest_distinguishable(monkeypatch):
    """The A/B contract: a ledger-on run and a ledger-off run must
    never share an execution digest, and differ on exactly this gate."""
    audit.reset()
    monkeypatch.setenv("ZKP2P_PERF_LEDGER", "1")
    assert pl.perf_arm() == "on"
    d_on = audit.execution_digest()
    arms_on = audit.gate_arms()
    audit.reset()
    monkeypatch.setenv("ZKP2P_PERF_LEDGER", "0")
    assert pl.perf_arm() == "off"
    d_off = audit.execution_digest()
    arms_off = audit.gate_arms()
    audit.reset()
    assert d_on != d_off
    assert {g for g in set(arms_on) | set(arms_off)
            if arms_on.get(g) != arms_off.get(g)} == {"perf_ledger"}


# ---------------------------------------------------------- bench backfill


def _write_bench(dirpath, name, rc, tail="", parsed=None):
    with open(os.path.join(dirpath, name), "w") as f:
        json.dump({"n": 1, "cmd": "bench", "rc": rc, "tail": tail,
                   "parsed": parsed or {}}, f)


def test_backfill_imports_once_and_normalizes(tmp_path):
    bench_dir = tmp_path / "hist"
    bench_dir.mkdir()
    ledger = str(tmp_path / "ledger.jsonl")
    _write_bench(str(bench_dir), "BENCH_r01.json", rc=1, tail="crashed")
    tail = "\n".join([
        "free text the bench printed",
        json.dumps({"stage": "prove_native_3/native/msm_h", "ms": 10.0}),
        json.dumps({"stage": "prove_native_3/native/msm_h", "ms": 12.0}),
        json.dumps({"stage": "prove_native_3", "ms": 50.0}),
        json.dumps({"not-a-stage": True}),
    ])
    _write_bench(str(bench_dir), "BENCH_r02.json", rc=0, tail=tail,
                 parsed={"p50_s": 0.08, "run_id": "r02run"})
    glob_pat = os.path.join(str(bench_dir), "BENCH_r*.json")
    assert pl.backfill_bench(glob_pat, path=ledger) == 1  # r01 (rc!=0) skipped
    entries, refused = pl.load_entries(ledger)
    assert sum(refused.values()) == 0 and len(entries) == 1
    e = entries[0]
    assert e["source"] == "bench_backfill" and e["backfill_of"] == "BENCH_r02.json"
    assert e["execution_digest"] == pl.BACKFILL_DIGEST  # predates the audit stamp
    # steady-rep paths normalized; the tail's measured prove wins over
    # the parsed p50_s fallback
    assert e["stages"]["native/msm_h"] == {"p50_ms": 12.0, "p95_ms": 12.0, "n": 2}
    assert e["stages"]["prove_native"]["p50_ms"] == 50.0
    # idempotent: a second import (the unconditional make perf-gate run)
    assert pl.backfill_bench(glob_pat, path=ledger) == 0
    assert len(pl.load_entries(ledger)[0]) == 1


# ------------------------------------------------------- baseline + gate


def test_write_baseline_fails_closed_on_empty_ledger(tmp_path):
    out = pl.write_baseline(
        baseline_path=str(tmp_path / "base.json"),
        ledger_path=str(tmp_path / "empty.jsonl"),
    )
    assert out is None and not os.path.exists(str(tmp_path / "base.json"))


def test_gate_ok_drift_and_fail_closed(tmp_path):
    ledger = str(tmp_path / "ledger.jsonl")
    base = str(tmp_path / "base.json")
    for ms in (100.0, 110.0):
        pl.append_entry(
            _entry(stages={"prove": {"p50_ms": ms, "p95_ms": ms, "n": 1}}), path=ledger)
    doc = pl.write_baseline(baseline_path=base, ledger_path=ledger,
                            window=8, tolerance=1.5)
    assert doc and doc["bands"]["toy"]["prove"]["budget_ms"] == 165.0

    rc, verdicts = pl.gate_check(baseline_path=base, ledger_path=ledger)
    assert rc == 0
    assert [v["verdict"] for v in verdicts] == ["ok"]

    # a NEW stage (added instrumentation) reports but never fails
    pl.append_entry(
        _entry(stages={"prove": {"p50_ms": 120.0, "p95_ms": 120.0, "n": 1},
                       "verify": {"p50_ms": 5.0, "p95_ms": 5.0, "n": 1}}), path=ledger)
    rc, verdicts = pl.gate_check(baseline_path=base, ledger_path=ledger)
    assert rc == 0
    assert {v["stage"]: v["verdict"] for v in verdicts} == {"prove": "ok", "verify": "new"}

    # head drifts past the band -> rc 1
    pl.append_entry(
        _entry(stages={"prove": {"p50_ms": 400.0, "p95_ms": 400.0, "n": 1}}), path=ledger)
    rc, verdicts = pl.gate_check(baseline_path=base, ledger_path=ledger)
    assert rc == 1
    assert [v for v in verdicts if v["verdict"] == "DRIFT"][0]["stage"] == "prove"

    # head BEATS the band median by more than the tolerance factor ->
    # informational IMPROVED (rc stays 0): the band is stale-loose and
    # should be re-frozen (`zkp2p-tpu perf --rebaseline`)
    pl.append_entry(
        _entry(stages={"prove": {"p50_ms": 40.0, "p95_ms": 40.0, "n": 1}}), path=ledger)
    rc, verdicts = pl.gate_check(baseline_path=base, ledger_path=ledger)
    assert rc == 0
    assert [v for v in verdicts if v["stage"] == "prove"][0]["verdict"] == "IMPROVED"
    # a merely-better head stays "ok" — IMPROVED must clear tolerance,
    # otherwise every within-band wobble would nag for a rebaseline
    pl.append_entry(
        _entry(stages={"prove": {"p50_ms": 95.0, "p95_ms": 95.0, "n": 1}}), path=ledger)
    rc, verdicts = pl.gate_check(baseline_path=base, ledger_path=ledger)
    assert rc == 0
    assert [v for v in verdicts if v["stage"] == "prove"][0]["verdict"] == "ok"

    # fail closed: no baseline, unreadable baseline schema, empty ledger
    assert pl.gate_check(baseline_path=str(tmp_path / "nope.json"),
                         ledger_path=ledger)[0] == 2
    with open(str(tmp_path / "drift.json"), "w") as f:
        json.dump({"schema": 999}, f)
    assert pl.gate_check(baseline_path=str(tmp_path / "drift.json"),
                         ledger_path=ledger)[0] == 2
    assert pl.gate_check(baseline_path=base,
                         ledger_path=str(tmp_path / "empty.jsonl"))[0] == 2


def test_gate_warns_on_foreign_baseline_but_compares(tmp_path):
    ledger = str(tmp_path / "ledger.jsonl")
    base = str(tmp_path / "base.json")
    pl.append_entry(_entry(), path=ledger)
    doc = pl.write_baseline(baseline_path=base, ledger_path=ledger)
    assert doc is not None
    with open(base) as f:
        b = json.load(f)
    b["fingerprint_key"] = "f" * 16  # frozen on different hardware
    with open(base, "w") as f:
        json.dump(b, f)
    log = []
    rc, verdicts = pl.gate_check(baseline_path=base, ledger_path=ledger,
                                 log=log.append)
    assert rc == 0 and verdicts  # still compared
    assert any("different hardware" in m for m in log)


# -------------------------------------------------------------- tune stages


def test_tune_stages_best_of_arms():
    prof = {"tune": {"sweep": {
        "threads": {"1": 0.5, "2": 0.3, "4": 0.4},
        "window": {"b1": {"3": 0.2, "4": 0.1}},
        "columns": {"on": 0.25, "off": 0.35},
    }}}
    st = pl.tune_stages(prof)
    assert st["tune/msm_threads_best"] == {"p50_ms": 300.0, "p95_ms": 300.0, "n": 3}
    assert st["tune/msm_window_b1"]["p50_ms"] == 100.0
    assert st["tune/msm_columns_best"]["p50_ms"] == 250.0
    assert pl.tune_stages({}) == {}


# ------------------------------------------------------------ alert plumbing


def _engine():
    cfg = load_config(environ={"ZKP2P_ALERT_FOR_S": "5", "ZKP2P_ALERT_CLEAR_S": "10"})
    from zkp2p_tpu.utils.metrics import Registry

    reg = Registry()
    return AlertEngine(fleet_rules(cfg), registry=reg, log=lambda m: None), reg


def test_perf_regression_holds_on_fresh_host():
    """No worker has budgets yet (budget_overruns signal is absent):
    the rule must HOLD, never page — a fresh host has no history to
    regress against."""
    eng, _ = _engine()
    for t in range(30):
        assert eng.evaluate({"overruns_recent": 9.0}, now=float(t)) == []
    assert eng.active() == []


def test_perf_regression_fires_after_for_s_and_clears():
    eng, reg = _engine()
    hot = {"budget_overruns": 12.0, "overruns_recent": 3.0}
    assert eng.evaluate(hot, now=0.0) == []              # pending
    trs = eng.evaluate(hot, now=5.0)                     # held for_s: fires
    assert [t["rule"] for t in trs] == ["perf_regression"]
    assert [t["event"] for t in trs] == ["fired"]
    # overruns stop growing (total stays, recent drains) -> clean ...
    calm = {"budget_overruns": 12.0, "overruns_recent": 0.0}
    assert eng.evaluate(calm, now=6.0) == []             # < clear_s
    assert eng.active()
    # ... and a scrape gap mid-episode HOLDs, never clears on absence
    assert eng.evaluate({}, now=8.0) == []
    assert eng.active()
    trs = eng.evaluate(calm, now=18.0)                   # clean clear_s
    assert [t["event"] for t in trs] == ["cleared"]
    assert eng.active() == []


# -------------------------------------------- end-to-end seeded regression

from zkp2p_tpu.native.lib import get_lib  # noqa: E402


@pytest.fixture(scope="module")
def world():
    from zkp2p_tpu.field.bn254 import R
    from zkp2p_tpu.prover.groth16_tpu import device_pk
    from zkp2p_tpu.snark.groth16 import setup
    from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem

    cs = ConstraintSystem("perf-sentry")
    out = cs.new_public("out")
    x = cs.new_wire("x")
    y = cs.new_wire("y")
    z = cs.new_wire("z")
    cs.enforce(LC.of(x), LC.of(y), LC.of(z), "mul")
    cs.enforce(LC.of(z), LC.of(z), LC.of(out), "sq")
    cs.compute(z, lambda a, b: a * b % R, [x, y])
    pk, vk = setup(cs, seed="perf-sentry")
    dpk = device_pk(pk, cs)

    def witness_fn(payload):
        xv, yv = int(payload["x"]), int(payload["y"])
        return cs.witness([pow(xv * yv, 2, R)], {x: xv, y: yv})

    return cs, dpk, vk, witness_fn


def _mk_service(world, circuit):
    from zkp2p_tpu.pipeline.service import ProvingService
    from zkp2p_tpu.prover.native_prove import prove_native

    cs, dpk, vk, witness_fn = world
    return ProvingService(
        cs, dpk, vk, witness_fn, public_fn=lambda w: [w[1]],
        prover_fn=lambda d, wits: [prove_native(d, w, r=1, s=2) for w in wits],
        batch_size=2, retry_backoff_s=0.0, circuit=circuit,
    )


def _write_reqs(spool, n):
    from zkp2p_tpu.field.bn254 import R  # noqa: F401 — witness domain

    for i in range(n):
        with open(os.path.join(spool, f"r{i}.req.json"), "w") as f:
            json.dump({"x": 3 + i, "y": 5}, f)


@pytest.mark.skipif(get_lib() is None, reason="native toolchain unavailable")
def test_seeded_regression_trips_overruns_clean_run_stays_quiet(
    world, tmp_path, monkeypatch
):
    """THE acceptance criterion: budgets derived from this host's
    ledger, a REAL service sweep with a seeded `prove:hang` slowdown
    trips the overruns counter and surfaces in the heartbeat perf
    block, while an identical clean sweep stays at zero."""
    # ledger in a tmp cache root (the service loads budgets from the
    # DEFAULT path — the production path, not a test-injected one)
    monkeypatch.setenv("ZKP2P_MSM_PRECOMP_CACHE", str(tmp_path / "cache"))
    pl.reset()
    for _ in range(3):  # history: prove ~150ms -> budget 225ms
        pl.append_entry(_entry(circuit="toy", digest="hist",
                               stages={"prove": {"p50_ms": 150.0, "p95_ms": 160.0, "n": 4}}))
    assert pl.load_entries()[0], "seed history must be valid on this host"

    # clean sweep: prove of a 2-constraint circuit is far under 225ms
    spool = str(tmp_path / "clean")
    os.makedirs(spool)
    _write_reqs(spool, 2)
    c0 = _counter("zkp2p_stage_budget_overruns_total", stage="prove")
    svc = _mk_service(world, "toy")
    assert svc.process_dir(spool)["done"] == 2
    assert _counter("zkp2p_stage_budget_overruns_total", stage="prove") - c0 == 0
    assert svc._perf_hb["budgets"] == 1 and svc._perf_hb["overruns"] == 0
    assert svc._perf_hb["checked"] == 2  # every terminal prove span checked

    # seeded regression: hang=0.6 pushes every prove span past 225ms
    monkeypatch.setenv("ZKP2P_FAULTS", "prove:hang=0.6")
    faults.reset()
    spool2 = str(tmp_path / "slow")
    os.makedirs(spool2)
    _write_reqs(spool2, 2)
    svc2 = _mk_service(world, "toy")
    assert svc2.process_dir(spool2)["done"] == 2
    assert _counter("zkp2p_stage_budget_overruns_total", stage="prove") - c0 == 2
    assert svc2._perf_hb["overruns"] == 2  # rides the fleet heartbeat

    # and the run's exit stamp lands a service-source ledger entry the
    # NEXT budget derivation will see (the live-sweep sampling arm)
    monkeypatch.delenv("ZKP2P_FAULTS")
    faults.reset()
    svc2._perf_stamp()
    entries, _ = pl.load_entries()
    assert entries[-1]["source"] == "service" and entries[-1]["circuit"] == "toy"
    assert entries[-1]["stages"]["prove"]["p50_ms"] > 225.0


@pytest.mark.skipif(get_lib() is None, reason="native toolchain unavailable")
def test_gate_off_sweep_counts_nothing(world, tmp_path, monkeypatch):
    monkeypatch.setenv("ZKP2P_MSM_PRECOMP_CACHE", str(tmp_path / "cache"))
    pl.reset()
    pl.append_entry(_entry(circuit="toy", stages={"prove": {"p50_ms": 0.001}}))
    monkeypatch.setenv("ZKP2P_PERF_LEDGER", "0")
    monkeypatch.setenv("ZKP2P_FAULTS", "prove:hang=0.2")
    faults.reset()
    spool = str(tmp_path / "spool")
    os.makedirs(spool)
    _write_reqs(spool, 1)
    c0 = _counter("zkp2p_stage_budget_overruns_total", stage="prove")
    svc = _mk_service(world, "toy")
    assert svc.process_dir(spool)["done"] == 1
    # an absurdly-tight budget exists on disk, but the gate is OFF: the
    # book is empty, nothing is checked, nothing pages
    assert _counter("zkp2p_stage_budget_overruns_total", stage="prove") - c0 == 0
    assert svc._perf_hb["budgets"] == 0


# ------------------------------------------------- trace_report --compare


def _trace_report():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    return trace_report


def _write_sink(path, digest_b="bbbb", gates_b=None):
    recs = [
        {"type": "manifest", "run_id": "runA", "execution_digest": "aaaa",
         "gates": {"msm_glv": "off", "perf_ledger": "on"}},
        {"type": "manifest", "run_id": "runB", "execution_digest": digest_b,
         "gates": gates_b if gates_b is not None
         else {"msm_glv": "on", "perf_ledger": "on"}},
    ]
    for ms in (100.0, 110.0):
        recs.append({"stage": "prove", "ms": ms, "run_id": "runA"})
    for ms in (150.0, 160.0):
        recs.append({"stage": "prove", "ms": ms, "run_id": "runB"})
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")


def test_compare_diffs_p50_and_names_diverging_arms(tmp_path, capsys):
    """--compare = the interleaved-A/B readout: per-stage p50 diff PLUS
    the digest callout naming WHICH arms differ — a delta between
    digest-divergent runs is a code-path change, not a regression."""
    tr = _trace_report()
    sink = str(tmp_path / "sink.jsonl")
    _write_sink(sink)
    assert tr.main([sink, "--compare", "runA", "runB", "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["a"]["prove"]["p50"] == 100.0 and out["b"]["prove"]["p50"] == 150.0
    assert any("DIFFER" in ln for ln in out["digest_callout"])
    assert any("msm_glv=off->on" in ln for ln in out["digest_callout"])
    # text mode renders the callout above the diff table
    assert tr.main([sink, "--compare", "runA", "runB"]) == 0
    text = capsys.readouterr().out
    assert "digests DIFFER" in text and "msm_glv=off->on" in text
    assert "prove" in text and "+50.0%" in text


def test_compare_matching_digests_calls_out_real_delta(tmp_path, capsys):
    tr = _trace_report()
    sink = str(tmp_path / "sink.jsonl")
    _write_sink(sink, digest_b="aaaa",
                gates_b={"msm_glv": "off", "perf_ledger": "on"})
    assert tr.main([sink, "--compare", "runA", "runB"]) == 0
    text = capsys.readouterr().out
    assert "digests MATCH (aaaa)" in text and "real perf delta" in text
    # a run with no records fails loudly, not an empty table
    assert tr.main([sink, "--compare", "runA", "ghost"]) == 1


# -------------------------------------------------------- fleet top column


def test_render_top_shows_overrun_column():
    from zkp2p_tpu.pipeline.fleet_obs import render_top

    body = {
        "ok": True, "fleet_id": "f1",
        "workers": {
            "w0": {"state": "up", "pid": 1, "restarts": 0,
                   "perf": {"overruns": 7, "checked": 40, "budgets": 3}},
            "w1": {"state": "up", "pid": 2, "restarts": 0},
        },
    }
    frame = render_top(body)
    lines = frame.splitlines()
    (head,) = [ln for ln in lines if "overrun" in ln]
    assert head  # the column exists
    (w0,) = [ln for ln in lines if ln.strip().startswith("w0")]
    (w1,) = [ln for ln in lines if ln.strip().startswith("w1")]
    assert "7" in w0.split()
    assert "-" in w1.split()  # no budgets -> dash, never a fake zero
