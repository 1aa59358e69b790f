"""The continuous-profiling loop (utils.flameprof + the service
capture hook + `zkp2p-tpu perf` cross-links + `trace_report --flame`),
tier-1 (`make flame-smoke`):

  * gating — ZKP2P_FLAME default OFF; the arm carries the sampling
    rate; a sampler-on run is digest-distinguishable from a
    sampler-off one on exactly the `flame` gate;
  * sampling — a hot Python loop shows up in the collapsed stacks
    under its own function frame;
  * synthetic native frames — a thread parked at a bridge file while
    native counters move gets `native:<stage>` (and `native:msm.<sub>`)
    frames stitched under its parked frame; native self-time with no
    parked thread observed folds under the `[native]` root with at
    least one count — nothing measured is dropped;
  * capture files — atomic tmp+rename writes, fail-closed loads
    (truncated / foreign kind / schema drift / non-int stacks are
    None, never a crash), captures_for filters by circuit and stage;
  * CaptureController — trigger refused when gated off, mid-capture,
    or cooling down; the capture lands after flame_capture_n sweep
    ticks, counted in zkp2p_flame_captures_total{trigger} and exposed
    via pointer();
  * the acceptance end-to-end — a REAL service sweep with a seeded
    `prove:hang` regression trips the budget overrun AND produces a
    flame capture whose stacks carry synthetic native stage frames,
    while an identical clean sweep produces zero captures;
  * federation — `zkp2p-tpu top` grows a flame column only when some
    worker's heartbeat perf block carries a capture pointer;
  * report paths — `zkp2p-tpu perf` prints the capture pointer under a
    REGRESSED trendline; `trace_report --flame` prints collapsed
    stacks, renders a nested flame track with --chrome-trace, and
    refuses invalid captures with rc 1.
"""

import glob
import json
import os
import sys
import threading
import time

import pytest

from zkp2p_tpu.utils import audit, faults
from zkp2p_tpu.utils import flameprof
from zkp2p_tpu.utils import perfledger as pl
from zkp2p_tpu.utils.metrics import REGISTRY

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    """Hermetic gate state: no flame/perf/fault env leaks between
    tests, and the process-wide capture controller never carries a
    previous test's sampler or cooldown stamp."""
    for var in ("ZKP2P_FLAME", "ZKP2P_FLAME_HZ", "ZKP2P_FLAME_CAPTURE_N",
                "ZKP2P_FLAME_COOLDOWN_S", "ZKP2P_PERF_LEDGER",
                "ZKP2P_PERF_TOLERANCE", "ZKP2P_PERF_WINDOW",
                "ZKP2P_FAULTS", "ZKP2P_MSM_PRECOMP_CACHE"):
        monkeypatch.delenv(var, raising=False)
    faults.reset()
    pl.reset()
    flameprof.controller().reset()
    yield
    faults.reset()
    pl.reset()
    flameprof.controller().reset()


def _counter(name, **labels):
    return REGISTRY.counter(name, labels or None).value


# ------------------------------------------------------------------ gating


def test_flame_gate_default_off_and_arm_carries_hz(monkeypatch):
    assert flameprof.flame_arm() == "off"
    monkeypatch.setenv("ZKP2P_FLAME", "1")
    assert flameprof.flame_arm() == "47hz"
    monkeypatch.setenv("ZKP2P_FLAME_HZ", "101")
    assert flameprof.flame_arm() == "101hz"


def test_flame_on_off_is_digest_distinguishable(monkeypatch):
    """The A/B contract: a sampler-on run and a sampler-off run must
    never share an execution digest, and differ on exactly this gate."""
    audit.reset()
    monkeypatch.setenv("ZKP2P_FLAME", "1")
    flameprof.flame_arm()
    d_on = audit.execution_digest()
    arms_on = audit.gate_arms()
    audit.reset()
    monkeypatch.delenv("ZKP2P_FLAME")
    flameprof.flame_arm()
    d_off = audit.execution_digest()
    arms_off = audit.gate_arms()
    audit.reset()
    assert d_on != d_off
    assert {g for g in set(arms_on) | set(arms_off)
            if arms_on.get(g) != arms_off.get(g)} == {"flame"}


def test_preflight_arms_flame_gate():
    rep = audit.preflight(workload=False)
    assert rep["gates"].get("flame") == "off"  # default: fully off


# ---------------------------------------------------------------- sampling


def _spin(stop_evt):
    while not stop_evt.is_set():
        sum(i * i for i in range(500))


def _park(stop_evt):
    # stands in for a GIL-released ctypes bridge call: the leaf Python
    # frame sits in THIS file while "native work" happens elsewhere
    while not stop_evt.is_set():
        time.sleep(0.002)


def _run_sampled(target, sampler_kw, seconds=0.15):
    stop_evt = threading.Event()
    t = threading.Thread(target=target, args=(stop_evt,), daemon=True)
    t.start()
    time.sleep(0.01)  # let the worker reach its loop
    s = flameprof.FlameSampler(
        thread_filter={t.ident}, **sampler_kw
    ).start()
    time.sleep(seconds)
    s.stop()
    stop_evt.set()
    t.join(timeout=5.0)
    return s


def test_hot_python_loop_shows_in_stacks():
    s = _run_sampled(_spin, {"hz": 200.0, "stats_source": lambda: None})
    stacks = s.stacks()
    assert s.samples > 0
    assert any("test_flameprof.py:_spin" in k for k in stacks), stacks
    # no stats block at all: zero native attribution, no [native] root
    assert not any(k.startswith("[native]") for k in stacks)
    assert sum(s.result()["native_ns"].values()) == 0


class _FakeStats:
    """A stats_snapshot stand-in whose counters advance every read —
    every sample window sees fresh native ns."""

    def __init__(self, **per_read_ns):
        self.per_read = per_read_ns
        self.t = {f: 0 for f in (
            "msm_wall_ns", "msm_fill_ns", "msm_suffix_ns", "msm_apply_ns",
            "matvec_ns", "ntt_stage_ns", "msm_inflight",
        )}

    def __call__(self):
        for f, ns in self.per_read.items():
            self.t[f] += ns
        return dict(self.t)


def test_bridge_parked_thread_gets_synthetic_native_frames(monkeypatch):
    """A thread whose leaf frame sits in a bridge file while msm
    counters move earns `native:msm;native:msm.fill` under its stack —
    and because the work WAS attributed, nothing folds under
    [native]."""
    monkeypatch.setattr(
        flameprof, "BRIDGE_SUFFIXES", ("tests/test_flameprof.py",)
    )
    fake = _FakeStats(msm_wall_ns=5_000_000, msm_fill_ns=3_000_000)
    s = _run_sampled(_park, {"hz": 200.0, "stats_source": fake})
    stacks = s.stacks()
    assert any(
        "test_flameprof.py:_park;native:msm;native:msm.fill" in k
        for k in stacks
    ), stacks
    body = s.result()
    assert body["native_ns"]["msm"] > 0
    assert body["native_unattributed_ns"]["msm"] == 0
    # honest overhead: the sampler clocks its own work in every capture
    assert body["sampler"]["self_ms"] >= 0.0


def test_unattributed_native_time_folds_under_native_root(monkeypatch):
    """Native ns that accrues while NO thread is parked at a bridge
    (pool workers did the work) lands under the synthetic [native]
    root at finalization — floor one count, so it is always visible."""
    monkeypatch.setattr(flameprof, "BRIDGE_SUFFIXES", ("no/such/file.py",))
    fake = _FakeStats(ntt_stage_ns=2_000_000)
    s = flameprof.FlameSampler(
        hz=100.0, stats_source=fake, thread_filter=set()
    ).start()
    time.sleep(0.1)
    s.stop()
    stacks = s.stacks()
    assert stacks.get("[native];native:ntt", 0) >= 1, stacks
    body = s.result()
    assert body["native_unattributed_ns"]["ntt"] == body["native_ns"]["ntt"] > 0


# ------------------------------------------------------------ capture files


def _quick_capture(tmp_path, circuit="toy", stage="prove", trigger="manual",
                   **kw):
    s = flameprof.FlameSampler(hz=200.0, stats_source=lambda: None).start()
    time.sleep(0.03)
    return flameprof.write_capture(
        s, circuit=circuit, stage=stage, trigger=trigger,
        out_dir=str(tmp_path), **kw,
    )


def test_write_capture_is_atomic_and_loads_back(tmp_path):
    c0 = _counter("zkp2p_flame_captures_total", trigger="manual")
    path = _quick_capture(tmp_path, entry_digest="ed1", budget_ms=225.0,
                          over_ms=400.0)
    assert path and os.path.basename(path).startswith("flame_toy_prove_")
    # atomic: no tmp litter beside the capture
    assert not glob.glob(os.path.join(str(tmp_path), "*.tmp.*"))
    doc = flameprof.load_capture(path)
    assert doc is not None
    assert doc["circuit"] == "toy" and doc["stage"] == "prove"
    assert doc["trigger"] == "manual" and doc["entry_digest"] == "ed1"
    assert doc["budget_ms"] == 225.0 and doc["over_ms"] == 400.0
    assert doc["schema"] == flameprof.CAPTURE_SCHEMA
    assert "execution_digest" in doc and "sampler" in doc
    assert _counter("zkp2p_flame_captures_total", trigger="manual") - c0 == 1


def test_write_capture_none_when_persistence_disabled(monkeypatch):
    monkeypatch.setenv("ZKP2P_MSM_PRECOMP_CACHE", "0")
    assert flameprof.capture_dir() is None
    s = flameprof.FlameSampler(hz=200.0, stats_source=lambda: None).start()
    assert flameprof.write_capture(s, "toy", "prove", "manual") is None


def test_load_capture_fails_closed(tmp_path):
    good = _quick_capture(tmp_path)
    doc = flameprof.load_capture(good)
    assert doc is not None
    # truncated mid-file (a torn write that bypassed the rename)
    torn = str(tmp_path / "torn.json")
    with open(good) as f, open(torn, "w") as g:
        g.write(f.read()[: 40])
    assert flameprof.load_capture(torn) is None
    # foreign kind / drifted schema / corrupt stacks
    for mutate in (
        lambda d: d.update(kind="other_thing"),
        lambda d: d.update(schema=flameprof.CAPTURE_SCHEMA + 1),
        lambda d: d.update(stacks={"a;b": "three"}),
        lambda d: d.update(stacks=["a;b"]),
    ):
        bad = dict(doc)
        mutate(bad)
        p = str(tmp_path / "bad.json")
        with open(p, "w") as f:
            json.dump(bad, f)
        assert flameprof.load_capture(p) is None, bad
    assert flameprof.load_capture(str(tmp_path / "nope.json")) is None


def test_captures_for_filters_circuit_and_stage(tmp_path):
    _quick_capture(tmp_path, circuit="toy", stage="prove")
    _quick_capture(tmp_path, circuit="toy", stage="witness")
    _quick_capture(tmp_path, circuit="venmo", stage="prove")
    got = flameprof.captures_for("toy", out_dir=str(tmp_path))
    assert {d["stage"] for _, d in got} == {"prove", "witness"}
    got = flameprof.captures_for("toy", stage="prove", out_dir=str(tmp_path))
    assert len(got) == 1 and got[0][1]["circuit"] == "toy"
    assert flameprof.captures_for("revolut", out_dir=str(tmp_path)) == []


def test_collapsed_text_heaviest_first():
    txt = flameprof.collapsed_text({"a;b": 3, "a;c": 7, "z": 7})
    assert txt.splitlines() == ["a;c 7", "z 7", "a;b 3"]


# ------------------------------------------------------- CaptureController


def test_trigger_refused_when_gated_off(tmp_path, monkeypatch):
    monkeypatch.setenv("ZKP2P_MSM_PRECOMP_CACHE", str(tmp_path))
    ctl = flameprof.CaptureController()
    assert ctl.trigger("toy", "prove") is False  # ZKP2P_FLAME unset
    assert not ctl.active() and ctl.sweep_tick() is None


def test_controller_capture_after_n_sweeps_then_cooldown(tmp_path, monkeypatch):
    monkeypatch.setenv("ZKP2P_FLAME", "1")
    monkeypatch.setenv("ZKP2P_FLAME_HZ", "200")
    monkeypatch.setenv("ZKP2P_FLAME_CAPTURE_N", "2")
    monkeypatch.setenv("ZKP2P_FLAME_COOLDOWN_S", "60")
    monkeypatch.setenv("ZKP2P_MSM_PRECOMP_CACHE", str(tmp_path / "cache"))
    ctl = flameprof.CaptureController()
    c0 = _counter("zkp2p_flame_captures_total", trigger="overrun")
    assert ctl.trigger("toy", "prove", entry_digest="ed9",
                       budget_ms=225.0, over_ms=750.0) is True
    assert ctl.active()
    assert ctl.trigger("toy", "prove") is False  # one capture at a time
    assert ctl.sweep_tick() is None              # sweep 1 of 2
    path = ctl.sweep_tick()                      # sweep 2: capture lands
    assert path and os.path.exists(path)
    doc = flameprof.load_capture(path)
    assert doc["trigger"] == "overrun" and doc["entry_digest"] == "ed9"
    assert _counter("zkp2p_flame_captures_total", trigger="overrun") - c0 == 1
    ptr = ctl.pointer()
    assert ptr["file"] == os.path.basename(path) and ptr["stage"] == "prove"
    # cooling down: a fresh overrun within cooldown_s must not retrigger
    assert ctl.trigger("toy", "prove") is False
    # cooldown disabled: retrigger allowed immediately
    monkeypatch.setenv("ZKP2P_FLAME_COOLDOWN_S", "0")
    assert ctl.trigger("toy", "prove") is True
    ctl.reset()


# -------------------------------------------- end-to-end seeded regression

from zkp2p_tpu.native.lib import get_lib  # noqa: E402


@pytest.fixture(scope="module")
def world():
    from zkp2p_tpu.field.bn254 import R
    from zkp2p_tpu.prover.groth16_tpu import device_pk
    from zkp2p_tpu.snark.groth16 import setup
    from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem

    cs = ConstraintSystem("flame-prof")
    out = cs.new_public("out")
    x = cs.new_wire("x")
    y = cs.new_wire("y")
    z = cs.new_wire("z")
    cs.enforce(LC.of(x), LC.of(y), LC.of(z), "mul")
    cs.enforce(LC.of(z), LC.of(z), LC.of(out), "sq")
    cs.compute(z, lambda a, b: a * b % R, [x, y])
    pk, vk = setup(cs, seed="flame-prof")
    dpk = device_pk(pk, cs)

    def witness_fn(payload):
        xv, yv = int(payload["x"]), int(payload["y"])
        return cs.witness([pow(xv * yv, 2, R)], {x: xv, y: yv})

    return cs, dpk, vk, witness_fn


def _mk_service(world, circuit):
    from zkp2p_tpu.pipeline.service import ProvingService
    from zkp2p_tpu.prover.native_prove import prove_native

    cs, dpk, vk, witness_fn = world
    # batch_size=1: requests prove SEQUENTIALLY, so the first overrun's
    # trigger puts the remaining proves of the sweep under the sampler
    return ProvingService(
        cs, dpk, vk, witness_fn, public_fn=lambda w: [w[1]],
        prover_fn=lambda d, wits: [prove_native(d, w, r=1, s=2) for w in wits],
        batch_size=1, retry_backoff_s=0.0, circuit=circuit,
    )


def _write_reqs(spool, n):
    for i in range(n):
        with open(os.path.join(spool, f"r{i}.req.json"), "w") as f:
            json.dump({"x": 3 + i, "y": 5}, f)


def _seed_history(circuit="toy"):
    for _ in range(3):  # history: prove ~150ms -> budget 225ms
        pl.append_entry(pl.make_entry(
            "bench", circuit,
            {"prove": {"p50_ms": 150.0, "p95_ms": 160.0, "n": 4}},
            execution_digest="hist",
        ))


@pytest.mark.skipif(get_lib() is None, reason="native toolchain unavailable")
def test_overrun_produces_flame_capture_clean_sweep_none(
    world, tmp_path, monkeypatch
):
    """THE acceptance criterion: with the flame gate armed, a seeded
    `prove:hang` regression through a REAL service sweep trips the
    budget overrun AND produces a flame capture whose stacks carry
    synthetic native stage frames; an identical clean sweep under the
    same arm produces zero captures."""
    cache = str(tmp_path / "cache")
    monkeypatch.setenv("ZKP2P_MSM_PRECOMP_CACHE", cache)
    pl.reset()
    _seed_history()
    monkeypatch.setenv("ZKP2P_FLAME", "1")
    monkeypatch.setenv("ZKP2P_FLAME_HZ", "97")
    monkeypatch.setenv("ZKP2P_FLAME_CAPTURE_N", "1")
    monkeypatch.setenv("ZKP2P_FLAME_COOLDOWN_S", "0")

    def _captures():
        return sorted(glob.glob(os.path.join(cache, "flame_toy_*.json")))

    # clean sweep under the SAME arm: budgets load, nothing overruns,
    # and the sampler never starts — zero capture files
    spool = str(tmp_path / "clean")
    os.makedirs(spool)
    _write_reqs(spool, 2)
    svc = _mk_service(world, "toy")
    assert svc.process_dir(spool)["done"] == 2
    assert svc._perf_hb["overruns"] == 0
    assert _captures() == []
    assert "capture" not in (svc._perf_hb or {})

    # seeded regression: hang=0.6 pushes every prove span past 225ms;
    # the first overrun triggers, the rest of the sweep samples, the
    # end-of-sweep tick writes the capture
    monkeypatch.setenv("ZKP2P_FAULTS", "prove:hang=0.6")
    faults.reset()
    c0 = _counter("zkp2p_flame_captures_total", trigger="overrun")
    spool2 = str(tmp_path / "slow")
    os.makedirs(spool2)
    _write_reqs(spool2, 3)
    svc2 = _mk_service(world, "toy")
    assert svc2.process_dir(spool2)["done"] == 3
    assert svc2._perf_hb["overruns"] >= 1
    caps = _captures()
    assert len(caps) == 1, caps
    assert _counter("zkp2p_flame_captures_total", trigger="overrun") - c0 == 1
    doc = flameprof.load_capture(caps[0])
    assert doc is not None and doc["trigger"] == "overrun"
    assert doc["circuit"] == "toy" and doc["stage"] == "prove"
    assert doc["samples"] > 0
    # ledger cross-link: the capture names the head entry_digest the
    # tripped budget was derived from
    entries, _ = pl.load_entries()
    assert doc["entry_digest"] in {e["entry_digest"] for e in entries}
    assert doc["budget_ms"] == pytest.approx(225.0)
    assert doc["over_ms"] > doc["budget_ms"]
    # synthetic native attribution: the proves that ran under the
    # sampler moved the native counters, so the stacks carry
    # native:<stage> frames (bridge-parked or the [native] root)
    assert any("native:" in k for k in doc["stacks"]), doc["stacks"]
    assert sum(doc["native_ns"].values()) > 0
    # the capture pointer rides the heartbeat perf block -> fleet top
    ptr = svc2._perf_hb.get("capture")
    assert ptr and ptr["file"] == os.path.basename(caps[0])
    assert ptr["stage"] == "prove" and ptr["samples"] == doc["samples"]


@pytest.mark.skipif(get_lib() is None, reason="native toolchain unavailable")
def test_overrun_without_flame_gate_produces_no_capture(
    world, tmp_path, monkeypatch
):
    """The sentry still counts the overrun, but with ZKP2P_FLAME unset
    the sampler never starts and no capture file appears."""
    cache = str(tmp_path / "cache")
    monkeypatch.setenv("ZKP2P_MSM_PRECOMP_CACHE", cache)
    pl.reset()
    _seed_history()
    monkeypatch.setenv("ZKP2P_FAULTS", "prove:hang=0.4")
    faults.reset()
    spool = str(tmp_path / "spool")
    os.makedirs(spool)
    _write_reqs(spool, 1)
    svc = _mk_service(world, "toy")
    assert svc.process_dir(spool)["done"] == 1
    assert svc._perf_hb["overruns"] == 1
    assert glob.glob(os.path.join(cache, "flame_*.json")) == []
    assert "capture" not in svc._perf_hb


# ---------------------------------------------------------------- fleet top


def _top_body(w0_perf=None):
    w0 = {"state": "up", "pid": 1, "restarts": 0}
    if w0_perf is not None:
        w0["perf"] = w0_perf
    return {
        "ok": True, "fleet_id": "f1",
        "workers": {
            "w0": w0,
            "w1": {"state": "up", "pid": 2, "restarts": 0},
        },
    }


def test_render_top_no_flame_column_on_fresh_fleet():
    from zkp2p_tpu.pipeline.fleet_obs import render_top

    frame = render_top(_top_body({"overruns": 0, "checked": 4, "budgets": 1}))
    assert "flame" not in frame  # nobody captured: the PR-18 table, unchanged


def test_render_top_flame_column_shows_capture_pointer():
    from zkp2p_tpu.pipeline.fleet_obs import render_top

    cap = {"file": "flame_toy_prove_1754000000.json", "stage": "prove",
           "ts": 1754000000, "samples": 42}
    frame = render_top(_top_body(
        {"overruns": 7, "checked": 40, "budgets": 3, "capture": cap}
    ))
    lines = frame.splitlines()
    (head,) = [ln for ln in lines if "overrun" in ln]
    assert "flame" in head
    (w0,) = [ln for ln in lines if ln.strip().startswith("w0")]
    (w1,) = [ln for ln in lines if ln.strip().startswith("w1")]
    assert "flame_toy_prove_1754000000.json" in w0
    assert w1.split()[-1] == "-"  # no capture on w1 -> dash


# ------------------------------------------------- perf report cross-link


def test_perf_trendline_points_regression_to_capture(
    tmp_path, monkeypatch, capsys
):
    """`zkp2p-tpu perf`: a REGRESSED trendline with an overrun capture
    on disk prints the pointer underneath — DRIFT row -> why file."""
    from zkp2p_tpu.pipeline.cli import main

    cache = str(tmp_path / "cache")
    monkeypatch.setenv("ZKP2P_MSM_PRECOMP_CACHE", cache)
    pl.reset()
    _seed_history()
    pl.append_entry(pl.make_entry(  # head: 400ms > 225ms budget
        "bench", "toy", {"prove": {"p50_ms": 400.0, "p95_ms": 410.0, "n": 4}},
        execution_digest="hist",
    ))
    path = _quick_capture(tmp_path / "cache", trigger="overrun",
                          entry_digest="ed42")
    main(["perf"])
    out = capsys.readouterr().out
    (row,) = [ln for ln in out.splitlines() if ln.startswith("toy/prove")]
    assert "REGRESSED" in row
    assert f"capture: {path}" in out and "entry ed42" in out


# --------------------------------------------------- trace_report --flame


def _trace_report():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    return trace_report


def _handcraft_capture(tmp_path, stacks):
    """A deterministic capture file (a real sampler's stacks depend on
    scheduling) — only the fields the readers validate."""
    p = str(tmp_path / "flame_toy_prove_1754000000.json")
    with open(p, "w") as f:
        json.dump({
            "kind": "zkp2p_flame_capture", "schema": 1, "circuit": "toy",
            "stage": "prove", "trigger": "overrun", "hz": 47.0,
            "samples": sum(stacks.values()), "ts": 1754000000,
            "stacks": stacks,
        }, f)
    return p


def test_trace_report_flame_prints_collapsed_stacks(tmp_path, capsys):
    tr = _trace_report()
    p = _handcraft_capture(tmp_path, {"a;b": 3, "a;c": 1})
    assert tr.main(["--flame", p]) == 0
    out = capsys.readouterr().out
    assert out.splitlines()[0] == "a;b 3"
    assert "a;c 1" in out


def test_trace_report_refuses_invalid_capture(tmp_path, capsys):
    tr = _trace_report()
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        f.write('{"kind": "something_else"}')
    assert tr.main(["--flame", bad]) == 1
    assert "refusing" in capsys.readouterr().err


def test_trace_report_flame_chrome_trace_nests_slices(tmp_path, capsys):
    """--chrome-trace renders the stack trie as nested X slices on a
    dedicated flame pid: parents emitted before children (equal-ts
    nesting), siblings laid out left-to-right, width = samples."""
    tr = _trace_report()
    p = _handcraft_capture(tmp_path, {"a;b": 3, "a;c": 1})
    out_json = str(tmp_path / "trace.json")
    assert tr.main(["--flame", p, "--chrome-trace", out_json]) == 0
    with open(out_json) as f:
        ev = json.load(f)["traceEvents"]
    meta = [e for e in ev if e["ph"] == "M"]
    assert any("flame toy/prove" in e["args"]["name"] for e in meta)
    slices = [e for e in ev if e["ph"] == "X"]
    by_name = {e["name"]: e for e in slices}
    assert by_name["a"]["dur"] == 4000.0   # 4 samples x 1000 us
    assert by_name["b"]["dur"] == 3000.0
    assert by_name["c"]["ts"] == 3000.0    # sibling laid out after b
    # parent before child at the same ts: importers nest by order
    names = [e["name"] for e in slices]
    assert names.index("a") < names.index("b")
    assert all(e["pid"] == 990001 for e in slices)
