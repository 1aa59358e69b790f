"""The supervised proving fleet (pipeline.fleet + the service drain
path), tier-1 (`make fleet-smoke`):

  * drain semantics — the ISSUE-10 satellite contract: SIGTERM (or
    request_drain) mid-batch means in-flight requests reach `done`, no
    NEW claims after the flag, held claims never age into peer takeover
    during a bounded drain, and the exit code distinguishes a clean
    drain from timeout escalation;
  * supervisor mechanics — restart with backoff, crash-loop circuit
    breaker parks a flapping worker (fleet degrades to N−1), watchdog,
    drain escalation exit codes;
  * the 2-worker fleet smoke — toy workers, one SIGKILLed mid-prove,
    one SIGTERM-drained, the PR-7 global invariant green, `/status`
    reachable on both auto-bound metrics ports;
  * ONE cold build across N processes — the flock'd precomp/plan
    sidecars (two cold subprocesses sharing one key: per family exactly
    one `built`, the loser loads `cache` with precomp_build_ns == 0);
  * worker identity stamped on records/time-series and surfaced by the
    Chrome-trace export.

The N=3 chaos acceptance run (worker SIGKILL + worker SIGTERM drain +
supervisor kill/restart under seeded faults) and the `--fleet 2`
loadgen scaling arm are `slow`-marked — `ZKP2P_RUN_SLOW=1` runs them;
the tier-1 smoke here covers the same machinery at 2-worker scale.
"""

import importlib.util
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from zkp2p_tpu.native.lib import get_lib

pytestmark = pytest.mark.skipif(get_lib() is None, reason="native toolchain unavailable")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHAOS = os.path.join(REPO, "tools", "chaos.py")

slow = pytest.mark.skipif(
    not os.environ.get("ZKP2P_RUN_SLOW"), reason="slow; set ZKP2P_RUN_SLOW=1 to run"
)


def _chaos_mod():
    spec = importlib.util.spec_from_file_location("zkp2p_chaos_for_fleet", CHAOS)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write_reqs(spool, n, start=0):
    os.makedirs(spool, exist_ok=True)
    rids = []
    for i in range(start, start + n):
        rid = f"q{i:03d}"
        with open(os.path.join(spool, rid + ".req.json"), "w") as f:
            json.dump({"x": 3 + i, "y": 5 + i}, f)
        rids.append(rid)
    return rids


def _clean_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("ZKP2P_FAULTS", None)
    env.pop("ZKP2P_METRICS_SINK", None)
    return env


def _svc(batch_size=2, prover_fn=None, **kw):
    from zkp2p_tpu.pipeline.service import ProvingService
    from zkp2p_tpu.prover.native_prove import prove_native_batch

    chaos = _chaos_mod()
    cs, dpk, vk, witness_fn = chaos._build_world()
    return ProvingService(
        cs, dpk, vk, witness_fn, public_fn=lambda w: [w[1]],
        batch_size=batch_size, prover_fn=prover_fn or prove_native_batch, **kw
    ), chaos


# ------------------------------------------------------------- drain


def test_drain_mid_batch_finishes_in_flight_and_claims_nothing_new(tmp_path):
    """Drain flips mid-first-batch: every request claimed BEFORE the
    flag reaches `done`; everything unclaimed stays open with no claim
    file — free for a peer, not stranded."""
    from zkp2p_tpu.prover.native_prove import prove_native_batch

    spool = str(tmp_path / "spool")
    rids = _write_reqs(spool, 8)
    in_prove = threading.Event()
    svc_box = {}

    def prover(dpk, wits):
        in_prove.set()
        # hold the first batch until the drain flag is provably up, so
        # the producer's per-batch gate (not luck) stops the claims
        svc_box["svc"]._drain.wait(timeout=30)
        return prove_native_batch(dpk, wits)

    prover.reads_msm_knobs = False
    svc, _ = _svc(batch_size=2, prover_fn=prover)
    svc_box["svc"] = svc

    done = {}

    def sweep():
        done["stats"] = svc.process_dir(spool)

    t = threading.Thread(target=sweep)
    t.start()
    assert in_prove.wait(timeout=30)
    time.sleep(0.3)  # let the producer claim ahead (prefetch window)
    claimed = sorted(
        f[: -len(".claim")] for f in os.listdir(spool) if f.endswith(".claim")
    )
    assert claimed, "expected in-flight claims before the drain"
    svc.request_drain()
    t.join(timeout=60)
    assert not t.is_alive()
    # in-flight -> done; nothing else claimed or terminal'd
    for rid in claimed:
        assert os.path.exists(os.path.join(spool, rid + ".proof.json")), rid
    open_rids = [r for r in rids if r not in claimed]
    assert open_rids, "drain claimed the whole spool — the gate never engaged"
    for rid in open_rids:
        assert not os.path.exists(os.path.join(spool, rid + ".proof.json")), rid
        assert not os.path.exists(os.path.join(spool, rid + ".error.json")), rid
        assert not os.path.exists(os.path.join(spool, rid + ".claim")), rid
    assert done["stats"]["done"] == len(claimed)


def test_drain_before_sweep_claims_nothing(tmp_path):
    spool = str(tmp_path / "spool")
    _write_reqs(spool, 4)
    svc, _ = _svc()
    svc.request_drain()
    stats = svc.process_dir(spool)
    assert not any(stats.values())
    assert not [f for f in os.listdir(spool) if f.endswith(".claim")]


def test_drain_keeps_claims_fresh_no_takeover_window(tmp_path):
    """A bounded drain longer than stale_claim_s: the sweep heartbeat
    must keep held claims fresh the whole time, or a peer would steal
    mid-drain work and duplicate the proof."""
    from zkp2p_tpu.prover.native_prove import prove_native_batch

    spool = str(tmp_path / "spool")
    _write_reqs(spool, 2)
    stale_s = 1.0
    max_age = {"v": 0.0}
    stop = threading.Event()

    def prover(dpk, wits):
        time.sleep(2.5)  # drain takes 2.5x the staleness threshold
        return prove_native_batch(dpk, wits)

    prover.reads_msm_knobs = False
    svc, _ = _svc(batch_size=2, prover_fn=prover, stale_claim_s=stale_s)

    def sample_ages():
        while not stop.is_set():
            now = time.time()
            for f in os.listdir(spool):
                if f.endswith(".claim"):
                    try:
                        age = now - os.path.getmtime(os.path.join(spool, f))
                        max_age["v"] = max(max_age["v"], age)
                    except OSError:
                        pass
            time.sleep(0.05)

    sampler = threading.Thread(target=sample_ages)
    sampler.start()

    def sweep():
        svc.process_dir(spool)

    t = threading.Thread(target=sweep)
    t.start()
    # flip the drain once the batch is claimed (mid-prove)
    deadline = time.time() + 10
    while time.time() < deadline and not any(f.endswith(".claim") for f in os.listdir(spool)):
        time.sleep(0.02)
    svc.request_drain()
    t.join(timeout=60)
    stop.set()
    sampler.join()
    assert max_age["v"] < stale_s, f"claim aged {max_age['v']:.2f}s past the takeover threshold"
    assert all(
        os.path.exists(os.path.join(spool, f"q{i:03d}.proof.json")) for i in range(2)
    )


def test_run_returns_drained(tmp_path):
    spool = str(tmp_path / "spool")
    os.makedirs(spool)
    svc, _ = _svc()
    out = {}

    def runner():
        out["why"] = svc.run(spool, poll_s=0.05)

    t = threading.Thread(target=runner)
    t.start()
    time.sleep(0.4)
    svc.request_drain()
    t.join(timeout=30)
    assert out["why"] == "drained"


def test_worker_sigterm_clean_exit_code(tmp_path):
    """The subprocess signal wiring end to end: SIGTERM mid-prove →
    worker exits 0 (clean drain), everything it held at signal time is
    `done`, the rest of the spool is untouched."""
    spool = str(tmp_path / "spool")
    _write_reqs(spool, 10)
    proc = subprocess.Popen(
        [sys.executable, CHAOS, "--worker", "--spool", spool, "--batch", "2",
         "--prove-s", "0.8", "--max-seconds", "120", "--poll-s", "0.05"],
        env=_clean_env(), cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    claimed = []
    deadline = time.time() + 60
    while time.time() < deadline and not claimed:
        claimed = sorted(
            f[: -len(".claim")] for f in os.listdir(spool) if f.endswith(".claim")
        )
        time.sleep(0.02)
    assert claimed, "worker never claimed anything"
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=120)
    assert proc.returncode == 0, out
    for rid in claimed:
        assert os.path.exists(os.path.join(spool, rid + ".proof.json")), (rid, out)
    proofs = [f for f in os.listdir(spool) if f.endswith(".proof.json")]
    assert len(proofs) < 10, "drain proved the whole spool — SIGTERM landed too late to test anything"


# -------------------------------------------------------- supervisor


def _supervisor(spool, cmd, **kw):
    from zkp2p_tpu.pipeline.fleet import FleetSupervisor

    kw.setdefault("log", lambda m: None)
    return FleetSupervisor(str(spool), cmd, **kw)


def test_breaker_parks_crash_looping_worker(tmp_path):
    sup = _supervisor(
        tmp_path, lambda wid: [sys.executable, "-c", "import sys; sys.exit(1)"],
        workers=1, breaker_k=2, breaker_window_s=30.0, restart_backoff_s=0.05,
    )
    rc = sup.run(poll_s=0.05, max_seconds=15, install_signals=False)
    assert rc == 4  # every worker parked = the fleet is dead
    slot = sup.slots["w0"]
    assert slot.state == "parked"
    assert slot.restarts == 1  # K=2: first crash restarts, second parks


def test_drain_escalation_exit_code(tmp_path):
    code = "import signal, time; signal.signal(signal.SIGTERM, signal.SIG_IGN); time.sleep(60)"
    sup = _supervisor(
        tmp_path, lambda wid: [sys.executable, "-c", code],
        workers=1, drain_timeout_s=1.0,
    )
    threading.Timer(0.8, sup.stop).start()
    rc = sup.run(poll_s=0.05, max_seconds=30, install_signals=False)
    assert rc == 3  # drain timed out -> SIGKILL escalation
    assert sup.escalations == 1


def test_sigkilled_worker_restarts_with_backoff(tmp_path):
    sup = _supervisor(
        tmp_path, lambda wid: [sys.executable, "-c", "import time; time.sleep(60)"],
        workers=1, restart_backoff_s=0.05, breaker_k=5,
    )
    out = {}
    t = threading.Thread(
        target=lambda: out.update(rc=sup.run(poll_s=0.05, max_seconds=60, install_signals=False))
    )
    t.start()
    deadline = time.time() + 20
    while time.time() < deadline and sup.slots["w0"].proc is None:
        time.sleep(0.02)
    first_pid = sup.slots["w0"].proc.pid
    os.kill(first_pid, signal.SIGKILL)
    while time.time() < deadline and sup.slots["w0"].restarts < 1:
        time.sleep(0.02)
    assert sup.slots["w0"].restarts == 1
    # wait for the replacement to be up, then stop cleanly
    while time.time() < deadline and (
        sup.slots["w0"].proc is None or sup.slots["w0"].proc.pid == first_pid
    ):
        time.sleep(0.02)
    sup.stop()
    t.join(timeout=30)
    assert out["rc"] == 0  # replacement drained cleanly (plain sleeper dies on SIGTERM)
    assert sup.slots["w0"].state != "parked"


def test_governor_soft_then_hard(tmp_path):
    """Supervisor-side RSS governor: a 1 MiB soft budget (any python
    process exceeds it) writes the degrade ctl; a 1 MiB hard budget
    drains + restarts WITHOUT a breaker penalty."""
    sleeper = lambda wid: [sys.executable, "-c", "import time; time.sleep(60)"]  # noqa: E731
    sup = _supervisor(tmp_path, sleeper, workers=1, rss_soft_mb=1, rss_hard_mb=0)
    sup.start()
    deadline = time.time() + 15
    ctl = os.path.join(sup.fleet_dir, "w0.ctl")
    while time.time() < deadline and not os.path.exists(ctl):
        sup.tick()
        time.sleep(0.05)
    assert os.path.exists(ctl)
    with open(ctl) as f:
        assert json.load(f)["degrade"] == 1
    assert sup.drain(timeout_s=10)

    sup2 = _supervisor(tmp_path / "h", sleeper, workers=1, rss_soft_mb=0, rss_hard_mb=1,
                       drain_timeout_s=5.0, restart_backoff_s=0.05)
    sup2.start()
    deadline = time.time() + 20
    while time.time() < deadline and sup2.slots["w0"].restarts < 1:
        sup2.tick()
        time.sleep(0.05)
    slot = sup2.slots["w0"]
    assert slot.restarts >= 1, "hard governor never recycled the worker"
    assert not slot.failures, "a governor restart must not count toward the circuit breaker"
    sup2.drain(timeout_s=10)


def test_watchdog_kills_hung_worker_after_first_heartbeat(tmp_path):
    """Liveness begins at the FIRST heartbeat (a cold start that has
    not beaten yet is never killed — real workers spend minutes in
    pre-run() setup); after it, a live pid with a stale heartbeat is
    hung and gets SIGKILLed."""
    code = (
        "import json, os, time\n"
        "d = os.environ['ZKP2P_FLEET_DIR']; w = os.environ['ZKP2P_WORKER_ID']\n"
        "json.dump({'pid': os.getpid(), 'ts': time.time()}, open(os.path.join(d, w + '.hb'), 'w'))\n"
        "time.sleep(120)\n"  # one beat, then silence = hung
    )
    sup = _supervisor(
        tmp_path, lambda wid: [sys.executable, "-c", code],
        workers=1, liveness_s=2.0, breaker_k=1, restart_backoff_s=0.05,
    )
    rc = sup.run(poll_s=0.1, max_seconds=30, install_signals=False)
    assert sup.watchdog_kills >= 1, "stale-heartbeat worker was never killed"
    assert rc == 4 and sup.slots["w0"].state == "parked"  # breaker_k=1: one kill parks it


def test_worker_side_soft_degrade(tmp_path, monkeypatch):
    """Worker side of the governor: a degrade ctl halves the batch
    columns and gates the precomp arm off (idempotently)."""
    from zkp2p_tpu.pipeline import fleet

    monkeypatch.setenv("ZKP2P_MSM_PRECOMP", "1")
    svc, _ = _svc(batch_size=4)
    svc._worker_id, svc._fleet_id = "w9", "ftest"
    fleet_dir = str(tmp_path / "fdir")
    os.makedirs(fleet_dir)
    fleet.worker_tick(svc, fleet_dir)
    hb_path = os.path.join(fleet_dir, "w9.hb")
    with open(hb_path) as f:
        hb = json.load(f)
    assert hb["worker"] == "w9" and hb["state"] == "up" and hb["degraded"] is False
    with open(os.path.join(fleet_dir, "w9.ctl"), "w") as f:
        json.dump({"degrade": 1}, f)
    fleet.worker_tick(svc, fleet_dir)
    assert svc.batch_size == 2
    assert os.environ["ZKP2P_MSM_PRECOMP"] == "0"
    fleet.worker_tick(svc, fleet_dir)  # idempotent: no second halving
    assert svc.batch_size == 2
    with open(hb_path) as f:
        assert json.load(f)["degraded"] is True


# -------------------------------------------------- fleet smoke (tier-1)


def test_fleet_smoke_kill_drain_invariant_and_status(tmp_path):
    """The `make fleet-smoke` acceptance: a 2-worker toy fleet under
    the in-process supervisor — `/status` answers 200 on BOTH workers'
    auto-bound metrics ports mid-run, one worker is SIGKILLed while it
    provably owns a claim (the supervisor restarts it), the other is
    SIGTERM-drained (its held claims terminal `done`), and the PR-7
    global invariant holds over the spool."""
    chaos = _chaos_mod()
    spool = str(tmp_path / "spool")
    _write_reqs(spool, 10)
    worker_cmd = lambda wid: [  # noqa: E731
        sys.executable, CHAOS, "--worker", "--spool", spool, "--batch", "2",
        "--prove-s", "0.5", "--stale-claim-s", "3", "--max-seconds", "120",
        "--poll-s", "0.05",
    ]
    sup = _supervisor(
        spool, worker_cmd, workers=2, restart_backoff_s=0.1,
        drain_timeout_s=20.0, fleet_dir=str(tmp_path / "fleet"),
        worker_env={**_clean_env(), "ZKP2P_METRICS_PORT": "auto"},
        log=lambda m: print(f"[sup] {m}", flush=True),
    )
    out = {}
    t = threading.Thread(
        target=lambda: out.update(rc=sup.run(poll_s=0.05, max_seconds=180, install_signals=False))
    )
    t.start()
    try:
        # both workers up with heartbeats + bound ports
        deadline = time.time() + 90
        ports = {}
        while time.time() < deadline and len(ports) < 2:
            for wid in ("w0", "w1"):
                hb = sup._hb(sup.slots[wid])
                if hb and hb.get("port"):
                    ports[wid] = hb["port"]
            time.sleep(0.05)
        assert len(ports) == 2, f"workers never published ports: {ports}"
        for wid, port in ports.items():
            body = json.loads(
                urllib.request.urlopen(f"http://127.0.0.1:{port}/status", timeout=5).read()
            )
            assert body["ok"] is True, (wid, body)
            assert "slo" in body

        def claim_owner(exclude):
            while time.time() < deadline:
                pids = {
                    s.proc.pid for s in sup.slots.values()
                    if s.proc is not None and s.proc.poll() is None
                } - exclude
                for fn in os.listdir(spool):
                    if fn.endswith(".claim"):
                        try:
                            with open(os.path.join(spool, fn)) as f:
                                pid = json.load(f).get("pid")
                        except (OSError, ValueError):
                            continue
                        if pid in pids:
                            rids = []
                            for g in os.listdir(spool):
                                if g.endswith(".claim"):
                                    try:
                                        with open(os.path.join(spool, g)) as f:
                                            if json.load(f).get("pid") == pid:
                                                rids.append(g[: -len(".claim")])
                                    except (OSError, ValueError):
                                        pass
                            return pid, sorted(rids)
                time.sleep(0.02)
            return None, []

        victim, _ = claim_owner(set())
        assert victim is not None, "no worker ever owned a live claim"
        os.kill(victim, signal.SIGKILL)
        drained, drained_claims = claim_owner({victim})
        assert drained is not None, "no second claim owner to drain"
        os.kill(drained, signal.SIGTERM)
    finally:
        t.join(timeout=240)
    assert not t.is_alive()
    assert out.get("rc") == 0, f"supervisor rc {out.get('rc')}"
    # the SIGKILL was restarted (not parked), the drain was counted done
    assert any(s.restarts >= 1 for s in sup.slots.values())
    assert all(s.state == "done" for s in sup.slots.values())
    # drained worker's held claims: terminal done, not deferred/stolen
    for rid in drained_claims:
        assert os.path.exists(os.path.join(spool, rid + ".proof.json")), rid
    report = chaos.check_invariants(spool)
    assert report["violations"] == [], report
    assert report["states"].get("open", 0) == 0
    # fleet status file named both workers and their scrape ports
    with open(os.path.join(sup.fleet_dir, "status.json")) as f:
        status = json.load(f)
    assert set(status["workers"]) == {"w0", "w1"}


# ------------------------------------------- one cold build per key


_BUILD_SCRIPT = r"""
import json, os, sys, time
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
import importlib.util
spec = importlib.util.spec_from_file_location("zc", {chaos!r})
zc = importlib.util.module_from_spec(spec); spec.loader.exec_module(zc)
cs, dpk, vk, witness_fn = zc._build_world()
from zkp2p_tpu.native.lib import stats_reset, stats_snapshot
from zkp2p_tpu.prover.precomp import precomputed_for
from zkp2p_tpu.prover.matvec_plan import plans_for
ready, go = sys.argv[1], sys.argv[2]
open(ready, "w").write("1")
while not os.path.exists(go):
    time.sleep(0.005)
stats_reset()
pk = precomputed_for(dpk)
plans = plans_for(dpk)
print(json.dumps({{
    "table_sources": {{f: t.source for f, t in pk.families.items()}},
    "plan_sources": {{m: p.source for m, p in plans.items()}},
    "build_ns": stats_snapshot()["precomp_build_ns"],
}}))
"""


def test_one_cold_build_across_two_processes(tmp_path):
    """The flock satellite: two cold processes resolving tables+plans
    for the SAME key concurrently perform exactly ONE build per family
    — the loser blocks on the sidecar lock, then loads the winner's
    atomic-renamed artifact (source == "cache", precomp_build_ns == 0
    when it built nothing at all)."""
    cache = str(tmp_path / "cache")
    script = _BUILD_SCRIPT.format(repo=REPO, chaos=CHAOS)
    env = _clean_env()
    env["ZKP2P_MSM_PRECOMP_CACHE"] = cache
    env["ZKP2P_MSM_PRECOMP_PERSIST_MIN"] = "1"
    go = str(tmp_path / "go")
    procs, readies = [], []
    for i in range(2):
        ready = str(tmp_path / f"ready{i}")
        readies.append(ready)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", script, ready, go],
            env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        ))
    # barrier: release both only when both are warmed up and waiting
    deadline = time.time() + 120
    while time.time() < deadline and not all(os.path.exists(r) for r in readies):
        time.sleep(0.05)
    assert all(os.path.exists(r) for r in readies), "subprocesses never became ready"
    with open(go, "w") as f:
        f.write("1")
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=120)
        assert p.returncode == 0, err
        outs.append(json.loads(out.strip().splitlines()[-1]))
    a, b = outs
    # per family: exactly one builder, the other a cache load
    for fam in a["table_sources"]:
        pair = sorted([a["table_sources"][fam], b["table_sources"][fam]])
        assert pair == ["built", "cache"], (fam, a, b)
    for mat in a["plan_sources"]:
        pair = sorted([a["plan_sources"][mat], b["plan_sources"][mat]])
        assert pair == ["built", "cache"], (mat, a, b)
    # the build counter tells the same story: an all-cache process ran
    # ZERO native table builds
    for o in outs:
        if all(v == "cache" for v in o["table_sources"].values()):
            assert o["build_ns"] == 0, o


# ------------------------------------------ identity + auto ports


def test_auto_port_binds_and_lands_in_manifest():
    from zkp2p_tpu.utils import metrics as M

    srv = M.maybe_start_metrics_server(port=0)
    try:
        assert srv is not None
        port = M.bound_metrics_port()
        assert isinstance(port, int) and port > 0
        body = urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz", timeout=5).read()
        assert json.loads(body)["ok"] is True
        assert M.run_manifest().get("metrics_port_bound") == port
    finally:
        M.stop_metrics_server()
    assert M.bound_metrics_port() is None


def test_worker_identity_on_records_timeseries_and_chrome_trace(tmp_path, monkeypatch):
    monkeypatch.setenv("ZKP2P_WORKER_ID", "w7")
    monkeypatch.setenv("ZKP2P_FLEET_ID", "fleet42")
    spool = str(tmp_path / "spool")
    _write_reqs(spool, 2)
    svc, _ = _svc(batch_size=2)
    stats = svc.process_dir(spool)
    assert stats["done"] == 2
    from zkp2p_tpu.pipeline.service import TimeseriesSampler

    sampler = TimeseriesSampler(interval_s=1000.0)
    ts_rec = sampler.maybe_sample(spool, svc._sink(spool), force=True)
    assert ts_rec["worker"] == "w7" and ts_rec["fleet"] == "fleet42"
    sink = spool.rstrip("/") + ".metrics.jsonl"
    reqs = []
    with open(sink) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("type") == "request":
                reqs.append(rec)
    assert reqs and all(r["worker"] == "w7" and r["fleet"] == "fleet42" for r in reqs)
    # chrome-trace rows are named by WORKER, not just pid
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    trace = trace_report.chrome_trace(reqs)
    names = [
        e["args"]["name"] for e in trace["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "process_name"
    ]
    assert names and all("w7" in n and "fleet42" in n for n in names)


# --------------------------------------------------- slow acceptance


@slow
def test_fleet_chaos_acceptance_n3(tmp_path):
    """The ISSUE-10 acceptance run at full scale: N=3 supervised
    workers, seeded faults armed, one worker SIGKILLed mid-prove, one
    worker SIGTERM-drained, the supervisor SIGKILLed and replaced —
    global invariant green and the drained worker's in-flight requests
    terminal `done`."""
    spool = str(tmp_path / "spool")
    report_path = str(tmp_path / "report.json")
    proc = subprocess.run(
        [sys.executable, CHAOS, "--fleet", "3", "--spool", spool,
         "--requests", "12", "--batch", "2", "--prove-s", "0.6",
         "--stale-claim-s", "3", "--max-seconds", "150", "--report", report_path],
        env=_clean_env(), cwd=REPO, capture_output=True, text=True, timeout=420,
    )
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"
    with open(report_path) as f:
        report = json.load(f)
    assert report["violations"] == []
    assert report["killed_worker"] and report["drained_worker"]
    assert report["drained_claims"], "the drained worker held nothing — not the acceptance shape"
    assert report["supervisor_rcs"][0] == -9 and report["supervisor_rcs"][-1] == 0
    assert report["states"].get("open", 0) == 0


@slow
def test_loadgen_fleet_scales_qps(tmp_path):
    """`tools/loadgen.py --fleet 2` sustains ≥1.8× the single-worker
    throughput under the same objective: both arms are offered the same
    over-capacity rate (sleep-dominated toy prover, so capacity is
    batch/prove_s per worker) and the fleet completes ≥1.8× as many."""

    def run(n_fleet, spool):
        out = str(tmp_path / f"cap{n_fleet}.json")
        env = _clean_env()
        # one native thread per worker — the N-workers-per-host shape
        # (ROADMAP item 2: "the C pool's width caps make this safe");
        # unpinned, two workers' pools oversubscribe the 2-core box and
        # the measured scaling is contention, not the serving layer
        env["ZKP2P_NATIVE_THREADS"] = "1"
        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "loadgen.py"),
             "--spool", spool, "--fleet", str(n_fleet), "--circuit", "toy",
             # one far-over-capacity step, per-REQUEST 1.5 s artificial
             # prove (sleep-dominated — a stand-in for real device
             # proves, which overlap perfectly across workers; the
             # python pairing verify, which DOES contend on 2 cores, is
             # amortized over batch 8).  Both arms saturate, so the
             # done-by-cutoff count IS the QPS each deployment
             # sustained under the objective's scoring window — the
             # small-n SLO-boundary framing is unusable at toy scale
             # (single-server queueing + a 0.95 target over <20
             # requests flips on one late arrival).
             "--rates", "4", "--step-s", "15", "--drain-s", "10",
             "--objective-s", "5", "--batch", "8", "--prove-s", "1.5",
             "--out", out],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=420,
        )
        assert p.returncode == 0, p.stderr
        with open(out) as f:
            return json.load(f)

    single = run(1, str(tmp_path / "s1"))
    fleet = run(2, str(tmp_path / "s2"))
    assert fleet["fleet_workers"] == 2 and single["fleet_workers"] == 1
    # the acceptance ratio on served-under-cutoff throughput: the fleet
    # sustains >=1.8x the single worker at the same objective/cutoff
    done1 = single["steps"][0]["done"]
    done2 = fleet["steps"][0]["done"]
    assert done1 >= 5, (done1, "single worker barely served — host too slow for the shape")
    assert done2 >= 1.8 * done1, (done1, done2)
