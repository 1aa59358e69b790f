"""The supervised proving fleet: N worker processes on one spool.

ROADMAP item 2's missing half: PR 7 made a *single* worker fault-
tolerant (rescue ladder, claims, takeover) and PR 8 made it observable
(SLO, waterfalls, loadgen) — but one `zkp2p-tpu service` process was
still the whole deployment.  A SIGTERM stranded claims until the
stale-claim timeout, a crash-looping worker restarted forever by hand,
and two workers cold-starting on one host each ran the multi-minute
precomp build.  This module is the serving layer SZKP/ZKProphet-style
accelerator provers assume: a supervisor that keeps the device fed
through worker crashes, restarts, and drains.

Topology (docs/ROBUSTNESS.md §fleet has the state machine):

  supervisor (this module, `zkp2p-tpu fleet`)
    ├─ spawns N workers (`zkp2p-tpu service` with ZKP2P_WORKER_ID /
    │  ZKP2P_FLEET_ID / ZKP2P_FLEET_DIR stamped into the env; any argv
    │  via `worker_cmd` — the chaos harness runs toy workers)
    ├─ liveness: per-worker heartbeat files (written each sweep by
    │  `worker_tick`) + process exit codes; a live pid with a stale
    │  heartbeat is HUNG and gets SIGKILL + restart
    ├─ restart policy: exponential backoff per consecutive failure,
    │  crash-loop circuit breaker — K failures inside W seconds PARKS
    │  the worker (counter + log line; the fleet degrades to N−1
    │  instead of flapping)
    ├─ graceful drain: SIGTERM fans out, each worker stops claiming,
    │  finishes in-flight batches, flushes sinks, exits 0; stragglers
    │  past ZKP2P_DRAIN_TIMEOUT_S are escalated to SIGKILL (counted —
    │  a clean fleet restart loses zero requests)
    └─ resource governor: per-worker RSS sampled from /proc; over the
       SOFT budget the worker is told (ctl file) to drop the precomp
       arm + shrink batch columns; over the HARD budget it is drained
       and restarted — OOM becomes a counted, recoverable event.

Worker↔supervisor plumbing is files in `fleet_dir` (default
`<spool>/.fleet/`), same crash-only philosophy as the spool itself:

  <wid>.hb    heartbeat, atomically replaced once per sweep:
              {pid, ts, worker, fleet, state, port, rss_mb, degraded}
              — `port` is the worker's BOUND metrics port (auto-port
              mode), so scrapes stay discoverable across a fleet
  <wid>.ctl   supervisor → worker control: {"degrade": 1} applies the
              soft-governor overlay at the worker's next sweep
  status.json supervisor state, atomically replaced per tick — the
              fleet's one-stop answer to "what is running where"

The supervisor holds no request state at all: killing it mid-run loses
nothing (workers keep sweeping; claims arbitrate), and a restarted
supervisor simply spawns fresh workers onto the same spool — the chaos
harness (`tools/chaos.py --fleet`) SIGKILLs it mid-prove to prove that.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

# ---------------------------------------------------------------------------
# Worker-side: drain signals, heartbeat, governor compliance.  These run
# inside the service process (hooked from ProvingService.run) — keep the
# imports lazy so a solo service without a fleet pays nothing.


def install_drain_handlers(svc) -> bool:
    """SIGTERM/SIGINT → svc.request_drain(): stop claiming, finish
    in-flight batches, flush, exit run() with status "drained".  A
    SECOND signal while already draining restores the default action
    and re-delivers itself — a worker wedged mid-drain (the hang class
    the fleet watchdog SIGKILLs, but a solo service has no supervisor)
    must stay killable by a repeated Ctrl-C / SIGTERM, not only by
    kill -9.  Main thread only (CPython restriction) — returns False
    elsewhere instead of raising, so library users can call it
    unconditionally."""

    def _handler(signum, _frame):
        if svc.draining:
            print(f"[service] signal {signum} again while draining: exiting NOW", flush=True)
            signal.signal(signum, signal.SIG_DFL)
            os.kill(os.getpid(), signum)
            return
        print(f"[service] signal {signum}: draining (finish in-flight, claim nothing)", flush=True)
        svc.request_drain()

    try:
        signal.signal(signal.SIGTERM, _handler)
        signal.signal(signal.SIGINT, _handler)
        return True
    except ValueError:  # not the main thread
        return False


def slowed_prover(inner, per_request_s: float, batch_overhead_s: float = 0.0):
    """Wrap a batch prover with artificial service time — THE one
    service-time model the toy capacity arms share (loadgen in-process
    AND the chaos/fleet workers), so their QPS numbers stay comparable
    by construction: `batch_overhead_s + per_request_s * fill` per
    prover call.  The per-BATCH overhead term models the real
    amortization curve's fixed cost (base sweep setup, dispatch) so
    scheduler A/Bs have a curve to sit on; 0 (the default) keeps the
    PR-8 purely-linear model.  Keeps the `reads_msm_knobs` marker: the
    degradation ladder gates on it."""
    if per_request_s <= 0 and batch_overhead_s <= 0:
        return inner

    def slowed(dpk, wits):
        time.sleep(batch_overhead_s + per_request_s * max(1, len(wits)))
        return inner(dpk, wits)

    slowed.reads_msm_knobs = getattr(inner, "reads_msm_knobs", False)
    return slowed


def _rss_mb(pid: int) -> Optional[float]:
    """Resident-set size of `pid` in MiB from /proc (None off-Linux or
    when the process is gone — the caller treats None as 'no sample',
    never as zero)."""
    try:
        with open(f"/proc/{pid}/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) / 1024.0
    except (OSError, ValueError, IndexError):
        pass
    return None


def apply_soft_degrade(svc) -> None:
    """The worker-side SOFT governor action (idempotent): gate the
    fixed-base precomp arm off via its PR-7 overlay (the knob is
    fresh-read per prove), drop the memoized tables — they are the
    gigabytes — and halve the batch columns.  Proof bytes are
    knob-invariant, so degraded proofs still byte-match the fast path;
    the arm lands in the execution digest via the fleet_governor gate,
    so a degraded run is provably not comparable to a clean one."""
    if getattr(svc, "_fleet_degraded", False):
        return
    from ..prover import precomp
    from ..utils.audit import record_arm
    from ..utils.metrics import REGISTRY

    os.environ["ZKP2P_MSM_PRECOMP"] = "0"
    try:
        precomp.reset()  # free resident tables (refcounts keep any in-flight prove safe)
    except Exception:  # noqa: BLE001 — degrade must never crash the worker
        pass
    svc.batch_size = max(1, svc.batch_size // 2)
    svc._fleet_degraded = True
    REGISTRY.counter("zkp2p_fleet_degrade_applied_total").inc()
    record_arm("fleet_governor", "soft-applied")
    print(
        f"[service] governor: soft degrade applied (precomp off, batch={svc.batch_size})",
        flush=True,
    )


def _atomic_write_json(path: str, obj: Dict) -> None:
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(obj, f)
        os.replace(tmp, path)
    except OSError:
        try:
            if os.path.exists(tmp):
                os.remove(tmp)
        except OSError:
            pass


def _write_heartbeat(svc, fleet_dir: str, state: Optional[str] = None) -> None:
    from ..utils.metrics import bound_metrics_port

    wid = getattr(svc, "_worker_id", "") or f"pid{os.getpid()}"
    os.makedirs(fleet_dir, exist_ok=True)
    hb = {
        "pid": os.getpid(),
        "ts": round(time.time(), 3),
        "worker": wid,
        "fleet": getattr(svc, "_fleet_id", ""),
        "state": state or ("draining" if svc.draining else "up"),
        "port": bound_metrics_port(),
        "rss_mb": _rss_mb(os.getpid()),
        "degraded": bool(getattr(svc, "_fleet_degraded", False)),
    }
    # worker tier advertisement (ZKP2P_WORKER_TIER): peers read this
    # from the heartbeat to route lanes — a sharded-tier peer takes the
    # bulk lane, native peers keep interactive (pipeline.sched).  Fresh
    # read + record_arm so the gate digest tracks what was advertised.
    try:
        from .sched import worker_tier_arm

        hb["tier"] = worker_tier_arm()
    except Exception:  # noqa: BLE001 — the heartbeat must always land
        hb["tier"] = "native"
    # the worker's last scheduler decision (pipeline.sched block:
    # mode, batch target, lane depths) — surfaces in fleet /status
    # and `zkp2p-tpu top` without another scrape route
    sched_hb = getattr(svc, "_sched_hb", None)
    if sched_hb:
        hb["sched"] = dict(sched_hb)
    # serialized SLO window (capped — the heartbeat is written every
    # ~5 s): the fleet plane's FALLBACK merge source when the worker's
    # /snapshot scrape fails (port not yet bound, worker mid-restart),
    # so a scrape gap degrades fleet attainment to slightly-stale
    # instead of punching a worker-sized hole in it
    try:
        from ..utils.slo import default_tracker

        hb["slo_window"] = default_tracker().window_state(max_samples=512)
    except Exception:  # noqa: BLE001 — the heartbeat must always land
        pass
    _atomic_write_json(os.path.join(fleet_dir, wid + ".hb"), hb)


def start_heartbeat_thread(svc, fleet_dir: str, interval_s: float = 5.0) -> threading.Event:
    """Background liveness heartbeat for a fleet worker, BETWEEN sweep
    ticks: a single sweep can legitimately run for minutes (the cold
    precomp build — and flock losers block for the winner's whole
    build), which a sweep-cadence heartbeat alone would render
    indistinguishable from a hang, so the default 60 s watchdog would
    SIGKILL a healthy cold-starting worker mid-build forever.  Long
    native calls release the GIL, so this thread keeps beating through
    them; a worker wedged holding the GIL (or deadlocked in Python)
    stops beating — exactly the distinction the watchdog needs.
    Returns the stop Event."""
    stop = threading.Event()

    def beat():
        while not stop.wait(interval_s):
            try:
                _write_heartbeat(svc, fleet_dir)
            except Exception:  # noqa: BLE001 — liveness must never crash the worker
                pass

    threading.Thread(target=beat, daemon=True, name="zkp2p-fleet-hb").start()
    return stop


def worker_tick(svc, fleet_dir: str, state: Optional[str] = None) -> None:
    """One per-sweep fleet tick inside a worker: write the heartbeat
    (liveness + bound metrics port + RSS) and apply any governor ctl.
    Failures degrade silently — fleet plumbing must never stop a sweep
    (the supervisor's watchdog covers a worker whose disk is so broken
    heartbeats stop landing).  Governor ctl is applied HERE only, never
    from the heartbeat thread — mutating batch_size mid-sweep would
    race the producer."""
    _write_heartbeat(svc, fleet_dir, state=state)
    wid = getattr(svc, "_worker_id", "") or f"pid{os.getpid()}"
    ctl_path = os.path.join(fleet_dir, wid + ".ctl")
    if not getattr(svc, "_fleet_degraded", False) and os.path.exists(ctl_path):
        try:
            with open(ctl_path) as f:
                ctl = json.load(f)
        except (OSError, ValueError):
            ctl = {}
        if ctl.get("degrade"):
            apply_soft_degrade(svc)


# ---------------------------------------------------------------------------
# Audit gates: fleet membership and the governor budgets are code-path
# arms (a degraded fleet run must never share a digest with a clean
# solo run) — registered like slo_arm/timeseries_arm.


def fleet_member_arm() -> str:
    """record_arm the fleet-membership gate: "worker" when a supervisor
    stamped ZKP2P_WORKER_ID into this process's env, else "off"."""
    from ..utils.audit import record_arm
    from ..utils.config import load_config

    return record_arm("service_fleet", "worker" if load_config().worker_id else "off")


def governor_arm() -> str:
    """record_arm the resource-governor budgets: "off" or
    "soft=<mb>mb,hard=<mb>mb"."""
    from ..utils.audit import record_arm
    from ..utils.config import load_config

    cfg = load_config()
    arm = (
        "off"
        if not (cfg.rss_soft_mb or cfg.rss_hard_mb)
        else f"soft={cfg.rss_soft_mb}mb,hard={cfg.rss_hard_mb}mb"
    )
    return record_arm("fleet_governor", arm)


# ---------------------------------------------------------------------------
# Supervisor.


@dataclass
class WorkerSlot:
    """One worker's supervisor-side state.

    States: starting → up → (done | backoff → up | parked |
    draining → done).  `done` = exited rc 0 (a deliberate exit: drained,
    or the spool went terminal) — never restarted.  `parked` = the
    crash-loop breaker tripped — never restarted; the fleet runs N−1.
    """

    wid: str
    proc: Optional[subprocess.Popen] = None
    state: str = "starting"
    started_at: float = 0.0
    restarts: int = 0
    last_rc: Optional[int] = None
    failures: List[float] = field(default_factory=list)  # failure timestamps (breaker window)
    consec_failures: int = 0
    backoff_until: float = 0.0
    soft_signalled: bool = False
    governor_deadline: float = 0.0  # hard-governor drain escalation deadline (0 = none)
    governor_restart: bool = False  # next exit is a governor restart, not a crash
    # autoscale scale-down: the worker was SIGTERM'd to leave the fleet
    # (graceful drain — zero lost requests); its exit is final whatever
    # the rc, and a drain overrunning scale_deadline escalates like the
    # fleet drain does
    retiring: bool = False
    scale_deadline: float = 0.0


class FleetSupervisor:
    """Spawn and keep healthy N workers on one spool.  `worker_cmd`
    maps a worker id to its argv; the supervisor adds ZKP2P_WORKER_ID /
    ZKP2P_FLEET_ID / ZKP2P_FLEET_DIR (+ `worker_env`) to each child's
    environment.  Policy args default from the typed config
    (ZKP2P_DRAIN_TIMEOUT_S, ZKP2P_BREAKER_K/WINDOW_S,
    ZKP2P_RESTART_BACKOFF_S, ZKP2P_RSS_SOFT_MB/HARD_MB)."""

    def __init__(
        self,
        spool: str,
        worker_cmd: Callable[[str], List[str]],
        workers: Optional[int] = None,
        fleet_dir: Optional[str] = None,
        worker_env: Optional[Dict[str, str]] = None,
        drain_timeout_s: Optional[float] = None,
        breaker_k: Optional[int] = None,
        breaker_window_s: Optional[float] = None,
        restart_backoff_s: Optional[float] = None,
        rss_soft_mb: Optional[int] = None,
        rss_hard_mb: Optional[int] = None,
        liveness_s: float = 60.0,
        fleet_metrics_port: Optional[int] = None,
        workers_min: Optional[int] = None,
        workers_max: Optional[int] = None,
        scale_up_s: Optional[float] = None,
        scale_down_s: Optional[float] = None,
        log: Callable[[str], None] = lambda m: print(f"[fleet] {m}", flush=True),
    ):
        from ..utils.audit import record_arm
        from ..utils.config import load_config

        cfg = load_config()
        self.spool = spool
        self.worker_cmd = worker_cmd
        self.n = workers if workers is not None else cfg.fleet_workers
        self.fleet_dir = fleet_dir or os.path.join(spool, ".fleet")
        self.worker_env = dict(worker_env or {})
        self.drain_timeout_s = (
            drain_timeout_s if drain_timeout_s is not None else cfg.drain_timeout_s
        )
        self.breaker_k = breaker_k if breaker_k is not None else cfg.breaker_k
        self.breaker_window_s = (
            breaker_window_s if breaker_window_s is not None else cfg.breaker_window_s
        )
        self.restart_backoff_s = (
            restart_backoff_s if restart_backoff_s is not None else cfg.restart_backoff_s
        )
        self.rss_soft_mb = rss_soft_mb if rss_soft_mb is not None else cfg.rss_soft_mb
        self.rss_hard_mb = rss_hard_mb if rss_hard_mb is not None else cfg.rss_hard_mb
        self.liveness_s = liveness_s
        self.log = log
        self.fleet_id = cfg.fleet_id or uuid.uuid4().hex[:8]
        self.slots: Dict[str, WorkerSlot] = {f"w{i}": WorkerSlot(wid=f"w{i}") for i in range(self.n)}
        self.escalations = 0
        self.watchdog_kills = 0
        self._stop = threading.Event()
        self._draining = False
        os.makedirs(self.fleet_dir, exist_ok=True)
        # fleet observability plane (pipeline.fleet_obs): scrape +
        # merge + alert + serve, when ZKP2P_FLEET_METRICS_PORT (or the
        # ctor arg) configures a port.  None = plane off — the PR-10
        # per-worker ephemeral-port behavior, unchanged.
        self.fleet_metrics_port = (
            fleet_metrics_port if fleet_metrics_port is not None else cfg.fleet_metrics_port
        )
        self.plane = None
        # fleet autoscaling (pipeline.sched.AutoscalePolicy; ROADMAP
        # item 2): live workers move inside [workers_min, workers_max]
        # on the plane's merged backlog trend + burn rate, with
        # hysteresis windows scale_up_s/scale_down_s.  workers_max == 0
        # (the default) = off, exactly the PR-10 static fleet.
        self.workers_min = workers_min if workers_min is not None else cfg.workers_min
        self.workers_max = workers_max if workers_max is not None else cfg.workers_max
        self.scale_up_s = scale_up_s if scale_up_s is not None else cfg.scale_up_s
        self.scale_down_s = scale_down_s if scale_down_s is not None else cfg.scale_down_s
        self.autoscale = self.workers_max > 0
        self._autoscaler = None
        self._scale_events: List[Dict] = []
        self._next_widx = self.n
        if self.autoscale:
            from .sched import AutoscalePolicy

            self.workers_min = max(1, self.workers_min)
            self.workers_max = max(self.workers_min, self.workers_max)
            # start inside the band: --workers seeds, the bounds clamp
            if self.n < self.workers_min or self.n > self.workers_max:
                was = self.n
                self.n = min(max(self.n, self.workers_min), self.workers_max)
                log(f"autoscale: initial workers {was} clamped to {self.n} "
                    f"(band [{self.workers_min}, {self.workers_max}])")
                self.slots = {f"w{i}": WorkerSlot(wid=f"w{i}") for i in range(self.n)}
                self._next_widx = self.n
            self._autoscaler = AutoscalePolicy(
                self.workers_min, self.workers_max,
                scale_up_s=self.scale_up_s, scale_down_s=self.scale_down_s,
                burn_threshold=cfg.alert_burn_rate,
            )
            # the policy consumes the plane's merged signals — without
            # an endpoint the plane never runs, so autoscale implies an
            # (ephemeral, if unconfigured) plane port
            if self.fleet_metrics_port is None:
                self.fleet_metrics_port = 0
                log("autoscale needs the fleet plane: enabling an ephemeral fleet metrics port")
        record_arm("service_fleet", f"supervisor:{self.n}")
        governor_arm()
        # host profile: arm the gate once at supervisor startup and say
        # which way it went — workers inherit the same .bench_cache, so
        # one line here covers the whole fleet's tuning provenance
        from ..utils.hostprof import profile_arm

        log(f"host profile: {profile_arm()}")

    # ------------------------------------------------------------ spawn

    def _spawn(self, slot: WorkerSlot) -> None:
        env = dict(os.environ)
        env.update(self.worker_env)
        env["ZKP2P_WORKER_ID"] = slot.wid
        env["ZKP2P_FLEET_ID"] = self.fleet_id
        env["ZKP2P_FLEET_DIR"] = self.fleet_dir
        # the fleet plane needs scrape targets: when it is on, workers
        # get auto-bound exposition even if the operator configured none
        # (the plane without per-worker /snapshot endpoints would be an
        # aggregator of nothing).  Parse-checked, not setdefault: an
        # explicitly EMPTY ZKP2P_METRICS_PORT also means exposition off,
        # and leaving it would strand /status at 503 for the whole run.
        if self.fleet_metrics_port is not None:
            from ..utils.config import _opt_port

            if _opt_port(env.get("ZKP2P_METRICS_PORT") or "") is None:
                env["ZKP2P_METRICS_PORT"] = "auto"
        # N workers cannot share one fixed metrics port: force auto-bind
        # for the children whenever exposition is on at all (the bound
        # port comes back via the heartbeat + run manifest)
        if env.get("ZKP2P_METRICS_PORT") not in (None, "", "auto", "0"):
            self.log(
                f"{slot.wid}: rewriting ZKP2P_METRICS_PORT="
                f"{env['ZKP2P_METRICS_PORT']!r} to 'auto' (fixed ports collide across workers)"
            )
            env["ZKP2P_METRICS_PORT"] = "auto"
        # a fresh spawn must not inherit the previous incarnation's ctl
        # OR heartbeat: a stale .hb would satisfy readiness gates (the
        # loadgen --fleet warm-up wait) and backdate the watchdog clock
        # before the new process ever runs
        for suffix in (".ctl", ".hb"):
            try:
                os.unlink(os.path.join(self.fleet_dir, slot.wid + suffix))
            except OSError:
                pass
        slot.proc = subprocess.Popen(self.worker_cmd(slot.wid), env=env)
        slot.state = "up"
        slot.started_at = time.time()
        slot.soft_signalled = False
        slot.governor_deadline = 0.0
        self.log(f"{slot.wid}: up (pid {slot.proc.pid})")

    def start(self) -> None:
        if self.fleet_metrics_port is not None and self.plane is None:
            from .fleet_obs import FleetPlane

            self.plane = FleetPlane(self, port=self.fleet_metrics_port, log=self.log)
            self.plane.start()
        for slot in self.slots.values():
            self._spawn(slot)

    # ------------------------------------------------------------- tick

    def _hb(self, slot: WorkerSlot) -> Optional[Dict]:
        try:
            with open(os.path.join(self.fleet_dir, slot.wid + ".hb")) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def _hb_age_s(self, slot: WorkerSlot) -> Optional[float]:
        try:
            return time.time() - os.path.getmtime(os.path.join(self.fleet_dir, slot.wid + ".hb"))
        except OSError:
            return None

    def _on_failure(self, slot: WorkerSlot, now: float, why: str) -> None:
        """Crashed/hung worker: count toward the circuit breaker, park
        or schedule a backoff restart."""
        from ..utils.metrics import REGISTRY

        slot.failures.append(now)
        slot.failures = [t for t in slot.failures if now - t <= self.breaker_window_s]
        # a crash after a healthy run longer than the breaker window is
        # a FRESH failure, not the next rung of a crash loop — without
        # this, rare unrelated crashes days apart compound the backoff
        # to its 30 s cap forever
        if slot.started_at and now - slot.started_at > self.breaker_window_s:
            slot.consec_failures = 0
        slot.consec_failures += 1
        if len(slot.failures) >= self.breaker_k:
            slot.state = "parked"
            REGISTRY.counter("zkp2p_fleet_parked_total").inc()
            self.log(
                f"{slot.wid}: PARKED by circuit breaker ({len(slot.failures)} failures "
                f"inside {self.breaker_window_s:g}s; {why}) — fleet degrades to "
                f"{sum(1 for s in self.slots.values() if s.state in ('up', 'backoff', 'starting'))} workers"
            )
            return
        delay = min(self.restart_backoff_s * (2 ** (slot.consec_failures - 1)), 30.0)
        slot.backoff_until = now + delay
        slot.state = "backoff"
        self.log(f"{slot.wid}: {why}; restart in {delay:.2f}s (failure {len(slot.failures)}/{self.breaker_k})")

    def _governor(self, slot: WorkerSlot, now: float) -> None:
        from ..utils.metrics import REGISTRY

        if not (self.rss_soft_mb or self.rss_hard_mb) or slot.proc is None:
            return
        rss = _rss_mb(slot.proc.pid)
        if rss is None:
            return
        REGISTRY.gauge("zkp2p_fleet_worker_rss_bytes", {"worker": slot.wid}).set(rss * 1048576)
        if self.rss_hard_mb and rss > self.rss_hard_mb and not slot.governor_deadline:
            # HARD: drain + restart.  The drain (not SIGKILL) lets the
            # worker terminal its in-flight batch first; the deadline
            # below escalates if even draining cannot finish.
            REGISTRY.counter("zkp2p_fleet_governor_hard_total", {"worker": slot.wid}).inc()
            self.log(
                f"{slot.wid}: RSS {rss:.0f} MiB over hard budget {self.rss_hard_mb} MiB — "
                "draining for restart"
            )
            try:
                slot.proc.send_signal(signal.SIGTERM)
            except OSError:
                pass
            slot.governor_deadline = now + (self.drain_timeout_s or 10.0)
            slot.governor_restart = True
        elif (
            self.rss_soft_mb
            and rss > self.rss_soft_mb
            and not slot.soft_signalled
            and not slot.governor_deadline
        ):
            # SOFT: tell the worker to shed memory (drop precomp arm,
            # shrink batch columns) via its ctl file
            REGISTRY.counter("zkp2p_fleet_governor_soft_total", {"worker": slot.wid}).inc()
            self.log(
                f"{slot.wid}: RSS {rss:.0f} MiB over soft budget {self.rss_soft_mb} MiB — "
                "writing degrade ctl"
            )
            _atomic_write_json(
                os.path.join(self.fleet_dir, slot.wid + ".ctl"), {"degrade": 1, "ts": now}
            )
            slot.soft_signalled = True

    def tick(self) -> None:
        """One supervisor pass: reap exits, restart/park, watchdog hung
        workers, run the governor, publish gauges + status.json."""
        from ..utils.metrics import REGISTRY

        now = time.time()
        for slot in self.slots.values():
            if slot.state in ("parked", "done"):
                continue
            if slot.state == "backoff":
                if now >= slot.backoff_until and not self._draining:
                    slot.restarts += 1
                    REGISTRY.counter("zkp2p_fleet_restarts_total", {"worker": slot.wid}).inc()
                    self._spawn(slot)
                continue
            if slot.proc is None:
                continue
            rc = slot.proc.poll()
            if rc is not None:
                slot.last_rc = rc
                if self._draining:
                    # during a fleet drain any exit is final
                    slot.state = "done"
                elif slot.retiring:
                    # autoscale scale-down: the exit we asked for — the
                    # worker drained its claims and left; final whatever
                    # the rc (a SIGKILL-escalated straggler's claims go
                    # stale and peers take them over — zero lost)
                    slot.state = "done"
                    slot.retiring = False
                    slot.scale_deadline = 0.0
                    self.log(f"{slot.wid}: scaled down (rc={rc})")
                elif slot.governor_restart:
                    # governor-requested recycle (hard RSS): immediate,
                    # no breaker penalty — OOM pressure is recoverable,
                    # not a crash loop.  Checked BEFORE the rc==0
                    # branch: a well-behaved worker drains CLEANLY on
                    # the governor's SIGTERM, and treating that rc 0 as
                    # "chose to leave" would silently shrink the fleet
                    # to N−1 on every hard-budget event.
                    slot.governor_restart = False
                    slot.governor_deadline = 0.0
                    slot.restarts += 1
                    REGISTRY.counter("zkp2p_fleet_restarts_total", {"worker": slot.wid}).inc()
                    self._spawn(slot)
                elif rc == 0:
                    # deliberate exit: drained, or the spool went
                    # terminal.  Never restarted — a worker that chose
                    # to leave is not a crash.
                    slot.state = "done"
                    slot.consec_failures = 0
                    self.log(f"{slot.wid}: exited cleanly")
                else:
                    self._on_failure(slot, now, f"exited rc={rc}")
                continue
            # alive: scale-down escalation, hard-governor escalation,
            # watchdog, governor
            if slot.retiring:
                if slot.scale_deadline and now > slot.scale_deadline:
                    self.log(f"{slot.wid}: scale-down drain timed out — SIGKILL")
                    self.escalations += 1
                    REGISTRY.counter("zkp2p_fleet_drain_escalations_total").inc()
                    try:
                        slot.proc.kill()
                    except OSError:
                        pass
                    slot.scale_deadline = 0.0
                continue  # a retiring worker is leaving: no watchdog/governor
            if slot.governor_deadline and now > slot.governor_deadline:
                self.log(f"{slot.wid}: governor drain timed out — SIGKILL")
                self.escalations += 1
                REGISTRY.counter("zkp2p_fleet_drain_escalations_total").inc()
                try:
                    slot.proc.kill()
                except OSError:
                    pass
                slot.governor_deadline = 0.0
                continue
            # Liveness begins at the FIRST heartbeat (the k8s
            # startup-vs-liveness probe distinction): a real service
            # worker spends minutes in pre-run() setup (circuit build,
            # zkey load, device_pk) before any heartbeat can land, and
            # killing on spawn-relative age would SIGKILL every healthy
            # cold start forever.  After the first beat, a live pid
            # whose heartbeat goes stale is HUNG (wedged holding the
            # GIL, deadlock — long native calls release the GIL, so the
            # background beat survives them).  SIGKILL — a SIGTERM
            # would need the very Python loop that stopped running.
            hb_age = self._hb_age_s(slot)
            grace = max(self.liveness_s, 2.0)
            if hb_age is not None and hb_age > grace and slot.started_at < now - hb_age:
                self.watchdog_kills += 1
                REGISTRY.counter("zkp2p_fleet_watchdog_kills_total").inc()
                self.log(f"{slot.wid}: heartbeat stale ({hb_age:.1f}s) with a live pid — watchdog SIGKILL")
                try:
                    slot.proc.kill()
                except OSError:
                    pass
                continue
            self._governor(slot, now)
        self._autoscale_tick(now)
        # fleet-level gauges + the status file
        counts: Dict[str, int] = {}
        for slot in self.slots.values():
            counts[slot.state] = counts.get(slot.state, 0) + 1
        for state in ("up", "backoff", "parked", "done", "starting", "retiring"):
            REGISTRY.gauge("zkp2p_fleet_workers", {"state": state}).set(counts.get(state, 0))
        self._write_status(now)

    # -------------------------------------------------------- autoscale

    def _live_workers(self) -> List[WorkerSlot]:
        """Slots currently serving (or about to): up/starting/backoff
        and not leaving — the count the autoscale band governs.
        Snapshot (list) because scale-up mutates `slots` while the
        plane's scrape thread and /status handlers also iterate it."""
        return [
            s for s in list(self.slots.values())
            if s.state in ("up", "starting", "backoff") and not s.retiring
        ]

    def _autoscale_tick(self, now: float) -> None:
        """One autoscale evaluation: feed the plane's merged signals
        (backlog trend, burn rates — nothing a single worker can see)
        through the hysteresis policy; apply at most one step.  Scale
        up = spawn a FRESH slot (ids never recycle — wN stays unique in
        records across the run); scale down = graceful drain of the
        newest live worker (SIGTERM → finishes in-flight claims, exits
        0; zero lost, zero duplicated — the PR-10 drain contract)."""
        if self._autoscaler is None or self._draining or self.plane is None:
            return
        from ..utils.metrics import REGISTRY

        signals = self.plane.last_signals()
        if signals is None:
            return
        live = self._live_workers()
        REGISTRY.gauge("zkp2p_fleet_workers_target").set(len(live))
        decision = self._autoscaler.update(now, len(live), signals)
        if decision is None:
            return
        if decision["direction"] == "up":
            wid = f"w{self._next_widx}"
            self._next_widx += 1
            slot = self.slots[wid] = WorkerSlot(wid=wid)
            self._spawn(slot)
            n_after = len(live) + 1
        else:
            # newest-first shrink: the highest-index live "up" worker —
            # the longest-lived keep their warm caches.  The floor
            # bounds RUNNING workers: slots in backoff/starting count
            # as live for the policy, but draining the only "up" worker
            # while its peers wait out a backoff would leave the spool
            # unserved below workers_min
            candidates = [s for s in live if s.state == "up" and s.proc is not None
                          and not s.governor_deadline]
            if not candidates or len(candidates) - 1 < self.workers_min:
                return
            victim = max(candidates, key=lambda s: int(s.wid[1:]) if s.wid[1:].isdigit() else 0)
            try:
                victim.proc.send_signal(signal.SIGTERM)
            except OSError:
                return
            victim.state = "retiring"
            victim.retiring = True
            victim.scale_deadline = now + (self.drain_timeout_s or 10.0)
            wid = victim.wid
            n_after = len(live) - 1
        REGISTRY.counter(
            "zkp2p_sched_decisions_total", {"kind": f"scale_{decision['direction']}"}
        ).inc()
        REGISTRY.gauge("zkp2p_fleet_workers_target").set(n_after)
        event = {
            "ts": round(now, 3), "direction": decision["direction"],
            "reason": decision["reason"], "worker": wid, "workers": n_after,
        }
        self._scale_events.append(event)
        self.log(
            f"autoscale: {decision['direction']} ({decision['reason']}) — "
            f"{wid}, fleet now targets {n_after} worker(s) "
            f"in [{self.workers_min}, {self.workers_max}]"
        )

    def status(self) -> Dict:
        workers = {}
        # list(): status() runs on plane HTTP-handler and scrape
        # threads while the autoscaler inserts slots from the tick
        for slot in list(self.slots.values()):
            hb = self._hb(slot) or {}
            workers[slot.wid] = {
                "pid": slot.proc.pid if slot.proc is not None else None,
                "state": slot.state,
                "restarts": slot.restarts,
                "last_rc": slot.last_rc,
                # the worker's BOUND metrics port (auto mode) — the
                # scrape-discovery contract: `/status` and `/metrics`
                # are reachable at 127.0.0.1:<port> per worker
                "port": hb.get("port"),
                "rss_mb": hb.get("rss_mb"),
                "hb_age_s": round(self._hb_age_s(slot), 3) if self._hb_age_s(slot) is not None else None,
                "hb_state": hb.get("state"),
                "degraded": hb.get("degraded", False),
            }
            # the worker's last scheduler decision (batch target, lane
            # depths) — rides the heartbeat, rendered by `zkp2p-tpu top`
            if hb.get("sched"):
                workers[slot.wid]["sched"] = hb["sched"]
        sched_block: Dict = {"autoscale": self.autoscale}
        if self.autoscale:
            sched_block.update({
                "workers_min": self.workers_min,
                "workers_max": self.workers_max,
                "workers_live": len(self._live_workers()),
                "scale_events": len(self._scale_events),
                "last_scale": self._scale_events[-1] if self._scale_events else None,
                # the full event history (newest 50 — a flapping-free
                # policy makes more an impossibility, but bound the
                # status payload anyway): the auditable record of every
                # grow/shrink this run took, in status.json and the
                # loadgen capacity JSON
                "events": list(self._scale_events[-50:]),
            })
        return {
            "type": "fleet_status",
            "fleet_id": self.fleet_id,
            "ts": round(time.time(), 3),
            "pid": os.getpid(),
            "spool": self.spool,
            "workers": workers,
            "sched": sched_block,
            "drain_timeout_s": self.drain_timeout_s,
            "escalations": self.escalations,
            "watchdog_kills": self.watchdog_kills,
            "draining": self._draining,
        }

    def _write_status(self, _now: float) -> None:
        # with the plane on, status.json is the FULL service-health view
        # (merged SLO, active alerts, scrape health, the plane's bound
        # port for endpoint discovery) — the same payload /status serves
        if self.plane is not None:
            try:
                status = self.plane.status_payload()
            except Exception:  # noqa: BLE001 — status must always land
                status = self.status()
        else:
            status = self.status()
        _atomic_write_json(os.path.join(self.fleet_dir, "status.json"), status)

    # ------------------------------------------------------------ drain

    def drain(self, timeout_s: Optional[float] = None) -> bool:
        """Propagate SIGTERM to every live worker and wait (bounded) for
        clean exits; stragglers are escalated to SIGKILL.  Returns True
        when every worker drained cleanly (no escalation) — the fleet
        exit-code contract: 0 = clean drain, 3 = escalation needed."""
        from ..utils.metrics import REGISTRY

        timeout = timeout_s if timeout_s is not None else self.drain_timeout_s
        self._draining = True
        live = [s for s in self.slots.values() if s.proc is not None and s.proc.poll() is None]
        for slot in live:
            # a retiring worker already got its SIGTERM — a second one
            # while it drains means "exit NOW" (install_drain_handlers'
            # stay-killable contract) and would strand its claims
            if not slot.retiring:
                slot.state = "draining"
                try:
                    slot.proc.send_signal(signal.SIGTERM)
                except OSError:
                    pass
        self.log(f"draining {len(live)} worker(s), timeout {timeout:g}s")
        deadline = time.time() + max(timeout, 0.0)
        clean = True
        for slot in live:
            remaining = deadline - time.time()
            try:
                slot.proc.wait(timeout=max(remaining, 0.05))
                slot.last_rc = slot.proc.returncode
                slot.state = "done"
            except subprocess.TimeoutExpired:
                clean = False
                self.escalations += 1
                REGISTRY.counter("zkp2p_fleet_drain_escalations_total").inc()
                self.log(f"{slot.wid}: drain timed out — SIGKILL")
                try:
                    slot.proc.kill()
                    slot.proc.wait(timeout=10)
                except (OSError, subprocess.TimeoutExpired):
                    pass
                slot.last_rc = slot.proc.returncode
                slot.state = "done"
        self._write_status(time.time())
        return clean

    def stop(self) -> None:
        """Ask run() to drain and exit (signal handlers / tests)."""
        self._stop.set()

    # -------------------------------------------------------------- run

    def run(
        self,
        poll_s: float = 0.25,
        max_seconds: Optional[float] = None,
        install_signals: bool = True,
    ) -> int:
        """Supervise until every worker is done/parked, a signal (or
        stop()) asks for a drain, or max_seconds expires (the fleet is
        then drained).  Exit codes: 0 = clean (drain clean or all
        workers exited cleanly), 3 = drain escalated to SIGKILL,
        4 = every worker parked (the fleet is dead — page someone)."""
        if install_signals:
            def _handler(signum, _frame):
                self.log(f"signal {signum}: draining the fleet")
                self._stop.set()

            try:
                signal.signal(signal.SIGTERM, _handler)
                signal.signal(signal.SIGINT, _handler)
            except ValueError:
                pass  # not the main thread (tests drive stop() directly)
        self.start()
        deadline = (time.time() + max_seconds) if max_seconds else None
        clean = True
        while not self._stop.is_set():
            self.tick()
            states = {s.state for s in self.slots.values()}
            if states <= {"done", "parked"}:
                break
            if deadline is not None and time.time() > deadline:
                self.log("max-seconds expired: draining")
                break
            self._stop.wait(poll_s)
        clean = self.drain()
        self.tick()
        if self.plane is not None:
            # final view into status.json (alert history survives the
            # exit — a storm that fired mid-run is still on record),
            # then stop the scrape thread and release the port
            try:
                self.plane.scrape_once()
            except Exception:  # noqa: BLE001
                pass
            self._write_status(time.time())
            self.plane.stop()
        parked = sum(1 for s in self.slots.values() if s.state == "parked")
        if parked:
            self.log(f"{parked} worker(s) parked by the circuit breaker")
        if parked == len(self.slots):
            return 4
        # exit 3 only when the FINAL drain escalated (requests may have
        # been stranded mid-prove).  Mid-run hard-governor escalations
        # that were recovered by a restart stay counted (the gauge/
        # counter + status.json) but do not fail an otherwise clean
        # shutdown — "counted, recoverable" is the governor's contract.
        if not clean:
            return 3
        return 0
