"""Process-wide metrics registry for the proving stack.

The reference's observability is `console.time("zk-gen")` and a UI
stopwatch (SURVEY.md §5); a proving *service* needs attributable
numbers: counters/gauges/histograms that every layer (bench, native
prover, device prover, pipeline service) publishes into, a run manifest
(host facts + knob states + run_id) that makes each dump self-
describing, a rotating JSONL sink for offline aggregation
(tools/trace_report.py), and Prometheus text exposition behind
ZKP2P_METRICS_PORT (default off).

Design constraints:
  - zero hard dependencies (stdlib + the already-present numpy-free
    paths): importable everywhere trace.py is;
  - instruments are cheap under the GIL (plain attribute updates; the
    registry lock is only taken on get-or-create);
  - histograms are FIXED-BUCKET and mergeable, so per-process snapshots
    can be combined across service workers without raw-sample transfer.
"""

from __future__ import annotations

import bisect
import json
import os
import threading
import time
import uuid
from typing import Dict, List, Optional, Tuple

# Log-spaced millisecond buckets covering one MSM chunk (~1 ms) up to a
# cold full-size prove (~minutes).  Upper bounds; +Inf is implicit.
DEFAULT_MS_BUCKETS: Tuple[float, ...] = (
    1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000, 2500, 5000, 10000, 30000, 60000, 180000,
)

_LabelKey = Tuple[Tuple[str, str], ...]

# `# HELP` text per metric family for the Prometheus exposition (0.0.4
# requires one HELP/TYPE block per family; an unknown family gets a
# generic pointer at the docs).  Kept HERE — beside the exposition —
# rather than at the scattered call sites, so the scrape never emits a
# family without its block.
METRIC_HELP: Dict[str, str] = {
    "zkp2p_stage_ms": "Stage latency histogram fed by every trace() span",
    "zkp2p_proves_total": "Proofs produced, by prover backend",
    "zkp2p_service_requests_total": "Terminal request transitions, by state (docs/ROBUSTNESS.md state machine)",
    "zkp2p_service_retries_total": "Transient failures retried or deferred instead of terminal-ed",
    "zkp2p_service_bisections_total": "Batch proves split in half to isolate a poisoned request",
    "zkp2p_service_degraded_total": "Proves rescued by the degradation ladder, by rung",
    "zkp2p_service_deadline_total": "Requests terminal-ed error-deadline-exceeded",
    "zkp2p_service_shed_total": "Requests shed by the spool admission cap",
    "zkp2p_service_emit_failures_total": "Proof-emit failures (transient ones defer the request)",
    "zkp2p_service_deferred_total": "Non-terminal sweep outcomes: claim released for a later sweep to retry",
    "zkp2p_service_takeovers_total": "Stale-claim steal attempts, by result (won|lost)",
    "zkp2p_service_claim_lost_total": "Claim attempts that found a peer's fresh claim file",
    "zkp2p_replica_batches_total": "Batches a replica of a set took into its prover, by replica",
    "zkp2p_replicas_live": "Replicas of this process's set whose run loop is up",
    "zkp2p_service_batch_fill": "Live requests per batch handed to the prover (fill vs batch_size)",
    "zkp2p_service_backlog": "Open spool requests at the last time-series sample",
    "zkp2p_service_in_flight": "Open spool requests under a fresh claim at the last time-series sample",
    "zkp2p_slo_attainment": "Fraction of rolling-window requests meeting the SLO (1.0 on an empty window)",
    "zkp2p_slo_burn_rate": "(1-attainment)/(1-target): error-budget burn multiple; 1.0 = at target",
    "zkp2p_slo_window_p95_s": "Exact p95 request latency (arrival->terminal) over the rolling window",
    "zkp2p_slo_window_requests": "Requests in the rolling SLO window",
    "zkp2p_slo_objective_s": "Configured p95 latency objective (ZKP2P_SLO_P95_S; 0 = none)",
    "zkp2p_trace_dropped_total": "Trace ring-buffer overflow evictions",
    "zkp2p_path_taken_total": "Gate consultations by resolved arm (execution audit)",
    "zkp2p_compile_events_total": "XLA/jit compiles attributed to the triggering trace stage",
    "zkp2p_compile_seconds_total": "XLA/jit compile seconds attributed to the triggering trace stage",
    "zkp2p_hbm_bytes_in_use": "Live device memory per device",
    "zkp2p_hbm_peak_bytes": "Process-lifetime device memory high-water mark per device",
    "zkp2p_hbm_bytes_limit": "Device memory capacity per device",
    "zkp2p_hbm_stage_peak_bytes": "Max-semantics per-stage device memory peak",
    "zkp2p_precomp_table_bytes": "Resident fixed-base table bytes per G1 family",
    "zkp2p_precomp_total_bytes": "Resident fixed-base table bytes, all families",
    "zkp2p_fleet_workers": "Fleet worker slots by state (up|backoff|parked|done) at the last supervisor tick",
    "zkp2p_fleet_restarts_total": "Worker restarts performed by the fleet supervisor, by worker",
    "zkp2p_fleet_parked_total": "Workers parked by the crash-loop circuit breaker",
    "zkp2p_fleet_drain_escalations_total": "Drains that exceeded ZKP2P_DRAIN_TIMEOUT_S and were escalated to SIGKILL",
    "zkp2p_fleet_governor_soft_total": "Soft RSS-budget breaches (degradation ctl written), by worker",
    "zkp2p_fleet_governor_hard_total": "Hard RSS-budget breaches (worker drained + restarted), by worker",
    "zkp2p_fleet_worker_rss_bytes": "Per-worker resident-set size at the last governor sample",
    "zkp2p_fleet_watchdog_kills_total": "Hung workers (stale heartbeat, live pid) killed by the supervisor watchdog",
    "zkp2p_fleet_degrade_applied_total": "Governor soft-degrade overlays applied inside a worker",
    "zkp2p_fleet_scrapes_total": "Fleet-plane scrape cycles completed by the supervisor",
    "zkp2p_fleet_scrape_failures_total": "Worker snapshot scrapes that failed (counted, never fatal), by worker",
    "zkp2p_fleet_merge_refusals_total": "Metric families refused during fleet merge (histogram bucket-layout mismatch), by family",
    "zkp2p_fleet_alerts_total": "Alert FIRE transitions by rule (hysteresis: one inc per episode, not per flap)",
    "zkp2p_fleet_slo_attainment": "Merged-window fleet SLO attainment (pooled worker samples)",
    "zkp2p_fleet_slo_burn_fast": "Fleet error-budget burn over the trailing fast window (merged samples)",
    "zkp2p_fleet_slo_burn_slow": "Fleet error-budget burn over the full merged window",
    "zkp2p_fleet_slo_window_p95_s": "Exact p95 over the pooled fleet SLO window",
    "zkp2p_fleet_slo_window_requests": "Samples across every worker's SLO window (sum of window sizes)",
    "zkp2p_fleet_slo_objective_s": "Configured p95 objective the fleet windows are judged against",
    "zkp2p_fleet_backlog": "Open spool requests at the last supervisor scrape (supervisor's own scan)",
    "zkp2p_sched_batch_size": "Adaptive controller's bulk-lane batch target at the last sweep plan",
    "zkp2p_sched_decisions_total": "Scheduler decisions by kind (batch|shed|lane|scale_up|scale_down)",
    "zkp2p_fleet_workers_target": "Autoscaler's live-worker target after the last evaluation",
}


def _label_key(labels: Optional[Dict[str, str]]) -> _LabelKey:
    if not labels:
        return ()
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


class Counter:
    """Monotonic counter.  inc() is a plain float add — atomic enough
    under the GIL for the per-stage/per-request rates this tracks."""

    kind = "counter"
    __slots__ = ("name", "labels", "value")

    def __init__(self, name: str, labels: _LabelKey = ()):  # noqa: D401
        self.name = name
        self.labels = labels
        self.value = 0.0

    def inc(self, v: float = 1.0) -> None:
        self.value += v

    def state(self) -> Dict:
        return {"value": self.value}

    def merge_state(self, st: Dict) -> None:
        self.value += st["value"]


class Gauge:
    """Last-write-wins instantaneous value (pool depth, knob arm...)."""

    kind = "gauge"
    __slots__ = ("name", "labels", "value")

    def __init__(self, name: str, labels: _LabelKey = ()):  # noqa: D401
        self.name = name
        self.labels = labels
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)

    def state(self) -> Dict:
        return {"value": self.value}

    def merge_state(self, st: Dict) -> None:
        # merging gauges across processes keeps the max (peak semantics —
        # the depth/arm gauges this registry uses are all peak-or-flag)
        self.value = max(self.value, st["value"])


class Histogram:
    """Fixed-bucket histogram: counts per upper bound (+Inf last), sum,
    count, max.  Mergeable ONLY across identical bucket layouts — the
    point of fixing the layout process-wide."""

    kind = "histogram"
    __slots__ = ("name", "labels", "buckets", "counts", "sum", "count", "max")

    def __init__(self, name: str, labels: _LabelKey = (), buckets: Optional[Tuple[float, ...]] = None):
        self.name = name
        self.labels = labels
        self.buckets = tuple(buckets) if buckets else DEFAULT_MS_BUCKETS
        self.counts = [0] * (len(self.buckets) + 1)  # last = +Inf
        self.sum = 0.0
        self.count = 0
        self.max = 0.0

    def observe(self, v: float) -> None:
        self.counts[bisect.bisect_left(self.buckets, v)] += 1
        self.sum += v
        self.count += 1
        if v > self.max:
            self.max = v

    def quantile(self, q: float) -> float:
        """Bucket-resolution quantile estimate (upper bound of the bucket
        holding the q-th sample) — for quick in-process reads; exact
        percentiles come from the raw JSONL records via trace_report."""
        if not self.count:
            return 0.0
        target = q * self.count
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if seen >= target:
                return self.buckets[i] if i < len(self.buckets) else self.max
        return self.max

    def state(self) -> Dict:
        return {
            "buckets": list(self.buckets),
            "counts": list(self.counts),
            "sum": self.sum,
            "count": self.count,
            "max": self.max,
        }

    def merge_state(self, st: Dict) -> None:
        if tuple(st["buckets"]) != self.buckets:
            raise ValueError(f"histogram {self.name}: bucket layout mismatch")
        for i, c in enumerate(st["counts"]):
            self.counts[i] += c
        self.sum += st["sum"]
        self.count += st["count"]
        self.max = max(self.max, st["max"])


class Registry:
    """Get-or-create instrument store.  One process-wide instance
    (REGISTRY) backs trace(), the service, and the provers; fresh
    instances exist for tests and for merging foreign snapshots."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[Tuple[str, str, _LabelKey], object] = {}
        # bumped by reset(): callers holding instrument references
        # (trace.py's per-stage cache) re-fetch when it moves, so a
        # reset never silently severs their exposition
        self.generation = 0

    def _get(self, cls, name: str, labels: Optional[Dict[str, str]], **kw):
        key = (cls.kind, name, _label_key(labels))
        with self._lock:
            m = self._metrics.get(key)
            if m is None:
                m = self._metrics[key] = cls(name, key[2], **kw)
            return m

    def counter(self, name: str, labels: Optional[Dict[str, str]] = None) -> Counter:
        return self._get(Counter, name, labels)

    def gauge(self, name: str, labels: Optional[Dict[str, str]] = None) -> Gauge:
        return self._get(Gauge, name, labels)

    def histogram(
        self, name: str, labels: Optional[Dict[str, str]] = None,
        buckets: Optional[Tuple[float, ...]] = None,
    ) -> Histogram:
        return self._get(Histogram, name, labels, buckets=buckets)

    def snapshot(self) -> List[Dict]:
        """JSON-able state of every instrument (mergeable elsewhere)."""
        with self._lock:
            metrics = list(self._metrics.values())
        return [
            {"kind": m.kind, "name": m.name, "labels": dict(m.labels), **m.state()}
            for m in metrics
        ]

    def merge(self, snapshot: List[Dict]) -> None:
        """Fold a snapshot() from another process/registry into this one."""
        for rec in snapshot:
            cls = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}[rec["kind"]]
            kw = {"buckets": tuple(rec["buckets"])} if rec["kind"] == "histogram" else {}
            self._get(cls, rec["name"], rec["labels"], **kw).merge_state(rec)

    def reset(self) -> None:
        with self._lock:
            self._metrics.clear()
            self.generation += 1

    # ------------------------------------------------------- exposition

    def to_prometheus(self) -> str:
        """Prometheus text format (0.0.4).  Metric names are used as
        registered (the zkp2p_ prefix convention lives at call sites)."""

        def fmt_labels(labels: _LabelKey, extra: str = "") -> str:
            parts = [f'{k}="{_esc(v)}"' for k, v in labels]
            if extra:
                parts.append(extra)
            return "{" + ",".join(parts) + "}" if parts else ""

        def _esc(v: str) -> str:
            return str(v).replace("\\", r"\\").replace('"', r"\"").replace("\n", r"\n")

        def _num(v: float) -> str:
            # %g truncates to 6 significant digits — a requests counter
            # past 1e6 (or a ns gauge in the billions) would stop
            # visibly incrementing between scrapes; emit integral values
            # exactly and floats at full precision
            if float(v).is_integer():
                return str(int(v))
            return repr(float(v))

        with self._lock:
            metrics = list(self._metrics.values())
        by_name: Dict[Tuple[str, str], List] = {}
        for m in metrics:
            by_name.setdefault((m.name, m.kind), []).append(m)
        out: List[str] = []
        for (name, kind), ms in sorted(by_name.items()):
            # native gauges share one templated help line; everything
            # else resolves through METRIC_HELP (0.0.4 HELP text escapes
            # only backslash and newline — quotes stay literal)
            if name.startswith("zkp2p_native_"):
                help_s = f"Mirror of the native C stats slot {name[len('zkp2p_native_'):]}"
            else:
                help_s = METRIC_HELP.get(name, "zkp2p metric (docs/OBSERVABILITY.md)")
            out.append("# HELP %s %s" % (name, help_s.replace("\\", r"\\").replace("\n", r"\n")))
            out.append(f"# TYPE {name} {kind}")
            for m in ms:
                if kind == "histogram":
                    cum = 0
                    for ub, c in zip(m.buckets, m.counts):
                        cum += c
                        le = 'le="%g"' % ub
                        out.append(f"{name}_bucket{fmt_labels(m.labels, le)} {cum}")
                    cum += m.counts[-1]
                    le_inf = 'le="+Inf"'
                    out.append(f"{name}_bucket{fmt_labels(m.labels, le_inf)} {cum}")
                    out.append(f"{name}_sum{fmt_labels(m.labels)} {_num(m.sum)}")
                    out.append(f"{name}_count{fmt_labels(m.labels)} {m.count}")
                else:
                    out.append(f"{name}{fmt_labels(m.labels)} {_num(m.value)}")
        return "\n".join(out) + "\n"


REGISTRY = Registry()

# ---------------------------------------------------------------------------
# Run manifest: every dump carries WHO produced it (run_id + pid), WHERE
# (host facts — PR 2's unattributable 3.28-3.68 s spread is why), and
# HOW (every knob state + provenance), so a trace file read weeks later
# is self-describing.

_run_id: Optional[str] = None


def run_id() -> str:
    """Stable per-process run identifier (12 hex chars)."""
    global _run_id
    if _run_id is None:
        _run_id = uuid.uuid4().hex[:12]
    return _run_id


def host_facts() -> Dict:
    """Host facts that explain run-to-run spread: the RESOLVED native
    worker count (ZKP2P_NATIVE_THREADS else core count — the same rule
    the C pool and prover apply), CPU identity, and IFMA availability.
    Shared by bench.py's BENCH record and the run manifest."""
    from .config import load_config

    cpu_model = "unknown"
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.lower().startswith("model name"):
                    cpu_model = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    ifma = 0
    try:
        from ..native.lib import get_lib

        lib = get_lib()
        if lib is not None:
            ifma = int(lib.zkp2p_ifma_available())
    except Exception:  # noqa: BLE001 — attribution must not break a prove
        pass
    cfg = load_config()
    return {
        "native_threads": cfg.native_threads or (os.cpu_count() or 1),
        "cpu_model": cpu_model,
        "cpu_count": os.cpu_count() or 1,
        "ifma": ifma,
    }


def serialize_knobs(cfg) -> Dict:
    """Every knob as a JSON-able value — THE one serialization shared by
    the run manifest and the doctor report (a divergent copy would let
    the two disagree about knob values)."""
    from .config import KNOBS

    return {
        attr: (v if isinstance(v, (int, float, bool, str, type(None))) else str(v))
        for attr, v in ((a, getattr(cfg, a)) for a in KNOBS)
    }


def run_manifest() -> Dict:
    """{run_id, pid, ts, host facts, every knob + provenance, observed
    gate arms + execution digest}."""
    from .audit import execution_digest, gate_arms
    from .config import load_config

    cfg = load_config()
    knobs = serialize_knobs(cfg)
    # host auto-tune profile (utils.hostprof): which arm resolved (off |
    # tuned | fallback), from which path, under which hardware
    # fingerprint — so a tuned-vs-fallback A/B is attributable from the
    # artifact alone, matching the precomp rows' geometry_source.
    # Resolved BEFORE the gate snapshot: profile_manifest() records the
    # host_profile arm, and the gates/digest below must include it.
    host_profile = None
    try:
        from .hostprof import profile_manifest

        host_profile = profile_manifest()
    except Exception:  # noqa: BLE001 — attribution must not break a dump
        pass
    man = {
        "run_id": run_id(),
        "pid": os.getpid(),
        "ts": round(time.time(), 3),
        "host": host_facts(),
        "knobs": knobs,
        "provenance": dict(cfg.provenance),
        # which arms actually executed (audit.record_arm call sites) —
        # the digest is the comparison key: equal digests = provably
        # identical code paths (docs/OBSERVABILITY.md §execution audit)
        "gates": gate_arms(),
        "execution_digest": execution_digest(),
    }
    # where THIS process's /metrics endpoint actually listens — under
    # ZKP2P_METRICS_PORT=auto the knob value (0) says nothing, so the
    # manifest records the OS-assigned port (scrape discoverability for
    # fleet workers; the fleet heartbeat carries the same number)
    if _bound_port is not None:
        man["metrics_port_bound"] = _bound_port
    # fixed-base precomputed-table memory accounting (prover.precomp):
    # per-family geometry + resident bytes + build-vs-cache provenance,
    # so table RAM is attributable in every trace/bench artifact
    try:
        from ..prover.precomp import precomp_manifest

        pm = precomp_manifest()
        if pm is not None:
            man["precomp"] = pm
    except Exception:  # noqa: BLE001 — attribution must not break a dump
        pass
    # circuit soundness audits performed in this process (snark.analysis
    # — the registry admission gate): digest + finding counts per
    # circuit, so every artifact records WHICH audited circuit it served
    try:
        from ..snark.analysis import audit_manifest

        am = audit_manifest()
        if am:
            man["circuit_audits"] = am
    except Exception:  # noqa: BLE001 — attribution must not break a dump
        pass
    # segmented matvec plans (prover.matvec_plan): per-matrix shape +
    # provenance + the pool width the segment partition used
    try:
        from ..prover.matvec_plan import matvec_plan_manifest

        mm = matvec_plan_manifest()
        if mm is not None:
            man["matvec_plans"] = mm
    except Exception:  # noqa: BLE001 — attribution must not break a dump
        pass
    if host_profile is not None:
        man["host_profile"] = host_profile
    return man


def publish_native_stats(registry: Optional[Registry] = None) -> Optional[Dict]:
    """Read the native runtime's counter block (native.lib
    stats_snapshot) into `zkp2p_native_<field>` gauges; returns the raw
    snapshot (None when the native lib is unavailable).  Gauges, not
    counters: the C block is itself cumulative, so last-write-wins
    mirrors it without double counting."""
    try:
        from ..native.lib import stats_snapshot

        snap = stats_snapshot()
    except Exception:  # noqa: BLE001 — numpy-less envs, stale .so:
        return None    # observation must never fail the prove around it
    if snap is None:
        return None
    reg = registry if registry is not None else REGISTRY
    for field, v in snap.items():
        reg.gauge(f"zkp2p_native_{field}").set(v)
    return snap


# ---------------------------------------------------------------------------
# Rotating JSONL sink: the durable side of the registry.  One record per
# line; each fresh file opens with a manifest line; every write is ONE
# O_APPEND write() so interleaved service workers produce intact lines.
# Rotation is guarded by an flock'd sidecar (<path>.lock) because the
# advertised mode is MULTIPLE worker processes sharing one path — two
# unsynchronized rotators would double-shift backups (losing records) or
# let a writer land on a fresh file between size-check and open without
# its manifest line.


class JsonlSink:
    def __init__(self, path: str, max_bytes: int = 16 << 20, backups: int = 3):
        self.path = path
        self.max_bytes = max_bytes
        self.backups = backups
        self._lock = threading.Lock()
        # Identity (st_dev, st_ino) of the file THIS instance last
        # stamped its manifest into: a restarted service appending to an
        # existing sub-cap sink must still stamp its run's manifest (new
        # run_id, possibly new knob arms), and a rotation performed by a
        # SIBLING process changes the identity under us — both cases
        # re-stamp, or trace_report --runs/--diff loses the stage-span
        # attribution for every run but the file's first.
        self._stamped_id: Optional[Tuple[int, int]] = None

    def _rotate_locked(self) -> None:
        for i in range(self.backups - 1, 0, -1):
            src, dst = f"{self.path}.{i}", f"{self.path}.{i + 1}"
            if os.path.exists(src):
                os.replace(src, dst)
        if os.path.exists(self.path):
            os.replace(self.path, f"{self.path}.1")

    def write(self, record: Dict) -> None:
        self.write_many([record])

    def write_many(self, records: List[Dict]) -> None:
        if not records:
            return
        payload = "".join(json.dumps(r, default=str) + "\n" for r in records)
        with self._lock:
            d = os.path.dirname(self.path)
            if d:
                os.makedirs(d, exist_ok=True)
            lock_fd = -1
            try:
                import fcntl

                lock_fd = os.open(self.path + ".lock", os.O_CREAT | os.O_WRONLY, 0o644)
                fcntl.flock(lock_fd, fcntl.LOCK_EX)
            except Exception:  # noqa: BLE001 — no flock (exotic fs): in-process lock only
                if lock_fd >= 0:
                    os.close(lock_fd)
                    lock_fd = -1
            try:
                try:
                    st = os.stat(self.path)
                    size, cur_id = st.st_size, (st.st_dev, st.st_ino)
                except OSError:
                    size, cur_id = -1, None  # fresh file
                if size >= 0 and size + len(payload) > self.max_bytes:
                    self._rotate_locked()
                    size, cur_id = -1, None
                if size < 0 or cur_id != self._stamped_id:
                    payload = json.dumps({"type": "manifest", **run_manifest()}) + "\n" + payload
                fd = os.open(self.path, os.O_APPEND | os.O_CREAT | os.O_WRONLY, 0o644)
                try:
                    os.write(fd, payload.encode())
                    fst = os.fstat(fd)
                    self._stamped_id = (fst.st_dev, fst.st_ino)
                finally:
                    os.close(fd)
            finally:
                if lock_fd >= 0:
                    os.close(lock_fd)  # releases the flock


# ---------------------------------------------------------------------------
# Prometheus exposition: a tiny stdlib HTTP endpoint, default OFF
# (ZKP2P_METRICS_PORT unset).  One server per process, daemon thread —
# observation must never keep a prover alive.

_server = None
_server_lock = threading.Lock()
# the port the endpoint actually bound — equals the configured port for
# a fixed port, and the OS-assigned ephemeral port under
# ZKP2P_METRICS_PORT=auto/0 (recorded in the run manifest and the fleet
# heartbeat so scrapes stay discoverable across N workers on one host)
_bound_port: Optional[int] = None


def bound_metrics_port() -> Optional[int]:
    """The port the /metrics endpoint is actually listening on (None
    when exposition is off / the server never started)."""
    return _bound_port


def maybe_start_metrics_server(port: Optional[int] = None, registry: Optional[Registry] = None):
    """Start (idempotently) the /metrics HTTP endpoint when a port is
    configured; returns the server or None when exposition is off.
    Port 0 ("auto") binds an OS-assigned ephemeral port — read it back
    via `bound_metrics_port()`.  Binds ZKP2P_METRICS_ADDR (default
    localhost — the payload discloses host facts and knob config;
    0.0.0.0 is an explicit opt-in)."""
    global _server, _bound_port
    reg = registry if registry is not None else REGISTRY
    from .config import load_config

    if port is None:
        port = load_config().metrics_port
    if port is None:
        return None
    addr = load_config().metrics_addr or "127.0.0.1"
    with _server_lock:
        if _server is not None:
            return _server
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        class Handler(BaseHTTPRequestHandler):
            def _send(self, code: int, body: bytes, ctype: str) -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802 — stdlib API
                path = self.path.split("?", 1)[0].rstrip("/")
                if path in ("", "/metrics"):
                    publish_native_stats(reg)  # scrape-time native refresh
                    try:  # scrape-time SLO gauge refresh (same contract)
                        from .slo import publish_slo

                        publish_slo(reg)
                    except Exception:  # noqa: BLE001 — exposition only
                        pass
                    self._send(200, reg.to_prometheus().encode(), "text/plain; version=0.0.4")
                elif path == "/status":
                    # fails CLOSED (503) while preflight hasn't run: a
                    # load balancer must not route to a worker whose
                    # gates nobody armed (slo.status_payload docs)
                    try:
                        from .slo import status_payload

                        body = status_payload()
                        code = 200 if body.get("ok") else 503
                    except Exception as e:  # noqa: BLE001 — degraded, not dead
                        body, code = {"ok": False, "reason": f"status error: {e}"}, 500
                    self._send(code, (json.dumps(body) + "\n").encode(), "application/json")
                elif path == "/healthz":
                    # liveness only: the process is up and serving HTTP.
                    # Readiness (gates armed, SLO state) is /status's job.
                    self._send(200, b'{"ok": true}\n', "application/json")
                elif path == "/snapshot":
                    # machine scrape for the FLEET PLANE (docs/
                    # OBSERVABILITY.md §fleet plane): the raw registry
                    # snapshot (mergeable — Registry.merge consumes it
                    # verbatim) plus the serialized SLO window, so the
                    # supervisor can sum counters, label gauges,
                    # bucket-merge histograms and pool SLO samples
                    # instead of re-parsing Prometheus text
                    publish_native_stats(reg)
                    try:  # same refresh-where-read contract as /metrics
                        from .slo import publish_slo

                        publish_slo(reg)
                    except Exception:  # noqa: BLE001 — exposition only
                        pass
                    body: Dict = {
                        "ts": round(time.time(), 3),
                        "pid": os.getpid(),
                        "run_id": run_id(),
                        "metrics": reg.snapshot(),
                    }
                    try:
                        from .audit import last_preflight
                        from .config import load_config
                        from .slo import default_tracker

                        body["armed"] = last_preflight() is not None
                        body["slo_window"] = default_tracker().window_state()
                        cfg = load_config()
                        if cfg.worker_id:
                            body["worker"] = cfg.worker_id
                        if cfg.fleet_id:
                            body["fleet"] = cfg.fleet_id
                    except Exception:  # noqa: BLE001 — a partial snapshot
                        pass           # still merges; armed defaults absent
                    self._send(200, (json.dumps(body) + "\n").encode(), "application/json")
                else:
                    self.send_response(404)
                    self.end_headers()

            def log_message(self, *_a):  # scrapes must not spam stderr
                pass

        try:
            srv = ThreadingHTTPServer((addr, int(port)), Handler)
        except OSError as e:
            # EADDRINUSE from a sibling worker sharing the port, a
            # privileged port, ... — observation must never fail a
            # prove: degrade to no endpoint, loudly
            import sys

            print(f"[metrics] endpoint on :{port} unavailable ({e}); exposition off", file=sys.stderr)
            return None
        threading.Thread(target=srv.serve_forever, daemon=True, name="zkp2p-metrics").start()
        _server = srv
        _bound_port = int(srv.server_address[1])
        if not port:
            # auto mode: say which port the OS handed out — the only
            # place a human would otherwise learn it is the manifest
            import sys

            print(f"[metrics] auto port: listening on :{_bound_port}", file=sys.stderr)
        return srv


def stop_metrics_server() -> None:
    """Tear down the exposition endpoint (tests; service shutdown)."""
    global _server, _bound_port
    with _server_lock:
        if _server is not None:
            srv = _server
            _server = None
            _bound_port = None
            srv.shutdown()
            srv.server_close()
