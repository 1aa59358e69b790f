"""The fleet observability plane: supervisor-hosted metrics federation.

PR 10 left observability per-process: each worker binds its own
ephemeral `/metrics` port, each holds a private SLO window, and
status.json knows about processes, not service health.  Nothing
answered the deployment's question — "is the FLEET meeting its SLO,
and which worker is why not?" — without a human joining N ephemeral
scrapes by hand.  This module is the missing aggregation layer
(ZKProphet's thesis applied at fleet scope: attribution first), and
the measurement substrate ROADMAP items 2 (adaptive scheduler) and 3
(multi-host federation) consume: fleet arrival rate, backlog, burn
rate, per-worker skew, all on ONE stable endpoint.

Topology:

  worker (N of them)                    supervisor (this module)
    /snapshot  ── registry snapshot ──►  scrape loop (background
    heartbeat  ── SLO window (fallback)  thread, ZKP2P_FLEET_SCRAPE_S)
                                           │ merge (rules below)
                                           ▼
                              fleet registry + merged SLO + alerts
                                           │
    ZKP2P_FLEET_METRICS_PORT serves  /metrics  /status  /healthz

Aggregation rules (the whole point — a family must merge the way its
semantics demand, not one-size-fits-all):

  counters    SUMMED across workers (labels preserved): fleet
              requests_total is the sum of worker requests_total.
              NOTE: the sum covers each worker's CURRENT incarnation —
              a restarted worker's counters restart at zero, exactly
              like a restarted Prometheus target.
  gauges      LABELLED per worker (`worker="w0"`), never summed or
              maxed: N workers sweeping one spool each report the same
              backlog, and their last-batch-fill gauges are skew
              signals only attribution preserves.
  histograms  BUCKET-MERGED via the fixed-layout merge_state path;
              a bucket-layout mismatch is REFUSED (that family is
              skipped and counted in zkp2p_fleet_merge_refusals_total)
              rather than silently mis-binned.

The merged fleet registry is rebuilt FROM SCRATCH every scrape cycle —
folding cumulative worker counters into a persistent registry would
double-count every cycle.  Scrape failures are counted per worker and
never fatal (the worker may be mid-restart; its heartbeat SLO window
is the fallback).  `/status` fails CLOSED (503) until every live
worker has armed its gates — the PR-8 single-worker discipline applied
fleet-wide: a load balancer must not trust a fleet whose members
nobody has preflighted.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.request
from typing import Callable, Dict, List, Optional, Tuple

from ..utils.metrics import Registry


def merge_worker_metrics(
    fleet_reg: Registry,
    snapshot: List[Dict],
    worker: Optional[str],
    refused: Optional[Callable[[str], None]] = None,
) -> None:
    """Fold one worker's registry snapshot into the fleet registry
    under the per-family aggregation rules (module docstring).
    `worker=None` merges WITHOUT relabelling gauges — the supervisor's
    own instruments are already fleet-scoped.  The fleet registry must
    be FRESH each cycle — counters here are cumulative, and re-merging
    them into yesterday's sums fabricates throughput."""
    for rec in snapshot:
        try:
            kind = rec["kind"]
            if kind == "counter":
                fleet_reg.counter(rec["name"], rec["labels"]).merge_state(rec)
            elif kind == "gauge":
                labels = dict(rec["labels"])
                if worker is not None:
                    labels["worker"] = worker
                fleet_reg.gauge(rec["name"], labels).merge_state(rec)
            elif kind == "histogram":
                fleet_reg.histogram(
                    rec["name"], rec["labels"], buckets=tuple(rec["buckets"])
                ).merge_state(rec)
        except ValueError:
            # bucket-layout mismatch: REFUSE the family (merging
            # mismatched layouts would bin samples into the wrong
            # latency ranges — worse than a counted gap)
            if refused:
                refused(rec.get("name", "?"))
        except Exception:  # noqa: BLE001 — one torn record, not the cycle
            if refused:
                refused(rec.get("name", "?"))


class FleetPlane:
    """Supervisor-side aggregation + exposition.  Owns a background
    scrape thread (never the supervisor's control loop: a wedged worker
    socket must not delay the watchdog) and a stable HTTP endpoint.

    The plane reads the supervisor via a narrow surface: `slots` (for
    liveness + restart counts), `_hb`/`_hb_age_s` (heartbeats), `spool`
    and `status()` — and never mutates it."""

    def __init__(
        self,
        supervisor,
        port: Optional[int] = None,
        scrape_s: Optional[float] = None,
        addr: Optional[str] = None,
        clock=time.time,
        log: Optional[Callable[[str], None]] = None,
    ):
        from ..utils.alerts import AlertEngine, TrendTracker, fleet_rules
        from ..utils.config import load_config
        from ..utils.metrics import REGISTRY

        cfg = load_config()
        self.sup = supervisor
        self.port = port if port is not None else cfg.fleet_metrics_port
        self.scrape_s = scrape_s if scrape_s is not None else cfg.fleet_scrape_s
        self.addr = addr or cfg.metrics_addr or "127.0.0.1"
        self.fast_window_s = cfg.slo_fast_window_s
        self._clock = clock
        self._log = log or supervisor.log
        self._registry = REGISTRY  # the supervisor process's own instruments
        self.engine = AlertEngine(fleet_rules(cfg), registry=REGISTRY, log=self._log, clock=clock)
        self._trend = TrendTracker(keep_s=max(10 * self.scrape_s, 4 * cfg.alert_for_s, 60.0))
        self._restart_trend = TrendTracker(keep_s=max(cfg.breaker_window_s, 60.0))
        self._restarts_window_s = cfg.breaker_window_s
        self._alert_for_s = cfg.alert_for_s
        self._lock = threading.Lock()
        # pre-first-scrape view: an EMPTY registry, not the raw process
        # REGISTRY — the supervisor process may host other instrumented
        # work (an in-process service in tests/tools), and serving it
        # unfiltered for the first scrape interval would briefly present
        # non-worker counters as fleet counters
        self._view: Dict = {
            "registry": Registry(),
            "ready": False,
            "reason": "no scrape cycle has completed",
            "slo": None,
            "workers_scraped": {},
            "ts": None,
        }
        self._alert_log: List[Dict] = []  # every fire/clear transition this run
        self.scrapes = 0
        self._stop = threading.Event()
        self._srv = None
        self._thread: Optional[threading.Thread] = None
        self.bound_port: Optional[int] = None

    # ----------------------------------------------------------- scrape

    def _fetch_snapshot(self, port: int) -> Optional[Dict]:
        # workers bind ZKP2P_METRICS_ADDR (inherited from this process's
        # env): scrape the same address — loopback only when the bind is
        # loopback/wildcard, else the configured interface (a worker
        # bound to 10.0.0.5 alone is unreachable via 127.0.0.1)
        addr = "127.0.0.1" if self.addr in ("", "0.0.0.0", "127.0.0.1") else self.addr
        try:
            with urllib.request.urlopen(
                f"http://{addr}:{port}/snapshot", timeout=2.0
            ) as resp:
                return json.loads(resp.read())
        except Exception:  # noqa: BLE001 — counted by the caller
            return None

    def scrape_once(self, now: Optional[float] = None) -> Dict:
        """One federation cycle: scrape every live worker, merge, score
        the fleet SLO, evaluate alerts, publish the new view.  Returns
        the view (tests drive this synchronously)."""
        from ..utils.metrics import REGISTRY
        from ..utils.slo import merge_window_states, publish_fleet_slo

        t = self._clock() if now is None else now
        slo_states: List[Dict] = []
        workers_scraped: Dict[str, Dict] = {}
        snapshots: List[Tuple[str, List[Dict]]] = []
        live = unarmed = unreachable = 0
        degraded = 0
        hb_gap: Optional[float] = None
        # list(): the supervisor's autoscaler inserts slots mid-run,
        # and iterating the live dict from this (scrape) thread would
        # RuntimeError exactly at scale events — when the merged
        # signals matter most
        for slot in list(self.sup.slots.values()):
            alive = slot.proc is not None and slot.proc.poll() is None
            if not alive or slot.state not in ("up", "starting", "draining", "retiring"):
                continue
            live += 1
            hb = self.sup._hb(slot) or {}
            if hb.get("degraded"):
                degraded += 1
            age = self.sup._hb_age_s(slot)
            if age is not None:
                hb_gap = age if hb_gap is None else max(hb_gap, age)
            port = hb.get("port")
            snap = self._fetch_snapshot(port) if port else None
            if snap is None:
                unreachable += 1
                # the failure counter ticks only for ATTEMPTED scrapes:
                # a worker that has not published a port yet (cold
                # imports before the first heartbeat) is expected
                # startup, not a scrape-health regression
                if port:
                    REGISTRY.counter(
                        "zkp2p_fleet_scrape_failures_total", {"worker": slot.wid}
                    ).inc()
                # heartbeat fallback: the SLO window still merges, so a
                # worker mid-restart does not punch a hole in fleet
                # attainment — but it cannot vouch for armed gates.
                # The serialized ages are relative to the heartbeat's
                # WRITE time: shift by the heartbeat's own age, or a
                # wedged worker's frozen samples would sit inside the
                # fast burn window forever.
                win = hb.get("slo_window")
                if win:
                    if age:
                        win = dict(win)
                        win["samples"] = [
                            [a + age, lat, good] for a, lat, good in win.get("samples") or []
                        ]
                    slo_states.append(win)
                # scraped-vs-armed stay separate fields: "scrape is
                # failing" and "gates not armed" are opposite
                # remediations and must be tellable apart per worker
                workers_scraped[slot.wid] = {"scraped": False, "armed": None, "port": port}
                continue
            if not snap.get("armed"):
                unarmed += 1
            if snap.get("slo_window"):
                slo_states.append(snap["slo_window"])
            snapshots.append((slot.wid, snap.get("metrics") or []))
            workers_scraped[slot.wid] = {
                "scraped": True, "armed": bool(snap.get("armed")),
                "port": port, "pid": snap.get("pid"),
            }

        # supervisor's own spool scan: the backlog signal must not
        # depend on any worker being scrapable
        from .service import scan_spool

        scan = scan_spool(self.sup.spool, t, self.scrape_s, 300.0)
        REGISTRY.gauge("zkp2p_fleet_backlog").set(scan["backlog"])
        self._trend.update(t, scan["backlog"])

        merged_slo = merge_window_states(slo_states, fast_window_s=self.fast_window_s)
        publish_fleet_slo(merged_slo, registry=REGISTRY)

        # alert signals out of the merged view + supervisor state
        total_restarts = sum(s.restarts for s in list(self.sup.slots.values()))
        self._restart_trend.update(t, total_restarts)
        restarts_recent = self._restart_trend.delta(self._restarts_window_s, t)
        signals = {
            "burn_fast": merged_slo["burn_fast"],
            "burn_slow": merged_slo["burn_slow"],
            "slo_n": merged_slo["n"],
            "backlog": scan["backlog"],
            "backlog_growing": self._trend.growing(self._alert_for_s, t),
            "restarts_recent": restarts_recent,
            "parked": sum(1 for s in list(self.sup.slots.values()) if s.state == "parked"),
            "degraded": degraded,
            "hb_gap_s": hb_gap,
        }
        for tr in self.engine.evaluate(signals, now=t):
            self._alert_log.append(tr)

        # build the merged fleet registry FRESH (counters are cumulative)
        fleet_reg = Registry()

        def refused(name: str) -> None:
            REGISTRY.counter("zkp2p_fleet_merge_refusals_total", {"family": name}).inc()

        # supervisor-process instruments first (restart/park/governor
        # counters, the just-published zkp2p_fleet_slo_* values);
        # worker=None = no relabelling — they are already fleet-scoped.
        # ONLY the zkp2p_fleet_* families: the supervisor process may
        # host other instrumented work (an in-process service in tests
        # or tools, its own trace histograms), and folding that into
        # the fleet view would break the federation invariant that
        # fleet service counters EQUAL the per-worker sums.
        sup_snap = [m for m in REGISTRY.snapshot() if m["name"].startswith("zkp2p_fleet_")]
        merge_worker_metrics(fleet_reg, sup_snap, worker=None, refused=refused)
        for wid, snap in snapshots:
            merge_worker_metrics(fleet_reg, snap, worker=wid, refused=refused)
        self.scrapes += 1
        REGISTRY.counter("zkp2p_fleet_scrapes_total").inc()

        ready = live > 0 and unreachable == 0 and unarmed == 0
        reason = None
        if not ready:
            if live == 0:
                reason = "no live workers"
            elif unreachable:
                reason = f"{unreachable}/{live} live worker(s) unreachable (no armed snapshot)"
            else:
                reason = f"{unarmed}/{live} live worker(s) have not armed their gates (preflight)"
        view = {
            "registry": fleet_reg,
            "ready": ready,
            "reason": reason,
            "slo": merged_slo,
            "signals": signals,
            "workers_scraped": workers_scraped,
            "ts": round(t, 3),
        }
        with self._lock:
            self._view = view
        return view

    # ------------------------------------------------------------ status

    def status_payload(self) -> Dict:
        """The fleet `/status` body (also folded into status.json by
        the supervisor): supervisor worker table + merged SLO + alerts
        + scrape health.  `ok` gates the HTTP code: False → 503."""
        with self._lock:
            view = dict(self._view)
        body = self.sup.status()
        body["ok"] = bool(view.get("ready"))
        if not body["ok"]:
            body["reason"] = view.get("reason") or "fleet plane not ready"
        body["slo"] = view.get("slo")
        body["alerts"] = self.engine.active()
        body["alerts_state"] = self.engine.state()
        body["signals"] = view.get("signals")
        body["scrape"] = {
            "cycles": self.scrapes,
            "interval_s": self.scrape_s,
            "last_ts": view.get("ts"),
            "workers": view.get("workers_scraped"),
        }
        if self.bound_port is not None:
            body["metrics_port"] = self.bound_port
        return body

    def alert_log(self) -> List[Dict]:
        return list(self._alert_log)

    def last_signals(self) -> Optional[Dict]:
        """The newest scrape cycle's alert/autoscale signal map (None
        before the first completed cycle) — the supervisor's autoscaler
        consumes this instead of re-deriving its own view."""
        with self._lock:
            return self._view.get("signals")

    # --------------------------------------------------------- lifecycle

    def start(self) -> Optional[int]:
        """Bind the endpoint (port 0/auto = ephemeral, recorded in
        `bound_port` + status.json) and start the scrape thread.
        Returns the bound port, or None when binding failed (counted
        behavior mirrors maybe_start_metrics_server: the fleet still
        runs; exposition degrades loudly)."""
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        plane = self

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
                    with plane._lock:
                        reg = plane._view["registry"]
                    self._send(200, reg.to_prometheus().encode(), "text/plain; version=0.0.4")
                elif path == "/status":
                    try:
                        body = plane.status_payload()
                        code = 200 if body.get("ok") else 503
                    except Exception as e:  # noqa: BLE001 — degraded, not dead
                        body, code = {"ok": False, "reason": f"status error: {e}"}, 500
                    self._send(code, (json.dumps(body) + "\n").encode(), "application/json")
                elif path == "/healthz":
                    self._send(200, b'{"ok": true}\n', "application/json")
                else:
                    self.send_response(404)
                    self.end_headers()

            def log_message(self, *_a):  # scrapes must not spam stderr
                pass

        try:
            self._srv = ThreadingHTTPServer((self.addr, int(self.port or 0)), Handler)
        except OSError as e:
            self._log(f"fleet metrics endpoint on :{self.port} unavailable ({e}); plane exposition off")
            self._srv = None
        else:
            self.bound_port = int(self._srv.server_address[1])
            threading.Thread(
                target=self._srv.serve_forever, daemon=True, name="zkp2p-fleet-metrics"
            ).start()
            self._log(f"fleet observability plane on :{self.bound_port} (/metrics /status /healthz)")

        def loop():
            while not self._stop.wait(self.scrape_s):
                try:
                    self.scrape_once()
                except Exception as e:  # noqa: BLE001 — the plane must outlive a bad cycle
                    self._log(f"fleet scrape cycle failed: {e}")

        self._thread = threading.Thread(target=loop, daemon=True, name="zkp2p-fleet-scrape")
        self._thread.start()
        return self.bound_port

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2 * self.scrape_s + 5)
        if self._srv is not None:
            self._srv.shutdown()
            self._srv.server_close()
            self._srv = None


# ---------------------------------------------------------------------------
# Shared client-side helpers: every consumer of the fleet /status
# contract (cli `top`, loadgen's readiness gate + teardown snapshot,
# chaos's plane assertions) goes through these two, so a change to the
# contract (payload shape, what a 503 carries) lands in ONE place.


def http_status_json(url: str, timeout: float = 3.0) -> Optional[Dict]:
    """GET `url` as JSON.  An HTTP error response whose body parses as
    JSON is RETURNED, not raised — the fleet /status 503 body IS the
    status (ok=False + reason).  Transport failures return None."""
    import urllib.error

    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return json.loads(resp.read())
    except urllib.error.HTTPError as e:
        try:
            return json.loads(e.read())
        except ValueError:
            return None
    except (OSError, ValueError):
        return None


def discover_fleet_port(fleet_dir: str) -> Optional[int]:
    """The plane's bound port out of `<fleet_dir>/status.json`
    (`metrics_port` — written by the supervisor every tick once the
    plane is up).  None while the file or field does not exist yet."""
    import os

    try:
        with open(os.path.join(fleet_dir, "status.json")) as f:
            port = json.load(f).get("metrics_port")
        return int(port) if port else None
    except (OSError, ValueError, TypeError):
        return None


# ---------------------------------------------------------------------------
# `zkp2p-tpu top`: render one fleet /status payload as a terminal frame
# (the CLI loops fetch→render; rendering lives here so tests can pin the
# format without a live endpoint).


def render_top(body: Dict) -> str:
    """One text frame of the live fleet view: health, merged SLO,
    active alerts, per-worker table, queue signals."""
    lines: List[str] = []
    ok = body.get("ok")
    lines.append(
        f"fleet {body.get('fleet_id', '?')}  "
        f"{'READY' if ok else 'NOT READY'}"
        + (f" ({body.get('reason')})" if not ok and body.get("reason") else "")
        + ("  DRAINING" if body.get("draining") else "")
    )
    slo = body.get("slo")
    if slo:
        lines.append(
            f"slo: attainment {slo['attainment']:.4f}  "
            f"burn fast/slow {slo['burn_fast']:g}/{slo['burn_slow']:g}  "
            f"p95 {slo['p95_s']:.3f}s"
            + (f" (objective {slo['objective_p95_s']:g}s)" if slo.get("objective_p95_s") else "")
            + f"  n={slo['n']} across {slo.get('workers', 0)} window(s)"
        )
    sig = body.get("signals") or {}
    if sig:
        lines.append(
            f"queue: backlog {sig.get('backlog')}  "
            f"restarts(win) {sig.get('restarts_recent')}  "
            f"parked {sig.get('parked')}  degraded {sig.get('degraded')}"
        )
    # scheduler block: per-worker batch targets + lane depths (worker
    # heartbeats) and the supervisor's autoscale state
    sched = body.get("sched") or {}
    wsched = {
        wid: w["sched"] for wid, w in (body.get("workers") or {}).items() if w.get("sched")
    }
    if wsched:
        lines.append("sched: " + "  ".join(
            f"{wid}[{s.get('mode', '?')}] tgt={s.get('batch_target')}"
            + (
                f" lanes i{s.get('lane_interactive', 0)}/b{s.get('lane_bulk', 0)}"
                if s.get("mode") == "adaptive" else ""
            )
            for wid, s in sorted(wsched.items())
        ))
    if sched.get("autoscale"):
        last = sched.get("last_scale")
        lines.append(
            f"autoscale: {sched.get('workers_live')} live in "
            f"[{sched.get('workers_min')}..{sched.get('workers_max')}]  "
            f"events {sched.get('scale_events', 0)}"
            + (
                f"  last {last['direction']} ({last.get('reason')}) -> {last.get('workers')} @ {last.get('ts')}"
                if last else "  last none"
            )
        )
    alerts = body.get("alerts") or []
    if alerts:
        for a in alerts:
            lines.append(f"ALERT {a['rule']}: {a.get('detail', '')} (since {a.get('since')})")
    else:
        lines.append("alerts: none firing")
    workers = body.get("workers") or {}
    if workers:
        lines.append(f"{'worker':<8} {'state':<9} {'pid':>7} {'port':>6} "
                     f"{'restarts':>8} {'rss_mb':>8} {'hb_age':>7} {'degr':>5}")
        for wid in sorted(workers):
            w = workers[wid]
            rss = w.get("rss_mb")
            age = w.get("hb_age_s")
            lines.append(
                f"{wid:<8} {w.get('state', '?'):<9} {str(w.get('pid') or '-'):>7} "
                f"{str(w.get('port') or '-'):>6} {w.get('restarts', 0):>8} "
                f"{(f'{rss:.0f}' if isinstance(rss, (int, float)) else '-'):>8} "
                f"{(f'{age:.1f}' if isinstance(age, (int, float)) else '-'):>7} "
                f"{('y' if w.get('degraded') else '-'):>5}"
            )
    scrape = body.get("scrape") or {}
    if scrape:
        lines.append(
            f"scrape: {scrape.get('cycles', 0)} cycle(s) @ {scrape.get('interval_s')}s"
            f"  last {scrape.get('last_ts')}"
        )
    return "\n".join(lines)
