"""The batched proving service: queue -> pad to batch -> prove -> verify
sample -> emit (the BASELINE.json north-star service shape).

Failure semantics mirror the reference UI's explicit state machine
(`SubmitOrderGenerateProofForm.tsx:45-56,171-220`), hardened for a
fleet (docs/ROBUSTNESS.md): each request ends in exactly one of
  done | error-bad-input | error-failed-to-prove |
  error-deadline-exceeded | error-shed
with the error recorded next to the request — no silent drops; plus the
verify-after-prove self-check the pipeline scripts do
(`5_gen_proof.sh:15-22` runs `snarkjs groth16 verify` right after prove).

Requests are JSON files in a spool directory (the S3/queue stand-in);
results and errors are written alongside.  Fault tolerance is layered
(docs/ROBUSTNESS.md has the full ladder):

  transient retries (bounded, exponential backoff)
    -> batch bisection (a poisoned request terminal-errors ALONE, its
       batchmates still ship `done`, <= log2(S) extra proves per mate)
      -> degradation ladder (precomp -> multi -> batch-affine ->
         sequential, reusing the existing knob gates)
        -> error-failed-to-prove

plus per-request deadlines (payload `deadline_s` or ZKP2P_DEADLINE_S,
checked at claim and again at batch assembly) and a spool backlog cap
(ZKP2P_SPOOL_CAP) that sheds load visibly instead of silently aging
requests.  Every layer is provable on demand via the fault-injection
sites (utils.faults, ZKP2P_FAULTS) and the chaos harness
(tools/chaos.py: N workers, SIGKILLs mid-prove, injected faults, one
global invariant).
"""

from __future__ import annotations

import collections
import contextlib
import errno
import json
import os
import queue
import re
import sys
import threading
import time
import traceback
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from ..formats.proof_json import dump
from ..snark import native_verify, witness_check
from ..utils.audit import execution_digest, install_compile_listener, preflight, sample_device_memory
from ..utils.faults import FaultInjected, fault_point
from ..utils.metrics import REGISTRY, JsonlSink, maybe_start_metrics_server, publish_native_stats, run_id, run_manifest
from ..utils.trace import (
    adopt_context,
    adopt_stack,
    current_context,
    current_stack,
    drain as drain_trace,
    record,
    set_context,
    trace,
)

# The terminal-state machine (docs/ROBUSTNESS.md): every request ends in
# EXACTLY ONE of these, recorded as a .proof.json/.error.json artifact
# plus a request record + requests_total{state} counter.
TERMINAL_STATES = (
    "done",
    "error-bad-input",
    "error-failed-to-prove",
    "error-deadline-exceeded",
    "error-shed",
)

# A torn .req.json younger than this is left alone for one more sweep —
# a non-atomic uploader may still be writing it — before the sweep
# judges it corrupt and terminals error-bad-input.
TORN_REQ_GRACE_S = 2.0
# a sweep that finds a request younger than this waits the difference out and lists again, once
BURST_SETTLE_S = 0.05

# Degradation ladder (last resort before error-failed-to-prove): each
# rung re-proves the isolated request with one more fast path gated off,
# reusing the existing knob gates — they are fresh-read per prove, so an
# env overlay flips them for exactly one attempt.  Proof BYTES are
# knob-invariant (the byte-parity oracles pin every arm), so a ladder
# rescue emits the same proof the fast path would have.  The overlay is
# process-global while it is applied; proves are serialized on the
# consumer thread, so no concurrent prove can observe a half-applied
# rung (the witness producer never proves).
_DEGRADATION_LADDER = (
    ("no-precomp", {"ZKP2P_MSM_PRECOMP": "0"}),
    ("no-multi", {"ZKP2P_MSM_PRECOMP": "0", "ZKP2P_MSM_MULTI": "0"}),
    ("no-batch-affine", {
        "ZKP2P_MSM_PRECOMP": "0", "ZKP2P_MSM_MULTI": "0",
        "ZKP2P_MSM_BATCH_AFFINE": "0",
    }),
    ("sequential", {
        "ZKP2P_MSM_PRECOMP": "0", "ZKP2P_MSM_MULTI": "0",
        "ZKP2P_MSM_BATCH_AFFINE": "0", "ZKP2P_MSM_OVERLAP": "0",
    }),
)

# Patterns that classify an exception as TRANSIENT (retry-worthy) when
# its type alone does not: allocator and pool exhaustion surface as
# RuntimeError text from the C/XLA layers.  Word-bounded: a bare
# substring scan classified any message merely CONTAINING "pool"
# ("spool", a path) or "resource" as transient, and a deterministic
# failure classified transient defer-livelocks in the witness path.
_TRANSIENT_RE = re.compile(
    r"\balloc\w*\b|\bpool\b|\bout of memory\b|\btemporarily unavailable\b|\bresource exhausted\b"
)

# OSError errnos that signal pressure that can clear (disk/fd/memory
# exhaustion, interruption) — retry-worthy.  Everything else in the
# class (ENOENT, EACCES, EISDIR, ...) is payload pathology: a request
# naming a missing file must terminal error-bad-input, not defer.
_TRANSIENT_ERRNOS = frozenset(
    getattr(errno, name)
    for name in (
        "ENOSPC", "EDQUOT", "EIO", "EAGAIN", "EWOULDBLOCK", "EINTR",
        "EMFILE", "ENFILE", "ENOMEM", "EBUSY", "ETIMEDOUT",
    )
    if hasattr(errno, name)
)


# Batch-fill histogram buckets: live requests per batch handed to the
# prover (upper bounds; +Inf implicit).  Fill vs batch_size is THE
# signal the ROADMAP-item-2 dynamic batch scheduler will size columns
# from, so it is recorded as a distribution, not a last-write gauge.
BATCH_FILL_BUCKETS: Tuple[float, ...] = (1, 2, 4, 8, 16, 32, 64)


@contextlib.contextmanager
def _span(reqs, name: str, **attrs):
    """One span of the served path, `service/<name>`, over every request
    in `reqs` (one Request or a batch list), with two readers: the stage
    record (utils.trace: path, `t0`, `id`, `parent`, `request_id` from
    the ambient context or the batch's `request_ids`) and, on close, the
    same `{name, t0, ms, **attrs}` appended to each request's `spans`
    list — the waterfall substrate, persisted on the request's next
    record and exported by `trace_report --chrome-trace` (one pid per
    worker, one tid per request).  `t0` is wall-clock (`time.time`): the
    waterfall is cross-process, so spans share the spool's arrival clock
    (req-file mtime).  A failed attempt's span closes on the way out of
    the exception."""
    many = isinstance(reqs, (list, tuple))
    ids = {"request_ids": [r.rid for r in reqs]} if many else {}
    sp = None
    try:
        with trace("service/" + name, **attrs, **ids) as sp:
            yield
    finally:
        if sp is not None:
            rec = {"name": name, "t0": sp["t0"], "ms": sp["ms"], **attrs}
            for r in (reqs if many else (reqs,)):
                r.spans.append(dict(rec))


def scan_spool(spool: str, now: float, window_s: float, stale_claim_s: float) -> Dict:
    """One queue-state pass over a spool: arrivals inside the trailing
    `window_s` (counted BEFORE the terminal skip — a request that
    arrived and completed inside one window is still offered load),
    open backlog, the claimable/in-flight split by claim freshness.
    Shared by the worker TimeseriesSampler and the fleet supervisor's
    scrape loop (pipeline.fleet_obs) — one definition of "backlog", so
    the per-worker time-series and the fleet alert signals can never
    disagree about what the queue looks like.  An unreadable spool
    degrades to zeros (observation must never raise)."""
    arrivals = backlog = claimable = in_flight = 0
    try:
        names = set(os.listdir(spool))
    except OSError:
        return {"arrivals": 0, "backlog": 0, "claimable": 0, "in_flight": 0}
    for fn in names:
        if not fn.endswith(".req.json"):
            continue
        base = fn[: -len(".req.json")]
        try:
            if window_s > 0 and now - os.path.getmtime(os.path.join(spool, fn)) <= window_s:
                arrivals += 1
        except OSError:
            pass
        if base + ".proof.json" in names or base + ".error.json" in names:
            continue
        backlog += 1
        fresh = False
        if base + ".claim" in names:
            try:
                fresh = now - os.path.getmtime(os.path.join(spool, base + ".claim")) < stale_claim_s
            except OSError:
                pass
        if fresh:
            in_flight += 1
        else:
            claimable += 1
    return {
        "arrivals": arrivals, "backlog": backlog,
        "claimable": claimable, "in_flight": in_flight,
    }


def spool_terminal(spool: str) -> bool:
    """True when every request in `spool` has a terminal artifact —
    the exit condition chaos/fleet/loadgen workers share (an unreadable
    spool reads as not-terminal: keep sweeping, don't die)."""
    try:
        names = set(os.listdir(spool))
    except OSError:
        return False
    for fn in names:
        if not fn.endswith(".req.json"):
            continue
        base = fn[: -len(".req.json")]
        if base + ".proof.json" not in names and base + ".error.json" not in names:
            return False
    return True


def _is_transient(exc: BaseException) -> bool:
    """Transient = retry may genuinely succeed: injected faults (their
    whole point), allocation pressure, and the exhaustion slice of the
    OSError class.  Everything else — bad witnesses, payloads naming
    missing files, proof-count mismatches, failed sample verification —
    is permanent and goes straight to isolation: a permanent failure
    classified transient would defer-livelock, re-claimed and re-failed
    every sweep with no terminal state ever written."""
    if isinstance(exc, (FaultInjected, MemoryError)):
        return True
    if isinstance(exc, OSError) and exc.errno is not None:
        return exc.errno in _TRANSIENT_ERRNOS
    if isinstance(exc, (RuntimeError, OSError)):
        # C/XLA-layer exhaustion carries only text (and an errno-less
        # OSError only its message); other types never marker-match —
        # a ValueError mentioning "resource" is a bad payload, not load
        return _TRANSIENT_RE.search(str(exc).lower()) is not None
    return False


@dataclass
class Request:
    path: str
    payload: Dict
    witness: Optional[list] = None
    error: Optional[str] = None
    # observability: request_id (the spool base name — unique per
    # request, stable across worker takeovers) + claim timestamp, so the
    # terminal record carries true claim->terminal latency
    rid: str = ""
    t_claim: float = 0.0
    # deadline anchor: the request file's mtime (the spool's arrival
    # clock — survives worker crashes and takeovers, unlike any
    # in-process timestamp)
    t_submit: float = 0.0
    # terminal state assigned THIS sweep (None = still open), and the
    # deliberate non-terminal outcome: a deferred request released its
    # claim for a later sweep to retry (emit failure, transient witness
    # failure) — the safety net must not terminal it
    done: Optional[str] = None
    deferred: bool = False
    # which degradation rung rescued the prove (None = fast path)
    degraded_rung: Optional[str] = None
    # slot in the batch the request was CLAIMED into (records keep the
    # original batch attribution across bisection)
    batch_index: Optional[int] = None
    # the batch size the scheduler INTENDED when this request's batch
    # was assembled (off arm: the static batch_size cap; adaptive: the
    # controller's choice) — batch_n alone cannot distinguish "low
    # load" from "controller chose small", so records carry both
    batch_target: Optional[int] = None
    # priority lane (payload `priority` key, default from the
    # ZKP2P_SCHED_PRIORITY_DEFAULT knob): "interactive" | "bulk".  The
    # static arm ignores it; the adaptive arm batches interactive-first.
    priority: str = "bulk"
    # lifecycle spans THIS sweep (witness/prove attempts/rungs/verify/
    # emit, each {name, t0, ms, ...}) — persisted on every record the
    # sweep emits, terminal or deferred, so the full waterfall survives
    # defer→re-prove cycles as one sink line per attempt
    spans: List[Dict] = field(default_factory=list)


class _BetweenSweeps:
    """What the proving thread does between one sweep's end and the next
    one's opening, as two spans: `service/poll`, the sleep of the poll
    (`run`'s `_drain.wait`), and `service/handover`, the rest (the line
    printed, the sink's flush, the sampler's and the fleet's tick, the
    spool's scan, the scheduler).  Both are timed in laps and written by
    the pass that opens a sweep, before it opens it: what the passes
    before it spent finding nothing is folded in, so a service that polls
    an empty spool writes nothing.  A folded span starts where its first
    piece started and lasts as long as its pieces together."""

    def __init__(self):
        self._at: Optional[Tuple] = None  # the last lap: (thread, time, perf_counter, thread_time)
        self._laps: Dict[str, List[float]] = {}  # stage -> [t0 of its first piece, seconds, cpu seconds]

    def lap(self, stage: Optional[str]) -> None:
        """This thread's time since its last lap counts to `stage` (None:
        to neither; it was a sweep's, or nothing was timed yet)."""
        now = (threading.current_thread(), time.time(), time.perf_counter(), time.thread_time())
        last, self._at = self._at, now
        if stage is not None and last is not None and last[0] is now[0]:
            lap = self._laps.setdefault(stage, [last[1], 0.0, 0.0])
            lap[1] += now[2] - last[2]
            lap[2] += now[3] - last[3]

    def write(self) -> None:
        for stage, (t0, secs, cpu) in self._laps.items():
            # a sum of pieces, not one interval: no `tid`, so the Perfetto view draws none
            record("service/" + stage, t0, t0 + secs, cpu_ms=round(cpu * 1e3, 3), tid=None)
        self._laps.clear()


class TimeseriesSampler:
    """Periodic service time-series: one `{"type": "timeseries", ...}`
    line per interval (ZKP2P_TS_SAMPLE_S; 0 = off) appended to the
    service's JSONL sink, so post-hoc analysis can correlate a latency
    spike with the queue state that caused it (the signal SZKP-style
    scheduling presumes and nothing here recorded before).

    Line schema (docs/OBSERVABILITY.md §time-series):
      ts / run_id / pid      identity (joins the run manifest)
      window_s               actual seconds since the previous sample
      arrivals               req files whose mtime landed in the window
      arrival_rate_hz        arrivals / window_s
      backlog                open requests (no terminal artifact yet)
      claimable              backlog minus fresh-claimed peer work
      in_flight              open requests under a fresh claim
      batch_fill_last        live size of the newest batch handed to the prover
      counters               cumulative service counters (registry values)
      native_delta           nonzero native C stat deltas since the last sample
      slo                    rolling-window SLO snapshot (utils.slo)
      hbm_*                  device-memory point sample (absent on XLA:CPU)

    One listdir + one stat per spool entry per sample — bounded by the
    spool size the admission cap already bounds; measured ≪1 ms on
    hundred-request spools."""

    def __init__(self, interval_s: float, stale_claim_s: float = 300.0):
        self.interval_s = interval_s
        self.stale_claim_s = stale_claim_s
        self.batch_fill_last = 0
        # the scheduler's intended size for the newest batch (static
        # arm: the batch_size cap) — recorded NEXT to batch_fill_last
        # so the time-series can separate "low load" (target high,
        # fill low) from "controller chose small" (target == fill)
        self.batch_target_last = 0
        self._last_ts: Optional[float] = None
        self._last_native: Dict = {}
        # fleet attribution on every line (same contract as the request
        # records): resolved once — identity cannot change under a
        # running sampler
        try:
            from ..utils.config import load_config

            cfg = load_config()
            self._worker_id, self._fleet_id = cfg.worker_id, cfg.fleet_id
        except Exception:  # noqa: BLE001 — observation only
            self._worker_id = self._fleet_id = ""

    def _scan(self, spool: str, now: float, window_s: float) -> Dict:
        # delegates to the module-level scan_spool — the fleet plane's
        # supervisor scrape uses the same function, so "backlog" means
        # one thing whether a worker or the supervisor measured it
        return scan_spool(spool, now, window_s, self.stale_claim_s)

    def maybe_sample(self, spool: str, sink: JsonlSink, force: bool = False) -> Optional[Dict]:
        """Sample when the interval elapsed (or `force`); returns the
        record (also written to `sink`) or None when off/not due.
        Failures degrade to None — observation must never stop a sweep."""
        if self.interval_s <= 0 and not force:
            return None
        now = time.time()
        if not force and self._last_ts is not None and now - self._last_ts < self.interval_s:
            return None
        try:
            window_s = (now - self._last_ts) if self._last_ts is not None else self.interval_s
            self._last_ts = now
            scan = self._scan(spool, now, window_s)
            rec: Dict = {
                "type": "timeseries",
                "ts": round(now, 3),
                "run_id": run_id(),
                "pid": os.getpid(),
                "window_s": round(window_s, 3),
                "arrival_rate_hz": round(scan["arrivals"] / window_s, 4) if window_s > 0 else 0.0,
                "batch_fill_last": self.batch_fill_last,
                "batch_size_target": self.batch_target_last,
                **scan,
            }
            if self._worker_id:
                rec["worker"] = self._worker_id
            if self._fleet_id:
                rec["fleet"] = self._fleet_id
            # cumulative service counters out of the registry (post-hoc
            # analysis diffs consecutive lines for rates)
            counters: Dict[str, float] = {}
            for m in REGISTRY.snapshot():
                name = m["name"]
                if not name.startswith("zkp2p_service_") or m["kind"] != "counter":
                    continue
                key = name[len("zkp2p_service_"):]
                if key.endswith("_total"):
                    key = key[: -len("_total")]
                lab = m["labels"]
                if lab:
                    key += "_" + "_".join(str(v) for v in lab.values())
                counters[key] = counters.get(key, 0) + m["value"]
            rec["counters"] = counters
            # live backlog gauges for the scrape (same numbers as the line)
            REGISTRY.gauge("zkp2p_service_backlog").set(scan["backlog"])
            REGISTRY.gauge("zkp2p_service_in_flight").set(scan["in_flight"])
            # native C stat deltas since the last sample, nonzero only
            try:
                from ..native.lib import stats_snapshot

                snap = stats_snapshot()
            except Exception:  # noqa: BLE001 — numpy-less env, no .so
                snap = None
            if snap:
                delta = {
                    k: v - self._last_native.get(k, 0)
                    for k, v in snap.items()
                    if v != self._last_native.get(k, 0)
                }
                self._last_native = dict(snap)
                if delta:
                    rec["native_delta"] = delta
            try:
                from ..utils.slo import default_tracker

                rec["slo"] = default_tracker().snapshot()
            except Exception:  # noqa: BLE001 — observation only
                pass
            mem = sample_device_memory("service/timeseries")
            if mem is not None:
                rec["hbm_bytes_in_use"] = mem["bytes_in_use"]
                rec["hbm_peak_bytes"] = mem["peak_bytes_in_use"]
            sink.write(rec)
            return rec
        except Exception:  # noqa: BLE001 — the sweep must not die for a sample
            return None


class ProvingService:
    def __init__(
        self,
        cs,
        dpk,
        vk,
        witness_fn: Callable[[Dict], list],
        public_fn: Callable[[list], list],
        batch_size: int = 4,
        max_wait_s: float = 2.0,
        inputs_fn: Optional[Callable[[Dict], tuple]] = None,
        prover_fn: Optional[Callable] = None,
        prefetch: int = 1,
        stale_claim_s: float = 300.0,
        deadline_s: Optional[float] = None,
        spool_cap: Optional[int] = None,
        retries: Optional[int] = None,
        retry_backoff_s: Optional[float] = None,
    ):
        """witness_fn: request payload -> witness vector (raises on bad
        input); public_fn: witness -> public signals.

        inputs_fn (optional): payload -> (public_inputs, seed); when
        given, the producer runs the whole batch through the vectorized
        `witness_batch` tier (r1cs BlockHooks) and falls back to
        per-request scalar witnessing if the batch evaluation fails.
        prover_fn (optional): (dpk, [witness]) -> [Proof]; defaults to
        the vmapped device `prove_tpu_batch` — on chip-less hosts pass
        `prover.native_prove.prove_native_batch` (the multi-column fast
        path: whole claimed batches ride ONE base sweep per G1 MSM
        family; ZKP2P_MSM_MULTI=0 degrades it to sequential proves).
        prefetch: ready-batch queue depth (witness ∥ prove overlap
        window; 1 = classic double buffering).
        stale_claim_s: concurrent workers sweeping one spool partition
        requests via O_EXCL <name>.claim files; a claim older than this
        is treated as a crashed worker's and taken over.
        deadline_s: default per-request deadline (seconds since the
        request file's mtime; a payload `deadline_s` key overrides it
        per request; None = the ZKP2P_DEADLINE_S config default; 0 =
        no deadline).
        spool_cap: pending-backlog admission cap per sweep — requests
        beyond it are shed as error-shed (None = ZKP2P_SPOOL_CAP; 0 =
        unlimited).
        retries / retry_backoff_s: bounded transient-failure retries per
        batch prove and the exponential-backoff base (None = the
        ZKP2P_PROVE_RETRIES / ZKP2P_RETRY_BACKOFF_S defaults)."""
        self.cs = cs
        # the self-check's plan, once a circuit (a second or two at 499k
        # constraints), here and not under the first request's check
        witness_check.plan_for(cs)
        self.dpk = dpk
        self.vk = vk
        self.witness_fn = witness_fn
        self.public_fn = public_fn
        self.batch_size = batch_size
        self.max_wait_s = max_wait_s
        self.inputs_fn = inputs_fn
        self.prover_fn = prover_fn
        self.prefetch = max(1, prefetch)
        self.stale_claim_s = stale_claim_s
        self.deadline_s = deadline_s
        self.spool_cap = spool_cap
        self.retries = retries
        self.retry_backoff_s = retry_backoff_s
        # per-spool rotating JSONL sinks (lazy; see _sink).  Locked:
        # the witness producer thread and the proving thread both emit
        # records, and two racing JsonlSink instances for one path
        # would rotate against each other.
        self._sinks: Dict[str, JsonlSink] = {}
        self._sinks_lock = threading.Lock()
        # knob manifest + sink override for request records, resolved
        # once per process (env-derived; cannot change under a running
        # service — and _emit_record must not re-parse the config per
        # record).  None = not yet resolved.
        self._knobs: Optional[Dict] = None
        self._sink_override: Optional[str] = None
        self._resolved = False
        # time-series sampler (run() installs one when ZKP2P_TS_SAMPLE_S
        # > 0; process_dir works standalone without it)
        self._sampler: Optional["TimeseriesSampler"] = None
        # graceful drain (docs/ROBUSTNESS.md §fleet): once set, the
        # producer claims NO new requests — in-flight batches (already
        # claimed, possibly queued in ready_q) still prove, verify, and
        # emit to their terminal states under the sweep heartbeat, so a
        # SIGTERM'd worker finishes what it owns and strands nothing.
        # run() exits after the draining sweep completes.
        self._drain = threading.Event()
        # fleet identity (ZKP2P_WORKER_ID / ZKP2P_FLEET_ID, stamped by
        # the supervisor into the worker env) — resolved with the policy
        # knobs, stamped on every record + time-series line
        self._worker_id = ""
        self._fleet_id = ""
        # adaptive scheduler (pipeline.sched, ZKP2P_SCHED=adaptive):
        # controller built lazily on the first adaptive sweep (the gate
        # is fresh-read per sweep, so one process can A/B both arms),
        # and the per-sweep decision summary the fleet heartbeat carries
        # (the `sched` block in fleet /status and `zkp2p-tpu top`)
        self._sched_ctl = None
        self._sched_hb: Optional[Dict] = None
        # a member of a replica set (pipeline.replicas; `join_set`): the
        # index of the local device this service's key lives on, carried
        # by every span its threads close and every request record; how
        # many loops of the set are up (its scheduler's peers); what to
        # call when this loop is up.  None: a solo service.
        self.replica: Optional[int] = None
        self._set_live: Optional[Callable[[], int]] = None
        self._on_loop_up: Optional[Callable[[int, Dict], None]] = None
        self._witness_turn: Optional[threading.Lock] = None  # the set's, `_in_witness_turn`
        # what a set reads when it has drained (`replicas/idle`): this
        # service's first claim and last terminal, the seconds it had a
        # batch in its prover, the batches and the proofs it served
        self.t_first_claim: Optional[float] = None
        self.t_last_terminal: Optional[float] = None
        self.busy_s = 0.0
        self.n_batches = 0
        self.n_done = 0
        self._between = _BetweenSweeps()

    def join_set(self, replica: int, live: Callable[[], int], sinks: Dict, sinks_lock,
                 on_loop_up: Callable[[int, Dict], None], witness_turn: threading.Lock) -> None:
        """Made replica `replica` of a set (pipeline.replicas.ReplicaSet),
        before `run`: the set's members write one sink a path between
        them (four JsonlSink instances on one file would rotate against
        each other), count each other as peers, take turns at the
        batched witness tier (`_in_witness_turn`), and the set stamps
        `last_preflight` itself, once every loop has called
        `on_loop_up`."""
        self.replica = replica
        self._set_live = live
        self._sinks, self._sinks_lock = sinks, sinks_lock
        self._on_loop_up = on_loop_up
        self._witness_turn = witness_turn

    @contextlib.contextmanager
    def _in_witness_turn(self, n: int):
        """One member of a set at a time inside `cs.witness_batch`.  That
        tier is thousands of short numpy calls on object columns, Python
        that holds the interpreter and hands it over at every call: four
        producers of one process inside it at once took 7.4-8.6 s each
        for what one alone does in 0.9-1.05 s (venmo 256/192, a batch of
        four, on the chip's host: PERF.md, PR 41), and every proving
        thread's `finish` waited behind them.  In turn the last of four
        has its witnesses after four times one, not nine.  The wait is
        the span `service/witness_turn` (`n`); a solo service waits for
        nobody and writes none.  The self-check after it is native code
        off the interpreter and stays outside the turn."""
        if self._witness_turn is None:
            yield
            return
        with trace("service/witness_turn", n=n):
            self._witness_turn.acquire()
        try:
            yield
        finally:
            self._witness_turn.release()

    def request_drain(self) -> None:
        """Flip the drain flag: stop claiming, finish in-flight work,
        then exit run().  Idempotent; callable from signal handlers
        (Event.set is async-signal-safe enough for CPython)."""
        self._drain.set()

    @property
    def draining(self) -> bool:
        return self._drain.is_set()

    def _resolve_policy(self) -> None:
        """Fill constructor-None policy knobs from the typed config,
        once per process (env cannot change under a running service)."""
        if self._resolved:
            return
        from ..utils.config import load_config

        cfg = load_config()
        self._deadline_default = self.deadline_s if self.deadline_s is not None else cfg.deadline_s
        self._spool_cap = self.spool_cap if self.spool_cap is not None else cfg.spool_cap
        self._retries = self.retries if self.retries is not None else cfg.prove_retries
        self._retry_backoff_s = (
            self.retry_backoff_s if self.retry_backoff_s is not None else cfg.retry_backoff_s
        )
        self._worker_id = cfg.worker_id
        self._fleet_id = cfg.fleet_id
        self._fleet_dir = cfg.fleet_dir
        self._priority_default = (
            "interactive" if cfg.sched_priority_default == "interactive" else "bulk"
        )
        self._resolved = True

    # a heartbeat younger than this marks a LIVE fleet peer (the hb
    # thread beats every ~5 s; 3 beats of slack before a peer stops
    # counting toward the scheduler's parallelism)
    _PEER_HB_FRESH_S = 15.0

    def _live_peers(self) -> int:
        """Live workers sharing this spool (self included): fresh
        heartbeat files in the fleet dir, one a process, and the other
        replicas of this process's set whose loops are up — the
        scheduler's parallelism: N workers pull ONE queue, so a worker
        predicting completion times as if it served the whole backlog
        alone would shed requests its peers could still serve.  Solo
        service (no fleet dir, no set) = 1; an unreadable dir degrades
        to this process's own count (predictions turn conservative,
        never wrong-side)."""
        mine = max(1, self._set_live()) if self._set_live is not None else 1
        if not getattr(self, "_fleet_dir", ""):
            return mine
        n = 0
        now = time.time()
        try:
            for fn in os.listdir(self._fleet_dir):
                if not fn.endswith(".hb"):
                    continue
                try:
                    if now - os.path.getmtime(os.path.join(self._fleet_dir, fn)) < self._PEER_HB_FRESH_S:
                        n += 1
                except OSError:
                    pass
        except OSError:
            return mine
        return max(1, n) - 1 + mine

    def _live_peer_tiers(self) -> List[str]:
        """Advertised tiers of live fleet peers (self EXCLUDED), from
        the `tier` field of fresh heartbeat JSON.  Feeds the scheduler's
        heterogeneous routing: a native worker seeing a live "sharded"
        peer defers its bulk lane to it (and vice versa for
        interactive).  Solo service or unreadable heartbeats = [] — the
        scheduler then serves both lanes itself, so a torn/legacy hb
        (no tier field) degrades to homogeneous routing, never to a
        starved lane."""
        if not getattr(self, "_fleet_dir", ""):
            return []
        my_wid = getattr(self, "_worker_id", "") or ""
        tiers: List[str] = []
        now = time.time()
        try:
            for fn in os.listdir(self._fleet_dir):
                if not fn.endswith(".hb") or fn == my_wid + ".hb":
                    continue
                path = os.path.join(self._fleet_dir, fn)
                try:
                    if now - os.path.getmtime(path) >= self._PEER_HB_FRESH_S:
                        continue
                    with open(path) as f:
                        hb = json.load(f)
                    tier = hb.get("tier")
                    if isinstance(tier, str) and tier:
                        tiers.append(tier)
                except (OSError, ValueError):
                    pass  # torn write / legacy hb: peer counts for parallelism, not routing
        except OSError:
            return []
        return tiers

    def _sched_controller(self):
        """The lazily-built BatchController (adaptive arm only).  The
        amortization model and objective are resolved once per process —
        calibration cannot change under a running service; the GATE
        stays fresh-read per sweep.  Resolution (sched.build_controller):
        explicit ZKP2P_SCHED_AMORT -> tuned host-profile points (the
        controller starts CALIBRATED — the points were measured on this
        hardware) -> built-in venmo curve with warm-up."""
        if self._sched_ctl is None:
            from ..utils.config import load_config
            from .sched import build_controller

            self._sched_ctl = build_controller(load_config())
        return self._sched_ctl

    # -------------------------------------------------------- observability
    #
    # Every request's terminal transition is RECORDED, not just counted:
    # one JSONL line per request (request_id, state, claim->terminal ms,
    # run_id/pid, the full knob manifest) in a rotating sink next to the
    # spool, aggregatable offline by tools/trace_report.py.  The env-level
    # ZKP2P_METRICS_SINK override redirects all spools to one path.

    def _sink(self, spool: str) -> JsonlSink:
        # keyed by the RESOLVED path, not the spool: a ZKP2P_METRICS_SINK
        # override funnels every spool into one file, which must mean one
        # JsonlSink instance (two would race each other's rotation)
        with self._sinks_lock:
            if self._sink_override is None:
                from ..utils.config import load_config

                self._sink_override = load_config().metrics_sink  # "" = per-spool
            path = self._sink_override or (spool.rstrip("/") + ".metrics.jsonl")
            s = self._sinks.get(path)
            if s is None:
                s = self._sinks[path] = JsonlSink(path)
            return s

    def _emit_record(
        self,
        spool: str,
        req: Request,
        state: str,
        knobs: Dict,
        batch_index: Optional[int] = None,
        batch_n: Optional[int] = None,
        **extra,
    ) -> None:
        try:
            fault_point("sink")
            rec = {
                "type": "request",
                "ts": round(time.time(), 3),
                "run_id": run_id(),
                "pid": os.getpid(),
                "request_id": req.rid,
                "state": state,
                "ms": round((time.time() - req.t_claim) * 1e3, 3) if req.t_claim else None,
                "knobs": knobs,
                # which code paths this process has exercised (the audit
                # gate→arm map hash): two requests are comparable only
                # when their digests match — see docs/OBSERVABILITY.md
                "execution_digest": execution_digest(),
            }
            # fleet attribution: which worker of which fleet produced
            # this record — pids recycle across restarts, worker ids
            # don't, so trace_report groups waterfall rows by worker
            if self.replica is not None:
                rec["replica"] = self.replica
            if self._worker_id:
                rec["worker"] = self._worker_id
            if self._fleet_id:
                rec["fleet"] = self._fleet_id
            # batched-prove attribution: which slot of which batch this
            # request rode, so trace_report can split a batch's prove
            # latency across its requests (a batch=4 multi-column prove
            # is ONE service/prove span covering four terminal records)
            if batch_index is not None:
                rec["batch_index"] = batch_index
            if batch_n is not None:
                rec["batch_n"] = batch_n
            # the scheduler's INTENDED batch size when this request was
            # assembled (off arm: the static cap): batch_n alone reads
            # the same for "low load" and "controller chose small"
            if req.batch_target is not None:
                rec["batch_size_target"] = req.batch_target
            # request waterfall: absolute arrival/claim timestamps, the
            # queue-wait they bound, and this sweep's lifecycle spans.
            # queue_wait_s is anchored to the req-file mtime, so across
            # defer→re-prove cycles (and worker takeovers) it is the
            # CUMULATIVE wait since the request entered the spool, not
            # this attempt's slice.
            if req.t_submit:
                rec["t_submit"] = round(req.t_submit, 6)
            if req.t_claim:
                rec["t_claim"] = round(req.t_claim, 6)
                if req.t_submit:
                    rec["queue_wait_s"] = round(max(0.0, req.t_claim - req.t_submit), 6)
            if req.spans:
                rec["spans"] = req.spans
            if extra:
                rec.update(extra)
            if req.error:
                rec["error"] = req.error[:500]
            # flight recorder: HBM watermark at terminal time.  NOTE
            # peak_bytes_in_use is the PROCESS-lifetime high-water mark
            # (PJRT exposes no per-interval peak/reset), so the first
            # record whose peak jumps names the request class that
            # raised the ceiling; in_use is the live point sample.
            # Absent on stats-less backends (XLA:CPU).
            mem = sample_device_memory("service/request")
            if mem is not None:
                rec["hbm_peak_bytes"] = mem["peak_bytes_in_use"]
                rec["hbm_bytes_in_use"] = mem["bytes_in_use"]
            self._sink(spool).write(rec)
        except Exception:  # noqa: BLE001 — observation must never fail a prove
            pass
        if state in TERMINAL_STATES:
            REGISTRY.counter("zkp2p_service_requests_total", {"state": state}).inc()
            self.t_last_terminal = time.time()
            self.n_done += state == "done"
            # SLO accounting: full-life latency (spool arrival ->
            # terminal) into the rolling-window tracker; only `done`
            # counts as good (docs/OBSERVABILITY.md §SLO).  The anchor
            # falls back to claim time for requests with no readable
            # arrival mtime (torn uploads).
            # observe() only here — O(1).  The zkp2p_slo_* gauges are
            # refreshed where they are READ (the /metrics scrape and the
            # time-series sampler both snapshot): a per-terminal
            # publish_slo() would sort the whole rolling window (tens of
            # thousands of samples at saturation) on every request.
            try:
                from ..utils.slo import default_tracker

                anchor = req.t_submit or req.t_claim
                if anchor:
                    default_tracker().observe(time.time() - anchor, ok=(state == "done"))
            except Exception:  # noqa: BLE001 — observation only
                pass
        else:
            # non-terminal sweep outcome (deferred): its own counter —
            # requests_total stays one-inc-per-TERMINAL-transition
            REGISTRY.counter("zkp2p_service_deferred_total").inc()

    def _record_deferred(
        self,
        spool: str,
        req: Request,
        reason: object,
        knobs: Dict,
        batch_index: Optional[int] = None,
        batch_n: Optional[int] = None,
    ) -> None:
        """Record a NON-terminal sweep outcome: the claim was released
        for a later sweep to retry (transient witness/emit failure,
        error-artifact write failure).  One `state="deferred"` line per
        attempt — with that attempt's spans and the cumulative
        queue_wait_s — so the request's full history survives
        defer→re-prove cycles: the eventual terminal record alone would
        erase every earlier attempt from the timeline."""
        self._emit_record(
            spool, req, "deferred", knobs,
            batch_index=batch_index, batch_n=batch_n,
            deferred_reason=str(reason)[:200],
        )

    # ------------------------------------------------------------- claims
    #
    # Crash/restart and multi-worker semantics (the service-level mirror
    # of the reference's claim-with-expiry escrow pattern,
    # `Ramp.sol:144` + `clawback`): a worker that dies mid-prove leaves
    # a .claim file but no terminal output; any later sweep — same
    # worker restarted or a peer — takes the request over once the claim
    # is stale.  Terminal outputs (.proof/.error) always win over
    # claims, so a request is never reprocessed after completion.

    def _try_claim(self, base_path: str) -> bool:
        # Terminal outputs are re-checked at CLAIM time, not just at scan
        # time: a peer may have completed this request (proof emitted,
        # claim released) between our scan and our dequeue — re-claiming
        # it would duplicate the prove and double-count `done`.  A
        # microscopic emit-between-check-and-claim window remains
        # (at-least-once, never wrong: terminal writes are atomic and any
        # duplicate proof still verifies).
        if os.path.exists(base_path + ".proof.json") or os.path.exists(base_path + ".error.json"):
            return False
        claim = base_path + ".claim"
        try:
            fault_point("claim")
            fd = os.open(claim, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
        except FileExistsError:
            try:
                age = time.time() - os.path.getmtime(claim)
            except OSError:
                return False  # vanished: owner just completed it
            if age < self.stale_claim_s:
                # a peer's fresh claim: an attempt lost (replicas and
                # fleet workers that scan one backlog lose most of
                # what they try)
                REGISTRY.counter("zkp2p_service_claim_lost_total").inc()
                return False
            # Stale claim: STEAL it by renaming it aside — rename is
            # atomic and the kernel picks exactly ONE winner (every
            # other taker's rename of the same source gets ENOENT and
            # backs off; a replace-in-place scheme would let two takers
            # each read back their own replace and both "win").  The
            # winner then re-creates the claim O_EXCL with ITS pid/ts —
            # the old refresh-mtime takeover left the dead worker's
            # identity in the file, so `cat *.claim` lied about who
            # owns in-flight work.
            stale_aside = f"{claim}.stale.{os.getpid()}"
            try:
                # last-moment re-check: if the claim was refreshed or
                # rewritten since our stat (owner alive after all, or a
                # faster taker already won), it is not ours to steal
                if time.time() - os.path.getmtime(claim) < self.stale_claim_s:
                    return False
                os.rename(claim, stale_aside)
            except OSError:
                # the kernel picked another taker (or the owner just
                # completed): a steal ATTEMPTED and lost — counted, so
                # production can watch takeover contention (PR 7 built
                # the mechanism; this is the meter on it)
                REGISTRY.counter("zkp2p_service_takeovers_total", {"result": "lost"}).inc()
                return False
            REGISTRY.counter("zkp2p_service_takeovers_total", {"result": "won"}).inc()
            try:
                os.unlink(stale_aside)
            except OSError:
                pass
            try:
                fd = os.open(claim, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
            except OSError:
                # an opportunistic claimer slipped into the freed slot
                # first — still exactly one owner, just not us
                return False
            try:
                with os.fdopen(fd, "w") as f:
                    f.write(json.dumps({"pid": os.getpid(), "ts": time.time(), "takeover": True}))
            except OSError:
                pass  # ownership = existence + mtime; identity is debug info
            # The old owner may have COMPLETED inside the stale-check →
            # steal window (it never re-checks its stolen claim;
            # terminal write, then its release unlinks OUR claim).
            # Terminal outputs always win: back off instead of
            # re-proving finished work and emitting a duplicate
            # terminal record.  (The pre-rewrite utime-based takeover
            # failed closed here with ENOENT; this re-check keeps that
            # behavior.)
            if os.path.exists(base_path + ".proof.json") or os.path.exists(base_path + ".error.json"):
                self._release_claim(base_path)
                return False
            return True
        except (OSError, FaultInjected):
            # claim-write failure (full disk, injected fault): the
            # request is simply not ours this sweep — a later sweep
            # retries; a claim failure must never kill the whole scan
            return False
        try:
            with os.fdopen(fd, "w") as f:
                f.write(json.dumps({"pid": os.getpid(), "ts": time.time()}))
        except OSError:
            # ownership = the file's existence + mtime; the identity
            # payload is best-effort debugging info
            pass
        # same completed-while-we-raced re-check as the steal path: a
        # peer may have emitted + released between our top-of-function
        # artifact check and the O_EXCL create landing on the freed slot
        if os.path.exists(base_path + ".proof.json") or os.path.exists(base_path + ".error.json"):
            self._release_claim(base_path)
            return False
        return True

    @staticmethod
    def _release_claim(base_path: str) -> None:
        try:
            os.unlink(base_path + ".claim")
        except OSError:
            pass

    # ---------------------------------------------------------- deadlines

    def _deadline_of(self, req: Request) -> Optional[float]:
        """Absolute wall-clock deadline for a request, or None.  The
        payload's `deadline_s` wins over the service default; both are
        seconds since the request file's mtime (the spool arrival clock,
        stable across worker crashes).  A malformed payload deadline
        degrades to the service default rather than killing the request
        (the witness builder will judge the payload)."""
        d = None
        if isinstance(req.payload, dict):
            d = req.payload.get("deadline_s")
        try:
            d = float(d) if d is not None else None
        except (TypeError, ValueError):
            d = None
        if d is None:
            d = self._deadline_default
        if not d or d <= 0 or not req.t_submit:
            return None
        return req.t_submit + d

    # ------------------------------------------------------ self-check

    def _self_check(self, ws: list, records=None) -> None:
        """Az∘Bz = Cz and every width tag, on EVERY witness of `ws`, before
        its batch is queued for the prover: a witness that fails raises
        `ConstraintSystem.check_witness`'s AssertionError, whichever path
        found it (snark.witness_check: the native products where the
        library is loaded and the witnesses carry their u64 rows, else
        the Python loop).  `records`: the batch whose request records take
        the span, where nothing else covers the check.  Without it (the
        scalar tier, whose `witness` span is open around this one) the
        span goes to the trace sink alone — two nested labels in the
        records would claim the same idle seconds twice in a gap
        attribution."""
        path = witness_check.path_for(self.cs, ws)
        attrs = {"n": len(ws), "path": path}
        span = trace("service/witness_check", **attrs) if records is None else _span(records, "witness_check", **attrs)
        checked = REGISTRY.counter("zkp2p_service_witness_check_total", {"path": path})
        with span:
            for w in ws:
                checked.inc()
                witness_check.check_witness(self.cs, w, path)

    # ------------------------------------------------------ terminal emit

    def _terminal_error(
        self,
        spool: str,
        req: Request,
        state: str,
        exc: BaseException,
        knobs: Dict,
        stats: Dict[str, int],
        batch_index: Optional[int] = None,
        batch_n: Optional[int] = None,
    ) -> bool:
        """Terminal a request into an error state: atomic .error.json
        artifact, claim release, request record, counter.  Returns False
        when the artifact itself cannot be written (disk full): the
        request is left NON-terminal (claim released) for a later sweep
        rather than half-terminal."""
        req.error = f"{state}: {exc}"
        try:
            self._emit_error(req, state, exc)
        except Exception:  # noqa: BLE001 — the error artifact failed to write
            self._release_claim(req.path)
            req.deferred = True
            # best-effort deferred record (the sink may sit on the same
            # full disk — _emit_record swallows its own failures)
            self._record_deferred(
                spool, req, f"error-artifact write failed for {state}", knobs,
                batch_index=batch_index, batch_n=batch_n,
            )
            return False
        self._emit_record(spool, req, state, knobs, batch_index=batch_index, batch_n=batch_n)
        req.done = state
        stats[state] += 1
        return True

    # ------------------------------------------------- resilient proving
    #
    # The retry -> bisect -> degrade ladder (docs/ROBUSTNESS.md).  All
    # of it runs on the consumer thread under the batch's heartbeat, so
    # claim age stays bounded however long the rescue takes.

    def _prove_verified(
        self, batch: List[Request], attempt: int = 0, rung: Optional[str] = None,
    ) -> list:
        """One prover call over `batch` + the sample verify.  Raises on
        ANY failure — including a prover that returns the wrong number
        of proofs, which a bare zip() would silently truncate.
        `attempt`/`rung` label this call's lifecycle span so retries,
        bisection halves, and degradation rungs all show as child spans
        on the request waterfall (failed attempts included — the span
        closes on the way out of the exception)."""
        from ..prover.groth16_tpu import prove_tpu_batch

        span_attrs: Dict = {"n": len(batch)}
        if attempt:
            span_attrs["attempt"] = attempt
        if rung:
            span_attrs["rung"] = rung
        witnesses = [r.witness for r in batch]
        if self.prover_fn is None:
            # The device prover lowers and compiles a program a batch shape
            # (ROADMAP R2: minutes each), so a batch short of the size it
            # was claimed for (a sweep's remainder, a peer's lost claims,
            # a bisected half) proves at that size, its last witness
            # repeated: device time a full batch's, nothing compiled.
            short = max((r.batch_target or 0 for r in batch), default=0) - len(batch)
            witnesses += witnesses[-1:] * max(0, short)
        t_busy = time.perf_counter()
        try:
            with _span(batch, "prove", **span_attrs):
                fault_point("prove")
                prove = self.prover_fn or prove_tpu_batch
                proofs = prove(self.dpk, witnesses)
        finally:
            self.busy_s += time.perf_counter() - t_busy
        proofs = list(proofs) if proofs is not None else []
        if len(proofs) != len(witnesses):
            raise RuntimeError(
                f"prover returned {len(proofs)} proofs for a batch of {len(witnesses)}"
            )
        del proofs[len(batch):]  # what the padding proved
        # snark.native_verify: the whole equation in the native library
        # where it is loaded, else snark.groth16.verify, which also speaks
        # for every native False
        path = native_verify.path_for()
        with _span(batch, "verify", path=path):
            fault_point("verify")
            sample_pub = self.public_fn(batch[0].witness)
            REGISTRY.counter("zkp2p_service_verify_total", {"path": path}).inc()
            ok, overruled = native_verify.verify(self.vk, proofs[0], sample_pub, path)
            if overruled:
                REGISTRY.counter("zkp2p_service_verify_disagree_total").inc()
                print(
                    f"[service] the native verify refused the sample proof of {batch[0].rid} and "
                    "snark.groth16.verify accepts it: the two disagree, the Python answer stands",
                    file=sys.stderr, flush=True,
                )
            if not ok:
                raise RuntimeError("sample proof failed verification")
        return proofs

    def _prove_with_retries(self, batch: List[Request]) -> list:
        """Bounded transient-failure retries with exponential backoff.
        Permanent failures (bad witness, count mismatch, verify fail)
        raise immediately — retrying them would only burn deadline."""
        attempt = 0
        while True:
            try:
                return self._prove_verified(batch, attempt=attempt)
            except Exception as e:  # noqa: BLE001 — classified below
                if attempt >= self._retries or not _is_transient(e):
                    raise
                attempt += 1
                REGISTRY.counter("zkp2p_service_retries_total").inc()
                delay = min(self._retry_backoff_s * (2 ** (attempt - 1)), 30.0)
                if delay > 0:
                    # backoff is part of the request's latency story:
                    # span it so the waterfall shows waiting, not a gap
                    with _span(batch, "retry_backoff", attempt=attempt):
                        time.sleep(delay)

    def _degraded_prove(self, batch: List[Request], cause: BaseException):
        """Last resort before error-failed-to-prove: walk the
        degradation ladder, one attempt per rung, each with one more
        fast path gated off via the (fresh-read) knob env.  Returns
        (proofs, rung) on the first success; re-raises the final rung's
        failure.  Only provers that actually READ the knobs get the
        ladder (prover fns marked `reads_msm_knobs` — native_prove sets
        it): for any other prover every rung would re-run the IDENTICAL
        prove, wasting full proves and misattributing a flaky success
        to the rung."""
        prove = self.prover_fn
        if prove is None or not getattr(prove, "reads_msm_knobs", False):
            raise cause
        last: BaseException = cause
        for rung, overlay in _DEGRADATION_LADDER:
            saved = {k: os.environ.get(k) for k in overlay}
            os.environ.update(overlay)
            try:
                proofs = self._prove_verified(batch, rung=rung)
                REGISTRY.counter("zkp2p_service_degraded_total", {"rung": rung}).inc()
                return proofs, rung
            except Exception as e:  # noqa: BLE001 — try the next rung
                last = e
            finally:
                for k, v in saved.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
        raise last

    def _prove_isolating(
        self,
        spool: str,
        batch: List[Request],
        knobs: Dict,
        stats: Dict[str, int],
        batch_n: int,
    ) -> None:
        """Prove `batch`, terminal-ing EVERY member exactly once: on
        failure the batch is bisected and the halves re-proven (a
        poisoned request costs each batchmate at most log2(S) extra
        proves), singles walk the degradation ladder before accepting
        error-failed-to-prove."""
        try:
            proofs = self._prove_with_retries(batch)
        except Exception as e:  # noqa: BLE001 — isolate below
            if len(batch) == 1:
                req = batch[0]
                try:
                    proofs, rung = self._degraded_prove(batch, e)
                    req.degraded_rung = rung
                except Exception as e2:  # noqa: BLE001 — truly failed
                    self._terminal_error(
                        spool, req, "error-failed-to-prove", e2, knobs, stats,
                        batch_index=req.batch_index, batch_n=batch_n,
                    )
                    return
            else:
                del e
                REGISTRY.counter("zkp2p_service_bisections_total").inc()
                mid = (len(batch) + 1) // 2
                self._prove_isolating(spool, batch[:mid], knobs, stats, batch_n)
                self._prove_isolating(spool, batch[mid:], knobs, stats, batch_n)
                return
        self._emit_done_batch(spool, batch, proofs, knobs, stats, batch_n)

    def _emit_done_batch(
        self,
        spool: str,
        batch: List[Request],
        proofs: list,
        knobs: Dict,
        stats: Dict[str, int],
        batch_n: int,
    ) -> None:
        from ..formats.proof_json import proof_to_json, public_to_json

        for req, proof in zip(batch, proofs):
            set_context(request_id=req.rid)
            try:
                try:
                    with _span(req, "emit"):
                        fault_point("emit")
                        # public first, proof last: the sweep treats
                        # .proof.json as the done marker, so a crash
                        # between the two atomic writes leaves a
                        # retryable request, never a proof without its
                        # public signals
                        dump(public_to_json(self.public_fn(req.witness)), req.path + ".public.json")
                        dump(proof_to_json(proof), req.path + ".proof.json")
                except Exception as e:  # noqa: BLE001 — emit failure is per-request
                    REGISTRY.counter("zkp2p_service_emit_failures_total").inc()
                    if _is_transient(e):
                        # disk full / injected ENOSPC: the proof is
                        # valid but unrecorded — and writing .error.json
                        # would fail on the same full disk — so the
                        # request stays NON-terminal: claim released, a
                        # later sweep re-proves it (at-least-once).  Its
                        # batchmates continue below.  The attempt still
                        # leaves a deferred record, so the waterfall
                        # keeps the prove this sweep paid for.
                        req.deferred = True
                        self._release_claim(req.path)
                        self._record_deferred(
                            spool, req, f"transient emit failure: {e}", knobs,
                            batch_index=req.batch_index, batch_n=batch_n,
                        )
                    else:
                        # deterministic emit-time failure (public_fn
                        # compute error): deferring would livelock the
                        # spool re-proving it forever — terminal it,
                        # exactly one record
                        self._terminal_error(
                            spool, req, "error-failed-to-prove", e, knobs, stats,
                            batch_index=req.batch_index, batch_n=batch_n,
                        )
                    continue
            finally:
                set_context(request_id=None)
            self._release_claim(req.path)
            extra = {"degraded_rung": req.degraded_rung} if req.degraded_rung else {}
            self._emit_record(
                spool, req, "done", knobs,
                batch_index=req.batch_index, batch_n=batch_n, **extra,
            )
            req.done = "done"
            stats["done"] += 1

    # --------------------------------------------------------- scheduler

    def _sched_sweep(self, spool: str, pending: List[Request], knobs: Dict, stats: Dict[str, int]) -> List[List[Request]]:
        """Adaptive-arm sweep planning (pipeline.sched): update the
        arrival EWMA, shed by expected deadline miss (+ admission cap by
        least slack), partition the survivors into lane-sorted batches.
        Applies the shed verdicts (claim -> error-shed terminal, counted
        per verdict) and publishes the decision telemetry: the
        zkp2p_sched_batch_size gauge, zkp2p_sched_decisions_total{kind}
        counters, one {"type": "sched"} line in the service sink, and
        the heartbeat `sched` block fleet /status renders."""
        from .sched import SchedRequest

        ctl = self._sched_controller()
        now = time.time()
        by_rid: Dict[str, Request] = {r.rid: r for r in pending}
        sreqs = [
            SchedRequest(
                rid=r.rid, t_submit=r.t_submit, deadline=self._deadline_of(r),
                interactive=(r.priority == "interactive"),
            )
            for r in pending
        ]
        peers = self._live_peers()
        peer_tiers = self._live_peer_tiers()
        plan = ctl.plan(
            now, sreqs, cap=max(1, self.batch_size),
            spool_cap=self._spool_cap or 0,
            # never shed while draining — same rule as the static arm
            allow_shed=not self._drain.is_set(),
            # fleet peers share this queue: predictions must not model
            # the whole backlog as served by this worker alone
            parallelism=peers,
            # heterogeneous routing: live peers' advertised tiers — a
            # native worker defers bulk to a live sharded peer (and a
            # sharded worker defers interactive to a native one).
            # Deferred requests stay UNCLAIMED in the spool for the
            # peer; they are never shed by this worker.
            peer_tiers=peer_tiers,
        )
        backlog = len(pending)
        for sr, reason in plan.shed:
            r = by_rid[sr.rid]
            if not self._try_claim(r.path):
                continue  # a peer is on it — not ours to shed
            r.t_claim = time.time()
            # counter only on a SUCCESSFUL terminal (a failed error-
            # artifact write defers the request — same rule as the
            # static cap shed)
            if self._terminal_error(
                spool, r, "error-shed",
                RuntimeError(f"sched: {reason} (backlog {backlog})"),
                knobs, stats,
            ):
                REGISTRY.counter("zkp2p_service_shed_total").inc()
                REGISTRY.counter("zkp2p_sched_decisions_total", {"kind": "shed"}).inc()
        REGISTRY.gauge("zkp2p_sched_batch_size").set(plan.batch_target)
        if plan.batches:
            REGISTRY.counter("zkp2p_sched_decisions_total", {"kind": "batch"}).inc(len(plan.batches))
        if plan.lanes.get("interactive"):
            REGISTRY.counter("zkp2p_sched_decisions_total", {"kind": "lane"}).inc()
        if plan.deferred:
            # lane handoff to a tier peer: the requests stay unclaimed
            # in the spool — count the DECISION (per sweep, per lane),
            # not the requests, so the counter reads "how often routing
            # engaged", aggregatable against the sched sink lines
            REGISTRY.counter("zkp2p_sched_decisions_total", {"kind": "defer"}).inc(len(plan.deferred))
        if plan.tier_fallback:
            # a sharded peer vanished while bulk work was pending: this
            # native worker resumes the bulk lane — the counted,
            # alertable "tier degraded to native" event
            REGISTRY.counter("zkp2p_sched_decisions_total", {"kind": "tier_fallback"}).inc()
        if self._sampler is not None:
            self._sampler.batch_target_last = plan.batch_target
        self._sched_hb = {
            "mode": "adaptive",
            "batch_target": plan.batch_target,
            "interactive_target": plan.interactive_target,
            "lane_interactive": plan.lanes.get("interactive", 0),
            "lane_bulk": plan.lanes.get("bulk", 0),
            "rate_hz": plan.rate_hz,
            "peers": peers,
            "tier": plan.tier,
        }
        if plan.deferred:
            self._sched_hb["deferred"] = dict(plan.deferred)
        if pending:
            # one decision line per sweep with queue activity: every
            # sizing/shed choice is auditable offline, next to the
            # request records it shaped
            try:
                rec: Dict = {
                    "type": "sched", "ts": round(now, 3),
                    "run_id": run_id(), "pid": os.getpid(),
                    "backlog": backlog,
                    "rate_hz": plan.rate_hz,
                    "oldest_wait_s": plan.oldest_wait_s,
                    "batch_target": plan.batch_target,
                    "batch_reason": plan.batch_reason,
                    "interactive_target": plan.interactive_target,
                    "lanes": plan.lanes,
                    "batches": len(plan.batches),
                    "shed": len(plan.shed),
                    "peers": peers,
                    "tier": plan.tier,
                }
                if peer_tiers:
                    rec["peer_tiers"] = peer_tiers
                if plan.deferred:
                    rec["deferred"] = dict(plan.deferred)
                if plan.tier_fallback:
                    rec["tier_fallback"] = True
                if self._worker_id:
                    rec["worker"] = self._worker_id
                if self._fleet_id:
                    rec["fleet"] = self._fleet_id
                self._sink(spool).write(rec)
            except Exception:  # noqa: BLE001 — observation must never stop a sweep
                pass
        return [[by_rid[sr.rid] for sr in b] for b in plan.batches]

    # ------------------------------------------------------------ one pass

    def process_dir(self, spool: str) -> Dict[str, int]:
        """One spool sweep; returns counters. Files: <name>.req.json in,
        <name>.proof.json / <name>.error.json out."""
        self._resolve_policy()
        if self.replica is not None:
            set_context(replica=self.replica)  # on every span this sweep's threads close
        t_sweep = time.time()
        stats = {s: 0 for s in TERMINAL_STATES}
        # draining before the sweep even starts: claim nothing, scan
        # nothing — the spool belongs to the peers now
        if self._drain.is_set():
            return stats
        # knob manifest stamped on every request record (the acceptance
        # contract: a record is attributable without joining against a
        # separate manifest line) — resolved once per process, not per
        # sweep: an idle 1 s poll loop must not re-read /proc/cpuinfo
        # and re-parse the config every tick
        if self._knobs is None:
            self._knobs = run_manifest()["knobs"]
        knobs = self._knobs
        # scheduler gate (pipeline.sched): fresh-read per sweep AND
        # record_arm'd, so adaptive-vs-off A/Bs are digest-
        # distinguishable and one process can flip arms between sweeps.
        # "off" keeps every decision below byte-for-byte the static
        # path (fixed batch_size slicing, newest-first cap shed).
        from .sched import sched_mode

        adaptive = sched_mode() == "adaptive"
        def scan() -> List[Request]:
            found: List[Request] = []
            for fn in sorted(os.listdir(spool)):
                if ".claim.stale." in fn:
                    # scavenge steal-aside litter: a taker SIGKILLed between
                    # its rename and its unlink leaves this behind, and no
                    # other path ever matches the name
                    p = os.path.join(spool, fn)
                    try:
                        if time.time() - os.path.getmtime(p) > self.stale_claim_s:
                            os.unlink(p)
                    except OSError:
                        pass
                    continue
                if not fn.endswith(".req.json"):
                    continue
                base = fn[: -len(".req.json")]
                if os.path.exists(os.path.join(spool, base + ".proof.json")) or os.path.exists(
                    os.path.join(spool, base + ".error.json")
                ):
                    self._release_claim(os.path.join(spool, base))
                    continue
                # a FRESH claim = a peer is on it right now: not claimable
                # this sweep, and counting it as backlog would let the
                # admission cap shed viable requests off an inflated number
                # (stale claims pass through — they are takeover candidates)
                try:
                    if time.time() - os.path.getmtime(os.path.join(spool, base + ".claim")) < self.stale_claim_s:
                        continue
                except OSError:
                    pass  # no claim: free for the taking
                fpath = os.path.join(spool, fn)
                try:
                    with open(fpath) as f:
                        payload = json.load(f)
                except ValueError as e:
                    # torn/malformed .req.json (half-written upload,
                    # truncated copy): terminal it as error-bad-input and
                    # KEEP SWEEPING — one corrupt file must not sink the
                    # sweep and every batchmate behind it.  A YOUNG torn
                    # file gets the benefit of the doubt first: a
                    # non-atomic uploader (scp, cp) may still be writing
                    # it, and a permanent terminal on a request that was
                    # about to become valid is unrecoverable.
                    try:
                        if time.time() - os.path.getmtime(fpath) < TORN_REQ_GRACE_S:
                            continue  # may still be mid-write: next sweep judges it
                    except OSError:
                        continue  # vanished: nothing to judge
                    req = Request(path=os.path.join(spool, base), payload={}, rid=base)
                    if self._try_claim(req.path):
                        req.t_claim = time.time()
                        self._terminal_error(spool, req, "error-bad-input", e, knobs, stats)
                    continue
                except OSError:
                    continue  # vanished/unreadable this sweep: retry next sweep
                try:
                    t_submit = os.path.getmtime(fpath)
                except OSError:
                    t_submit = time.time()
                # priority lane: explicit payload value wins, anything
                # unrecognized falls to the configured default (bulk) — a
                # typo'd priority must not mint a third lane
                prio = payload.get("priority") if isinstance(payload, dict) else None
                if prio not in ("interactive", "bulk"):
                    prio = self._priority_default
                found.append(
                    Request(
                        path=os.path.join(spool, base), payload=payload, rid=base,
                        t_submit=t_submit, priority=prio,
                    )
                )
            return found

        pending = scan()
        if pending:
            # a burst still arriving: callers that submit together take a few
            # milliseconds to write their requests, and a listing between two
            # of them would cut the burst into a full batch and a padded one
            # (at 40 s a batch, PERF.md §6, PR 45) — let the youngest settle
            # and list once more
            age = time.time() - max(r.t_submit for r in pending)
            if 0 <= age < BURST_SETTLE_S:
                time.sleep(BURST_SETTLE_S - age)
                pending = scan()

        # Admission control.  Adaptive arm: the controller plans the
        # whole sweep — expected-deadline-miss shedding (shed exactly
        # what the amortization model predicts cannot finish, never
        # what still can), lane-sorted batch partition, SLO-sized
        # batches (pipeline.sched; docs/SCHEDULING.md).  Static arm:
        # a backlog beyond the cap is SHED newest-first (the oldest
        # are closest to their deadlines and already aged in the
        # spool), each with a visible error-shed terminal + counter,
        # instead of silently aging until every deadline in the queue
        # is dead on arrival.
        # (never shed while draining: this worker is leaving — terminal-
        # erroring backlog a surviving peer could serve would turn a
        # routine restart into dropped requests)
        batch_plan: Optional[List[List[Request]]] = None
        if adaptive:
            batch_plan = self._sched_sweep(spool, pending, knobs, stats)
        elif self._spool_cap and len(pending) > self._spool_cap and not self._drain.is_set():
            backlog = len(pending)
            pending.sort(key=lambda r: (r.t_submit, r.rid))
            keep, shed = pending[: self._spool_cap], pending[self._spool_cap:]
            for r in shed:
                if not self._try_claim(r.path):
                    continue  # a peer is on it — not ours to shed
                r.t_claim = time.time()
                # counter only on a SUCCESSFUL terminal: a failed
                # error-artifact write defers the request, and the next
                # sweep would shed-count it again
                if self._terminal_error(
                    spool, r, "error-shed",
                    RuntimeError(f"spool backlog {backlog} over admission cap {self._spool_cap}"),
                    knobs, stats,
                ):
                    REGISTRY.counter("zkp2p_service_shed_total").inc()
            pending = sorted(keep, key=lambda r: r.rid)

        if not adaptive:
            # static-arm telemetry: the target IS the cap — recorded so
            # the time-series and fleet `sched` view stay comparable
            # across arms (fill < target reads as low load here)
            if self._sampler is not None:
                self._sampler.batch_target_last = self.batch_size
            self._sched_hb = {"mode": "off", "batch_target": self.batch_size}

        # Pipeline overlap (SURVEY.md §2.7 "witness ∥ prove"): witness
        # generation is host CPU, proving is device compute — a producer
        # thread builds upcoming batches while the device proves the
        # current one.  The queue holds at most `prefetch` ready batches
        # (so up to prefetch+1 batches of witnesses may be live; size the
        # knob with host memory in mind).  Mirrors the reference's
        # two-stage shell pipeline (2_gen_wtns.sh -> 5_gen_proof.sh),
        # overlapped instead of sequential.
        ready_q: "queue.Queue[Optional[List[Request]]]" = queue.Queue(maxsize=self.prefetch)
        # A batch is claimed when the queue has a place for it, not
        # before: claimed, witnessed and waiting at a full queue, it is
        # one more batch that no peer on the spool can take while this
        # worker's prover is two batches away from it (four replicas on
        # 32 requests held 3 / 3 / 2 / 0).  The consumer frees a place
        # when it takes a batch; in flight are the batch in the prover
        # and `prefetch` behind it.
        slots = threading.Semaphore(self.prefetch)
        producer_error: List[BaseException] = []

        # Sweep-level claim heartbeat: refreshes EVERY claim this sweep
        # holds — including batches sitting in ready_q behind a slow
        # rescue (retries + bisection + ladder can far exceed
        # stale_claim_s) — so claim age stays bounded by the refresh
        # interval, not by queue wait + rescue time.  A per-batch
        # heartbeat would leave queued batches' claims aging toward peer
        # takeover and duplicate terminal records.  Terminal'd/deferred
        # requests drop out via their done/deferred flags: their claims
        # are already released, and utime-ing a path a peer has since
        # re-claimed would delay that peer's legitimate takeover window.
        hb_reqs: List[Request] = []
        hb_lock = threading.Lock()
        stop_hb = threading.Event()

        def _sweep_heartbeat():
            while True:
                with hb_lock:
                    reqs = [r for r in hb_reqs if r.done is None and not r.deferred]
                for r in reqs:
                    try:
                        os.utime(r.path + ".claim", None)
                    except OSError:
                        pass
                if stop_hb.wait(max(self.stale_claim_s / 3.0, 0.05)):
                    return

        def scalar_witness(req: Request) -> bool:
            set_context(request_id=req.rid)
            try:
                with _span(req, "witness"):
                    fault_point("witness")
                    req.witness = self.witness_fn(req.payload)
                    self._self_check([req.witness])
                return True
            except Exception as e:  # noqa: BLE001 — recorded, not silenced
                if _is_transient(e):
                    # injected fault / allocation pressure: NOT the
                    # payload's fault — release the claim for a later
                    # sweep instead of terminal-ing a good request
                    REGISTRY.counter("zkp2p_service_retries_total").inc()
                    self._release_claim(req.path)
                    req.deferred = True
                    self._record_deferred(spool, req, f"transient witness failure: {e}", knobs)
                    return False
                self._terminal_error(spool, req, "error-bad-input", e, knobs, stats)
                return False
            finally:
                set_context(request_id=None)

        def batched_witness(cand: List[Request]) -> List[Request]:
            """Vectorized tier: per-request input derivation (errors stay
            per request), ONE witness_batch evaluation, sample Az∘Bz=Cz
            check (the prove step verifies a sample proof anyway); any
            batch-level failure falls back to the scalar path."""
            batch: List[Request] = []
            inputs = []
            for req in cand:
                try:
                    set_context(request_id=req.rid)
                    with _span(req, "inputs"):
                        fault_point("witness")
                        inputs.append(self.inputs_fn(req.payload))
                    batch.append(req)
                except Exception as e:  # noqa: BLE001
                    if _is_transient(e):
                        REGISTRY.counter("zkp2p_service_retries_total").inc()
                        self._release_claim(req.path)
                        req.deferred = True
                        self._record_deferred(spool, req, f"transient inputs failure: {e}", knobs)
                    else:
                        self._terminal_error(spool, req, "error-bad-input", e, knobs, stats)
                finally:
                    set_context(request_id=None)
            if not batch:
                return []
            try:
                with self._in_witness_turn(len(batch)), _span(batch, "witness_batch", n=len(batch)):
                    ws = self.cs.witness_batch(inputs)
                # EVERY witness gets the Az∘Bz=Cz self-check, exactly like
                # the scalar tier — only checking a sample would let an
                # unsatisfying witness at index > 0 ship an invalid proof
                # as done (the consumer pairing-verifies one sample too).
                self._self_check(ws, records=batch)
                for req, w in zip(batch, ws):
                    req.witness = w
                return batch
            except Exception:  # noqa: BLE001 — batch tier is an optimization
                return [r for r in batch if scalar_witness(r)]

        def claim_batch(source: "collections.deque", target: int) -> List[Request]:
            """Up to `target` requests claimed off the front of `source`,
            skipping what a peer holds: a batch is filled from what is
            still free, not cut from a slice of the scan a peer has
            been through (claim at DEQUEUE, not at scan: a long sweep
            must not hold scan-time claims that go stale while earlier
            batches prove)."""
            cand: List[Request] = []
            while source and len(cand) < target:
                r = source.popleft()
                if not self._try_claim(r.path):
                    continue
                r.t_claim = time.time()
                if self.t_first_claim is None:
                    self.t_first_claim = r.t_claim
                r.batch_target = target
                with hb_lock:
                    hb_reqs.append(r)  # heartbeat from claim to terminal
                # deadline gate #1, at claim: a request that
                # arrived already-expired (or aged out in the
                # spool) terminals before any witness work
                dl = self._deadline_of(r)
                if dl is not None and r.t_claim > dl:
                    if self._terminal_error(
                        spool, r, "error-deadline-exceeded",
                        RuntimeError(
                            f"deadline exceeded at claim "
                            f"({r.t_claim - r.t_submit:.3f}s since submit)"
                        ),
                        knobs, stats,
                    ):
                        REGISTRY.counter("zkp2p_service_deadline_total").inc()
                    continue
                cand.append(r)
            return cand

        def batches():
            """(what to claim from, the INTENDED size) a batch — records
            carry the size as batch_size_target.  Adaptive: the
            controller's lane-sorted partition, a planned chunk a batch;
            static: batch_size at a time off the scan order, until it
            is used up."""
            if batch_plan is not None:
                for chunk in batch_plan:
                    yield collections.deque(chunk), len(chunk)
            else:
                todo = collections.deque(pending)
                while todo:
                    yield todo, self.batch_size

        def produce():
            adopt_stack(sweep_stack)  # the producer's spans are the sweep's children too
            adopt_context(sweep_ctx)
            try:
                for source, target in batches():
                    slots.acquire()  # a place in ready_q for what is claimed next
                    # Drain gate: once the flag is up, claim NOTHING
                    # more.  Checked per batch, before any claim — the
                    # batches already claimed (proving now, or queued in
                    # ready_q) finish to terminal under the heartbeat;
                    # everything unclaimed stays free for peers, so a
                    # fleet restart loses zero requests and duplicates
                    # zero proofs (docs/ROBUSTNESS.md §fleet).
                    if self._drain.is_set():
                        break
                    cand = claim_batch(source, target)
                    if self.inputs_fn is not None:
                        batch = batched_witness(cand)
                    else:
                        batch = [r for r in cand if scalar_witness(r)]
                    if batch:
                        ready_q.put(batch)
                    else:
                        slots.release()
            except BaseException as e:  # noqa: BLE001 — re-raised by the consumer
                producer_error.append(e)
            finally:
                # The sentinel MUST go out even if this thread dies (e.g.
                # _emit_error hitting a full disk) — otherwise the
                # consumer blocks on ready_q.get() forever.
                ready_q.put(None)

        # One span around a pass that found pending requests (leaf: the
        # spans under it keep their paths and name it as their parent).
        # What this thread did since the last one closed is written first,
        # beside it (`service/handover`, `service/poll`).
        if pending:
            self._between.lap("handover")
            self._between.write()
        sweep = (
            trace("service/sweep", leaf=True, t0=t_sweep, n_pending=len(pending))
            if pending else contextlib.nullcontext({})
        )
        with sweep as sweep_rec:
            sweep_stack, sweep_ctx = current_stack(), current_context()
            hb = threading.Thread(target=_sweep_heartbeat, daemon=True)
            hb.start()
            producer = threading.Thread(target=produce, daemon=True)
            producer.start()
            try:
                sweep_rec["n_batches"] = self._consume(spool, ready_q, knobs, stats, slots)
            finally:
                stop_hb.set()
                hb.join()
            producer.join()
        if pending:
            self._between.lap(None)  # the sweep's own time
        if producer_error:
            # Requests after the failure point got no witness, no proof
            # and no record this sweep — the claim-file discipline means
            # a later sweep (or another worker) picks them up.
            raise producer_error[0]
        return stats

    def _consume(self, spool, ready_q, knobs, stats, slots) -> int:
        """Drain ready batches: deadline-gate, then prove with the full
        rescue ladder, terminal-ing every request exactly once.  Claims
        stay fresh via the caller's sweep-level heartbeat.  Each batch
        taken frees a place in the queue (`slots`): the producer may
        claim the next.  Returns how many batches it fetched."""
        n_batches = 0
        while True:
            t_wait = time.time()
            batch = ready_q.get()
            if batch is None:
                return n_batches
            slots.release()
            # the prover had nothing to prove while it waited here (the
            # sentinel's wait is the sweep ending, not a starved prover)
            record("service/starved", t_wait, time.time(), n=len(batch))
            n_batches += 1
            self.n_batches += 1
            if self.replica is not None:
                REGISTRY.counter("zkp2p_replica_batches_total", {"replica": str(self.replica)}).inc()
            # deadline gate #2, at batch assembly: queue wait behind a
            # slow batch may have burned the remaining budget — check
            # again immediately before committing prove compute
            live: List[Request] = []
            for req in batch:
                dl = self._deadline_of(req)
                if dl is not None and time.time() > dl:
                    if self._terminal_error(
                        spool, req, "error-deadline-exceeded",
                        RuntimeError(
                            f"deadline exceeded at batch assembly "
                            f"({time.time() - req.t_submit:.3f}s since submit)"
                        ),
                        knobs, stats,
                    ):
                        REGISTRY.counter("zkp2p_service_deadline_total").inc()
                else:
                    live.append(req)
            if not live:
                continue
            for bi, req in enumerate(live):
                req.batch_index = bi
            # batch-fill distribution: live requests per prover call —
            # fill vs batch_size is the amortization signal the dynamic
            # batch scheduler (ROADMAP item 2) will size columns from
            REGISTRY.histogram(
                "zkp2p_service_batch_fill", buckets=BATCH_FILL_BUCKETS
            ).observe(len(live))
            if self._sampler is not None:
                self._sampler.batch_fill_last = len(live)
            t_batch0 = time.perf_counter()
            try:
                self._prove_isolating(spool, live, knobs, stats, batch_n=len(live))
                # online amortization calibration (adaptive arm): feed
                # the batch's ACTUAL wall cost back into the controller
                # — the static curve can be arbitrarily wrong for this
                # circuit/host, and until the first observation lands
                # the controller sheds only already-expired requests
                if self._sched_ctl is not None:
                    self._sched_ctl.observe_batch(len(live), time.perf_counter() - t_batch0)
            except Exception as e:  # noqa: BLE001 — safety net
                # _prove_isolating terminals every request itself; an
                # exception escaping it is a bug in the rescue path —
                # requests still open (and not deliberately deferred)
                # get the honest terminal instead of silently hanging
                for req in live:
                    if req.done is None and not req.deferred:
                        self._terminal_error(
                            spool, req, "error-failed-to-prove", e, knobs, stats,
                            batch_index=req.batch_index, batch_n=len(live),
                        )

    @classmethod
    def _emit_error(cls, req: Request, state: str, exc: BaseException) -> None:
        # atomic (temp+rename) like every other terminal artifact: a crash
        # or racing peer mid-write must never leave a torn .error.json that
        # the sweep's existence check treats as final
        # format_exception(exc), not format_exc(): shed/deadline
        # terminals pass a CONSTRUCTED exception that was never raised —
        # format_exc() there would stamp "NoneType: None" (or whatever
        # unrelated exception happens to be in flight) into the artifact
        trace_s = "".join(traceback.format_exception(type(exc), exc, exc.__traceback__, limit=3))
        dump(
            {"state": state, "error": str(exc), "trace": trace_s, "ts": time.time()},
            req.path + ".error.json",
        )
        cls._release_claim(req.path)

    # ------------------------------------------------------------- daemon

    @classmethod
    def _from_inputs_fn(cls, cs, dpk, vk, inputs_fn, **kw) -> "ProvingService":
        """A service whose witness comes from `inputs_fn` (payload ->
        (public signals, seed)): set as the service's `inputs_fn`, so
        whole batches take the `witness_batch` tier."""

        def witness_fn(payload: Dict) -> list:
            pubs, seed = inputs_fn(payload)
            return cs.witness(pubs, seed)

        def public_fn(witness: list) -> list:
            return list(witness[1 : cs.num_public + 1])

        kw.setdefault("inputs_fn", inputs_fn)
        return cls(cs, dpk, vk, witness_fn, public_fn, **kw)

    @classmethod
    def for_venmo(cls, cs, lay, params, dpk, vk, keys=None, **kw) -> "ProvingService":
        """Service wired for the flagship circuit: request payloads are
        either {"eml_path": ...} (real DKIM email, keys resolved from the
        known-keys registry) or the synthetic-demo shape {"raw_id",
        "amount", "order_id", "claim_id"} (hermetic tests)."""
        from ..inputs.email import email_from_eml, generate_inputs, make_test_key, make_venmo_email

        demo_key = make_test_key(1)

        def inputs_fn(payload: Dict) -> tuple:
            order_id = int(payload.get("order_id", 1))
            claim_id = int(payload.get("claim_id", 0))
            if "eml_path" in payload:
                with open(payload["eml_path"], "rb") as f:
                    email = email_from_eml(f.read(), keys)  # unknown keys raise
                modulus = email.modulus
            else:
                email = make_venmo_email(
                    demo_key, raw_id=str(payload["raw_id"]), amount=str(payload["amount"])
                )
                modulus = demo_key.n
            inputs = generate_inputs(email, modulus, order_id, claim_id, params, lay)
            return inputs.public_signals, inputs.seed

        return cls._from_inputs_fn(cs, dpk, vk, inputs_fn, **kw)

    @classmethod
    def for_email_verify(cls, cs, lay, params, dpk, vk, keys=None, **kw) -> "ProvingService":
        """Service wired for the generic EmailVerify circuit (models.
        email_verify, TwitterResetRegex body): request payloads are either
        {"eml_path": ...} (real DKIM email, keys resolved from the
        known-keys registry) or the synthetic shape {"handle", "filler"}
        (a password-reset email for @handle with `filler` body bytes
        before it, signed under the demo key; hermetic tests and the
        benchmark)."""
        from ..inputs.email import (
            email_verify_from_eml,
            generate_email_verify_inputs,
            make_test_key,
            make_twitter_email,
        )

        demo_key = make_test_key(1)

        def inputs_fn(payload: Dict) -> tuple:
            if "eml_path" in payload:
                with open(payload["eml_path"], "rb") as f:
                    email, modulus = email_verify_from_eml(f.read(), keys)  # unknown keys raise
            else:
                email = make_twitter_email(
                    demo_key, handle=str(payload["handle"]), filler=int(payload.get("filler", 0))
                )
                modulus = demo_key.n
            inputs = generate_email_verify_inputs(email, modulus, params, lay)
            return inputs.public_signals, inputs.seed

        return cls._from_inputs_fn(cs, dpk, vk, inputs_fn, **kw)

    @classmethod
    def for_sha256_preimage(cls, cs, msg_wires, dpk, vk, **kw) -> "ProvingService":
        """Service wired for the fixed-length SHA-256 preimage circuit
        (`models.registry.build_sha256_preimage`, whose `msg_wires` these
        are): a request's payload is {"msg": [one int 0-255 a message
        byte]} or {"msg_hex": "two hex digits a byte"}, and its proof's
        two public signals are the SHA-256 digest of those bytes.  A
        payload of another length, or with a value outside a byte, is
        that request's error, not its batch's."""
        from ..models.registry import sha256_preimage_inputs

        return cls._from_inputs_fn(cs, dpk, vk, lambda payload: sha256_preimage_inputs(msg_wires, payload), **kw)

    def run(
        self,
        spool: str,
        poll_s: float = 1.0,
        max_sweeps: Optional[int] = None,
        max_seconds: Optional[float] = None,
        exit_when_spool_terminal: bool = False,
    ) -> str:
        """Sweep `spool` until drained / exhausted; returns WHY the loop
        ended — "drained" (request_drain / SIGTERM: in-flight work
        finished, claims all released, sinks flushed), "terminal"
        (exit_when_spool_terminal and every request reached a terminal
        state — chaos/fleet workers), "sweeps" (max_sweeps), or
        "timeout" (max_seconds) — so callers can map a clean drain to a
        clean exit code."""
        # Prometheus exposition (ZKP2P_METRICS_PORT, default off) — the
        # scrape sees stage histograms, request-state counters, and a
        # scrape-time native counter refresh.
        maybe_start_metrics_server()
        # lower/compile events counted per stage from the first
        # sweep on, and logged when they fall under a service/* span
        install_compile_listener()
        # Preflight (execution audit): arm every gate and report the
        # arms before the first request is claimed.  A failure here is
        # not swallowed, and a worker that proves on the device
        # (prover_fn None -> prove_tpu_batch) REFUSES to start on a
        # mis-armed gate (pallas requested on a CPU backend, bucket-h
        # without signed digits...) — a mis-armed device run must stop
        # here, not show up in the numbers after.  Preflight initialises
        # the JAX backend; the CLI pins a `--prover native` worker's JAX
        # to the host platform first, so it never takes the chip.
        rep = preflight(
            workload=False,
            log=lambda m: print(f"[service] {m}", file=sys.stderr, flush=True),
            stamp=self._on_loop_up is None,  # a set stamps once, when every loop is up
        )
        print(
            f"[service] preflight: backend={rep['backend']} "
            f"execution_digest={rep['execution_digest']}",
            flush=True,
        )
        if self.prover_fn is None and rep["warnings"]:
            raise RuntimeError(f"preflight: mis-armed gates: {rep['warnings']}")
        # service observability arms + time-series sampler: the SLO
        # objective and sampler interval are digest-visible gates (a
        # sampler-off A/B differs from sampler-on only on these), and
        # the sampler appends zkp2p_timeseries lines to the same sink
        # the request records ride.
        from ..utils.config import load_config
        from ..utils.slo import slo_arm, timeseries_arm

        slo_arm()
        timeseries_arm()
        # fleet membership gate: "worker" when the supervisor stamped an
        # identity into our env, else "off" — a fleet member and a solo
        # service are digest-distinguishable code paths (the ONE
        # resolver preflight also calls; a divergent inline copy could
        # split run()'s digest from doctor's)
        from .fleet import fleet_member_arm

        self._resolve_policy()
        fleet_member_arm()
        if self.replica is None:
            from .replicas import replicas_arm

            replicas_arm(None)  # a solo service: "off" (a set records its own count)
        fleet_dir = load_config().fleet_dir or None
        self._sampler = TimeseriesSampler(load_config().ts_sample_s, self.stale_claim_s)

        def _flush():
            rid, pid = run_id(), os.getpid()
            spans = [
                {"type": "stage", "run_id": rid, "pid": pid, **r} for r in drain_trace()
            ]
            try:
                self._sink(spool).write_many(spans)
            except Exception:  # noqa: BLE001 — observation only
                pass
            publish_native_stats()

        # first heartbeat BEFORE the first sweep (the supervisor's
        # watchdog needs a liveness baseline while the worker is still
        # inside a long first sweep) plus a BACKGROUND heartbeat thread:
        # a single sweep can legitimately run minutes (cold precomp
        # build; flock losers block for the winner's whole build), and
        # a sweep-cadence heartbeat alone would read as a hang — the
        # watchdog would SIGKILL a healthy cold start mid-build forever
        hb_stop = None
        if fleet_dir:
            try:
                from .fleet import start_heartbeat_thread, worker_tick

                worker_tick(self, fleet_dir)
                hb_stop = start_heartbeat_thread(self, fleet_dir)
            except Exception:  # noqa: BLE001
                pass
        if self._on_loop_up is not None:
            self._on_loop_up(self.replica, rep["stamp"])
        deadline = (time.time() + max_seconds) if max_seconds else None
        sweeps = 0
        why = "sweeps"
        self._between.lap(None)  # between sweeps from here on
        while max_sweeps is None or sweeps < max_sweeps:
            if deadline is not None and time.time() > deadline:
                why = "timeout"
                break
            stats = self.process_dir(spool)
            if any(stats.values()):
                print(f"[service] {stats}", flush=True)
                # Per-sweep observability flush: buffered stage spans go
                # to the rotating sink (stamped with run_id/pid so
                # concurrent workers stay separable) and the native C
                # counter block is re-published for the next scrape.
                # The trace ring is DRAINED, which with the bounded
                # buffer closes the unbounded-growth leak the run() loop
                # had.
                _flush()
            # time-series tick rides the sweep cadence (interval-gated
            # inside; idle sweeps still sample, so a quiet queue is a
            # recorded fact, not a gap in the series)
            self._sampler.maybe_sample(spool, self._sink(spool))
            # fleet tick: heartbeat out (liveness for the supervisor's
            # watchdog + the bound metrics port for scrape discovery),
            # governor ctl in (soft RSS degrade)
            if fleet_dir:
                try:
                    from .fleet import worker_tick

                    worker_tick(self, fleet_dir)
                except Exception:  # noqa: BLE001 — fleet plumbing must not stop sweeps
                    pass
            if self._drain.is_set():
                why = "drained"
                break
            if exit_when_spool_terminal and spool_terminal(spool):
                why = "terminal"
                break
            sweeps += 1
            # interruptible sleep: a SIGTERM mid-poll exits promptly
            # instead of burning up to poll_s — by this point the sweep
            # above already finished every claim it held
            self._between.lap("handover")
            drained = self._drain.wait(poll_s)
            self._between.lap("poll")
            if drained:
                why = "drained"
                break
        # exit flush: whatever the reason, buffered spans and native
        # stats land in the sink before the process goes away (the
        # drain contract: in-flight work is not just proven but
        # RECORDED), and the fleet heartbeat says "draining" so the
        # supervisor sees a deliberate exit, not a hang
        _flush()
        if hb_stop is not None:
            hb_stop.set()
        if fleet_dir:
            try:
                from .fleet import worker_tick

                worker_tick(self, fleet_dir, state=why)
            except Exception:  # noqa: BLE001
                pass
        print(f"[service] exiting ({why})", flush=True)
        return why
