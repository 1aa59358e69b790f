#!/usr/bin/env python
"""Aggregate zkp2p observability JSONL sinks into per-stage tables.

Input: one or more JSONL files produced by utils.trace.dump_trace, the
ProvingService sink, or bench.py with ZKP2P_METRICS_SINK set.  Lines:

  {"type": "manifest", "run_id": ..., "host": {...}, "knobs": {...}}
  {"stage": "native/msm_a", "ms": 812.3, "run_id": ..., "pid": ...}
  {"type": "request", "request_id": ..., "state": "done", "ms": ...}

Modes:
  default      per-stage n / p50 / p95 / max / total table (+ request
               state summary when request records are present)
  --tree       stage-path tree (indented by "/" nesting) with the same
               percentiles per node
  --runs       list the run_ids found (with knob arms + execution
               digest) and exit
  --run RID    restrict aggregation to one run_id
  --diff A B   A/B: two files OR (with one file) two run_ids — per-stage
               p50 delta table, replacing eyeballed min-of-5 comparisons
  --json       machine output: {"stages", "requests", "runs",
               "timeseries"} with the per-stage aggregates,
               request-state aggregates, sampler-line summary, and each
               run's knobs + gate arms + execution digest — so CI can
               gate on digests/latencies instead of scraping text
               tables.  Honors --run; with --diff, emits {"a","b"} of
               per-stage aggregates instead.
  --chrome-trace OUT
               export the request records' lifecycle spans as Chrome
               trace-event JSON (one pid per worker process, one tid
               per request, queue-wait vs witness/prove/emit slices,
               FLOW arrows stitching a deferred/taken-over request's
               attempts across worker process rows), and under each
               worker the stage spans on one row per thread
               (service/sweep, service/starved, tpu/prove_batch's
               prep/device/finish, dispatch and the six stage/* spans)
               — load OUT in https://ui.perfetto.dev.  Honors --run.
  --fleet-dir DIR
               cross-worker mode: discover every sink a fleet run left
               behind (the shared spool sink + rotation backups, plus
               any per-worker ZKP2P_METRICS_SINK files dropped inside
               DIR) instead of naming files by hand — `--fleet-dir
               <spool>/.fleet --chrome-trace out.json` renders the
               whole fleet, one process row per worker.
  --request RID
               single-request forensics: a text timeline of RID's
               journey — arrival, every claim with its owning worker
               and queue-wait, defer/takeover hops, spans per attempt,
               terminal state.  The "which worker did what, when" view
               chasing one stuck request needs.

Exact percentiles from the raw records (the registry's histograms are
bucket-resolution; this reads the records themselves).
"""

from __future__ import annotations

import argparse
import glob as _glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple


def load_records(
    paths: List[str],
) -> Tuple[List[dict], List[dict], List[dict], List[dict]]:
    """(stage_records, request_records, manifests, timeseries) from
    JSONL files, rotation backups included if named explicitly.
    Unparseable lines are counted, not fatal (a torn tail from a
    crashed worker must not hide the rest of the file)."""
    stages: List[dict] = []
    requests: List[dict] = []
    manifests: List[dict] = []
    timeseries: List[dict] = []
    bad = 0
    for path in paths:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    bad += 1
                    continue
                t = rec.get("type")
                if t == "manifest":
                    manifests.append(rec)
                elif t == "request":
                    requests.append(rec)
                elif t == "timeseries":
                    timeseries.append(rec)
                elif "stage" in rec and "ms" in rec:
                    stages.append(rec)
    if bad:
        print(f"[trace_report] skipped {bad} unparseable line(s)", file=sys.stderr)
    return stages, requests, manifests, timeseries


def _pct(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile on a pre-sorted list."""
    if not sorted_vals:
        return 0.0
    k = max(0, min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[k]


def aggregate(stages: List[dict], run: Optional[str] = None) -> Dict[str, dict]:
    """stage path -> {n, p50, p95, max, total_ms}."""
    by_stage: Dict[str, List[float]] = {}
    for rec in stages:
        if run and rec.get("run_id") != run:
            continue
        by_stage.setdefault(rec["stage"], []).append(float(rec["ms"]))
    out: Dict[str, dict] = {}
    for stage, vals in by_stage.items():
        vals.sort()
        out[stage] = {
            "n": len(vals),
            "p50": _pct(vals, 0.50),
            "p95": _pct(vals, 0.95),
            "max": vals[-1],
            "total_ms": sum(vals),
        }
    return out


def _fmt_ms(v: float) -> str:
    if v >= 10000:
        return f"{v / 1000:.1f}s"
    return f"{v:.1f}"


def render_table(agg: Dict[str, dict]) -> str:
    rows = sorted(agg.items(), key=lambda kv: -kv[1]["total_ms"])
    w = max([len("stage")] + [len(s) for s, _ in rows]) if rows else 5
    lines = [f"{'stage':<{w}}  {'n':>6}  {'p50':>9}  {'p95':>9}  {'max':>9}  {'total':>9}"]
    lines.append("-" * len(lines[0]))
    for stage, a in rows:
        lines.append(
            f"{stage:<{w}}  {a['n']:>6}  {_fmt_ms(a['p50']):>9}  {_fmt_ms(a['p95']):>9}  "
            f"{_fmt_ms(a['max']):>9}  {_fmt_ms(a['total_ms']):>9}"
        )
    return "\n".join(lines)


def render_tree(agg: Dict[str, dict]) -> str:
    """Stage-path tree: each node indented by its '/' depth, children
    under their parent, siblings ordered by total time."""
    children: Dict[str, List[str]] = {"": []}
    for stage in agg:
        parts = stage.split("/")
        for d in range(len(parts)):
            node = "/".join(parts[: d + 1])
            parent = "/".join(parts[:d])
            children.setdefault(parent, [])
            children.setdefault(node, [])
            if node not in children[parent]:
                children[parent].append(node)

    lines: List[str] = []
    w = max([len("stage") + 2] + [len(s) + 2 * s.count("/") for s in agg]) if agg else 5
    lines.append(f"{'stage':<{w}}  {'n':>6}  {'p50':>9}  {'p95':>9}  {'total':>9}")
    lines.append("-" * len(lines[0]))

    def total(node: str) -> float:
        a = agg.get(node)
        if a:
            return a["total_ms"]
        return sum(total(c) for c in children.get(node, []))

    def walk(node: str, depth: int) -> None:
        if node:
            a = agg.get(node)
            label = "  " * (depth - 1) + node.split("/")[-1]
            if a:
                lines.append(
                    f"{label:<{w}}  {a['n']:>6}  {_fmt_ms(a['p50']):>9}  "
                    f"{_fmt_ms(a['p95']):>9}  {_fmt_ms(a['total_ms']):>9}"
                )
            else:
                lines.append(f"{label:<{w}}  {'-':>6}  {'-':>9}  {'-':>9}  {_fmt_ms(total(node)):>9}")
        for c in sorted(children.get(node, []), key=lambda n: -total(n)):
            walk(c, depth + 1)

    walk("", 0)
    return "\n".join(lines)


def render_requests(requests: List[dict], run: Optional[str] = None) -> str:
    agg = _aggregate_requests(requests, run=run)
    if not agg:
        return ""
    lines = ["request states:"]
    for state, a in sorted(agg.items()):
        if state.startswith("_"):
            continue
        lines.append(
            f"  {state:<24} n={a['n']:<6} p50={_fmt_ms(a['p50'])} "
            f"p95={_fmt_ms(a['p95'])} max={_fmt_ms(a['max'])}"
        )
    b = agg.get("_batched")
    if b:
        # batched-prove attribution (records carrying batch_index/batch_n):
        # mean fill names the latency-vs-batch-fill tradeoff the service
        # batch_size knob sets; the amortized p50 divides each request's
        # claim->terminal ms by its batch width — the per-proof share of
        # a multi-column batch prove that one request's `ms` conflates.
        lines.append(
            f"  batched proves:          n={b['n']:<6} mean_fill={b['mean_fill']:.2f} "
            f"p50_amortized={_fmt_ms(b['p50_amortized'])}"
        )
    return "\n".join(lines)


def render_diff(agg_a: Dict[str, dict], agg_b: Dict[str, dict], label_a: str, label_b: str) -> str:
    """Per-stage p50 A-vs-B — the knob-arm comparison the bench notes
    used to eyeball from two min-of-5 logs."""
    stages = sorted(
        set(agg_a) | set(agg_b),
        key=lambda s: -(agg_a.get(s, {}).get("total_ms", 0) + agg_b.get(s, {}).get("total_ms", 0)),
    )
    w = max([len("stage")] + [len(s) for s in stages]) if stages else 5
    head = (
        f"{'stage':<{w}}  {'n(A)':>5} {'n(B)':>5}  {'p50 A':>9}  {'p50 B':>9}  {'delta':>8}"
    )
    lines = [f"A = {label_a}", f"B = {label_b}", head, "-" * len(head)]
    for s in stages:
        a, b = agg_a.get(s), agg_b.get(s)
        pa = a["p50"] if a else None
        pb = b["p50"] if b else None
        if pa is not None and pb is not None and pa > 0:
            delta = f"{(pb - pa) / pa * 100:+.1f}%"
        else:
            delta = "-"
        lines.append(
            f"{s:<{w}}  {a['n'] if a else 0:>5} {b['n'] if b else 0:>5}  "
            f"{_fmt_ms(pa) if pa is not None else '-':>9}  "
            f"{_fmt_ms(pb) if pb is not None else '-':>9}  {delta:>8}"
        )
    return "\n".join(lines)


def digest_callout(runs_detail: List[dict], run_a: str, run_b: str) -> List[str]:
    """The interleaved-A/B sanity line every bench note used to write
    by hand: do the two runs share an execution digest (apples to
    apples), and if not, WHICH gate arms differ — a perf delta between
    digest-divergent runs is a code-path change, not a regression."""
    by = {r["run_id"]: r for r in runs_detail}
    a, b = by.get(run_a, {}), by.get(run_b, {})
    da, db = a.get("execution_digest"), b.get("execution_digest")
    if not da or not db:
        missing = [r for r, d in ((run_a, da), (run_b, db)) if not d]
        return [f"digest callout unavailable: no manifest digest for {', '.join(missing)}"]
    if da == db:
        return [f"digests MATCH ({da}) — same code paths, the delta is a real perf delta"]
    lines = [f"digests DIFFER: A={da}  B={db} — the runs took different code paths"]
    ga, gb = a.get("gates") or {}, b.get("gates") or {}
    diffs = [
        f"{g}={ga.get(g, '?')}->{gb.get(g, '?')}"
        for g in sorted(set(ga) | set(gb))
        if ga.get(g) != gb.get(g)
    ]
    if diffs:
        lines.append("  differing arms: " + "  ".join(diffs))
    return lines


STAGE_TID_BASE = 1_000_000  # stage-span rows sort below a worker's request rows


def chrome_trace(requests: List[dict], run: Optional[str] = None,
                 stages: Optional[List[dict]] = None) -> dict:
    """Chrome trace-event JSON (loads in Perfetto / chrome://tracing)
    from the service's request records: **one pid per worker process,
    one tid per request**, so the UI shows each request as its own
    waterfall row under its worker.

    `stages`: the stage spans (utils/trace.py records with `t0`, `id`,
    `parent`, `tid`) drawn under the same worker on **one row per
    thread**, below its requests: `service/sweep`, `service/starved`,
    `tpu/prove_batch/prep|device|finish|dispatch` and the six
    `tpu/prove_batch/stage/*` (on the row of the thread that watches
    the stages' results) nest there as they ran.

    Per record: a synthesized `queue_wait` slice (req-file mtime →
    claim — the spool wait the `queue_wait_s` field sums), one complete
    ("X") slice per lifecycle span (witness / prove attempts / rungs /
    verify / emit, `spans` on the record), and an instant marker at the
    terminal/deferred transition.  Deferred attempt records share their
    request's tid, so a defer→re-prove cycle reads as one row with two
    prove slices.

    Cross-attempt FLOW events: a request with more than one record
    (defer→re-prove, takeover after a SIGKILL) gets a flow arrow from
    each attempt's last slice to the next attempt's first slice — the
    ph "s"/"f" pair Perfetto draws as an arrow BETWEEN process rows.
    Before this, a defer whose re-prove landed on another worker
    rendered as two unrelated rows with nothing saying they were the
    same request's journey.  Timestamps are µs relative to the
    earliest event (Chrome's `ts` unit), emitted sorted so they are
    monotonic."""
    recs = [
        r for r in requests
        if r.get("request_id") and (not run or r.get("run_id") == run)
    ]
    events: List[dict] = []
    tids: Dict[tuple, int] = {}  # (pid, request_id) -> tid
    next_tid: Dict[int, int] = {}  # per-pid tid allocator

    def tid_for(pid: int, rid: str) -> int:
        key = (pid, rid)
        if key not in tids:
            next_tid[pid] = next_tid.get(pid, 0) + 1
            tids[key] = next_tid[pid]
            events.append({
                "ph": "M", "name": "thread_name", "pid": pid, "tid": tids[key],
                "args": {"name": rid},
            })
        return tids[key]

    seen_pids = set()
    for r in recs:
        pid = int(r.get("pid") or 0)
        if pid not in seen_pids:
            seen_pids.add(pid)
            # fleet attribution: records stamped with a worker id (and
            # fleet id) name the row by WORKER — pids recycle across
            # supervisor restarts, worker ids don't, so "w1 pid 123" and
            # "w1 pid 456" read as one worker's two incarnations
            wname = r.get("worker")
            fname = r.get("fleet")
            label = (
                f"zkp2p {wname}" + (f"@{fname}" if fname else "") + f" (pid {pid})"
                if wname else f"zkp2p worker {pid}"
            )
            events.append({
                "ph": "M", "name": "process_name", "pid": pid,
                "args": {"name": label},
            })
        tid = tid_for(pid, r["request_id"])
        t_submit, t_claim = r.get("t_submit"), r.get("t_claim")
        if t_submit and t_claim and t_claim >= t_submit:
            events.append({
                "ph": "X", "name": "queue_wait", "cat": "request",
                "pid": pid, "tid": tid,
                "ts": t_submit * 1e6, "dur": (t_claim - t_submit) * 1e6,
                "args": {"queue_wait_s": r.get("queue_wait_s")},
            })
        for s in r.get("spans") or []:
            args = {k: v for k, v in s.items() if k not in ("name", "t0", "ms")}
            events.append({
                "ph": "X", "name": s["name"], "cat": "request",
                "pid": pid, "tid": tid,
                "ts": float(s["t0"]) * 1e6, "dur": float(s["ms"]) * 1e3,
                "args": args,
            })
        if r.get("ts"):
            events.append({
                "ph": "i", "s": "t", "name": r.get("state", "?"), "cat": "request",
                "pid": pid, "tid": tid, "ts": float(r["ts"]) * 1e6,
                "args": {k: r[k] for k in ("batch_index", "batch_n", "degraded_rung",
                                           "deferred_reason") if r.get(k) is not None},
            })

    # ---- stage spans: a row per thread under the worker that wrote them
    thread_rows: Dict[tuple, int] = {}  # (pid, thread ident) -> tid
    for sp in stages or []:
        if sp.get("t0") is None or sp.get("tid") is None or (run and sp.get("run_id") != run):
            continue
        pid = int(sp.get("pid") or 0)
        key = (pid, sp["tid"])
        if key not in thread_rows:
            n = sum(1 for k in thread_rows if k[0] == pid) + 1
            thread_rows[key] = STAGE_TID_BASE + n
            events.append({"ph": "M", "name": "thread_name", "pid": pid, "tid": thread_rows[key],
                           "args": {"name": f"stage spans, thread {n}"}})
        events.append({
            "ph": "X", "name": sp["stage"], "cat": "stage", "pid": pid, "tid": thread_rows[key],
            "ts": float(sp["t0"]) * 1e6, "dur": float(sp["ms"]) * 1e3,
            "args": {k: v for k, v in sp.items()
                     if k not in ("stage", "t0", "ms", "tid", "pid", "type", "run_id")},
        })

    # ---- flow events: stitch a request's attempts across process rows.
    # Each record is one ATTEMPT; consecutive attempts get an arrow
    # from the earlier attempt's last slice to the later attempt's
    # first slice.  The "s"/"f" anchors must land INSIDE a slice on
    # their row for importers to bind them, so the ts is nudged one µs
    # off the slice edge.
    def _anchor_slices(r: dict) -> Tuple[Optional[dict], Optional[dict]]:
        """(first, last) anchorable slices of one record: lifecycle
        spans preferred; the synthesized queue_wait slice as the
        fallback for span-less records (a claim-then-shed terminal)."""
        spans = [s for s in (r.get("spans") or []) if s.get("ms", 0) > 0]
        if spans:
            first = min(spans, key=lambda s: float(s["t0"]))
            last = max(spans, key=lambda s: float(s["t0"]) + float(s["ms"]) / 1e3)
            return first, last
        t_submit, t_claim = r.get("t_submit"), r.get("t_claim")
        if t_submit and t_claim and t_claim > t_submit:
            qw = {"t0": t_submit, "ms": (t_claim - t_submit) * 1e3}
            return qw, qw
        return None, None

    by_rid: Dict[str, List[dict]] = {}
    for r in recs:
        by_rid.setdefault(r["request_id"], []).append(r)
    flow_id = 0
    for rid, attempts in sorted(by_rid.items()):
        if len(attempts) < 2:
            continue
        attempts.sort(key=lambda r: float(r.get("ts") or 0.0))
        for prev, cur in zip(attempts, attempts[1:]):
            _, prev_last = _anchor_slices(prev)
            cur_first, _ = _anchor_slices(cur)
            if prev_last is None or cur_first is None:
                continue
            flow_id += 1
            prev_pid, cur_pid = int(prev.get("pid") or 0), int(cur.get("pid") or 0)
            start_ts = float(prev_last["t0"]) * 1e6 + max(0.0, float(prev_last["ms"]) * 1e3 - 1.0)
            finish_ts = float(cur_first["t0"]) * 1e6 + min(1.0, float(cur_first["ms"]) * 1e3 / 2)
            hop = "takeover" if cur_pid != prev_pid else "re-prove"
            common = {"cat": "flow", "name": f"{rid} {hop}", "id": flow_id}
            events.append({
                "ph": "s", **common, "pid": prev_pid,
                "tid": tid_for(prev_pid, rid), "ts": start_ts,
            })
            events.append({
                "ph": "f", "bp": "e", **common, "pid": cur_pid,
                "tid": tid_for(cur_pid, rid), "ts": max(finish_ts, start_ts + 1.0),
            })
    # normalize to the earliest event and sort: Perfetto wants sane
    # (small, monotonic-sortable) µs timestamps, not epoch µs
    slices = [e for e in events if "ts" in e]
    if slices:
        t0 = min(e["ts"] for e in slices)
        for e in slices:
            e["ts"] = round(e["ts"] - t0, 3)
            if "dur" in e:
                e["dur"] = round(e["dur"], 3)
    meta = [e for e in events if "ts" not in e]
    # Equal-ts slices sort LONGEST first: importers nest same-timestamp
    # complete events by assuming the enclosing slice precedes the
    # enclosed one, and a defer→re-prove request emits two queue_wait
    # slices both anchored at t_submit (shorter-first would mis-nest).
    slices.sort(key=lambda e: (e["ts"], -e.get("dur", 0)))
    return {"traceEvents": meta + slices, "displayTimeUnit": "ms"}


def fleet_sinks(fleet_dir: str) -> List[str]:
    """Discover every JSONL sink a fleet run left behind, from its
    fleet dir (default `<spool>/.fleet`): the shared per-spool sink
    `<spool>.metrics.jsonl` with its rotation backups, plus any
    `*.jsonl` dropped inside the fleet dir itself (a per-worker
    ZKP2P_METRICS_SINK override pointed there).  The spool path comes
    from status.json when present (the supervisor records it), else
    from the directory layout."""
    spool = None
    try:
        with open(os.path.join(fleet_dir, "status.json")) as f:
            spool = json.load(f).get("spool")
    except (OSError, ValueError):
        pass
    if not spool:
        spool = os.path.dirname(os.path.abspath(fleet_dir))
    base = spool.rstrip("/") + ".metrics.jsonl"
    paths = [p for p in [base] + [f"{base}.{i}" for i in range(1, 10)] if os.path.exists(p)]
    paths += sorted(
        p for p in _glob.glob(os.path.join(fleet_dir, "*.jsonl")) if os.path.isfile(p)
    )
    return paths


def request_timeline(requests: List[dict], rid: str) -> str:
    """Single-request forensics: every attempt (record) for `rid` in
    time order — owning worker, claim offset, queue-wait for THAT hop,
    span breakdown, outcome — with takeover hops called out where the
    owner changed between attempts.  Offsets are relative to the spool
    arrival (t_submit), the clock every worker shares."""
    recs = sorted(
        (r for r in requests if r.get("request_id") == rid),
        key=lambda r: float(r.get("ts") or 0.0),
    )
    if not recs:
        return f"(no records for request {rid!r})"
    t0 = min(
        [float(r["t_submit"]) for r in recs if r.get("t_submit")]
        or [float(r.get("t_claim") or r.get("ts") or 0.0) for r in recs]
    )

    def owner(r: dict) -> str:
        w = r.get("worker")
        return f"{w} (pid {r.get('pid')})" if w else f"pid {r.get('pid')}"

    lines = [f"request {rid} — {len(recs)} attempt(s)"]
    lines.append("  +0.000s  arrival (spool mtime)")
    prev_owner = None
    for i, r in enumerate(recs, 1):
        hop = ""
        if prev_owner is not None and owner(r) != prev_owner:
            hop = "  TAKEOVER"
        prev_owner = owner(r)
        t_claim = r.get("t_claim")
        claim_s = f"+{float(t_claim) - t0:.3f}s" if t_claim else "?"
        qw = r.get("queue_wait_s")
        qw_s = f"  queue_wait {float(qw):.3f}s" if qw is not None else ""
        spans = r.get("spans") or []
        span_s = ", ".join(f"{s['name']} {float(s['ms']):.0f}ms" for s in spans)
        state = r.get("state", "?")
        outcome = state
        if state == "deferred" and r.get("deferred_reason"):
            outcome += f" ({r['deferred_reason']})"
        if r.get("degraded_rung"):
            outcome += f" [rescued: {r['degraded_rung']}]"
        ts = r.get("ts")
        end_s = f" at +{float(ts) - t0:.3f}s" if ts else ""
        lines.append(
            f"  attempt {i}  {owner(r)}{hop}  claim {claim_s}{qw_s}"
            + (f"\n             {span_s}" if span_s else "")
            + f"\n             -> {outcome}{end_s}"
        )
    return "\n".join(lines)


def _aggregate_timeseries(timeseries: List[dict], run: Optional[str] = None) -> dict:
    """Compact summary of the sampler lines: sample count, time covered,
    and min/mean/max of the queue-state signals — enough for the text
    report to say "backlog peaked at N while arrivals ran at X Hz"
    (full-resolution analysis reads the raw lines)."""
    recs = [r for r in timeseries if not run or r.get("run_id") == run]
    if not recs:
        return {}

    def series(key):
        vals = [float(r[key]) for r in recs if r.get(key) is not None]
        if not vals:
            return None
        return {
            "min": min(vals),
            "mean": round(sum(vals) / len(vals), 4),
            "max": max(vals),
        }

    out = {"n": len(recs)}
    ts = [float(r["ts"]) for r in recs if r.get("ts")]
    if len(ts) >= 2:
        out["span_s"] = round(max(ts) - min(ts), 3)
    for key in ("arrival_rate_hz", "backlog", "claimable", "in_flight", "batch_fill_last"):
        s = series(key)
        if s is not None:
            out[key] = s
    return out


def render_timeseries(agg: dict) -> str:
    if not agg:
        return ""
    parts = [f"timeseries: {agg['n']} samples"]
    if "span_s" in agg:
        parts.append(f"over {agg['span_s']:.0f}s")
    for key, label in (
        ("arrival_rate_hz", "arrivals/s"), ("backlog", "backlog"),
        ("in_flight", "in_flight"), ("batch_fill_last", "batch_fill"),
    ):
        if key in agg:
            a = agg[key]
            parts.append(f"{label} mean={a['mean']:g} max={a['max']:g}")
    return "  ".join(parts)


def _aggregate_requests(requests: List[dict], run: Optional[str] = None) -> Dict[str, dict]:
    """state -> {n, p50, p95, max} over request terminal records; plus a
    `_batched` pseudo-state over records carrying batch_index/batch_n
    (mean batch fill + amortized-per-proof latency p50)."""
    by_state: Dict[str, List[float]] = {}
    batched: List[dict] = []
    for rec in requests:
        if run and rec.get("run_id") != run:
            continue
        by_state.setdefault(rec.get("state", "?"), []).append(float(rec.get("ms") or 0.0))
        if rec.get("batch_n"):
            batched.append(rec)
    out: Dict[str, dict] = {}
    for state, vals in by_state.items():
        vals.sort()
        out[state] = {
            "n": len(vals),
            "p50": _pct(vals, 0.50),
            "p95": _pct(vals, 0.95),
            "max": vals[-1] if vals else 0.0,
        }
    if batched:
        amortized = sorted(
            float(r.get("ms") or 0.0) / max(1, int(r["batch_n"])) for r in batched
        )
        # mean fill counts each BATCH once (its index-0 record), not each
        # request — averaging batch_n over per-request records would weight
        # every batch by its own width and inflate the mean toward full
        # batches (a 4-batch plus a 1-batch is fill 2.5, not 3.4)
        heads = [int(r["batch_n"]) for r in batched if int(r.get("batch_index", 0)) == 0]
        out["_batched"] = {
            "n": len(batched),
            "mean_fill": (sum(heads) / len(heads)) if heads else float(batched[0]["batch_n"]),
            "p50_amortized": _pct(amortized, 0.50),
        }
    return out


def _runs_detail(
    stages: List[dict], requests: List[dict], manifests: List[dict],
    run: Optional[str] = None,
) -> List[dict]:
    """One entry per run_id (restricted to `run` when given): record
    count, knobs, gate arms, execution digest (from the newest manifest
    carrying one — a process stamps a manifest per dump, and the latest
    reflects its final arm map)."""
    counts: Dict[str, int] = {}
    for rec in stages:
        rid = rec.get("run_id", "?")
        counts[rid] = counts.get(rid, 0) + 1
    for rec in requests:
        # request records count too: a service run whose stage spans
        # were dropped/drained before a dump still HAS data
        rid = rec.get("run_id", "?")
        counts[rid] = counts.get(rid, 0) + 1
    if run:
        counts = {rid: n for rid, n in counts.items() if rid == run}
    man_by_run: Dict[str, dict] = {}
    for m in manifests:  # later manifests win (file order = append order)
        man_by_run[m.get("run_id")] = m
    out = []
    for rid, n in sorted(counts.items()):
        m = man_by_run.get(rid, {})
        out.append(
            {
                "run_id": rid,
                "records": n,
                "knobs": m.get("knobs", {}),
                "gates": m.get("gates", {}),
                "execution_digest": m.get("execution_digest"),
                # fixed-base table accounting (family geometry + resident
                # bytes + built-vs-cache provenance) — so a cold start's
                # precomp_build cost in the stage table is attributable
                # to the tables it produced
                "precomp": m.get("precomp"),
            }
        )
    return out


def _runs_summary(runs: List[dict]) -> str:
    """Text render of _runs_detail — ONE aggregation behind both views,
    so the text and --json listings can never disagree about which runs
    exist or what their digests are."""
    lines = []
    for r in runs:
        k = r["knobs"]
        arms = " ".join(
            f"{name}={k[name]}"
            for name in ("msm_glv", "msm_batch_affine", "msm_overlap", "msm_precomp")
            if name in k
        )
        if r["execution_digest"]:
            arms = f"digest={r['execution_digest']}  {arms}"
        pm = r.get("precomp")
        if pm:
            built = sum(1 for f in pm.get("families", {}).values() if f.get("source") == "built")
            arms += (
                f"  precomp_tables={len(pm.get('families', {}))}"
                f" ({pm.get('total_bytes', 0) / 1e6:.0f} MB, {built} built)"
            )
        lines.append(f"{r['run_id']}: {r['records']} records  {arms}")
    return "\n".join(lines) or "(no run_ids found)"


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("files", nargs="*", help="JSONL sink file(s)")
    ap.add_argument("--tree", action="store_true", help="stage-path tree view")
    ap.add_argument("--runs", action="store_true", help="list run_ids and exit")
    ap.add_argument("--run", help="restrict to one run_id")
    ap.add_argument(
        "--diff", nargs=2, metavar=("A", "B"),
        help="two run_ids (single input) or ignored-with-two-files A/B p50 diff",
    )
    ap.add_argument(
        "--compare", nargs=2, metavar=("RUN_A", "RUN_B"),
        help="two run_ids: per-stage p50 diff table WITH the execution-digest "
             "callout (match = real perf delta; differ = names the diverging arms)",
    )
    ap.add_argument("--json", action="store_true", help="machine output (stages/requests/runs + digests)")
    ap.add_argument(
        "--chrome-trace", metavar="OUT",
        help="write the request waterfalls as Chrome trace-event JSON (Perfetto-loadable)",
    )
    ap.add_argument(
        "--fleet-dir", metavar="DIR",
        help="discover a fleet run's sinks from its fleet dir (<spool>/.fleet) "
             "instead of naming files — composes with every other mode",
    )
    ap.add_argument(
        "--request", metavar="RID",
        help="single-request timeline: arrival -> claims -> takeovers -> terminal, "
             "with owning worker and queue-wait per hop",
    )
    args = ap.parse_args(argv)
    if args.fleet_dir:
        found = fleet_sinks(args.fleet_dir)
        if not found and not args.files:
            print(f"[trace_report] no sinks found for fleet dir {args.fleet_dir}", file=sys.stderr)
            return 1
        args.files = list(args.files) + [p for p in found if p not in args.files]
    if not args.files:
        ap.error("need sink file(s) or --fleet-dir")

    if args.diff and len(args.files) == 2:
        # file-vs-file diff: --diff labels the columns
        sa, _, _, _ = load_records([args.files[0]])
        sb, _, _, _ = load_records([args.files[1]])
        if args.json:
            print(json.dumps({"a": aggregate(sa), "b": aggregate(sb)}))
        else:
            print(render_diff(aggregate(sa), aggregate(sb), args.diff[0], args.diff[1]))
        return 0

    stages, requests, manifests, timeseries = load_records(args.files)
    if args.request:
        reqs = [r for r in requests if not args.run or r.get("run_id") == args.run]
        print(request_timeline(reqs, args.request))
        return 0
    if args.chrome_trace:
        trace = chrome_trace(requests, run=args.run, stages=stages)
        n_slices = sum(1 for e in trace["traceEvents"] if e.get("ph") == "X")
        n_flows = sum(1 for e in trace["traceEvents"] if e.get("ph") == "s")
        with open(args.chrome_trace, "w") as f:
            json.dump(trace, f)
        print(
            f"[trace_report] wrote {n_slices} spans + {n_flows} cross-attempt flow(s) across "
            f"{len({e['pid'] for e in trace['traceEvents']})} worker pid(s) to "
            f"{args.chrome_trace} (load in https://ui.perfetto.dev)",
            file=sys.stderr,
        )
        if not n_slices:
            print("[trace_report] no request spans found (pre-PR-8 sink?)", file=sys.stderr)
        return 0
    if args.runs:
        runs = _runs_detail(stages, requests, manifests, run=args.run)
        if args.json:
            print(json.dumps({"runs": runs}))
        else:
            print(_runs_summary(runs))
        return 0
    if args.compare:
        run_a, run_b = args.compare
        agg_a = aggregate(stages, run=run_a)
        agg_b = aggregate(stages, run=run_b)
        if not agg_a or not agg_b:
            print(f"no records for run_id {run_a if not agg_a else run_b}", file=sys.stderr)
            return 1
        callout = digest_callout(_runs_detail(stages, requests, manifests), run_a, run_b)
        if args.json:
            print(json.dumps({"a": agg_a, "b": agg_b, "digest_callout": callout}))
        else:
            print("\n".join(callout))
            print(render_diff(agg_a, agg_b, run_a, run_b))
        return 0
    if args.diff:
        agg_a = aggregate(stages, run=args.diff[0])
        agg_b = aggregate(stages, run=args.diff[1])
        if not agg_a or not agg_b:
            print(f"no records for run_id {args.diff[0] if not agg_a else args.diff[1]}", file=sys.stderr)
            return 1
        if args.json:
            print(json.dumps({"a": agg_a, "b": agg_b}))
        else:
            print(render_diff(agg_a, agg_b, args.diff[0], args.diff[1]))
        return 0
    agg = aggregate(stages, run=args.run)
    if args.json:
        print(
            json.dumps(
                {
                    "stages": agg,
                    "requests": _aggregate_requests(requests, run=args.run),
                    "runs": _runs_detail(stages, requests, manifests, run=args.run),
                    "timeseries": _aggregate_timeseries(timeseries, run=args.run),
                }
            )
        )
        return 0
    print(render_tree(agg) if args.tree else render_table(agg))
    req_view = render_requests(requests, run=args.run)
    if req_view:
        print()
        print(req_view)
    ts_view = render_timeseries(_aggregate_timeseries(timeseries, run=args.run))
    if ts_view:
        print()
        print(ts_view)
    return 0


if __name__ == "__main__":
    sys.exit(main())
