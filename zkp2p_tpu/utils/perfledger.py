"""Perf-regression sentry: the fingerprint-keyed stage-cost ledger.

The engine has a fleet observability plane (docs/OBSERVABILITY.md) and
SLO accounting, but until now no LONGITUDINAL memory: per-stage costs
lived in loose `BENCH_*.json` tails and one-off interleaved A/Bs, so a
perf regression — the same silent-failure class as a gate disarm, just
in seconds instead of bytes — was only caught by a human rereading
bench output.  This module gives the repo that memory:

  - an append-only JSONL ledger beside `.bench_cache`
    (`perf_ledger_<fingerprint>.jsonl`, the `hostprof` 16-hex host key)
    recording per-(circuit, stage, arm-digest) p50/p95 span costs from
    bench runs, tune sweeps, warm-cache round trips and sampled live
    service sweeps;
  - per-stage BUDGETS derived from it (trailing-window median ×
    ZKP2P_PERF_TOLERANCE) that `service.py` checks every terminal
    request's spans against (`zkp2p_stage_budget_overruns_total`);
  - a baseline band (`PERF_BASELINE.json`, frozen with `--rebaseline`;
    none is committed) that `zkp2p-tpu perf --gate` replays the ledger
    head against, exiting nonzero on drift.

Trust model mirrors `hostprof`: every line is stamped with this host's
fingerprint key AND a content digest over its own body.  At read time,
foreign-fingerprint lines (a ledger copied from another box) and
digest-mismatched lines (a body edited after signing) are REFUSED and
counted, never silently blended into budgets — a budget derived from
someone else's hardware would page on every healthy request, and a
doctored history would hide the regression the sentry exists to catch.

Gating: ZKP2P_PERF_LEDGER (`perf_ledger` knob, default on) is
record_arm'd and preflight-armed like every other knob, so a
ledger-on/ledger-off A/B pair is digest-distinguishable on exactly
this gate.  Off means the WHOLE subsystem is off: no appends, no
budget loads, no overrun counting — the fail-closed oracle arm.
"""

from __future__ import annotations

import glob
import hashlib
import json
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

SCHEMA_VERSION = 1
LEDGER_PREFIX = "perf_ledger_"
BASELINE_NAME = "PERF_BASELINE.json"

# a backfilled BENCH tail predates the execution-audit stamp in the
# parsed record; the constant groups history entries under one arm
BACKFILL_DIGEST = "backfill"

_lock = threading.Lock()
# (path, mtime_ns, window, tolerance) -> budgets dict (the service
# checks every terminal request; re-deriving budgets per request would
# re-read and re-sort the whole ledger on the prove hot path)
_budget_memo: Optional[Tuple[Tuple, Dict]] = None


def default_ledger_path() -> Optional[str]:
    """`<precomp cache dir>/perf_ledger_<fingerprint>.jsonl` — beside
    the `.bench_cache` tables and the host profile; None when
    persistence is disabled (ZKP2P_MSM_PRECOMP_CACHE=0)."""
    from ..prover.precomp import _cache_dir

    from .hostprof import fingerprint_key

    d = _cache_dir()
    if d is None:
        return None
    return os.path.join(d, LEDGER_PREFIX + fingerprint_key() + ".jsonl")


def default_baseline_path() -> str:
    """`<repo>/PERF_BASELINE.json` — the band `zkp2p-tpu perf --gate`
    replays the ledger head against."""
    here = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(here, BASELINE_NAME)


def _entry_digest(body: Dict) -> str:
    """16-hex content digest over the entry body (entry_digest field
    excluded) — the hostprof embedded-key trick applied per line: a
    body edited after signing fails this check and is refused."""
    blob = json.dumps(
        {k: v for k, v in body.items() if k != "entry_digest"},
        sort_keys=True, separators=(",", ":"),
    )
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def stage_stats(ms_values: List[float]) -> Optional[Dict]:
    """Nearest-rank p50/p95 over a span-cost sample (the trace_report
    percentile convention, so ledger entries and report tables agree)."""
    vals = sorted(float(v) for v in ms_values if v is not None)
    if not vals:
        return None

    def pct(p: float) -> float:
        i = max(0, min(len(vals) - 1, int(round(p / 100.0 * len(vals) + 0.5)) - 1))
        return vals[i]

    return {
        "p50_ms": round(pct(50), 3),
        "p95_ms": round(pct(95), 3),
        "n": len(vals),
    }


def make_entry(
    source: str,
    circuit: str,
    stages: Dict[str, Dict],
    run_id: Optional[str] = None,
    execution_digest: Optional[str] = None,
    extra: Optional[Dict] = None,
) -> Dict:
    """One signed ledger line: source ∈ {bench, tune, warm_cache,
    service, bench_backfill}, stages = {name: {p50_ms, p95_ms, n}}."""
    from .hostprof import fingerprint_key

    if execution_digest is None:
        from .audit import execution_digest as _xd

        execution_digest = _xd()
    body: Dict = {
        "schema": SCHEMA_VERSION,
        "ts": round(time.time(), 3),
        "source": source,
        "circuit": circuit,
        "fingerprint_key": fingerprint_key(),
        "execution_digest": execution_digest,
        "stages": {
            name: {
                "p50_ms": round(float(st["p50_ms"]), 3),
                "p95_ms": round(float(st.get("p95_ms", st["p50_ms"])), 3),
                "n": int(st.get("n", 1)),
            }
            for name, st in stages.items()
        },
    }
    if run_id:
        body["run_id"] = run_id
    if extra:
        body.update(extra)
    body["entry_digest"] = _entry_digest(body)
    return body


def append_entry(entry: Dict, path: Optional[str] = None) -> Optional[str]:
    """Append one line, atomically: a single O_APPEND write() per line
    (the JsonlSink/dump_trace discipline — concurrent workers' lines
    interleave whole, never torn).  Returns the path, None when
    persistence is off or the write failed (observation must never
    sink the measured work)."""
    path = path or default_ledger_path()
    if not path:
        return None
    line = (json.dumps(entry, sort_keys=True, separators=(",", ":")) + "\n").encode()
    try:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        try:
            os.write(fd, line)
        finally:
            os.close(fd)
    except OSError:
        return None
    _invalidate_memo()
    return path


def record(
    source: str,
    circuit: str,
    stages: Dict[str, Dict],
    run_id: Optional[str] = None,
    path: Optional[str] = None,
    extra: Optional[Dict] = None,
) -> Optional[str]:
    """Gate-checked stamp: resolve + arm the perf_ledger gate, append
    one entry when it is on.  The single producer-side entry point —
    bench, tune, warm-cache and the service all come through here, so
    the gate's off arm silences every producer at once."""
    if perf_arm() != "on":
        return None
    if not stages:
        return None
    return append_entry(
        make_entry(source, circuit, stages, run_id=run_id, extra=extra), path=path
    )


def perf_arm() -> str:
    """Resolve + arm the perf-ledger gate (the preflight hook):
    "on" | "off".  A ledger-on run must never share an execution
    digest with a ledger-off one."""
    from .audit import record_arm
    from .config import load_config

    return record_arm("perf_ledger", "on" if load_config().perf_ledger else "off")


def load_entries(path: Optional[str] = None) -> Tuple[List[Dict], Dict[str, int]]:
    """Every VALID entry in file (append) order, plus refusal counts.
    Refused like tampered host profiles — never blended into budgets:
      unparseable  — not one JSON object per line
      schema       — schema version drift
      foreign      — fingerprint key is not this host's
      tampered     — entry_digest does not match the body
    """
    from .hostprof import fingerprint_key

    refused = {"unparseable": 0, "schema": 0, "foreign": 0, "tampered": 0}
    path = path or default_ledger_path()
    if not path:
        return [], refused
    try:
        with open(path) as f:
            lines = f.readlines()
    except OSError:
        return [], refused
    me = fingerprint_key()
    out: List[Dict] = []
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            e = json.loads(line)
        except ValueError:
            refused["unparseable"] += 1
            continue
        if not isinstance(e, dict) or not isinstance(e.get("stages"), dict):
            refused["unparseable"] += 1
            continue
        if e.get("schema") != SCHEMA_VERSION:
            refused["schema"] += 1
            continue
        if e.get("entry_digest") != _entry_digest(e):
            refused["tampered"] += 1  # body edited after signing
            continue
        if e.get("fingerprint_key") != me:
            refused["foreign"] += 1  # another box's costs: never budget from them
            continue
        out.append(e)
    return out, refused


def _invalidate_memo() -> None:
    global _budget_memo
    with _lock:
        _budget_memo = None


def reset() -> None:
    """Test hook: drop the budget memo (a test that rewrites the ledger
    under one process must not read the previous file's budgets)."""
    _invalidate_memo()


def derive_budgets(
    entries: List[Dict],
    window: Optional[int] = None,
    tolerance: Optional[float] = None,
) -> Dict[str, Dict[str, Dict]]:
    """{circuit: {stage: {budget_ms, median_ms, n, tolerance}}} from
    valid entries in ledger order.

    Per (circuit, stage): take the trailing `window` entries, keep only
    those sharing the HEAD entry's execution digest (mixing arms would
    blend two different cost distributions into one budget — the
    skipped count is recorded as arm_skipped), then
    budget = median(p50_ms) × tolerance.
    """
    from .config import load_config

    cfg = load_config()
    window = cfg.perf_window if window is None else max(1, int(window))
    tolerance = cfg.perf_tolerance if tolerance is None else float(tolerance)
    series: Dict[Tuple[str, str], List[Tuple[float, str, str]]] = {}
    for e in entries:
        circuit = str(e.get("circuit", "?"))
        digest = str(e.get("execution_digest", "?"))
        entry_d = str(e.get("entry_digest", "?"))
        for stage, st in e["stages"].items():
            try:
                p50 = float(st["p50_ms"])
            except (KeyError, TypeError, ValueError):
                continue
            series.setdefault((circuit, stage), []).append((p50, digest, entry_d))
    out: Dict[str, Dict[str, Dict]] = {}
    for (circuit, stage), rows in series.items():
        tail = rows[-window:]
        head_digest = tail[-1][1]
        vals = sorted(v for v, d, _ed in tail if d == head_digest)
        if not vals:
            continue
        # UPPER median (even-count windows take the higher middle): the
        # budget's job is to catch drift, not to page on the slower of
        # two equally-valid historical rounds — a lower-median two-entry
        # window would flag the round that produced it
        med = vals[len(vals) // 2]
        out.setdefault(circuit, {})[stage] = {
            "budget_ms": round(med * tolerance, 3),
            "median_ms": round(med, 3),
            "n": len(vals),
            "arm_skipped": len(tail) - len(vals),
            "tolerance": tolerance,
            # entry_digest of the HEAD ledger entry this budget window
            # is anchored to — a flame capture triggered by this budget
            # records it, so `zkp2p-tpu perf` can walk a DRIFT verdict
            # to the capture that explains it
            "head_digest": tail[-1][2],
        }
    return out


class BudgetBook:
    """The service-side view: per-stage budgets for ONE circuit, loaded
    once (memoized by ledger path+mtime) and consulted per terminal
    request with dict lookups only — the <1% overhead contract."""

    def __init__(self, budgets: Dict[str, Dict]):
        self._budgets = dict(budgets)

    def __len__(self) -> int:
        return len(self._budgets)

    def budget_ms(self, stage: str) -> Optional[float]:
        b = self._budgets.get(stage)
        return None if b is None else b["budget_ms"]

    def head_digest(self, stage: str) -> Optional[str]:
        """The ledger entry_digest this stage's budget window was
        filtered against (None for a stage with no budget) — what a
        triggered flame capture records as its cross-link."""
        b = self._budgets.get(stage)
        return None if b is None else b.get("head_digest")

    def over(self, stage: str, ms: Optional[float]) -> Optional[bool]:
        """True = over budget, False = within, None = NO budget for
        this stage (a fresh host / new stage must not page — the alert
        rule HOLDs on None)."""
        if ms is None:
            return None
        b = self._budgets.get(stage)
        if b is None:
            return None
        return float(ms) > b["budget_ms"]

    @classmethod
    def load(
        cls,
        circuit: str,
        path: Optional[str] = None,
        window: Optional[int] = None,
        tolerance: Optional[float] = None,
    ) -> "BudgetBook":
        """Budgets for `circuit` from the on-disk ledger; an EMPTY book
        (every check returns None) when the gate is off, persistence is
        off, or the ledger has no entries for this host."""
        global _budget_memo

        if perf_arm() != "on":
            return cls({})
        path = path or default_ledger_path()
        if not path:
            return cls({})
        try:
            mtime = os.stat(path).st_mtime_ns
        except OSError:
            return cls({})
        key = (path, mtime, window, tolerance)
        with _lock:
            memo = _budget_memo
        if memo is not None and memo[0] == key:
            budgets = memo[1]
        else:
            entries, _refused = load_entries(path)
            budgets = derive_budgets(entries, window=window, tolerance=tolerance)
            with _lock:
                _budget_memo = (key, budgets)
        return cls(budgets.get(circuit, {}))


def tune_stages(profile: Dict) -> Dict[str, Dict]:
    """Ledger stages out of a `zkp2p-tpu tune` profile: the measured
    BEST wall time per sweep family (threads, window tags, columns).
    Best-of-arms is the regression-tracking quantity — a slower box
    moves the best, whichever arm wins it; per-arm spread is the tune
    sweep's own concern."""
    stages: Dict[str, Dict] = {}
    sweep = (profile.get("tune") or {}).get("sweep") or {}

    def best(rows: Dict, name: str) -> None:
        vals = [v for v in (rows or {}).values() if isinstance(v, (int, float))]
        if vals:
            ms = round(min(vals) * 1e3, 3)
            stages[name] = {"p50_ms": ms, "p95_ms": ms, "n": len(vals)}

    best(sweep.get("threads"), "tune/msm_threads_best")
    for tag, rows in (sweep.get("window") or {}).items():
        best(rows, f"tune/msm_window_{tag}")
    best(sweep.get("columns"), "tune/msm_columns_best")
    return stages


# --------------------------------------------------------------------------
# BENCH-history backfill: trendlines start with the committed history,
# not an empty file.


def _bench_tail_stages(tail: str) -> Dict[str, List[float]]:
    """Per-stage span samples out of a BENCH record's free-text tail
    (JSONL trace lines interleaved with log text).  Steady-rep stage
    paths are normalized (`prove_native_3/native/msm_h` →
    `native/msm_h`, `prove_native_3` → `prove_native`) so reps pool
    into one sample per stage."""
    stages: Dict[str, List[float]] = {}
    for line in tail.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        st, ms = rec.get("stage"), rec.get("ms")
        if not isinstance(st, str) or not isinstance(ms, (int, float)):
            continue
        root, _, rest = st.partition("/")
        if root.startswith("prove_native"):
            st = rest if rest else "prove_native"
        stages.setdefault(st, []).append(float(ms))
    return stages


def backfill_bench(
    bench_glob: Optional[str] = None,
    path: Optional[str] = None,
    log=None,
) -> int:
    """Import the committed `BENCH_r*.json` tails as ledger entries
    (source=bench_backfill, one per successful round), idempotently:
    a round already in the ledger (matched by its `backfill_of` stamp)
    is skipped, so `zkp2p-tpu perf --gate` can run this unconditionally.

    The history predates the fingerprint stamp; entries are signed with
    THIS host's key on the documented assumption that the committed
    history and the gate run share the container image.  Returns the
    number of entries appended."""
    here = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    bench_glob = bench_glob or os.path.join(here, "BENCH_r*.json")
    path = path or default_ledger_path()
    if not path:
        return 0
    entries, _refused = load_entries(path)
    seen = {e.get("backfill_of") for e in entries if e.get("backfill_of")}
    added = 0
    for bench_path in sorted(glob.glob(bench_glob)):
        name = os.path.basename(bench_path)
        if name in seen:
            continue
        try:
            with open(bench_path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        if rec.get("rc") != 0:
            continue  # a failed round measured nothing
        stages = {
            st: stats
            for st, samples in _bench_tail_stages(rec.get("tail", "")).items()
            for stats in [stage_stats(samples)]
            if stats is not None
        }
        parsed = rec.get("parsed") or {}
        p50_s = parsed.get("p50_s")
        if not stages and p50_s is None:
            continue
        if p50_s is not None:
            stages.setdefault(
                "prove_native",
                {"p50_ms": round(float(p50_s) * 1e3, 3), "p95_ms": round(float(p50_s) * 1e3, 3), "n": 1},
            )
        entry = make_entry(
            "bench_backfill",
            "venmo",
            stages,
            run_id=parsed.get("run_id"),
            execution_digest=parsed.get("execution_digest") or BACKFILL_DIGEST,
            extra={"backfill_of": name},
        )
        if append_entry(entry, path=path):
            added += 1
            if log:
                log(f"perf: backfilled {name} ({len(stages)} stage(s))")
    return added


# --------------------------------------------------------------------------
# Baseline band + drift gate (`zkp2p-tpu perf --gate`).


def write_baseline(
    baseline_path: Optional[str] = None,
    ledger_path: Optional[str] = None,
    window: Optional[int] = None,
    tolerance: Optional[float] = None,
) -> Optional[Dict]:
    """Freeze the current budgets as the committed band (tmp+rename —
    a torn baseline must never judge a gate run).  None when the
    ledger is empty (an empty band would make every future gate
    vacuously green — fail closed instead)."""
    from .config import load_config
    from .hostprof import fingerprint_key

    cfg = load_config()
    entries, _refused = load_entries(ledger_path)
    if not entries:
        return None
    budgets = derive_budgets(entries, window=window, tolerance=tolerance)
    if not budgets:
        return None
    doc = {
        "schema": SCHEMA_VERSION,
        "generated_ts": round(time.time(), 3),
        "fingerprint_key": fingerprint_key(),
        "window": cfg.perf_window if window is None else int(window),
        "tolerance": cfg.perf_tolerance if tolerance is None else float(tolerance),
        "bands": budgets,
    }
    baseline_path = baseline_path or default_baseline_path()
    tmp = f"{baseline_path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, baseline_path)
    except OSError:
        try:
            if os.path.exists(tmp):
                os.remove(tmp)
        except OSError:
            pass
        return None
    return doc


def gate_check(
    baseline_path: Optional[str] = None,
    ledger_path: Optional[str] = None,
    log=None,
) -> Tuple[int, List[Dict]]:
    """Replay the ledger HEAD (most recent valid entry per circuit/
    stage) against the committed band.  Returns (rc, verdict rows):

      rc 0 — every head stage with a band is within budget (rows may
             still carry the informational IMPROVED verdict: the head
             p50 lands well under the committed band — a stale-loose
             band that wants `zkp2p-tpu perf --rebaseline`)
      rc 1 — DRIFT: at least one head p50 exceeds its band
      rc 2 — fail closed: no baseline, or no valid ledger entries
             (a gate that cannot compare must not pass)

    Stages present on only one side are reported (`new` / `gone`) but
    do not fail the gate — adding instrumentation must not require a
    same-commit rebaseline.  A fingerprint mismatch between the band
    and this host is WARNED about and still compared: absolute ms on
    foreign hardware is suspect either way, and the warning names the
    remediation (`zkp2p-tpu perf --rebaseline`)."""
    log = log or (lambda m: None)
    baseline_path = baseline_path or default_baseline_path()
    try:
        with open(baseline_path) as f:
            base = json.load(f)
    except (OSError, ValueError):
        log(f"perf-gate: FAIL CLOSED — no readable baseline at {baseline_path}")
        return 2, []
    if not isinstance(base, dict) or base.get("schema") != SCHEMA_VERSION:
        log("perf-gate: FAIL CLOSED — baseline schema drift")
        return 2, []
    entries, refused = load_entries(ledger_path)
    if not entries:
        log(
            "perf-gate: FAIL CLOSED — no valid ledger entries for this host "
            f"(refused: {refused})"
        )
        return 2, []
    from .hostprof import fingerprint_key

    if base.get("fingerprint_key") != fingerprint_key():
        log(
            "perf-gate: WARNING — baseline was frozen on different hardware "
            f"({base.get('fingerprint_key')} vs {fingerprint_key()}); comparing "
            "anyway, rebaseline with `zkp2p-tpu perf --rebaseline`"
        )
    # head = last valid entry's p50 per (circuit, stage)
    head: Dict[Tuple[str, str], Dict] = {}
    for e in entries:
        for stage, st in e["stages"].items():
            head[(str(e.get("circuit", "?")), stage)] = {
                "p50_ms": st["p50_ms"],
                "source": e.get("source"),
                "execution_digest": e.get("execution_digest"),
            }
    bands = base.get("bands") or {}
    verdicts: List[Dict] = []
    rc = 0
    for (circuit, stage), h in sorted(head.items()):
        band = (bands.get(circuit) or {}).get(stage)
        if band is None:
            verdicts.append({
                "circuit": circuit, "stage": stage, "verdict": "new",
                "p50_ms": h["p50_ms"],
            })
            continue
        drift = float(h["p50_ms"]) > float(band["budget_ms"])
        # IMPROVED: the head p50 lands as far UNDER the band's median as
        # the tolerance allows over it (head * tol < median) — the band
        # is stale-loose and no longer guards the real floor.  Informs,
        # never fails: rc stays 0, the remediation is a rebaseline
        # (`zkp2p-tpu perf --rebaseline`) so the speedup becomes the
        # guarded floor instead of headroom a regression can hide in.
        tol = float(base.get("tolerance") or 1.5)
        improved = (not drift) and float(h["p50_ms"]) * tol < float(band["median_ms"])
        verdicts.append({
            "circuit": circuit, "stage": stage,
            "verdict": "DRIFT" if drift else ("IMPROVED" if improved else "ok"),
            "p50_ms": h["p50_ms"],
            "budget_ms": band["budget_ms"],
            "median_ms": band["median_ms"],
            "execution_digest": h["execution_digest"],
        })
        if drift:
            rc = 1
    for circuit, stages in sorted(bands.items()):
        for stage in sorted(stages):
            if (circuit, stage) not in head:
                verdicts.append({"circuit": circuit, "stage": stage, "verdict": "gone"})
    return rc, verdicts
