"""Structured stage tracing for the proving service.

The reference's observability is `start=$(date +%s)` brackets in shell
scripts, `console.time("zk-dl"/"zk-gen")` and a UI stopwatch
(SURVEY.md §5 tracing).  This is the structured version: nested spans
with one JSON-lines sink.

    with trace("prove", batch=16):
        with trace("h_poly"):
            ...
    dump_trace()  ->  [{"stage": "prove", "ms": ..., "t0": ..., "id": 1,
                        "parent": None, "batch": 16}, ...]

A closed span records its path (`stage`), its duration (`ms`), its start
on the wall clock (`t0`, `time.time()` — the clock of the spool's
mtimes and of the request records), an `id` unique in the process and
the `id` of the span that was open on its thread when it opened
(`parent`; `adopt_stack` carries it to worker threads), the thread that
closed it (`tid`), the CPU time that thread spent inside it (`cpu_ms`,
`time.thread_time()` at its two ends: `ms` less `cpu_ms` is what the
thread waited, for a lock, a core, a device or a sleep), plus the ambient
context (`request_id`) and its own attributes.  `record()` writes a span
whose ends were read from clocks instead of bracketed by a `with`; it
carries a `cpu_ms` only where its caller read one.

Each thread also keeps a tally of what its spans cost it: the self time
of every span it closed (its time less what its children covered), by
the span's last path element, wall and CPU (`thread_tally`).  Two
readings partition the thread's time between them by span name.

While a span is open it is also a `jax.profiler.TraceAnnotation` of the
same path, so a profiler capture shows the program's spans in the host
plane on the trace's own clock — only where `jax` is already imported:
this module never imports it (the native prover and the tools stay
JAX-free).

Every closed span also feeds the process metrics registry
(utils.metrics REGISTRY, `zkp2p_stage_ms{stage=...}` histograms), so a
Prometheus scrape sees stage latencies without any dump.

Records are held in a bounded ring (ZKP2P_TRACE_MAX, default 64k): a
service loop tracing forever stays at a fixed memory footprint and the
overflow is COUNTED (`zkp2p_trace_dropped_total` + the dump manifest),
never silent.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import os
import sys
import threading
import time
from typing import Any, Deque, Dict, List, Optional, Tuple


def _ring_capacity() -> int:
    from .config import load_config

    return load_config().trace_max


_records: Deque[Dict[str, Any]] = collections.deque(maxlen=_ring_capacity())
_dropped = 0  # lifetime count of ring-overflow evictions (GIL-guarded)
# Stage nesting is PER THREAD (the service overlaps a witness thread with
# the proving thread; a shared stack would interleave their frames and
# pop across threads).  Appends to _records are atomic under the GIL.
_tls = threading.local()

# stage-path -> histogram, cached so the registry lock is not taken per
# span close (get-or-create only on first sight of a stage).  Keyed by
# the registry GENERATION too: REGISTRY.reset() orphans instruments, and
# feeding an orphan would silently blank exposition for cached stages.
_stage_hists: Dict[str, Any] = {}
_stage_hists_gen = -1


def _observe_stage(path: str, ms: float) -> None:
    global _stage_hists_gen
    from .metrics import REGISTRY

    if REGISTRY.generation != _stage_hists_gen:
        _stage_hists.clear()
        _stage_hists_gen = REGISTRY.generation
    h = _stage_hists.get(path)
    if h is None:
        h = _stage_hists[path] = REGISTRY.histogram("zkp2p_stage_ms", {"stage": path})
    h.observe(ms)


_append_lock = threading.Lock()


def _append(rec: Dict[str, Any]) -> None:
    # Locked: two threads both seeing len == maxlen-1 would each append
    # (one eviction) yet neither count the drop — and the drop counter's
    # whole contract is "overflow counted, never silent".
    global _dropped
    with _append_lock:
        dropped = _records.maxlen is not None and len(_records) == _records.maxlen
        if dropped:
            _dropped += 1
        _records.append(rec)
    if dropped:
        from .metrics import REGISTRY

        REGISTRY.counter("zkp2p_trace_dropped_total").inc()


# One frame per open span: (its path, its id, the path its children extend).
_Frame = Tuple[str, int, str]
_ids = itertools.count(1)  # next() is atomic under the GIL


def _open(stage: str, leaf: bool = False):
    """(stack, frame, parent id) for a span named `stage` under the span
    open on this thread."""
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    parent_id, prefix = (stack[-1][1], stack[-1][2]) if stack else (None, "")
    path = f"{prefix}/{stage}" if prefix else stage
    return stack, (path, next(_ids), prefix if leaf else path), parent_id


def _tallied(name: str, secs: float, cpu: float, kids_secs: float = 0.0, kids_cpu: float = 0.0) -> None:
    """A span of this thread has closed: its self time to the thread's
    tally under `name`, its whole time to what the span open around it
    has covered by children."""
    tally = getattr(_tls, "tally", None)
    if tally is None:
        tally = _tls.tally = {}
    own = tally.get(name)
    if own is None:
        tally[name] = [secs - kids_secs, cpu - kids_cpu]
    else:
        own[0] += secs - kids_secs
        own[1] += cpu - kids_cpu
    opened = getattr(_tls, "opened", None)
    if opened:
        opened[-1][3] += secs
        opened[-1][4] += cpu


def thread_tally() -> Dict[str, Tuple[float, float]]:
    """{a span's last path element: (self ms, self cpu_ms)}, summed over
    the spans THIS thread has closed since it started, and over the part
    so far of those it has open.  A span's self time is its time less
    what the spans that closed under it on this thread covered (one that
    a worker thread closes under an adopted stack is that thread's); a
    span opened with `t0=` counts from where it was opened, a `record()`
    its whole interval.  So the difference of two readings partitions
    the thread's time between them by name, and what no name claims was
    spent in no span."""
    out = {name: (own[0] * 1e3, own[1] * 1e3) for name, own in (getattr(_tls, "tally", None) or {}).items()}
    opened = getattr(_tls, "opened", None)
    if opened:
        # an open span's self time so far: since it opened, less its closed
        # children and the child that is open now
        end, end_cpu = time.perf_counter(), time.thread_time()
        for name, p0, c0, kids_secs, kids_cpu in reversed(opened):
            ms, cpu_ms = out.get(name, (0.0, 0.0))
            out[name] = (ms + (end - p0 - kids_secs) * 1e3, cpu_ms + (end_cpu - c0 - kids_cpu) * 1e3)
            end, end_cpu = p0, c0  # what the span around it has spent in this one
    return out


def _close(rec: Dict[str, Any]) -> None:
    for k, v in (getattr(_tls, "ctx", None) or {}).items():
        rec.setdefault(k, v)  # explicit attributes win over the ambient context
    _append(rec)
    _observe_stage(rec["stage"], rec["ms"])


def _annotation(path: str):
    """The open span as a TraceAnnotation, where jax is already imported
    (inactive outside a profiler capture: one C++ flag test)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        ann = jax.profiler.TraceAnnotation(path)
        ann.__enter__()
        return ann
    except Exception:  # noqa: BLE001 — jax half-imported or without a profiler: no annotation
        return None


@contextlib.contextmanager
def trace(stage: str, *, leaf: bool = False, t0: Optional[float] = None, **attrs):
    """Open a span; yields its record, to which the body may add
    attributes (`ms` is filled in at close).

    leaf: the span is the parent of what opens under it, but their paths
    extend its parent's path, not its own — a phase ("tpu/prove_batch/
    device") whose children are named as the batch's, or a sweep around
    spans whose paths predate it.
    t0: the span began at this `time.time()` reading, taken before it was
    known that there was a span to open."""
    stack, frame, parent = _open(stage, leaf)
    stack.append(frame)
    rec = {"stage": frame[0], "ms": None, "t0": round(time.time() if t0 is None else t0, 6),
           "id": frame[1], "parent": parent, "tid": threading.get_ident(), **attrs}
    opened = getattr(_tls, "opened", None)
    if opened is None:
        opened = _tls.opened = []
    p0, c0 = time.perf_counter(), time.thread_time()
    # the span's account in the thread's tally: name, the two clocks at its
    # opening, and what its closed children have covered of each
    own = [stage.rpartition("/")[2], p0, c0, 0.0, 0.0]
    opened.append(own)
    ann = _annotation(frame[0])
    try:
        yield rec
    finally:
        p1, cpu = time.perf_counter(), time.thread_time() - c0
        secs = p1 - p0 if t0 is None else time.time() - t0
        if ann is not None:
            ann.__exit__(None, None, None)
        rec["ms"], rec["cpu_ms"] = round(secs * 1e3, 3), round(cpu * 1e3, 3)
        stack.pop()
        opened.pop()
        _tallied(own[0], p1 - p0, cpu, own[3], own[4])
        _close(rec)


def record(stage: str, t0: float, t1: float, *, cpu_ms: Optional[float] = None,
           parent: Optional[int] = None, tally: bool = True, **attrs) -> int:
    """A span whose ends were read from clocks (`time.time()`), not
    bracketed by a `with`: it nests under the span open on this thread
    like any other, and being in the past gets no TraceAnnotation.
    Returns its `id`.

    cpu_ms: the CPU time this thread spent in the interval, where the
    caller read `time.thread_time()` at its ends too.
    parent: the `id` of a span already written that this one is a part
    of, where that is not the span open on this thread.
    tally: False for an interval that accounts for time other spans
    already cover (a gap and its parts): it feeds no thread's tally.
    An account that is a sum and no one interval of a thread is written
    with `tid=None`: the Perfetto view (tools/trace_report.py), which
    draws a thread's spans on a row where they must nest, draws none."""
    _stack, frame, open_id = _open(stage)
    if cpu_ms is not None:
        attrs["cpu_ms"] = cpu_ms
    if tally:
        _tallied(stage.rpartition("/")[2], t1 - t0, (cpu_ms or 0.0) / 1e3)
    _close({"stage": frame[0], "ms": round((t1 - t0) * 1e3, 3), "t0": round(t0, 6), "id": frame[1],
            "parent": open_id if parent is None else parent, "tid": threading.get_ident(), **attrs})
    return frame[1]


def current_path() -> str:
    """The path of the span open on this thread ("" at a root)."""
    stack = getattr(_tls, "stack", None)
    return stack[-1][0] if stack else ""


def current_stack() -> List[_Frame]:
    """Snapshot of this thread's stack of open spans — hand it to worker
    threads (with adopt_stack) so their records keep the submitting
    span's path prefix and name it as their parent instead of starting a
    fresh root."""
    return list(getattr(_tls, "stack", None) or [])


def adopt_stack(stack: List[_Frame]) -> None:
    """Seed THIS thread's stack of open spans (see current_stack)."""
    _tls.stack = list(stack)


def set_context(**attrs) -> None:
    """Merge ambient attributes into every record THIS thread closes
    (request_id through witness -> prove -> emit; a None value removes
    the key).  Context rides the same per-thread rail as the stack —
    current_context()/adopt_context() hand it across worker pools."""
    ctx = getattr(_tls, "ctx", None)
    if ctx is None:
        ctx = _tls.ctx = {}
    for k, v in attrs.items():
        if v is None:
            ctx.pop(k, None)
        else:
            ctx[k] = v


def clear_context() -> None:
    _tls.ctx = {}


def current_context() -> Dict[str, Any]:
    return dict(getattr(_tls, "ctx", None) or {})


def adopt_context(ctx: Dict[str, Any]) -> None:
    _tls.ctx = dict(ctx)


def _resize_ring(capacity: int) -> None:
    """Swap the ring for a new bound, keeping the newest records (tests;
    long-lived services retuning ZKP2P_TRACE_MAX without a restart)."""
    global _records
    _records = collections.deque(_records, maxlen=max(1, capacity))


def records() -> List[Dict[str, Any]]:
    return list(_records)


def dropped() -> int:
    return _dropped


def reset() -> None:
    global _dropped
    _records.clear()
    _dropped = 0


def drain() -> List[Dict[str, Any]]:
    """Atomically take every buffered record (the service's per-sweep
    flush into its JSONL sink) — records appended concurrently after the
    snapshot stay buffered for the next drain."""
    out: List[Dict[str, Any]] = []
    while True:
        try:
            out.append(_records.popleft())
        except IndexError:
            return out


def dump_trace(path: Optional[str] = None) -> None:
    """Emit buffered records.  To a file: ONE atomic O_APPEND write —
    safe for many service workers sharing a sink — with a manifest line
    (run_id, pid, host facts, knob states, drop count) stamped first and
    run_id/pid on every record line, so interleaved multi-process dumps
    stay separable and self-describing.  Without a path: stderr.

    Deliberately NOT routed through metrics.JsonlSink: that sink stamps
    a manifest only on a fresh/rotated file, but a trace sink is shared
    ACROSS processes and knob arms (the A/B workflow appends two bench
    runs to one file), so every dump must carry its own manifest or
    trace_report --runs loses the later runs' knob attribution.  The
    trade-off: a process looping dump_trace on one path grows it
    unboundedly — dump once per process, or point heavy loops at a
    JsonlSink."""
    from .metrics import run_id, run_manifest

    recs = records()
    if path:
        rid, pid = run_id(), os.getpid()
        manifest = {"type": "manifest", **run_manifest(), "trace_dropped": _dropped}
        lines = [json.dumps(manifest)]
        lines += [json.dumps({**r, "run_id": rid, "pid": pid}) for r in recs]
        payload = ("\n".join(lines) + "\n").encode()
        fd = os.open(path, os.O_APPEND | os.O_CREAT | os.O_WRONLY, 0o644)
        try:
            os.write(fd, payload)
        finally:
            os.close(fd)
    else:
        print("\n".join(json.dumps(r) for r in recs), file=sys.stderr)
