"""From a jax.profiler trace to the device's busy seconds, the operations
that took most time, and the idle gaps labelled by what the host was doing.

Two steps, so that the arithmetic can be checked on a small recorded slice:

  load_events(xplane.pb)  ->  events: plain lists, JSON-serialisable
  reduce_events(events, host_spans)  ->  busy_s, window_s, device_ops, idle_gaps

Clocks.  Trace timestamps count from the profiler's own start.  The traced
run writes one `TraceAnnotation(ANCHOR, wall_ns=...)` right after the start;
`wall_ns - start_ns` of that event moves host spans recorded on the wall
clock (the service's request records) onto the trace's clock.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

ANCHOR = "bench/anchor"
# lines of a device plane that do not hold single operations
_NOT_OPS = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops", "Framework Name Scope", "Source code")


def op_name(name: str) -> str:
    """The trace prints an operation as its whole HLO text ("%g1_add.14 =
    (u32[4,16,262144]{...}) custom-call(...)"): keep the result's name."""
    return name.split(" = ", 1)[0].lstrip("%")[:96]


def load_events(xplane_path: str) -> Dict:
    """{"device": {plane: {line: [[name, start_ns, dur_ns], ...]}},
        "anchor": {"start_ns", "wall_ns"} or None}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    device: Dict[str, Dict[str, List]] = {}
    anchor = None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = device.setdefault(plane.name, {})
            for line in plane.lines:
                lines[line.name] = [[op_name(e.name), float(e.start_ns), float(e.duration_ns)] for e in line.events]
        elif plane.name.startswith("/host:") and anchor is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == ANCHOR:
                        stats = dict(e.stats)
                        anchor = {"start_ns": float(e.start_ns), "wall_ns": int(stats["wall_ns"])}
                        break
                if anchor:
                    break
    return {"device": device, "anchor": anchor}


def op_lines(lines: Dict[str, List]) -> List[str]:
    """The lines of a device plane whose events are single operations."""
    if "XLA Ops" in lines:
        return ["XLA Ops"]
    return [name for name in lines if name not in _NOT_OPS]


def merge(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def subtract(intervals: List[Tuple[float, float]], cover: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The parts of `intervals` (merged) not inside `cover` (merged)."""
    out = []
    for s, e in intervals:
        at = s
        for cs, ce in cover:
            if ce <= at or cs >= e:
                continue
            if cs > at:
                out.append((at, cs))
            at = max(at, ce)
        if at < e:
            out.append((at, e))
    return out


def overlap_s(a: List[Tuple[float, float]], b: List[Tuple[float, float]]) -> float:
    total = 0.0
    for s, e in a:
        for bs, be in b:
            lo, hi = max(s, bs), min(e, be)
            if hi > lo:
                total += hi - lo
    return total


def self_times(events: Sequence[Sequence]) -> Dict[str, float]:
    """Self nanoseconds by operation name on ONE line: an event that
    encloses others (a while loop and its body) keeps only what its
    children do not cover, so the sum over names is the line's busy time."""
    out: Dict[str, float] = {}
    stack: List[List] = []  # [name, end, self]
    for name, start, dur in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and start >= stack[-1][1]:
            done = stack.pop()
            out[done[0]] = out.get(done[0], 0.0) + done[2]
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    for done in stack:
        out[done[0]] = out.get(done[0], 0.0) + done[2]
    return out


def plane_index(plane: str) -> Optional[int]:
    """"/device:TPU:2" -> 2: the index a replica's spans carry as `replica`."""
    tail = plane.rsplit(":", 1)[-1]
    return int(tail) if tail.isdigit() else None


def credit_innermost(left: List[Tuple[float, float]], spans: Sequence[Tuple[str, Tuple[float, float]]],
                     gap_ns: Dict[str, float]) -> List[Tuple[float, float]]:
    """Credit the idle intervals `left` to the spans of ONE rank and return
    what none of them covers.  Where spans overlap (a span and the spans
    nested in it), an instant goes to the innermost only: the one that
    began last."""
    for label, iv in sorted(spans, key=lambda sp: (-sp[1][0], sp[1][1])):
        got = overlap_s(left, [iv])
        if got > 0:
            gap_ns[label] = gap_ns.get(label, 0.0) + got
            left = subtract(left, [iv])
    return left


def reduce_events(events: Dict, host_spans: Sequence[Dict], wall_start_ns: Optional[int] = None,
                  wall_stop_ns: Optional[int] = None, top: int = 10) -> Optional[Dict]:
    """host_spans: {"label", "t0_wall_s", "ms", "rank", "replica"}; lower rank
    claims a gap first (the proving thread's spans before the witness
    thread's), within a rank the innermost span; a span with a `replica`
    is laid against that device's plane alone.  None when the trace holds
    no device operation."""
    anchor = events.get("anchor")
    offset = (anchor["wall_ns"] - anchor["start_ns"]) if anchor else None
    per_device = {}
    for plane, lines in events["device"].items():
        ops = [ev for name in op_lines(lines) for ev in lines[name]]
        if ops:
            per_device[plane] = ops
    if not per_device:
        return None
    all_ops = [ev for ops in per_device.values() for ev in ops]
    if offset is not None and wall_start_ns and wall_stop_ns:
        lo, hi = wall_start_ns - offset, wall_stop_ns - offset
    else:
        lo, hi = min(ev[1] for ev in all_ops), max(ev[1] + ev[2] for ev in all_ops)
    spans = []
    if offset is not None:
        for sp in host_spans:
            s = sp["t0_wall_s"] * 1e9 - offset
            if s < hi and s + sp["ms"] * 1e6 > lo:  # the sink holds the whole window's spans, the slice a few seconds
                spans.append((sp.get("rank", 0), sp.get("replica"), sp["label"], (s, s + sp["ms"] * 1e6)))
    ranks = sorted({r for r, _, _, _ in spans})

    busy_ns = 0.0
    op_ns: Dict[str, float] = {}
    gap_ns: Dict[str, float] = {}
    for plane, ops in per_device.items():
        busy = clip(merge([(ev[1], ev[1] + ev[2]) for ev in ops]), lo, hi)
        busy_ns += sum(e - s for s, e in busy)
        for name in op_lines(events["device"][plane]):
            inside = [[ev[0], max(ev[1], lo), min(ev[1] + ev[2], hi) - max(ev[1], lo)]
                      for ev in events["device"][plane][name] if min(ev[1] + ev[2], hi) > max(ev[1], lo)]
            for op, ns in self_times(inside).items():
                op_ns[op] = op_ns.get(op, 0.0) + ns
        left = subtract([(lo, hi)], busy)  # this device's idle gaps
        index = plane_index(plane)
        for rank in ranks:  # a solo service's spans against every plane, a replica's against its own device's
            left = credit_innermost(
                left, [(label, iv) for r, replica, label, iv in spans
                       if r == rank and (replica is None or index is None or replica == index)], gap_ns)
        rest = sum(e - s for s, e in left)
        if rest > 0:
            gap_ns["unattributed"] = gap_ns.get("unattributed", 0.0) + rest
    n = len(per_device)

    def ranked(d: Dict[str, float]) -> List[List]:
        return [[k, v / n / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy_ns / n / 1e9, "window_s": (hi - lo) / 1e9, "devices": n,
            "device_ops": ranked(op_ns), "idle_gaps": ranked(gap_ns)}


def slice_events(events: Dict, lo_ns: float, hi_ns: float) -> Dict:
    """The events that start inside [lo, hi): how a small fixture is cut
    from a real trace."""
    device = {plane: {name: [ev for ev in evs if lo_ns <= ev[1] < hi_ns] for name, evs in lines.items()}
              for plane, lines in events["device"].items()}
    return {"device": device, "anchor": events.get("anchor")}
