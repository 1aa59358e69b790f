"""Per-layer metric readers.  A metric is a file under layer_metrics/ that
names one reader kind and what it reads; a reader that finds nothing to
read returns None and the harness leaves the metric out of the line.

`run` is what a run gathered:
  stage_spans      utils/trace.py records of the window ({"stage", "ms", ...})
  request_records  the service's per-request records of the window
  counters         {name: {"before": snapshot entry or None, "after": ...}}
  monitoring       [{"name", "secs" (None for a plain event), "phase"}]
  memory           one memory_stats() dict per device, after the window
  phases           {name: seconds} of the harness's own set-up clock
  batch_size       the service's batch size
  score            score.score_window's dict: what the end-to-end metrics were taken from
  lateness         score.lateness's dict of an open loop's generator, None in a closed loop
"""

from __future__ import annotations

import statistics
from typing import Callable, Dict, List, Optional


def _stat(values: List[float], stat: str) -> Optional[float]:
    if not values:
        return None
    if stat == "median":
        return statistics.median(values)
    if stat == "mean":
        return sum(values) / len(values)
    if stat == "sum":
        return sum(values)
    if stat == "max":
        return max(values)
    if stat == "count":
        return float(len(values))
    raise ValueError(f"unknown stat {stat!r}")


def _spans(run: Dict, names: List[str]) -> List[Dict]:
    """A span's stage path is the nesting of its thread ("service/prove/
    tpu/prove_batch"): match the name at the end of the path."""
    return [r for r in run["stage_spans"]
            if any(r["stage"] == n or r["stage"].endswith("/" + n) for n in names)]


def span_ms(spec: Dict, run: Dict) -> Optional[float]:
    return _stat([r["ms"] for r in _spans(run, spec["spans"])], spec.get("stat", "median"))


def span_ms_per_n(spec: Dict, run: Dict) -> Optional[float]:
    return _stat([r["ms"] / max(1, int(r.get("n", 1))) for r in _spans(run, spec["spans"])],
                 spec.get("stat", "median"))


def record_field(spec: Dict, run: Dict) -> Optional[float]:
    vals = [float(r[spec["field"]]) * spec.get("scale", 1.0) for r in run["request_records"]
            if r.get(spec["field"]) is not None and r.get("state") == spec.get("state", "done")]
    return _stat(vals, spec.get("stat", "median"))


def counter(spec: Dict, run: Dict) -> Optional[float]:
    """A counter's growth over the window, or a histogram's mean over the
    window's observations; `per` divides by a quantity of the run; `absent`
    is the reading where the program has not created the counter yet."""
    c = run["counters"].get(spec["counter"])
    if c is None or c["after"] is None:
        return spec.get("absent")  # a counter the program creates at its first count reads `absent` (0) until then
    before, after = c["before"] or {}, c["after"]
    if after.get("kind") == "histogram":
        n = after.get("count", 0) - before.get("count", 0)
        if n <= 0:
            return None
        value = (after.get("sum", 0.0) - before.get("sum", 0.0)) / n
    else:
        value = after.get("value", 0.0) - before.get("value", 0.0)
    return value * spec.get("scale", 1.0) / (run[spec["per"]] if spec.get("per") else 1.0)


def monitoring_sum(spec: Dict, run: Dict) -> Optional[float]:
    """jax.monitoring durations whose name ends in `suffix`, in one phase
    (`setup` or `window`).  Summed seconds, or with stat "count" how many:
    0 is a reading (a warmed window compiles nothing), not an absence."""
    vals = [m["secs"] or 0.0 for m in run["monitoring"]
            if m["name"].endswith(spec["suffix"]) and m["phase"] == spec["phase"]]
    return float(len(vals)) if spec.get("stat") == "count" else float(sum(vals))


def memory_stat(spec: Dict, run: Dict) -> Optional[float]:
    vals = [float(m[spec["stat"]]) for m in run["memory"] if spec["stat"] in m]
    return max(vals) if vals else None


def phase_s(spec: Dict, run: Dict) -> Optional[float]:
    return run["phases"].get(spec["phase"])


def run_field(spec: Dict, run: Dict) -> Optional[float]:
    """A number the harness itself took: `field` of the run's `of` dict
    (`score` or `lateness`); nothing where the run has no such dict (a
    closed loop has no lateness) or the field holds no number."""
    value = (run.get(spec["of"]) or {}).get(spec["field"])
    return None if value is None else float(value) * spec.get("scale", 1.0)


READERS: Dict[str, Callable[[Dict, Dict], Optional[float]]] = {
    "span_ms": span_ms, "span_ms_per_n": span_ms_per_n, "record_field": record_field,
    "counter": counter, "monitoring_sum": monitoring_sum, "memory_stat": memory_stat,
    "phase_s": phase_s, "run_field": run_field,
}


def read_metric(metric: Dict, run: Dict) -> Optional[float]:
    spec = metric["reader"]
    return READERS[spec["kind"]](spec, run)
