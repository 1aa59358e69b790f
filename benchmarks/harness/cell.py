"""A cell, found by name: its entry in BENCHMARK.json, its configuration
file, its traffic file and the per-layer metric files that list it.

Nothing here names a configuration, a mix or a metric: a later PR adds
files and entries and edits no harness file.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List

# the traffic vocabulary (benchmarks/README.md); a key outside it is an error,
# so a typo cannot silently run the default
TRAFFIC_DEFAULTS = {
    "loop": None,            # "closed" | "open"
    "clients": 1,            # closed: concurrent callers
    "think_s": 0.0,          # closed: pause between a reply and the next request
    "rate_per_s": None,      # open: mean arrivals per second
    "arrival": "poisson",    # open: "poisson" | "uniform" | "burst"
    "burst_size": 1,         # open/burst: requests that arrive together
    "batch_size": None,      # service batch; null = the configuration's
    "max_wait_s": None,      # service max_wait_s; null = the service's default
    "poll_s": 0.2,           # service sweep poll
    "deadline_s": None,      # per-request deadline written into each payload
    "circuits": None,        # [{"config": name, "weight": w}, ...]; one entry at most today
    "why": "",
}


class BenchmarkFileError(ValueError):
    """A file of the benchmark is malformed or missing."""


def _load(path: str) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise BenchmarkFileError(f"cannot read {path}: {e}") from None
    except ValueError as e:
        raise BenchmarkFileError(f"{path} is not JSON: {e}") from None


def load_traffic(path: str) -> Dict:
    raw = _load(path)
    unknown = sorted(set(raw) - set(TRAFFIC_DEFAULTS))
    if unknown:
        raise BenchmarkFileError(f"{path}: keys outside the traffic vocabulary: {unknown}")
    t = dict(TRAFFIC_DEFAULTS, **raw)
    if t["loop"] not in ("closed", "open"):
        raise BenchmarkFileError(f"{path}: loop must be 'closed' or 'open', got {t['loop']!r}")
    if t["loop"] == "closed" and int(t["clients"]) < 1:
        raise BenchmarkFileError(f"{path}: a closed loop needs clients >= 1")
    if t["loop"] == "open":
        if not t["rate_per_s"] or float(t["rate_per_s"]) <= 0:
            raise BenchmarkFileError(f"{path}: an open loop needs rate_per_s > 0")
        if t["arrival"] not in ("poisson", "uniform", "burst"):
            raise BenchmarkFileError(f"{path}: arrival {t['arrival']!r} is not poisson/uniform/burst")
        if int(t["burst_size"]) < 1:
            raise BenchmarkFileError(f"{path}: burst_size must be >= 1")
    if t["circuits"] is not None and len(t["circuits"]) != 1:
        raise BenchmarkFileError(
            f"{path}: {len(t['circuits'])} circuits — one ProvingService serves one circuit; "
            "a multi-circuit queue is an Open question in PERF.md")
    return t


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict
    traffic_name: str
    traffic: Dict
    end_to_end: List[Dict]     # this cell's end-to-end metric entries
    per_layer: List[Dict]      # this cell's per-layer metric entries, each with its "reader"
    root: str                  # the checkout


def _lists_cell(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, workload: str) -> Cell:
    """`root` holds BENCHMARK.json; traffic/ and layer_metrics/ sit under its
    first `paths` entry (the tests hand a root of fixtures)."""
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    bench_dir = os.path.join(root, bench["paths"][0])
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise BenchmarkFileError(f"no workload {workload!r} in BENCHMARK.json (have {sorted(by_name)})")
    w = by_name[workload]
    cfg_entry = next((c for c in bench["configs"] if c["name"] == w["config"]), None)
    if cfg_entry is None:
        raise BenchmarkFileError(f"workload {workload!r} names config {w['config']!r}, which BENCHMARK.json lacks")
    config = _load(os.path.join(root, cfg_entry["file"]))
    traffic = load_traffic(os.path.join(bench_dir, "traffic", w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _lists_cell(m, workload)]
    per_layer = []
    for m in bench["per_layer"]:
        if not _lists_cell(m, workload):
            continue
        spec = _load(os.path.join(bench_dir, "layer_metrics", m["name"] + ".json"))
        for key in ("unit", "layer", "moves", "source"):
            if spec.get(key) != m[key]:
                raise BenchmarkFileError(
                    f"layer_metrics/{m['name']}.json says {key}={spec.get(key)!r}, BENCHMARK.json {m[key]!r}")
        per_layer.append(dict(m, reader=spec["reader"]))
    return Cell(name=workload, chips=int(w["chips"]), config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=traffic, end_to_end=e2e, per_layer=per_layer, root=root)
