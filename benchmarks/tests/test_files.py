"""BENCHMARK.json and the benchmark's data files against the contract's
limits that can be checked without a run."""

import json
import os
import re

import pytest

from benchmarks.harness import readers
from benchmarks.harness.cell import load_cell
from benchmarks.tests.conftest import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(bench["command"]) <= 32 and all(_line(w) for w in bench["command"])
    assert not any(w.startswith("/") or ".." in w for w in bench["command"])
    assert all(PATH.match(p) and os.path.isdir(os.path.join(REPO, p)) for p in bench["paths"])
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51


def test_configs_and_cells(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names) <= 24
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"]) and PATH.match(c["file"])
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])
    cells = bench["workloads"]
    assert 1 <= len(cells) <= 24 and len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["config"] in names
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert os.path.exists(os.path.join(REPO, bench["paths"][0], "traffic", w["traffic"] + ".json"))
    assert {w["config"] for w in cells} == set(names)            # every configuration keeps a cell
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names) and "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert 1 <= len(bench["end_to_end"]) <= 16 and 1 <= len(bench["per_layer"]) <= 128
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    reports = {c: {n for n, m in e2e.items() if c in m.get("workloads", cells)} for c in cells}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"]) and m["moves"] in e2e
        for c in m.get("workloads", cells):
            assert c in cells and m["moves"] in reports[c], (m["name"], c)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for c in cells:
        assert len(reports[c]) >= 2 and any(c in m.get("workloads", cells) for m in bench["per_layer"])


def test_every_cell_loads_and_every_metric_has_a_reader_of_a_known_kind(bench):
    for w in bench["workloads"]:
        cell = load_cell(REPO, w["name"])
        assert cell.chips == w["chips"] and cell.traffic["loop"] in ("closed", "open")
        assert {"adapter", "payload", "public_tie", "key_seed", "batch_size", "trace", "env", "arms"} <= set(cell.config)
        assert cell.per_layer and all(m["reader"]["kind"] in readers.READERS for m in cell.per_layer)
        assert "setup_s" in {m["name"] for m in cell.end_to_end}


def test_files_under_paths_are_named_from_the_allowed_characters(bench):
    import subprocess

    listed = subprocess.run(["git", "ls-files", "--cached", "--others", "--exclude-standard", "--"] + bench["paths"],
                            cwd=REPO, capture_output=True, text=True).stdout.split()
    assert listed and all(PATH.match(p) for p in listed), [p for p in listed if not PATH.match(p)]


def test_the_open_cells_load_from_the_committed_files_as_stated(bench):
    under, over = load_cell(REPO, "sha2b.open-80"), load_cell(REPO, "sha2b.open-120")
    for cell in (under, over):
        assert (cell.chips, cell.config_name, cell.traffic["loop"], cell.traffic["batch_size"]) == (1, "sha2b", "open", 4)
        assert cell.traffic["poll_s"] == 0.2 and cell.traffic["max_wait_s"] is None
    assert (under.traffic["arrival"], under.traffic["rate_per_s"], under.traffic["deadline_s"]) == ("uniform", 2.0, 10.0)
    assert (over.traffic["arrival"], over.traffic["burst_size"], over.traffic["rate_per_s"], over.traffic["deadline_s"]) == ("burst", 16, 3.1, None)
    # under the knee the tails are judged and the rate is the offered one; above it the rate is judged and the tails recorded
    assert {m["name"] for m in under.end_to_end} == {"latency_p90_s", "setup_s"}
    assert {m["name"] for m in over.end_to_end} == {"proofs_per_s", "setup_s"}
    assert {"deadline_refusals_in_window", "unclaimed_at_close.open", "generator_late_p95_ms.open", "batch_fill.open"} <= {m["name"] for m in under.per_layer}
    assert {"unclaimed_at_close", "generator_late_p95_ms", "backlog_latency_p90_s", "batch_fill"} <= {m["name"] for m in over.per_layer}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 4 and len(bench["workloads"]) == 10


def test_the_open_loops_metric_files_read_what_a_run_hands_them():
    sc = {"unclaimed_at_end": 27, "latency_p50_s": 6.5, "latency_p90_s": 9.25}
    run = {"score": sc, "lateness": {"n": 155, "mean_s": 0.001, "p95_s": 0.0042, "max_s": 0.02},
           "counters": {"zkp2p_service_deadline_total": {"before": {"kind": "counter", "value": 1.0}, "after": {"kind": "counter", "value": 4.0}}}}
    closed = {"score": dict(sc, unclaimed_at_end=0), "lateness": None, "counters": {}}
    read = {m["name"]: m for cell in ("sha2b.open-80", "sha2b.open-120") for m in load_cell(REPO, cell).per_layer}
    got = {n: readers.read_metric(read[n], run) for n in read if read[n]["reader"]["kind"] == "run_field" or "deadline" in n}
    assert got == {"generator_late_p95_ms": pytest.approx(4.2), "generator_late_p95_ms.open": pytest.approx(4.2),
                   "unclaimed_at_close": 27.0, "unclaimed_at_close.open": 27.0, "deadline_refusals_in_window": 3.0,
                   "backlog_latency_p50_s": 6.5, "backlog_latency_p90_s": 9.25}
    # a counter the program has not created reads 0; a loop with no schedule has no lateness to read
    assert readers.read_metric(read["deadline_refusals_in_window"], closed) == 0.0
    assert readers.read_metric(read["unclaimed_at_close"], closed) == 0.0
    assert readers.read_metric(read["generator_late_p95_ms"], closed) is None
    # a metric split by what its cells report reads what the unsplit one reads
    for name, m in read.items():
        if name.endswith(".open") and name[:-5] in read:
            assert m["reader"] == read[name[:-5]]["reader"] and m["moves"] == "latency_p90_s", name
