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
