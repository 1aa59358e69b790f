"""The per-layer metrics that read the program's own account of the device's
idle time (PERF.md, PR 35): `tpu/prove_batch/device_idle` a batch, its eight
causes and `offcpu`, and `tpu/prove_batch/upload`.  Data files only: the
`span_ms` reader that was there reads them, from a toy traced run in which the
real `prove_tpu_batch` and the real service write the spans around a stood-in
device."""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import readers
from benchmarks.tests.conftest import REPO, StubChip
from benchmarks.tests.test_stage_metrics import stood_in_device  # noqa: F401 — a fixture

CAUSES = ("finish", "verify", "emit", "prep", "starved", "handover", "poll", "other")
NEW_METRICS = ({"device_idle_ms_per_batch", "idle_offcpu_ms_per_batch", "prove_upload_ms_per_batch"}
               | {f"idle_{c}_ms_per_batch" for c in CAUSES})
SEED = 2**31 + 35


def _new_span(stage: str) -> bool:
    return "/device_idle" in stage or stage.endswith(("prove_batch/upload", "service/handover", "service/poll"))


@pytest.fixture
def idle_root(toy_root):
    """The fixture checkout with every span metric the repo has listed for
    the toy cell as BENCHMARK.json lists it: the new ones and the old."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        committed = {m["name"]: m for m in json.load(f)["per_layer"] if m["source"] == "program_span"}
    assert NEW_METRICS <= set(committed)
    assert all(committed[n]["workloads"] == committed["prove_device_ms_per_batch"]["workloads"] for n in NEW_METRICS)
    path = os.path.join(toy_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    have = {m["name"] for m in bench["per_layer"]}
    bench["per_layer"] += [dict(m, workloads=["toy.closed8"]) for n, m in sorted(committed.items()) if n not in have]
    with open(path, "w") as f:
        json.dump(bench, f)
    return toy_root


def test_the_idle_metrics_read_the_gap_its_causes_and_the_upload_from_a_toy_traced_run(capsys, idle_root, stood_in_device):  # noqa: F811
    rc = bench_run.main(["--workload", "toy.closed8", "--seed", str(SEED), "--seconds", "4", "--trace", "1"],
                        chip=StubChip(), root=idle_root)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True and res["failed"] == 0 and stood_in_device["calls"] > 2
    got = {n: m["value"] for n, m in res["metrics"].items()}
    assert NEW_METRICS <= set(got), sorted(NEW_METRICS - set(got))
    assert all(got[n] >= 0 and res["metrics"][n]["unit"] == "ms" for n in NEW_METRICS)

    # the eight causes partition the gap
    gap = got["device_idle_ms_per_batch"]
    assert sum(got[f"idle_{c}_ms_per_batch"] for c in CAUSES) == pytest.approx(gap, rel=0.01)
    assert got["idle_offcpu_ms_per_batch"] <= gap and got["prove_upload_ms_per_batch"] <= got["stage_h_planes_ms"]

    # the gap and the device step make the batch period
    with open(os.path.join(idle_root, ".bench_runs", f"toy.closed8-s{SEED}-t1", "spool.metrics.jsonl")) as f:
        spans = [r for r in map(json.loads, f) if r.get("type") == "stage"]
    # (the service thread's batches: where the warm-up compiled, the sink also holds the warm-up's own batch, of
    # another thread, and one served before the window)
    devices = sorted((r for r in spans if r["stage"] == "service/prove/tpu/prove_batch/device"), key=lambda r: r["t0"])
    gaps = {r["parent"]: r for r in spans if r["stage"].endswith("prove_batch/device_idle")}
    # every batch of the service thread but its first wrote one gap
    pairs = [(a, b) for a, b in zip(devices, devices[1:]) if b["id"] in gaps]
    assert len(gaps) == len(devices) - 1 == len(pairs) >= 2
    spacing_ms = sum(b["t0"] - a["t0"] for a, b in pairs) / len(pairs) * 1e3
    assert sum(gaps[b["id"]]["ms"] + a["ms"] for a, b in pairs) / len(pairs) == pytest.approx(spacing_ms, rel=0.01)
    # the metrics: a mean over the gaps and a median over the batches, the latter off the mean by at most the range
    device_range = max(r["ms"] for r in devices) - min(r["ms"] for r in devices)
    assert gap + got["prove_device_ms_per_batch"] == pytest.approx(spacing_ms, abs=0.01 * spacing_ms + device_range)

    # each cause once a gap; no idle pass wrote a span
    for c in CAUSES + ("offcpu",):
        assert sum(r["stage"].endswith("device_idle/" + c) for r in spans) == len(gaps)
    n_sweeps = sum(r["stage"].endswith("service/sweep") for r in spans)
    assert 1 <= sum(r["stage"] == "service/handover" for r in spans) <= n_sweeps
    assert 1 <= sum(r["stage"] == "service/poll" for r in spans) <= n_sweeps

    # the path-end hazard: no span this PR adds is read by a metric that was there
    run_data = {"stage_spans": spans}
    for fn in sorted(os.listdir(os.path.join(REPO, "benchmarks", "layer_metrics"))):
        with open(os.path.join(REPO, "benchmarks", "layer_metrics", fn)) as f:
            spec = json.load(f)
        if spec["name"].removesuffix(".open") in NEW_METRICS or "spans" not in spec["reader"]:  # `.open`: the same reader, split by what it moves
            continue
        matched = readers._spans(run_data, spec["reader"]["spans"])
        assert not [r["stage"] for r in matched if _new_span(r["stage"])], spec["name"]
    n_batches = len(readers._spans(run_data, ["prove_batch/device"]))  # the warm-up's too, where the sink holds it
    for old in ("prove_batch/finish", "prove_batch/prep", "prove_batch/dispatch"):
        assert len(readers._spans(run_data, [old])) == n_batches
    assert len(readers._spans(run_data, ["service/starved"])) == len(readers._spans(run_data, ["service/verify"])) == len(devices)
