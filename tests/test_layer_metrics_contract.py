"""The contract between the spans and counters the program writes and the
files under `benchmarks/layer_metrics/` that read them: every per-layer
metric of BENCHMARK.json whose `source` is `program_span` or
`program_counter` reads a finite number, in its committed unit, from a CPU run
of the whole command in a toy cell of the shape its committed `workloads`
name.  A program PR that renames a span or a counter fails its own case here,
not the driver's check (`null` under `per_layer`).

Five runs serve all cases, each made once: one chip under a closed loop, one
chip under an open loop with a deadline, the 1x4 mesh, the replica set, and one
chip whose batches are more than it holds and run as chunks.
The cases are read from the committed file, so a new metric is a new case.
The fixtures and the stand-ins for the device are `benchmarks/tests`' and
`test_run_mesh`'s; nothing is written there."""

import contextlib
import inspect
import io
import json
import math
import os
import shutil

import pytest

from test_run_mesh import mesh_road_with_the_oracle_s_proofs
from test_run_sha256 import stood_in_device_in_chunks_of_two

from benchmarks import run as bench_run
from benchmarks.tests.conftest import FIXTURE_ROOT, REPO, StubChip, host_backed_device_prover
from benchmarks.tests.test_stage_metrics import stood_in_device
from zkp2p_tpu.utils import trace

TESTS = os.path.dirname(os.path.abspath(__file__))

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    _BENCH = json.load(_f)
METRICS = {m["name"]: m for m in _BENCH["per_layer"] if m["source"] in ("program_span", "program_counter")}


def _shape(workload: dict) -> str:
    """A committed cell's shape, from its own files."""
    with open(os.path.join(REPO, "benchmarks", "configs", workload["config"] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(REPO, "benchmarks", "traffic", workload["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if "replicas" in config:
        return "replicas"
    if config["arms"]["tpu_shard"] != "off":
        return "mesh"
    if int(config["arms"]["batch_chunk"]) < int(traffic.get("batch_size") or config["batch_size"]):
        return "chunked"  # a batch is several chunks
    return "open" if traffic["loop"] == "open" else "closed"


SHAPE_OF = {w["name"]: _shape(w) for w in _BENCH["workloads"]}

# (the fixture checkout, its toy cell, how the device is stood in for)
RUNS = {
    "closed": (FIXTURE_ROOT, "toy.closed8", stood_in_device),
    "open": (FIXTURE_ROOT, "toy.open-deadline", stood_in_device),
    # a batch of one, whose h stage the four chips share: the only shape that moves `h_ici_bytes_in_window`
    "mesh": (os.path.join(TESTS, "fixture_root_mesh"), "toy-mesh4.single", mesh_road_with_the_oracle_s_proofs),
    # the replicas behind an `inputs_fn`, as venmo-256-192-replica4's are
    "replicas": (os.path.join(TESTS, "fixture_root_replicas"), "toy-inputs-replica4.bulk32", host_backed_device_prover),
    # the preimage circuit at 64 bytes, a batch of four as two chunks of two, as sha256-4k's are
    "chunked": (os.path.join(TESTS, "fixture_root_sha256"), "toy-sha256.bulk", stood_in_device_in_chunks_of_two),
}
# `service/inputs` is the batched witness tier's span; the one-chip toy builds witnesses one by one
ONLY_IN = {"inputs_ms_per_proof": "replicas"}


def run_of(metric: dict) -> str:
    """One chip under a closed loop where the metric's cells include one; else the one shape they all have."""
    if metric["name"] in ONLY_IN:
        return ONLY_IN[metric["name"]]
    shapes = {SHAPE_OF[w] for w in metric["workloads"]}
    if "closed" in shapes:
        return "closed"
    (shape,) = shapes
    return shape


@pytest.fixture(scope="module")
def metrics_of(tmp_path_factory):
    """`metrics_of(run)`: the result line's `metrics` of that run, made at its first use."""
    made = {}

    def make(run: str) -> dict:
        if run in made:
            return made[run]
        fixture_root, cell, stand_in = RUNS[run]
        root = str(tmp_path_factory.mktemp(run) / "root")
        shutil.copytree(fixture_root, root)
        for sub in ("traffic", "layer_metrics"):  # the committed files, beside the fixture's own
            shutil.copytree(os.path.join(REPO, "benchmarks", sub), os.path.join(root, "benchmarks", sub), dirs_exist_ok=True)
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bench = json.load(f)
        mine = [dict(m, workloads=[cell]) for m in METRICS.values() if run_of(m) == run]
        bench["per_layer"] = [m for m in bench["per_layer"] if m["name"] not in METRICS] + mine
        with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
            json.dump(bench, f)
        out = io.StringIO()
        with pytest.MonkeyPatch.context() as mp:
            standing = stand_in.__wrapped__(mp)  # the fixture's function: these runs outlive a test
            if inspect.isgenerator(standing):
                next(standing)
            with contextlib.redirect_stdout(out):
                rc = bench_run.main(["--workload", cell, "--seed", str(2**31 + 44), "--seconds", "3", "--trace", "1"],
                                    chip=StubChip(), root=root)
        trace.reset()  # a service's last sweep closes after its last flush: not into the next test's sink
        res = json.loads(out.getvalue().strip().splitlines()[-1])
        assert rc == 0 and res["correct"] is True and res["attempted"] > 0, res
        made[run] = res["metrics"]
        return made[run]

    return make


def test_every_run_serves_a_case():
    assert {run_of(m) for m in METRICS.values()} == set(RUNS)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_program_still_writes_what_the_metric_reads(metrics_of, name):
    metric = METRICS[name]
    got = metrics_of(run_of(metric)).get(name)
    assert got is not None, f"{name}: nothing to read in the toy run `{run_of(metric)}` (a span or counter renamed?)"
    assert got["unit"] == metric["unit"] and math.isfinite(got["value"]), got
