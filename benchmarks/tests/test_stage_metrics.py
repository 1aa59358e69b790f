"""The per-layer metrics that read the program's own spans and counters
(PERF.md, PR 24): each file under layer_metrics/ added with them reads a
number from a toy traced run, in which the real `prove_tpu_batch` writes
its phases and stages around a stood-in device."""

import json
import os
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.tests.conftest import REPO, StubChip

SPAN_METRICS = {
    "prove_prep_ms_per_batch", "prove_finish_ms_per_batch", "prove_dispatch_ms_per_batch", "prove_device_ms_per_batch",
    "stage_h_planes_ms", "stage_msm_a_ms", "stage_msm_b1_ms", "stage_msm_b2_ms", "stage_msm_c_ms", "stage_msm_h_ms",
    "prover_starved_ms_per_batch", "sweep_ms",
}
NEW_METRICS = SPAN_METRICS | {"lowerings_in_window"}


@pytest.fixture
def staged_root(toy_root):
    """The fixture checkout (the committed layer metrics copied beside it), with
    the new ones listed for the toy cell as BENCHMARK.json lists them."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        committed = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert NEW_METRICS <= set(committed)
    path = os.path.join(toy_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["per_layer"] += [dict(committed[n], workloads=["toy.closed8", "toy.closed1"]) for n in sorted(NEW_METRICS)]
    with open(path, "w") as f:
        json.dump(bench, f)
    return toy_root


@pytest.fixture
def stood_in_device(monkeypatch):
    """`prove_tpu_batch` itself, with the device stood in for: the six
    stages are enqueued as values that are ready at once, and the proof is
    the C++ prover's (XLA:CPU takes minutes to compile the real stages)."""
    import numpy as np

    from zkp2p_tpu.prover import groth16_tpu
    from zkp2p_tpu.prover.native_prove import prove_native

    real, state = groth16_tpu.prove_tpu_batch, {"calls": 0}

    def entry(dpk, witnesses, rs=None, ss=None):
        state["calls"] += 1
        state["witnesses"] = iter(list(witnesses))
        return real(dpk, witnesses, rs=rs, ss=ss)

    def device(dpk, w_mont, batched=False, watch=None):
        done = np.zeros(w_mont.shape[0], np.uint32)
        for name in groth16_tpu.STAGES:
            time.sleep(0.002)
            watch.enqueued(name, done)
        return (done,) * 5

    monkeypatch.setattr(groth16_tpu, "prove_tpu_batch", entry)
    monkeypatch.setattr(groth16_tpu, "_prove_device", device)
    monkeypatch.setattr(groth16_tpu, "g1_jac_to_host", lambda acc: [None] * len(acc))
    monkeypatch.setattr(groth16_tpu, "g2_jac_to_host", lambda acc: [None] * len(acc))
    monkeypatch.setattr(groth16_tpu, "_assemble", lambda dpk, acc, r, s: prove_native(dpk, next(state["witnesses"]), r, s))
    monkeypatch.delenv("ZKP2P_TPU_SHARD", raising=False)
    return state


@pytest.mark.parametrize("cell", ["toy.closed8", "toy.closed1"])
def test_each_new_metric_file_reads_a_number_from_a_toy_traced_run(capsys, staged_root, stood_in_device, cell):
    """With eight callers a sweep catches four of the first eight requests or fewer, by timing;
    with one caller every batch is short of four, on any machine.  Either way a short batch
    proves at the size it was claimed for, so nothing is lowered in the window."""
    rc = bench_run.main(["--workload", cell, "--seed", str(2**31 + 24), "--seconds", "2", "--trace", "1"],
                        chip=StubChip(), root=staged_root)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True and res["failed"] == 0 and stood_in_device["calls"] > 1
    got = res["metrics"]
    assert NEW_METRICS <= set(got), sorted(NEW_METRICS - set(got))
    assert all(got[n]["value"] >= 0 and got[n]["unit"] == "ms" for n in SPAN_METRICS)
    assert got["lowerings_in_window"] == {"value": 0.0, "unit": "count"}  # 0 is a reading, not an absence
    # the phases partition the batch, which the older metric times from outside
    phases = sum(got[n]["value"] for n in ("prove_prep_ms_per_batch", "prove_device_ms_per_batch", "prove_finish_ms_per_batch"))
    assert phases == pytest.approx(got["prove_batch_ms_per_proof"]["value"] * 4, rel=0.25)
    assert got["prove_dispatch_ms_per_batch"]["value"] <= got["prove_device_ms_per_batch"]["value"]
    assert got["sweep_ms"]["value"] >= got["prove_device_ms_per_batch"]["value"]

