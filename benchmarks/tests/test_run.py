"""The whole command's control flow at a toy circuit, the device requirement
stubbed; and the same run with the timed path broken underneath, which
must come out `correct: false`."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import run as bench_run
from benchmarks.tests.conftest import REPO, StubChip


def _run(capsys, root, argv):
    chip = StubChip()
    rc = bench_run.main(argv, chip=chip, root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out, chip


def test_fails_at_once_naming_the_platform_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "sha2b.bulk", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_fails_alone_without_the_program(tmp_path):
    import shutil

    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "sha2b.bulk", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and '"correct"' not in proc.stdout


@pytest.mark.parametrize("cell,trace", [("toy.closed8", 0), ("toy.closed8", 1), ("toy.closed1", 0), ("toy.open-poisson", 0)])
def test_a_run_end_to_end(capsys, toy_root, host_backed_device_prover, cell, trace):
    seed = 2**31 + 77  # the driver's seeds do not fit 32 signed bits
    rc, res, out, chip = _run(capsys, toy_root, ["--workload", cell, "--seed", str(seed), "--seconds", "2", "--trace", str(trace)])
    assert rc == 0 and res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert chip.calls[0] == "require" and "arm_faults" in chip.calls
    assert res["device"] == {"platform": "stub", "kind": "stub", "count": 1, "memory_peak_bytes": 12345}
    if trace:
        # per-layer metrics only, the fixture's added one among them; no device plane on the CPU
        assert {"batch_fill", "prove_batch_ms_per_proof", "compiles_in_window", "setup_key_s", "emit_ms"} <= set(res["metrics"])
        assert "proofs_per_s" not in res["metrics"] and "busy_s" not in res["device"]
        assert 25.0 <= res["metrics"]["batch_fill"]["value"] <= 100.0 and res["metrics"]["compiles_in_window"]["value"] == 0
    else:
        assert set(res["metrics"]) == {"proofs_per_s", "latency_p50_s", "latency_p90_s", "setup_s"}
        assert all(m["value"] > 0 for m in res["metrics"].values())
    # the warm-up batch is the oracle batch: one pinned call, then the window's
    assert host_backed_device_prover["calls"][0] == 4
    if cell == "toy.closed1":
        # one caller, so every batch holds one request, and proves at the size it was claimed
        # for with that witness repeated (pipeline/service.py, since PR 30): no second shape
        assert set(host_backed_device_prover["calls"][1:]) == {4} and res["attempted"] == len(host_backed_device_prover["calls"]) - 1
    if cell == "toy.open-poisson":
        assert any(line.startswith("[bench] generator lateness") for line in out)
        assert res["attempted"] >= 3  # 8 due in 2 s at 4/s
    # every number compared is printed beside its limit
    assert sum(1 for line in out if line.startswith("[bench] check:") and "(limit 0)" in line) >= 7


def _flip_limb(proof):
    import dataclasses

    return dataclasses.replace(proof, c=(proof.c[0] ^ 1, proof.c[1]))


@pytest.mark.parametrize("what", ["window", "pinned"])
def test_a_broken_timed_path_reads_not_correct(capsys, toy_root, host_backed_device_prover, what):
    """One proof of each batch altered where it is produced (not the first:
    the service's own sample verify would catch that one and bisect)."""
    def tamper(proofs, pinned):
        if pinned == (what == "pinned") and len(proofs) > 1:
            proofs[-1] = _flip_limb(proofs[-1])
        return proofs

    host_backed_device_prover["tamper"] = tamper
    rc, res, out, _ = _run(capsys, toy_root, ["--workload", "toy.closed8", "--seed", "5", "--seconds", "1.5", "--trace", "0"])
    assert rc == 0 and res["correct"] is False
    if what == "window":
        assert res["failed"] > 0
    else:
        assert res["failed"] == 0 and any("pinned_pairing_failures = 1" in line for line in out)
        assert any("pinned_bytes_differing_from_native = 1 " in line for line in out)


def test_after_a_warm_up_that_compiled_one_batch_is_served_before_the_window(capsys, toy_root, host_backed_device_prover, monkeypatch):
    """On the chip, a window after a warm-up that compiled found the service lowering
    everything again in its own thread; one batch through the spool takes that on in set-up."""
    real = bench_run.Monitor.cache_misses
    state = {"n": 0}

    def misses(self):  # the look before the warm-up sees none, the look after it sees one
        state["n"] += 1
        return real(self) + (1 if state["n"] > 1 else 0)

    monkeypatch.setattr(bench_run.Monitor, "cache_misses", misses)
    rc, res, out, _ = _run(capsys, toy_root, ["--workload", "toy.closed8", "--seed", "9", "--seconds", "1", "--trace", "1"])
    assert rc == 0 and res["correct"] is True
    assert host_backed_device_prover["calls"][:2] == [4, 4] and any("one batch is served before the window" in line for line in out)
    # set-up's batch is no request of the window: not attempted, not in the per-layer numbers
    assert res["attempted"] == sum(host_backed_device_prover["calls"][2:])
    assert res["metrics"]["batch_fill"]["value"] <= 100.0


def test_the_script_itself_takes_the_same_road(capsys, toy_root, host_backed_device_prover, monkeypatch):
    """`python3 benchmarks/run.py ...` is main() and nothing beside it."""
    import runpy

    from benchmarks.harness import device

    monkeypatch.setattr(device, "Chip", StubChip)
    monkeypatch.chdir(toy_root)
    monkeypatch.setattr(sys, "argv", ["benchmarks/run.py", "--workload", "toy.closed8", "--seed", "3", "--seconds", "1", "--trace", "0"])
    with pytest.raises(SystemExit) as done:
        runpy.run_path(os.path.join(REPO, "benchmarks", "run.py"), run_name="__main__")
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert done.value.code == 0 and res["correct"] is True and host_backed_device_prover["calls"][0] == 4


def test_proofs_that_do_not_answer_their_requests_read_not_correct(capsys, toy_root, host_backed_device_prover, monkeypatch):
    """A witness replayed for every request: each proof verifies, under signals that are not its request's."""
    from benchmarks.tests import toy

    rc, res, out, _ = _run(capsys, toy_root, ["--workload", "toy-signal.closed8", "--seed", "11", "--seconds", "1", "--trace", "0"])
    assert rc == 0 and res["correct"] is True and res["failed"] == 0
    monkeypatch.setitem(toy.MESSAGE_OF, "fn", lambda payload: [3, 5, 7, 11])
    rc, res, out, _ = _run(capsys, toy_root, ["--workload", "toy-signal.closed8", "--seed", "12", "--seconds", "1", "--trace", "0"])
    assert rc == 0 and res["correct"] is False and res["failed"] == res["attempted"] > 0
    assert any("proofs_with_signals_not_their_requests = " + str(res["failed"]) in line for line in out)
    assert any("pinned_signals_not_the_requests = 4" in line for line in out)
