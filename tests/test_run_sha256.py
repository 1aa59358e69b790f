"""The configuration sha256-4k's world (`benchmarks/worlds_sha256.py`,
`reference/sha_signals.py`, the metrics `chunk_wait_ms` and
`prove_chunks_in_window`) through the whole command at the registry's CI
shape (64 message bytes, two blocks, 54,546 constraints), the device
requirement stubbed.  `prove_tpu_batch` is the real one at a chunk of two,
so a batch of four runs the chunk loop as the cell does on the chip; its
device is stood in for and the proofs are the C++ prover's.  Then the same
run with two requests' proofs (and signals) swapped in the spool, which must
come out `correct: false`; and the adapter against a program without the
entry point.  The fixture root is its own (`fixture_root_sha256/`): the
committed traffic and metric files are copied in beside it."""

import json
import os
import shutil

import pytest

from benchmarks import run as bench_run
from benchmarks.tests.conftest import REPO, StubChip
from benchmarks.tests.test_stage_metrics import stood_in_device
from zkp2p_tpu.utils import trace

FIXTURE_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixture_root_sha256")
CELL = "toy-sha256.bulk"


@pytest.fixture(scope="module")
def sha256_root(tmp_path_factory):
    """One checkout for the module: the key is kept in its .bench_cache
    across the tests, as a real checkout's is across runs."""
    root = tmp_path_factory.mktemp("sha256") / "root"
    shutil.copytree(FIXTURE_ROOT, root)
    for sub in ("traffic", "layer_metrics"):
        shutil.copytree(os.path.join(REPO, "benchmarks", sub), root / "benchmarks" / sub)
    return str(root)


@pytest.fixture
def stood_in_device_in_chunks_of_two(monkeypatch):
    """`test_stage_metrics.stood_in_device` under `ZKP2P_BATCH_CHUNK=2`, and
    no h table (its build compiles for minutes on XLA:CPU at 2^16)."""
    from zkp2p_tpu.prover import groth16_tpu

    state = stood_in_device.__wrapped__(monkeypatch)
    monkeypatch.setattr(groth16_tpu, "_h_table", lambda dpk: None)
    monkeypatch.setattr(groth16_tpu, "BATCH_CHUNK", "2")
    yield state
    trace.reset()  # a service's last sweep closes after its last flush: not into the next test's sink


def _run(capsys, root, seed, trace_flag=0, seconds=3):
    rc = bench_run.main(["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace_flag)],
                        chip=StubChip(), root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


def test_the_cell_end_to_end_and_traced(capsys, sha256_root, stood_in_device_in_chunks_of_two):
    rc, res, out = _run(capsys, sha256_root, 2**31 + 45)
    # the first sweep catches what the callers had written by then, 8 or fewer: on a loaded machine 3 (PR 45's tier-1 run)
    assert rc == 0 and res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"proofs_per_s", "setup_s"}
    assert any("circuit: 54546 constraints" in line and "2 public signals" in line for line in out)
    assert any("gate_arm_faults = 0 " in line for line in out)  # arms.batch_chunk is "2"
    assert any("proofs_with_signals_not_their_requests = 0 " in line for line in out)
    assert any("pinned_signals_not_the_requests = 0 " in line for line in out)

    # a window of two sweeps: whatever the first caught, the second has a batch of more than one chunk
    rc, res, out = _run(capsys, sha256_root, 2**31 + 46, trace_flag=1, seconds=6)
    assert rc == 0 and res["correct"] is True
    got = res["metrics"]
    assert {"batch_fill", "inputs_ms_per_proof", "chunk_wait_ms", "prove_chunks_in_window"} <= set(got)
    assert got["inputs_ms_per_proof"]["value"] > 0  # `service/inputs`: whole batches took the `witness_batch` tier
    assert got["chunk_wait_ms"]["value"] > 0 and got["chunk_wait_ms"]["unit"] == "ms"
    sink = os.path.join(sha256_root, ".bench_runs", f"{CELL}-s{2**31 + 46}-t1", "spool.metrics.jsonl")
    with open(sink) as f:
        recs = [r for r in map(json.loads, f) if r.get("type") == "stage"]
    batches = [r for r in recs if r["stage"].endswith("tpu/prove_batch")]
    assert batches and all((r["chunk"], r["n_chunks"]) == (2, 2) for r in batches if r["n"] == 4)
    # two chunks a batch of four (a short batch of one or two is one chunk), and the sink holds a batch served in set-up
    assert 0 < got["prove_chunks_in_window"]["value"] <= sum(r["n_chunks"] for r in batches)
    waits = [r for r in recs if r["stage"].endswith("/chunk_wait")]
    assert waits and all(r["chunk"] == 1 for r in waits)
    assert len(waits) == sum(r["n_chunks"] - 1 for r in batches)


def test_two_requests_proofs_swapped_in_the_spool_read_not_correct(capsys, sha256_root, monkeypatch,
                                                                    stood_in_device_in_chunks_of_two):
    """Both proofs verify, each under the digest of the other's request."""
    from benchmarks.harness import check

    real = check.check_window

    def swapped(vk_ints, spool, requests, workers, expected_public=None):
        a, b = [os.path.join(spool, r["rid"]) for r in requests if r.get("state") == "done"][:2]
        for ext in (".proof.json", ".public.json"):
            os.replace(a + ext, a + ext + ".tmp")
            os.replace(b + ext, a + ext)
            os.replace(a + ext + ".tmp", b + ext)
        return real(vk_ints, spool, requests, workers, expected_public)

    monkeypatch.setattr(check, "check_window", swapped)
    rc, res, out = _run(capsys, sha256_root, 2**31 + 47, seconds=6)
    assert rc == 0 and res["correct"] is False and res["failed"] == 2 < res["attempted"]
    assert any("proofs_with_signals_not_their_requests = 2 " in line for line in out)
    assert any("requests_not_done_or_pairing_invalid_or_passed_over = 0 " in line for line in out)
    assert any("pinned_signals_not_the_requests = 0 " in line for line in out)  # the pinned batch was not touched


def test_a_program_without_the_entry_point_ends_the_run_before_the_circuit_is_built(sha256_root, monkeypatch):
    from zkp2p_tpu.models import registry
    from zkp2p_tpu.pipeline.service import ProvingService

    def never(*_a, **_kw):
        raise AssertionError("the circuit was built")

    monkeypatch.delattr(ProvingService, "for_sha256_preimage")
    monkeypatch.setattr(registry, "build_sha256_preimage", never)
    with pytest.raises(SystemExit, match="no ProvingService.for_sha256_preimage .* nothing measured"):
        bench_run.main(["--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"],
                       chip=StubChip(), root=sha256_root)
