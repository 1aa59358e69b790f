"""The configuration sha2b-replica4's world (`benchmarks/worlds_replicas.py`,
`reference/exactly_once.py`, the mix `bulk32`, the metrics
`claims_lost_in_window` and `replica_idle_max_ms`) through the whole
command at a toy circuit with one public signal, four replicas on the CPU's
virtual devices, the device requirement stubbed and the C++ prover standing
in for the device; and the same run with one replica's proofs answering
another request than their own, which must come out `correct: false`.  The
fixture root is its own (`fixture_root_replicas/`): the committed traffic
and metric files are copied in beside it."""

import json
import os
import shutil

import pytest

from benchmarks import run as bench_run
from benchmarks.tests import toy
from benchmarks.tests.conftest import REPO, StubChip, host_backed_device_prover  # noqa: F401 — a fixture
from zkp2p_tpu.utils import trace

FIXTURE_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixture_root_replicas")


@pytest.fixture
def replica_root(tmp_path):
    root = tmp_path / "root"
    shutil.copytree(FIXTURE_ROOT, root)
    for sub in ("traffic", "layer_metrics"):
        shutil.copytree(os.path.join(REPO, "benchmarks", sub), root / "benchmarks" / sub)
    return str(root)


def _run(capsys, root, seed, trace_flag=0):
    rc = bench_run.main(["--workload", "toy-replica4.bulk32", "--seed", str(seed), "--seconds", "3",
                         "--trace", str(trace_flag)], chip=StubChip(), root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


def test_the_replica_cell_end_to_end_and_traced(capsys, replica_root, host_backed_device_prover):  # noqa: F811
    rc, res, out = _run(capsys, replica_root, 2**31 + 30)
    assert rc == 0 and res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 8
    assert set(res["metrics"]) == {"proofs_per_s", "setup_s"}
    assert any("gate_arm_faults = 0 " in line for line in out)  # arms.replicas is "4"
    assert any("request_ids_in_two_replicas_records = 0 " in line for line in out)
    assert any("[replicas] 4 replicas up" in line for line in out)
    # the set warmed every replica with a batch of the cell's shape before its loops came up
    assert host_backed_device_prover["calls"].count(4) >= 5

    rc, res, out = _run(capsys, replica_root, 2**31 + 31, trace_flag=1)
    assert rc == 0 and res["correct"] is True
    assert {"batch_fill", "claims_lost_in_window", "replica_idle_max_ms", "compiles_in_window",
            "lowerings_in_window"} <= set(res["metrics"])
    assert res["metrics"]["claims_lost_in_window"]["value"] > 0  # four scans of one backlog
    assert res["metrics"]["replica_idle_max_ms"]["value"] > 0
    assert res["metrics"]["lowerings_in_window"]["value"] == 0


def test_a_replica_whose_proofs_answer_another_request_reads_not_correct(capsys, replica_root, monkeypatch,
                                                                        host_backed_device_prover):  # noqa: F811
    """Replica 1's proofs verify, under the signal of a request that is not their own."""
    monkeypatch.setitem(toy.MESSAGE_OF, "fn", lambda payload: (
        [7, 9, 2, 3] if trace.current_context().get("replica") == 1 else payload["msg"]))
    rc, res, out = _run(capsys, replica_root, 2**31 + 32)
    assert rc == 0 and res["correct"] is False and 0 < res["failed"] < res["attempted"]
    assert any(f"proofs_with_signals_not_their_requests = {res['failed']} " in line for line in out)
    assert any("request_ids_in_two_replicas_records = 0 " in line for line in out)  # the spool itself is sound
