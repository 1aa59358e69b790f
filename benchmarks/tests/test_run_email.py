"""The configuration email-1024-1536's world (`benchmarks/worlds_email.py`,
`reference/email_signals.py`, the mixes `pair` and `single`, the metrics
`inputs_ms_per_proof` and `setup_circuit_s`) through the whole command at
the registry's CI shape (256/128, 461,148 constraints), the device
requirement stubbed and the C++ prover standing in for the device; and the
same run with every email written for one handle, which must come out
`correct: false`.  The fixture root is its own (`fixture_root_email/`): the
committed traffic and metric files are copied in beside it."""

import json
import os
import shutil

import pytest

from benchmarks import run as bench_run
from benchmarks.tests.conftest import REPO, StubChip

FIXTURE_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixture_root_email")


@pytest.fixture(scope="module")
def email_root(tmp_path_factory):
    """One checkout for the module: the key (22 s to build) is kept in its
    .bench_cache across the tests, as a real checkout's is across runs."""
    root = tmp_path_factory.mktemp("email") / "root"
    shutil.copytree(FIXTURE_ROOT, root)
    for sub in ("traffic", "layer_metrics"):
        shutil.copytree(os.path.join(REPO, "benchmarks", sub), root / "benchmarks" / sub)
    return str(root)


def _run(capsys, root, cell, seed, trace=0):
    rc = bench_run.main(["--workload", cell, "--seed", str(seed), "--seconds", "4", "--trace", str(trace)],
                        chip=StubChip(), root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


def test_the_pair_cell_end_to_end(capsys, email_root, host_backed_device_prover):
    rc, res, out = _run(capsys, email_root, "toy-email.pair", 2**31 + 26)
    assert rc == 0 and res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 2
    assert set(res["metrics"]) == {"proofs_per_s", "setup_s"}  # two completions carry no percentile
    # the warm-up batch and every batch of the window are batches of one
    assert set(host_backed_device_prover["calls"]) == {1}
    assert any("pinned_signals_not_the_requests = 0 " in line for line in out)
    assert any("circuit: 461148 constraints" in line and "20 public signals" in line for line in out)


def test_the_single_cell_traced_reports_the_two_new_metrics(capsys, email_root, host_backed_device_prover):
    rc, res, _out = _run(capsys, email_root, "toy-email.single", 2**31 + 27, trace=1)
    assert rc == 0 and res["correct"] is True and res["attempted"] >= 1
    assert {"batch_fill", "prove_batch_ms_per_proof", "inputs_ms_per_proof", "setup_circuit_s", "setup_key_s"} <= set(res["metrics"])
    assert res["metrics"]["inputs_ms_per_proof"]["value"] > 0 and res["metrics"]["setup_circuit_s"]["value"] > 1
    assert res["metrics"]["batch_fill"]["value"] == 100.0


def test_emails_written_for_another_handle_read_not_correct(capsys, email_root, host_backed_device_prover, monkeypatch):
    """Every proof verifies, under the handle of a request that is not its own."""
    from zkp2p_tpu.inputs import email

    real = email.make_twitter_email
    monkeypatch.setattr(email, "make_twitter_email", lambda key, handle, filler=0: real(key, handle="zk_pranker", filler=filler))
    rc, res, out = _run(capsys, email_root, "toy-email.pair", 2**31 + 28)
    assert rc == 0 and res["correct"] is False and res["failed"] == res["attempted"] > 0
    assert any(f"proofs_with_signals_not_their_requests = {res['failed']} " in line for line in out)
    assert any("pinned_signals_not_the_requests = " in line and " = 0 " not in line for line in out)
