"""`zkp2p-tpu doctor` smoke (tier-1 resident; Makefile `doctor`) and
the trace_report --json machine output.

The doctor contract: under JAX_PLATFORMS=cpu the report parses, every
gate reports an arm, the digest is stable across in-process runs, and a
deliberately mis-armed run (ZKP2P_FIELD_MUL=pallas on a CPU host) is
flagged, stays on the XLA arm, and fails `--strict`.
"""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_doctor(extra_env=None, extra_args=(), rc=0):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(extra_env or {})
    proc = subprocess.run(
        [sys.executable, "-m", "zkp2p_tpu", "doctor", "--json", "--no-workload", *extra_args],
        capture_output=True, text=True, timeout=300, cwd=REPO, env=env,
    )
    assert proc.returncode == rc, proc.stderr[-2000:]
    return json.loads(proc.stdout)


@pytest.fixture(scope="module")
def doctor_report():
    return _run_doctor()


def test_doctor_report_parses_and_every_gate_reports_an_arm(doctor_report):
    rep = doctor_report
    assert rep["backend"] == "cpu"
    assert "tpu_probe" not in rep
    for gate in (
        "on_tpu", "field_mul", "curve_kernel", "batch_chunk", "tpu_shard", "native_msm_glv",
        "native_batch_affine", "native_msm_multi", "native_msm_precomp",
        "native_tier",
    ):
        assert rep["gates"].get(gate), f"gate {gate} reported no arm"
    assert rep["gates"]["on_tpu"] == "host"
    assert rep["gates"]["field_mul"] == "xla"
    assert re.fullmatch(r"[0-9a-f]{16}", rep["execution_digest"])
    assert "knobs" in rep and "provenance" in rep
    assert isinstance(rep["warnings"], list)


def test_doctor_digest_identical_across_two_inprocess_runs():
    from zkp2p_tpu.utils.audit import preflight

    r1 = preflight(workload=False)
    r2 = preflight(workload=False)
    assert r1["gates"] == r2["gates"]
    assert r1["execution_digest"] == r2["execution_digest"]


def test_doctor_flags_misarmed_pallas_and_strict_fails(doctor_report):
    mis = _run_doctor({"ZKP2P_FIELD_MUL": "pallas"}, extra_args=("--strict",), rc=1)
    # never interpret mode: the gate stays on the XLA arm, loudly
    assert mis["gates"]["field_mul"] == "xla"
    assert any("field_mul=pallas requested" in w for w in mis["warnings"]), mis["warnings"]
    assert not any("field_mul" in w for w in doctor_report["warnings"])


# ------------------------------------------------- trace_report --json


def _write_sink(path):
    lines = [
        {"type": "manifest", "run_id": "runA", "pid": 1, "knobs": {"msm_glv": True},
         "gates": {"on_tpu": "host", "field_mul": "xla"}, "execution_digest": "aa" * 8},
        {"stage": "native/msm_a", "ms": 10.0, "run_id": "runA", "pid": 1},
        {"stage": "native/msm_a", "ms": 30.0, "run_id": "runA", "pid": 1},
        {"stage": "native/h_ladder", "ms": 5.0, "run_id": "runA", "pid": 1},
        {"type": "request", "request_id": "q0", "state": "done", "ms": 42.0, "run_id": "runA"},
        {"type": "manifest", "run_id": "runB", "pid": 2, "knobs": {"msm_glv": False},
         "gates": {"on_tpu": "host", "field_mul": "pallas"}, "execution_digest": "bb" * 8},
        {"stage": "native/msm_a", "ms": 20.0, "run_id": "runB", "pid": 2},
    ]
    with open(path, "w") as f:
        for rec in lines:
            f.write(json.dumps(rec) + "\n")


def _trace_report(*args):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"), *args],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    return proc.stdout


def test_trace_report_json_stages_requests_runs(tmp_path):
    sink = str(tmp_path / "sink.jsonl")
    _write_sink(sink)
    rep = json.loads(_trace_report(sink, "--json"))
    assert rep["stages"]["native/msm_a"]["n"] == 3
    assert rep["stages"]["native/msm_a"]["max"] == 30.0
    assert rep["requests"]["done"]["n"] == 1
    runs = {r["run_id"]: r for r in rep["runs"]}
    assert runs["runA"]["execution_digest"] == "aa" * 8
    assert runs["runB"]["execution_digest"] == "bb" * 8
    assert runs["runA"]["gates"]["field_mul"] == "xla"
    # --run filter narrows the stage table to one run
    only_b = json.loads(_trace_report(sink, "--json", "--run", "runB"))
    assert only_b["stages"]["native/msm_a"]["n"] == 1
    assert "native/h_ladder" not in only_b["stages"]


def test_trace_report_json_diff_and_runs(tmp_path):
    sink = str(tmp_path / "sink.jsonl")
    _write_sink(sink)
    diff = json.loads(_trace_report(sink, "--json", "--diff", "runA", "runB"))
    assert diff["a"]["native/msm_a"]["n"] == 2 and diff["b"]["native/msm_a"]["n"] == 1
    runs = json.loads(_trace_report(sink, "--json", "--runs"))["runs"]
    assert {r["run_id"] for r in runs} == {"runA", "runB"}
    # the text --runs view names the digest too (CI greppability)
    text = _trace_report(sink, "--runs")
    assert "digest=" + "aa" * 8 in text
