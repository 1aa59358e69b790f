"""loadgen-smoke (Makefile `loadgen-smoke`, tier-1 resident): a
2-second open-loop Poisson burst against the stub-speed toy prover on a
temp spool must yield a capacity JSON that parses with the full step
schema, a live /status scrape during the run, and a sink that
trace_report renders as a waterfall (Chrome-trace export + time-series
lines)."""

import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from zkp2p_tpu.native import lib as native

pytestmark = pytest.mark.skipif(native.get_lib() is None, reason="native toolchain unavailable")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

STEP_KEYS = {
    "qps_target", "offered", "done", "errors", "unfinished", "served_under_slo",
    "duration_s", "completed_qps", "p50_s", "p95_s", "max_s", "attainment",
    "burn_rate", "ok",
}


def _free_port() -> int:
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_loadgen_burst_capacity_status_and_waterfall(tmp_path):
    spool = str(tmp_path / "spool")
    cap_path = str(tmp_path / "capacity.json")
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["ZKP2P_METRICS_PORT"] = str(port)
    env["ZKP2P_TS_SAMPLE_S"] = "1"  # several sampler lines in a short run
    env.pop("ZKP2P_METRICS_SINK", None)
    env.pop("ZKP2P_FAULTS", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "loadgen.py"),
         "--spool", spool, "--rates", "1.5,25", "--step-s", "1.2",
         "--objective-s", "8", "--prove-s", "0.3", "--drain-s", "30",
         "--out", cap_path],
        env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        # /status during the run: preflight ran -> 200 with SLO payload
        status = None
        deadline = time.time() + 30
        while time.time() < deadline and proc.poll() is None:
            try:
                r = urllib.request.urlopen(f"http://127.0.0.1:{port}/status", timeout=2)
                status = json.loads(r.read())
                break
            except (urllib.error.URLError, ConnectionError, OSError):
                time.sleep(0.2)
        out, err = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, err
    assert status is not None and status["ok"] is True, (out, err)
    assert "slo" in status and "attainment" in status["slo"]

    # capacity JSON: full schema, scored steps, an honest max
    with open(cap_path) as f:
        cap = json.load(f)
    assert cap["type"] == "capacity" and cap["arrivals"] == "open-loop poisson"
    for key in ("run_id", "host", "execution_digest", "objective_p95_s", "target",
                "steps", "max_sustainable_qps"):
        assert key in cap, key
    assert cap["host"]["cpu_count"] >= 1
    assert len(cap["steps"]) == 2
    for s in cap["steps"]:
        assert STEP_KEYS <= set(s), s
        assert s["offered"] == s["done"] + s["errors"] + s["unfinished"]
        assert 0.0 <= s["attainment"] <= 1.0
    assert "worker_errors" not in cap, cap.get("worker_errors")
    # saturation degrades monotonically: the 25 QPS step cannot beat the
    # in-capacity step, and the reported max is one of the offered rates
    assert cap["steps"][0]["attainment"] >= cap["steps"][1]["attainment"]
    assert cap["max_sustainable_qps"] in (0.0, *[s["qps_target"] for s in cap["steps"]])
    passing = [s["qps_target"] for s in cap["steps"] if s["ok"]]
    assert cap["max_sustainable_qps"] == (max(passing) if passing else 0.0)

    # the sink renders: waterfall spans export to Chrome trace JSON and
    # the time-series lines aggregate
    sink = spool.rstrip("/") + ".metrics.jsonl"
    assert os.path.exists(sink)
    trace_out = str(tmp_path / "trace.json")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"), sink,
         "--chrome-trace", trace_out],
        capture_output=True, text=True, timeout=60,
    )
    assert p.returncode == 0, p.stderr
    with open(trace_out) as f:
        trace = json.load(f)
    xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert {e["name"] for e in xs} >= {"queue_wait", "prove"}
    ts_vals = [e["ts"] for e in xs]
    assert ts_vals == sorted(ts_vals)
    p2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"), sink, "--json"],
        capture_output=True, text=True, timeout=60,
    )
    assert p2.returncode == 0, p2.stderr
    rep = json.loads(p2.stdout)
    assert rep["timeseries"].get("n", 0) >= 1
    assert "done" in rep["requests"]


def test_parse_trace_segments():
    """--trace grammar: 'RATExSECONDS,...' segments; malformed specs
    fail LOUDLY before any multi-minute ramp."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "zkp2p_loadgen_for_trace", os.path.join(REPO, "tools", "loadgen.py"))
    lg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lg)
    assert lg.parse_trace("0.2x30,4x20,0.2x30") == [(0.2, 30.0), (4.0, 20.0), (0.2, 30.0)]
    assert lg.parse_trace("1X5") == [(1.0, 5.0)]  # case-insensitive x
    for bad in ("", "junk", "0x5", "1x-3", "1:5"):
        with pytest.raises(ValueError):
            lg.parse_trace(bad)
