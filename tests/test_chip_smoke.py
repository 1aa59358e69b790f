"""chip_smoke.py off the chip (tier-1, CPU).

The smoke's contract here is to FAIL: under JAX_PLATFORMS=cpu it must
exit non-zero within seconds, name the platform it found and print no
result; alone in a directory, without the program, it must fail too.
Its steps' control flow (spool waves, terminal-state counting, pairing
checks, the pinned batch against prove_native, key hand-off to the
second step, observations) is then run at a tiny shape with the three
things only the chip's machine can satisfy stubbed — the `Chip` class,
and the device prover, whose XLA:CPU compile alone takes minutes — so
it is debugged here and not on chip time.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.time()
    proc = subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)
    return proc, time.time() - t0


def test_fails_at_once_naming_the_platform_without_a_tpu():
    proc, secs = _run(REPO, os.path.join(REPO, "chip_smoke.py"))
    assert proc.returncode != 0
    assert secs < 60
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout  # no result printed, nothing proved


def test_fails_alone_without_the_program(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path / "chip_smoke.py")
    proc, _ = _run(str(tmp_path), "chip_smoke.py")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


class StubChip(chip_smoke.Chip):
    """What only the chip's machine can satisfy, recorded instead."""

    def __init__(self):
        self.calls = []

    def require(self):
        self.calls.append("require")
        return {"platform": "stub", "kind": "stub", "count": 1}

    def kernel_differential(self):
        self.calls.append("kernel_differential")

    def rebuild_native(self):
        self.calls.append("rebuild_native")

    def assert_arms(self, arms, mesh):
        self.calls.append("assert_arms")
        assert not mesh and arms["tpu_shard"] == "off"

    def assert_device_held(self, key_bytes, mesh):
        self.calls.append("assert_device_held")
        return key_bytes + 1


def toy_world():
    from zkp2p_tpu.field.bn254 import R
    from zkp2p_tpu.pipeline.service import ProvingService
    from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem

    cs = ConstraintSystem("toy")
    out = cs.new_public("out")
    x, y, z = cs.new_wire("x"), cs.new_wire("y"), cs.new_wire("z")
    cs.enforce(LC.of(x), LC.of(y), LC.of(z), "mul")
    cs.enforce(LC.of(z), LC.of(z), LC.of(out), "sq")
    cs.compute(z, lambda a, b: a * b % R, [x, y])

    def witness_fn(p):
        return cs.witness([pow(p["x"] * p["y"], 2, R)], {x: p["x"], y: p["y"]})

    return {
        "name": "toy", "reduced": "tier-1 control-flow shape", "cs": cs,
        "payload": lambda i: {"x": 3 + i, "y": 5 + 2 * i},
        "make_service": lambda dpk, vk: ProvingService(
            cs, dpk, vk, witness_fn, lambda w: [w[1]], batch_size=chip_smoke.BATCH, prover_fn=None),
    }


@pytest.fixture
def host_backed_device_prover(monkeypatch):
    """prove_tpu_batch's signature and determinism contract, computed by
    the C++ prover: the steps under test never see the difference."""
    from zkp2p_tpu.native.lib import get_lib
    from zkp2p_tpu.prover import groth16_tpu
    from zkp2p_tpu.prover.native_prove import prove_native

    if get_lib() is None:
        pytest.skip("native library unavailable")
    seen = []

    def fake(dpk, witnesses, rs=None, ss=None):
        seen.append(len(witnesses))
        groth16_tpu._shard_mesh()  # records the tpu_shard arm like the real entry
        return [prove_native(dpk, w, rs[i] if rs else None, ss[i] if ss else None)
                for i, w in enumerate(witnesses)]

    monkeypatch.setattr(groth16_tpu, "prove_tpu_batch", fake)
    monkeypatch.delenv("ZKP2P_TPU_SHARD", raising=False)
    # the smoke's process is its own; here earlier tests of this worker left
    # spans in the ring, which the first wave would flush into the smoke's sink
    from zkp2p_tpu.utils import trace

    trace.reset()
    return seen


def test_steps_control_flow_at_a_tiny_shape(tmp_path, host_backed_device_prover):
    out = str(tmp_path)
    chip = StubChip()
    res = chip_smoke.step_serve(out, chip, make_world=toy_world)
    assert chip.calls == ["require", "kernel_differential", "rebuild_native",
                          "assert_arms", "assert_device_held"]
    # two served waves + the pinned batch, every one a full batch
    assert host_backed_device_prover == [chip_smoke.BATCH] * 3
    n = 2 * chip_smoke.BATCH
    done = [f for f in os.listdir(os.path.join(out, "spool")) if f.endswith(".proof.json")]
    assert len(done) == n and not [f for f in os.listdir(os.path.join(out, "spool")) if f.endswith(".error.json")]
    assert res["device"]["kind"] == "stub"
    assert res["obs"]["circuit"] == "toy" and res["obs"]["batch"] == chip_smoke.BATCH
    assert len(res["obs"]["spans_ms"]["service/prove"]) == 2  # cold wave, warm wave
    json.dumps(res)  # what the child hands the parent must serialize

    # the second step finds the key the first one wrote
    again = chip_smoke.step_again(out, StubChip(), make_world=toy_world)
    assert host_backed_device_prover == [chip_smoke.BATCH] * 4
    assert again["compile_s"] >= 0.0 and again["wall_s"] > 0.0
    json.dumps(again)


def test_a_request_that_does_not_end_done_fails_the_wave(tmp_path, host_backed_device_prover):
    """The smoke counts terminal states itself: a worker that exits 0
    with a request in error-* must not pass."""
    world = toy_world()
    bad = dict(world, payload=lambda i: {"x": "not-a-number", "y": 1} if i == 1 else world["payload"](i))
    from zkp2p_tpu.prover.setup_device import setup_device

    dpk, vk = setup_device(world["cs"], seed="t")
    svc = world["make_service"](dpk, vk)
    with pytest.raises(AssertionError, match="req001 ended"):
        chip_smoke.serve_wave(bad, svc, vk, str(tmp_path / "spool"), first=0)


def test_cache_verdict():
    def run(compile_s, requests, hits):
        return {"compile_s": compile_s, "cache": {"cache_requests": requests, "cache_hits": hits}}

    cold = run(90.9, 200, 0)
    assert chip_smoke.cache_fault(cold, run(4.0, 200, 198)) is None
    # what the first chip run showed: the kernel-bearing executables missed
    assert "did not hit" in chip_smoke.cache_fault(cold, run(80.7, 200, 120))
    # all hits, yet loading cost a large share of the cold compile
    assert "small fraction" in chip_smoke.cache_fault(cold, run(40.0, 200, 199))
    # a machine that kept its cache: no cold figure to compare against
    assert chip_smoke.cache_fault(run(3.0, 200, 199), run(3.5, 200, 199)) is None
