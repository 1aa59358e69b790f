"""python -m pytest benchmarks/tests  (JAX_PLATFORMS=cpu; not part of tier-1)."""

import os
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
FIXTURE_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixture_root")


@pytest.fixture
def toy_root(tmp_path):
    """A checkout of fixtures: the toy configuration, three traffic files and
    one more per-layer metric, ADDED as files beside copies of the committed
    layer metrics — no harness file knows any of them."""
    root = tmp_path / "root"
    shutil.copytree(FIXTURE_ROOT, root)
    src = os.path.join(REPO, "benchmarks", "layer_metrics")
    for name in os.listdir(src):
        shutil.copy(os.path.join(src, name), root / "benchmarks" / "layer_metrics" / name)
    return str(root)


class StubChip:
    """What only the chip's machine can satisfy, recorded instead."""

    def __init__(self):
        self.calls = []

    def require(self, chips):
        self.calls.append("require")
        return {"platform": "stub", "kind": "stub", "count": chips}

    def native_library(self):
        from zkp2p_tpu.native.lib import get_lib

        if get_lib() is None:
            pytest.skip("native library unavailable")

    def arm_faults(self, arms, want):
        self.calls.append("arm_faults")
        return [f"{g}={arms.get(g)!r}" for g, w in want.items() if arms.get(g) != w]

    def memory_stats(self):
        return [{"peak_bytes_in_use": 12345}]


@pytest.fixture
def host_backed_device_prover(monkeypatch):
    """prove_tpu_batch's signature and determinism contract, computed by the
    C++ prover (XLA:CPU takes minutes to compile the device prover).  The
    returned dict's `tamper` hook lets a test break the timed path."""
    from zkp2p_tpu.prover import groth16_tpu
    from zkp2p_tpu.prover.native_prove import prove_native
    from zkp2p_tpu.utils.trace import trace

    hooks = {"calls": [], "tamper": None}

    def fake(dpk, witnesses, rs=None, ss=None):
        hooks["calls"].append(len(witnesses))
        groth16_tpu._shard_mesh()  # records the tpu_shard arm like the real entry
        with trace("tpu/prove_batch", n=len(witnesses)):
            proofs = [prove_native(dpk, w, rs[i] if rs else None, ss[i] if ss else None)
                      for i, w in enumerate(witnesses)]
        return hooks["tamper"](proofs, pinned=rs is not None) if hooks["tamper"] else proofs

    monkeypatch.setattr(groth16_tpu, "prove_tpu_batch", fake)
    monkeypatch.delenv("ZKP2P_TPU_SHARD", raising=False)
    return hooks
