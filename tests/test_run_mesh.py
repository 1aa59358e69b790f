"""The cell venmo-256-192-mesh4.bulk's shape through the whole command on
the CPU: a toy circuit with one public signal under the mix `bulk`, the
configuration's `env` arming the 1x4 mesh road on the virtual devices, the
device requirement stubbed.  `prove_tpu_batch` is the real one: it places
the key on the mesh, uploads the witnesses, runs the exchange program and
writes the seven stage spans; the h program and the pod MSMs are stood in
for (each compiles for minutes on XLA:CPU), and the proofs handed back are
the C++ prover's for the same (witness, r, s), so `correct` compares what it
compares on the chip.  The fixture root is its own (`fixture_root_mesh/`):
the committed traffic and metric files are copied in beside it."""

import json
import os
import shutil

import pytest

from test_tpu_shard import _stand_in_for_the_mesh_programs

from benchmarks import run as bench_run
from benchmarks.tests.conftest import REPO, StubChip
from zkp2p_tpu.utils import trace

FIXTURE_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixture_root_mesh")


@pytest.fixture
def mesh_root(tmp_path):
    root = tmp_path / "root"
    shutil.copytree(FIXTURE_ROOT, root)
    for sub in ("traffic", "layer_metrics"):
        shutil.copytree(os.path.join(REPO, "benchmarks", sub), root / "benchmarks" / sub)
    return str(root)


@pytest.fixture
def mesh_road_with_the_oracle_s_proofs(monkeypatch):
    from zkp2p_tpu.prover import groth16_tpu
    from zkp2p_tpu.prover.native_prove import prove_native

    for knob in ("ZKP2P_TPU_SHARD", "ZKP2P_TPU_MESH"):  # the run sets them from the configuration: restored after it
        monkeypatch.setenv(knob, "")
    _stand_in_for_the_mesh_programs(monkeypatch)
    the_road = groth16_tpu.prove_tpu_batch
    sizes = []

    def prove(dpk, witnesses, rs=None, ss=None):
        sizes.append(len(witnesses))
        the_road(dpk, witnesses, rs=rs, ss=ss)
        return [prove_native(dpk, w, rs[i] if rs else None, ss[i] if ss else None) for i, w in enumerate(witnesses)]

    monkeypatch.setattr(groth16_tpu, "prove_tpu_batch", prove)
    yield sizes
    # a solo service's last sweep closes after its last flush: left in the ring, the next
    # service of this process (a replica set's, in another test file) would write it to its sink
    trace.reset()


def _run(capsys, root, seed, trace_flag):
    rc = bench_run.main(["--workload", "toy-mesh4.bulk", "--seed", str(seed), "--seconds", "3",
                         "--trace", str(trace_flag)], chip=StubChip(), root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


def test_the_mesh_cell_end_to_end_and_traced(capsys, mesh_root, mesh_road_with_the_oracle_s_proofs):
    rc, res, out = _run(capsys, mesh_root, 2**31 + 40, 0)
    assert rc == 0 and res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 8
    assert set(res["metrics"]) == {"proofs_per_s", "setup_s"}
    assert any("gate_arm_faults = 0 " in line for line in out)  # arms.tpu_shard is "1x4"
    assert any('"tpu_shard": "1x4"' in line for line in out if line.startswith("[bench] gates:"))
    assert 4 in mesh_road_with_the_oracle_s_proofs  # the pinned warm-up batch, of the cell's shape

    rc, res, out = _run(capsys, mesh_root, 2**31 + 41, 1)
    assert rc == 0 and res["correct"] is True
    assert {"batch_fill", "stage_h_planes_ms", "stage_exchange_ms", "stage_msm_h_ms", "prove_device_ms_per_batch",
            "key_placed_bytes_in_window"} <= set(res["metrics"])
    assert res["metrics"]["stage_exchange_ms"]["value"] > 0
    # the warm-up batch placed the key: the window's batches moved none of it
    assert res["metrics"]["key_placed_bytes_in_window"]["value"] == 0


def test_the_mesh_cell_under_single_shares_each_proof_s_h_stage(capsys, mesh_root, mesh_road_with_the_oracle_s_proofs):
    """The cell venmo-full-mesh4.single's shape: the same toy key under the
    mix `single`, batches of one, which the four chips do not divide, so
    they share each proof's h stage (`h_shards` on its span) and the run's
    line carries what that moved over ICI, the counter's growth over the
    window: the `ici_bytes` of the window's `h_planes` spans, summed."""
    rc = bench_run.main(["--workload", "toy-mesh4.single", "--seed", str(2**31 + 42), "--seconds", "3", "--trace", "1"],
                        chip=StubChip(), root=mesh_root)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True and res["attempted"] >= 1
    assert 1 in mesh_road_with_the_oracle_s_proofs and 4 not in mesh_road_with_the_oracle_s_proofs
    sink = os.path.join(mesh_root, ".bench_runs", f"toy-mesh4.single-s{2**31 + 42}-t1", "spool.metrics.jsonl")
    with open(sink) as f:
        h_stages = [r for r in map(json.loads, f) if r.get("type") == "stage" and r["stage"].endswith("/stage/h_planes")]
    assert h_stages and all(r["h_shards"] == 4 and r["proofs_a_chip"] == 1 and r["mesh"] == "1x4" for r in h_stages)
    assert {"h_ici_bytes_in_window", "stage_h_planes_ms", "stage_exchange_ms", "key_placed_bytes_in_window"} <= set(res["metrics"])
    # a batch of one a request: the window's batches are the requests taken on (a batch served in set-up is in the sink too)
    per_batch = h_stages[0]["ici_bytes"]
    assert per_batch > 0 and all(r["ici_bytes"] == per_batch for r in h_stages) and len(h_stages) >= res["attempted"]
    assert res["metrics"]["h_ici_bytes_in_window"]["value"] == per_batch * res["attempted"]
    assert res["metrics"]["key_placed_bytes_in_window"]["value"] == 0
