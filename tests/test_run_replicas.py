"""The replica configurations' world (`benchmarks/worlds_replicas.py`,
`reference/exactly_once.py`, the mix `bulk32`, the metrics
`claims_lost_in_window`, `replica_idle_max_ms` and `setup_service_start_s`)
through the whole command at a toy circuit with one public signal, four
replicas on the CPU's virtual devices, the device requirement stubbed and
the C++ prover standing in for the device; and the same run with one
replica's proofs answering another request than their own, which must come
out `correct: false`.  Two cells: the replicas built with a `witness_fn`
(sha2b-replica4's tier) and with an `inputs_fn` (venmo-256-192-replica4's:
`ProvingService.for_venmo` builds its service through `_from_inputs_fn`, and
whole batches take `cs.witness_batch`).  The fixture root is its own
(`fixture_root_replicas/`): the committed traffic and metric files are
copied in beside it.  Last, the committed configuration
venmo-256-192-replica4 itself, as files: no circuit is built."""

import json
import os
import shutil

import pytest

from benchmarks import run as bench_run
from benchmarks.harness.cell import load_cell
from benchmarks.harness.worlds import _resolve
from benchmarks.tests import toy
from benchmarks.tests.conftest import REPO, StubChip, host_backed_device_prover  # noqa: F401 — a fixture
from zkp2p_tpu.utils import trace

FIXTURE_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixture_root_replicas")


CELLS = ("toy-replica4.bulk32", "toy-inputs-replica4.bulk32")


def inputs_adapter(config):
    """`replica_of` of the fixture configuration toy-inputs-replica4: the toy
    with one public signal behind an `inputs_fn`, as `for_venmo` has it."""
    from zkp2p_tpu.pipeline.service import ProvingService

    cs = toy.build_toy_signal()
    wires = sorted(cs.input_wires)

    def inputs_fn(payload):
        msg = toy.MESSAGE_OF["fn"](payload)
        return [msg[0] * msg[1]], dict(zip(wires, msg))

    def make_service(dpk, vk, **kw):
        return ProvingService._from_inputs_fn(cs, dpk, vk, inputs_fn, prover_fn=None, **kw)
    return cs, make_service


@pytest.fixture
def replica_root(tmp_path):
    root = tmp_path / "root"
    shutil.copytree(FIXTURE_ROOT, root)
    for sub in ("traffic", "layer_metrics"):
        shutil.copytree(os.path.join(REPO, "benchmarks", sub), root / "benchmarks" / sub)
    return str(root)


def _run(capsys, root, cell, seed, trace_flag=0):
    rc = bench_run.main(["--workload", cell, "--seed", str(seed), "--seconds", "3",
                         "--trace", str(trace_flag)], chip=StubChip(), root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1]), out


@pytest.mark.parametrize("cell", CELLS)
def test_the_replica_cell_end_to_end_and_traced(capsys, replica_root, cell, host_backed_device_prover):  # noqa: F811
    rc, res, out = _run(capsys, replica_root, cell, 2**31 + 30)
    assert rc == 0 and res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 8
    assert set(res["metrics"]) == {"proofs_per_s", "setup_s"}
    assert any("gate_arm_faults = 0 " in line for line in out)  # arms.replicas is "4"
    assert any("request_ids_in_two_replicas_records = 0 " in line for line in out)
    assert any("[replicas] 4 replicas up" in line for line in out)
    # the set warmed every replica with a batch of the cell's shape before its loops came up
    assert host_backed_device_prover["calls"].count(4) >= 5
    # and its bring-up is in the run's sink before the window's first record: a placement and a warm
    # batch a replica (`table_ms` 0: the stand-in builds no h table)
    with open(os.path.join(replica_root, ".bench_runs", f"{cell}-s{2**31 + 30}-t0", "spool.metrics.jsonl")) as f:
        recs = [json.loads(line) for line in f if line.strip()]
    first_request = next(i for i, r in enumerate(recs) if r.get("type") == "request")
    for stage in ("replicas/place", "replicas/warm"):
        assert sorted(r["replica"] for r in recs[:first_request] if r.get("stage") == stage) == [0, 1, 2, 3], stage
    assert all(r["table_ms"] == 0 for r in recs if r.get("stage") == "replicas/warm")

    rc, res, out = _run(capsys, replica_root, cell, 2**31 + 31, trace_flag=1)
    assert rc == 0 and res["correct"] is True
    assert {"batch_fill", "claims_lost_in_window", "replica_idle_max_ms", "compiles_in_window",
            "lowerings_in_window", "setup_service_start_s"} <= set(res["metrics"])
    assert ("inputs_ms_per_proof" in res["metrics"]) == (cell == CELLS[1])  # `service/inputs`: the batched tier ran
    assert res["metrics"]["setup_service_start_s"]["value"] > 0  # the set's warm-up, until every loop is up
    assert res["metrics"]["claims_lost_in_window"]["value"] > 0  # four scans of one backlog
    assert res["metrics"]["replica_idle_max_ms"]["value"] > 0
    assert res["metrics"]["lowerings_in_window"]["value"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_a_replica_whose_proofs_answer_another_request_reads_not_correct(capsys, replica_root, cell, monkeypatch,
                                                                        host_backed_device_prover):  # noqa: F811
    """Replica 1's proofs verify, under the signal of a request that is not their own."""
    monkeypatch.setitem(toy.MESSAGE_OF, "fn", lambda payload: (
        [7, 9, 2, 3] if trace.current_context().get("replica") == 1 else payload["msg"]))
    rc, res, out = _run(capsys, replica_root, cell, 2**31 + 32)
    assert rc == 0 and res["correct"] is False and 0 < res["failed"] < res["attempted"]
    assert any(f"proofs_with_signals_not_their_requests = {res['failed']} " in line for line in out)
    assert any("request_ids_in_two_replicas_records = 0 " in line for line in out)  # the spool itself is sound


def test_the_committed_configuration_of_the_onramp_circuit_as_four_replicas_resolves():
    """venmo-256-192-replica4 as the harness finds it: every name it gives imports, its replicas
    are the cell's chips and the arm the run is held to, and its circuit and cut are
    venmo-256-192's, so one key seed and one set of widths serve both."""
    cell = load_cell(REPO, "venmo-256-192-replica4.bulk32")
    cfg = cell.config
    assert (cell.config_name, cell.traffic_name, cell.chips) == ("venmo-256-192-replica4", "bulk32", 4)
    assert all(callable(_resolve(cfg[key])) for key in ("adapter", "replica_of", "payload", "public_tie"))
    assert _resolve(cfg["adapter"]).__name__ == "replica_set" and _resolve(cfg["replica_of"]).__name__ == "venmo"
    assert cfg["replicas"] == cell.chips == int(cfg["arms"]["replicas"])
    assert (cfg["arms"]["tpu_shard"], cfg["arms"]["batch_chunk"], cfg["batch_size"]) == ("off", "4", 4)
    assert "ZKP2P_TPU_SHARD" not in cfg["env"]  # every replica runs the one-chip road
    with open(os.path.join(REPO, "benchmarks", "configs", "venmo-256-192.json")) as f:
        one_chip = json.load(f)
    for key in ("reduced", "source_sizes", "max_header_bytes", "max_body_bytes", "n", "k", "key_seed", "env",
                "payload", "public_tie", "registry", "shapes_kept"):
        assert cfg[key] == one_chip[key], key
    assert cfg["replica_of"] == one_chip["adapter"]  # a replica is built as the one-chip cell's service is
    assert (cell.traffic["loop"], cell.traffic["clients"], cell.traffic["batch_size"]) == ("closed", 32, 4)
    assert {m["name"] for m in cell.end_to_end} == {"proofs_per_s", "setup_s"}
    assert {"setup_service_start_s", "replica_idle_max_ms", "claims_lost_in_window", "inputs_ms_per_proof",
            "idle_offcpu_ms_per_batch", "prove_finish_ms_per_batch"} <= {m["name"] for m in cell.per_layer}
