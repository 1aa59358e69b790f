"""Proving-service spool semantics: done / error-bad-input /
error-failed-to-prove, idempotent sweeps, verify-after-prove."""

import json
import os

import pytest

from zkp2p_tpu.field.bn254 import R
from zkp2p_tpu.pipeline.service import ProvingService
from zkp2p_tpu.prover.groth16_tpu import device_pk
from zkp2p_tpu.snark.groth16 import setup
from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem

# prove_tpu_batch compiles per batch size: XLA-compile-heavy, opt-in
# (ZKP2P_RUN_SLOW=1); the CLI drive and bench exercise this path too.
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def world():
    cs = ConstraintSystem("svc")
    out = cs.new_public("out")
    x = cs.new_wire("x")
    y = cs.new_wire("y")
    z = cs.new_wire("z")
    cs.enforce(LC.of(x), LC.of(y), LC.of(z), "mul")
    cs.enforce(LC.of(z), LC.of(z), LC.of(out), "sq")
    cs.compute(z, lambda a, b: a * b % R, [x, y])
    pk, vk = setup(cs, seed="svc")
    dpk = device_pk(pk, cs)

    def witness_fn(payload):
        x_v, y_v = int(payload["x"]), int(payload["y"])
        out_v = pow(x_v * y_v, 2, R)
        return cs.witness([out_v], {x: x_v, y: y_v})

    return ProvingService(cs, dpk, vk, witness_fn, public_fn=lambda w: [w[1]], batch_size=2)


@pytest.mark.xslow
def test_spool_processing(world, tmp_path):
    spool = str(tmp_path)
    for i, (xv, yv) in enumerate([(3, 5), (2, 7), (4, 4)]):
        with open(os.path.join(spool, f"r{i}.req.json"), "w") as f:
            json.dump({"x": xv, "y": yv}, f)
    # a malformed request
    with open(os.path.join(spool, "bad.req.json"), "w") as f:
        json.dump({"x": "not-a-number"}, f)

    stats = world.process_dir(spool)
    assert stats["done"] == 3
    assert stats["error-bad-input"] == 1
    assert os.path.exists(os.path.join(spool, "r0.proof.json"))
    assert os.path.exists(os.path.join(spool, "bad.error.json"))
    with open(os.path.join(spool, "bad.error.json")) as f:
        assert json.load(f)["state"] == "error-bad-input"

    # idempotent: a second sweep finds nothing new
    stats2 = world.process_dir(spool)
    assert not any(stats2.values())

    # emitted proofs verify via the public JSON path
    from zkp2p_tpu.formats.proof_json import load, proof_from_json
    from zkp2p_tpu.snark.groth16 import verify

    proof = proof_from_json(load(os.path.join(spool, "r0.proof.json")))
    pub = [int(v) for v in load(os.path.join(spool, "r0.public.json"))]
    assert verify(world.vk, proof, pub)
    assert pub == [225]


@pytest.fixture(scope="module")
def batched_world(world):
    """Same circuit, service wired through the vectorized witness tier
    (inputs_fn + witness_batch) and the multi-column native batch
    prover — the service fast path (whole claimed batches ride one
    base sweep per G1 MSM family)."""
    from zkp2p_tpu.prover.native_prove import prove_native_batch

    cs = world.cs
    # wire ids from the module fixture's circuit: x=2, y=3 (out=1, z=4)
    def inputs_fn(payload):
        x_v, y_v = int(payload["x"]), int(payload["y"])
        return [pow(x_v * y_v, 2, R)], {2: x_v, 3: y_v}

    return ProvingService(
        cs,
        world.dpk,
        world.vk,
        world.witness_fn,
        public_fn=world.public_fn,
        batch_size=2,
        inputs_fn=inputs_fn,
        prover_fn=prove_native_batch,
        prefetch=2,
    )


def test_batched_service_with_native_prover(batched_world, tmp_path):
    """witness_batch tier + per-request bad-input isolation + the
    multi-column native batch prover, end to end through the spool —
    and every prove-terminal record carries its batch_index/batch_n
    attribution."""
    spool = str(tmp_path)
    for i, (xv, yv) in enumerate([(3, 5), (2, 7), (6, 6), (9, 2), (5, 5)]):
        with open(os.path.join(spool, f"r{i}.req.json"), "w") as f:
            json.dump({"x": xv, "y": yv}, f)
    with open(os.path.join(spool, "bad.req.json"), "w") as f:
        json.dump({"x": "nope", "y": 1}, f)

    stats = batched_world.process_dir(spool)
    assert stats["done"] == 5
    assert stats["error-bad-input"] == 1
    recs = []
    with open(spool.rstrip("/") + ".metrics.jsonl") as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("type") == "request":
                recs.append(rec)
    done = [r for r in recs if r["state"] == "done"]
    assert len(done) == 5
    # batch_size=2 over 5 good requests -> batches of 2/2/1 (the bad
    # one drops at witness time, shrinking its batch)
    assert all("batch_index" in r and "batch_n" in r for r in done)
    assert all(0 <= r["batch_index"] < r["batch_n"] for r in done)
    assert sorted(r["batch_n"] for r in done) == [1, 2, 2, 2, 2]
    bad = [r for r in recs if r["state"] == "error-bad-input"]
    assert bad and all("batch_index" not in r for r in bad)

    from zkp2p_tpu.formats.proof_json import load, proof_from_json
    from zkp2p_tpu.snark.groth16 import verify

    for i, (xv, yv) in enumerate([(3, 5), (2, 7), (6, 6), (9, 2), (5, 5)]):
        proof = proof_from_json(load(os.path.join(spool, f"r{i}.proof.json")))
        pub = [int(v) for v in load(os.path.join(spool, f"r{i}.public.json"))]
        assert verify(batched_world.vk, proof, pub)
        assert pub == [pow(xv * yv, 2, R)]


def test_service_restart_resumes_where_it_stopped(batched_world, tmp_path):
    """Crash-recovery semantics: the spool IS the
    durable state — a sweep after a 'crash' (simulated by deleting one
    result, as if the process died before emitting it) reprocesses ONLY
    the unfinished request."""
    spool = str(tmp_path)
    for i in range(3):
        with open(os.path.join(spool, f"r{i}.req.json"), "w") as f:
            json.dump({"x": 2 + i, "y": 3}, f)
    assert batched_world.process_dir(spool)["done"] == 3

    os.remove(os.path.join(spool, "r1.proof.json"))  # "crashed" mid-emit
    stats = batched_world.process_dir(spool)
    assert stats["done"] == 1  # only the lost one is redone
    assert os.path.exists(os.path.join(spool, "r1.proof.json"))
    stats2 = batched_world.process_dir(spool)
    assert not any(stats2.values())


def _write_reqs(spool, pairs, prefix="r"):
    for i, (xv, yv) in enumerate(pairs):
        with open(os.path.join(spool, f"{prefix}{i}.req.json"), "w") as f:
            json.dump({"x": xv, "y": yv}, f)


def test_crash_recovery_restart_completes(world, tmp_path):
    """A worker that dies mid-sweep (simulated KeyboardInterrupt in the
    prover) leaves bare .req.json files and stale claims; a restarted
    sweep with a healthy prover takes them over and finishes every
    request exactly once."""
    from zkp2p_tpu.prover.native_prove import prove_native

    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5), (2, 7), (4, 4), (9, 2)])

    calls = []

    def dying_prover(dpk, wits):
        if calls:  # first batch proves, second crashes the process
            raise KeyboardInterrupt
        calls.append(1)
        return [prove_native(dpk, w) for w in wits]

    crashy = ProvingService(
        world.cs, world.dpk, world.vk, world.witness_fn,
        public_fn=world.public_fn, batch_size=2,
        prover_fn=dying_prover, stale_claim_s=0.0,
    )
    with pytest.raises(KeyboardInterrupt):
        crashy.process_dir(spool)
    done_before = len([f for f in os.listdir(spool) if f.endswith(".proof.json")])
    assert done_before == 2  # first batch landed, second did not

    healthy = ProvingService(
        world.cs, world.dpk, world.vk, world.witness_fn,
        public_fn=world.public_fn, batch_size=2,
        prover_fn=lambda dpk, wits: [prove_native(dpk, w) for w in wits],
        stale_claim_s=0.0,  # dead worker's claims are immediately stale
    )
    stats = healthy.process_dir(spool)
    assert stats["done"] == 2  # exactly the crashed remainder, no re-proves
    assert len([f for f in os.listdir(spool) if f.endswith(".proof.json")]) == 4
    assert not [f for f in os.listdir(spool) if f.endswith(".claim")]


def test_two_workers_partition_one_spool(world, tmp_path):
    """Two concurrent workers on one spool: claim files partition the
    requests — every request proven exactly once across both."""
    import threading

    from zkp2p_tpu.prover.native_prove import prove_native

    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5), (2, 7), (4, 4), (9, 2), (5, 5), (6, 6)])

    def mk():
        return ProvingService(
            world.cs, world.dpk, world.vk, world.witness_fn,
            public_fn=world.public_fn, batch_size=1,
            prover_fn=lambda dpk, wits: [prove_native(dpk, w) for w in wits],
        )

    results = {}

    def run(name):
        results[name] = mk().process_dir(spool)

    t1 = threading.Thread(target=run, args=("a",))
    t2 = threading.Thread(target=run, args=("b",))
    t1.start(); t2.start(); t1.join(); t2.join()

    total_done = results["a"]["done"] + results["b"]["done"]
    assert total_done == 6  # partitioned, not duplicated
    assert len([f for f in os.listdir(spool) if f.endswith(".proof.json")]) == 6
    assert not [f for f in os.listdir(spool) if f.endswith(".claim")]
