"""Service fault-tolerance layer (docs/ROBUSTNESS.md), tier-1: batch
bisection isolates a poisoned request with byte-identical batchmate
proofs and a bounded prove count, transient failures retry with backoff,
the degradation ladder rescues knob-sensitive failures, deadlines and
the spool cap terminal visibly, torn requests and short prover returns
fail loudly without sinking the sweep, and stale-claim takeover rewrites
the claim file to the new owner.

Everything here drives the REAL native prover on a 2-constraint circuit
(fast; tier-1 resident — the slow-marked test_service.py covers the
XLA batch prover).  REGISTRY counters are process-global: tests assert
deltas, never absolutes.
"""

import json
import math
import os
import time

import pytest

from zkp2p_tpu.field.bn254 import R
from zkp2p_tpu.native.lib import get_lib
from zkp2p_tpu.pipeline.service import ProvingService
from zkp2p_tpu.utils import faults
from zkp2p_tpu.utils.metrics import REGISTRY

pytestmark = pytest.mark.skipif(get_lib() is None, reason="native toolchain unavailable")


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    """No ZKP2P_FAULTS leakage between tests: the plan cache is keyed by
    the raw env value, and a stale cached plan would carry spent once/n
    counters into a test that sets the same spec string."""
    monkeypatch.delenv("ZKP2P_FAULTS", raising=False)
    monkeypatch.delenv("ZKP2P_METRICS_SINK", raising=False)
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module")
def world():
    from zkp2p_tpu.prover.groth16_tpu import device_pk
    from zkp2p_tpu.snark.groth16 import setup
    from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem

    cs = ConstraintSystem("svc-faults")
    out = cs.new_public("out")
    x = cs.new_wire("x")
    y = cs.new_wire("y")
    z = cs.new_wire("z")
    cs.enforce(LC.of(x), LC.of(y), LC.of(z), "mul")
    cs.enforce(LC.of(z), LC.of(z), LC.of(out), "sq")
    cs.compute(z, lambda a, b: a * b % R, [x, y])
    pk, vk = setup(cs, seed="svc-faults")
    dpk = device_pk(pk, cs)

    def witness_fn(payload):
        xv, yv = int(payload["x"]), int(payload["y"])
        return cs.witness([pow(xv * yv, 2, R)], {x: xv, y: yv})

    return cs, dpk, vk, witness_fn


def _prove_batch(dpk, wits):
    """Deterministic batch prover: fixed (r, s) so the same witness
    always yields byte-identical proof JSON (the byte-parity anchor for
    the isolation tests; r/s secrecy is irrelevant in a test vector)."""
    from zkp2p_tpu.prover.native_prove import prove_native

    return [prove_native(dpk, w, r=123456789, s=987654321) for w in wits]


def _mk(world, **kw):
    cs, dpk, vk, witness_fn = world
    kw.setdefault("prover_fn", _prove_batch)
    kw.setdefault("batch_size", 2)
    kw.setdefault("retry_backoff_s", 0.0)  # tests must not sleep
    return ProvingService(cs, dpk, vk, witness_fn, public_fn=lambda w: [w[1]], **kw)


def _write_reqs(spool, pairs, prefix="r", **extra):
    for i, (xv, yv) in enumerate(pairs):
        with open(os.path.join(spool, f"{prefix}{i}.req.json"), "w") as f:
            json.dump({"x": xv, "y": yv, **extra}, f)


def _records(spool):
    path = str(spool).rstrip("/") + ".metrics.jsonl"
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(ln) for ln in f if json.loads(ln).get("type") == "request"]


def _counter(name, **labels):
    return REGISTRY.counter(name, labels or None).value


# ------------------------------------------------------- torn requests


def test_torn_req_json_terminals_bad_input_and_sweep_continues(world, tmp_path):
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5), (2, 7)])
    torn = os.path.join(spool, "aatorn.req.json")
    with open(torn, "w") as f:
        f.write('{"x": 3, "y"')  # half-written upload; sorts FIRST
    # age it past the mid-write grace window: this one is genuinely torn
    past = time.time() - 60
    os.utime(torn, (past, past))
    stats = _mk(world).process_dir(spool)
    assert stats["done"] == 2 and stats["error-bad-input"] == 1
    with open(os.path.join(spool, "aatorn.error.json")) as f:
        err = json.load(f)
    assert err["state"] == "error-bad-input"
    assert os.path.exists(os.path.join(spool, "r0.proof.json"))
    assert os.path.exists(os.path.join(spool, "r1.proof.json"))
    # idempotent: the torn file stays terminal, nothing reprocessed
    assert not any(_mk(world).process_dir(spool).values())


def test_young_torn_req_gets_grace_then_completes(world, tmp_path):
    """A torn file YOUNGER than the grace window may still be mid-write
    by a non-atomic uploader: the sweep must leave it open (a permanent
    error-bad-input on a request about to become valid is
    unrecoverable), and process it once the write completes."""
    spool = str(tmp_path)
    torn = os.path.join(spool, "r0.req.json")
    with open(torn, "w") as f:
        f.write('{"x": 3, "y"')  # fresh mtime: inside the grace window
    svc = _mk(world)
    assert not any(svc.process_dir(spool).values())
    assert not os.path.exists(os.path.join(spool, "r0.error.json"))
    with open(torn, "w") as f:  # the upload completes
        json.dump({"x": 3, "y": 5}, f)
    assert svc.process_dir(spool)["done"] == 1


def test_permanent_oserror_in_witness_terminals_bad_input(world, tmp_path):
    """A payload naming a missing file raises FileNotFoundError out of
    the witness builder — payload pathology, NOT transient pressure.
    Deferring it would livelock the spool: re-claimed, re-failed, and
    never terminal, every sweep, forever."""
    cs, dpk, vk, _ = world

    def witness_fn(payload):
        with open(payload["eml_path"]) as f:  # ENOENT
            f.read()

    svc = ProvingService(
        cs, dpk, vk, witness_fn, public_fn=lambda w: [w[1]],
        prover_fn=_prove_batch, batch_size=2, retry_backoff_s=0.0,
    )
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5)], eml_path=os.path.join(spool, "no-such.eml"))
    stats = svc.process_dir(spool)
    assert stats["error-bad-input"] == 1
    with open(os.path.join(spool, "r0.error.json")) as f:
        assert f.read().find("error-bad-input") >= 0
    # terminal, not deferred: the next sweep finds nothing to do
    assert not any(svc.process_dir(spool).values())


# --------------------------------------------------- short prover return


def test_short_prover_return_fails_loudly_not_truncated(world, tmp_path):
    """A prover_fn returning S-1 proofs for an S batch must never
    zip-truncate (last request silently dropped, or worse, mates
    emitted under the wrong rid) — the batch fails loudly, bisection
    re-proves, and every request still terminals correctly."""
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5), (2, 7)])
    calls = []

    def short_prover(dpk, wits):
        calls.append(len(wits))
        proofs = _prove_batch(dpk, wits)
        return proofs[:-1] if len(wits) > 1 else proofs

    b0 = _counter("zkp2p_service_bisections_total")
    stats = _mk(world, prover_fn=short_prover).process_dir(spool)
    # the short return is a PERMANENT batch failure -> bisected to
    # singles, which the prover handles correctly -> both still done
    assert stats["done"] == 2 and stats["error-failed-to-prove"] == 0
    assert calls == [2, 1, 1]
    assert _counter("zkp2p_service_bisections_total") - b0 == 1
    # and each proof landed under its OWN rid (no truncation shift)
    from zkp2p_tpu.formats.proof_json import load, proof_from_json
    from zkp2p_tpu.snark.groth16 import verify

    for i, (xv, yv) in enumerate([(3, 5), (2, 7)]):
        proof = proof_from_json(load(os.path.join(spool, f"r{i}.proof.json")))
        pub = [int(v) for v in load(os.path.join(spool, f"r{i}.public.json"))]
        assert pub == [pow(xv * yv, 2, R)]
        assert verify(world[2], proof, pub)


# ------------------------------------------------------ batch isolation


def test_poisoned_batch_isolates_to_one_error(world, tmp_path):
    """The acceptance criterion: a batch of 4 with one poisoned request
    completes the other three as done, with proofs byte-identical to a
    clean run and at most 1 + log2(S) prove calls touching each mate."""
    cs, dpk, vk, witness_fn = world
    pairs = [(3, 5), (2, 7), (4, 4), (9, 2)]
    poison_pub = pow(4 * 4, 2, R)  # r2 is the poisoned request

    clean_spool = str(tmp_path / "clean")
    os.makedirs(clean_spool)
    _write_reqs(clean_spool, pairs)
    assert _mk(world, batch_size=4).process_dir(clean_spool)["done"] == 4

    calls = []

    def poisoned_prover(dpk_, wits):
        calls.append(len(wits))
        if any(w[1] == poison_pub for w in wits):
            raise ValueError("poisoned witness")  # permanent: no retry
        return _prove_batch(dpk_, wits)

    spool = str(tmp_path / "dirty")
    os.makedirs(spool)
    _write_reqs(spool, pairs)
    b0 = _counter("zkp2p_service_bisections_total")
    stats = _mk(world, batch_size=4, prover_fn=poisoned_prover).process_dir(spool)
    assert stats["done"] == 3 and stats["error-failed-to-prove"] == 1
    assert _counter("zkp2p_service_bisections_total") - b0 >= 1
    with open(os.path.join(spool, "r2.error.json")) as f:
        assert json.load(f)["state"] == "error-failed-to-prove"

    # byte-identical batchmate proofs vs the clean run
    for i in (0, 1, 3):
        with open(os.path.join(spool, f"r{i}.proof.json"), "rb") as a, open(
            os.path.join(clean_spool, f"r{i}.proof.json"), "rb"
        ) as b:
            assert a.read() == b.read(), f"r{i} proof differs from clean run"

    # prove-call bound: every SUCCESSFUL call is a mate's final prove;
    # each mate additionally rides at most log2(S) failed bisection
    # probes (the poisoned single's ladder rescue attempts are its own
    # cost, not the mates') — bound the failing calls that contain any
    # mate by S/2 * log2(S) in aggregate, i.e. <= log2(S) each
    S = 4
    good_calls = [c for c in calls if c > 0]
    assert sum(1 for c in good_calls) <= (1 + math.ceil(math.log2(S))) * S
    # the sharpest observable: mates' proofs each emitted exactly once
    recs = [r for r in _records(spool) if r["state"] == "done"]
    assert sorted(r["request_id"] for r in recs) == ["r0", "r1", "r3"]


def test_batch_of_all_poisoned_terminals_every_request(world, tmp_path):
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5), (2, 7)])

    def broken_prover(dpk_, wits):
        raise ValueError("poisoned witness")

    stats = _mk(world, prover_fn=broken_prover).process_dir(spool)
    assert stats["error-failed-to-prove"] == 2 and stats["done"] == 0
    for i in range(2):
        assert os.path.exists(os.path.join(spool, f"r{i}.error.json"))
    # exactly one terminal record each, none duplicated
    recs = _records(spool)
    assert sorted(r["request_id"] for r in recs) == ["r0", "r1"]


# ---------------------------------------------------- transient retries


def test_transient_prove_failures_retry_with_bound(world, tmp_path, monkeypatch):
    """prove:raise:n=2 exhausts exactly the first two attempts; the
    bounded retry loop (retries=2) lands the third — all done, no
    bisection, retry counter +2."""
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5), (2, 7)])
    monkeypatch.setenv("ZKP2P_FAULTS", "prove:raise:n=2")
    faults.reset()
    r0 = _counter("zkp2p_service_retries_total")
    b0 = _counter("zkp2p_service_bisections_total")
    stats = _mk(world, retries=2).process_dir(spool)
    assert stats["done"] == 2 and stats["error-failed-to-prove"] == 0
    assert _counter("zkp2p_service_retries_total") - r0 == 2
    assert _counter("zkp2p_service_bisections_total") - b0 == 0


def test_retries_exhausted_falls_through_to_bisection(world, tmp_path, monkeypatch):
    """A fault that outlives the retry budget drops into bisection and
    the singles (retried again per-half) eventually terminal — the
    ladder below the retry loop, exercised end to end."""
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5), (2, 7)])
    # fires on every prove attempt forever: retries cannot save it, and
    # every bisection half + every ladder rung fails the same way
    monkeypatch.setenv("ZKP2P_FAULTS", "prove:raise")
    faults.reset()
    stats = _mk(world, retries=1).process_dir(spool)
    assert stats["error-failed-to-prove"] == 2 and stats["done"] == 0
    recs = _records(spool)
    assert sorted(r["request_id"] for r in recs) == ["r0", "r1"]
    assert all(r["state"] == "error-failed-to-prove" for r in recs)


# -------------------------------------------------- degradation ladder


def test_degradation_ladder_rescues_and_is_recorded(world, tmp_path):
    """A prover that only works with the multi-column path off (the
    classic 'fast path is broken on this host' failure) is rescued by
    the no-multi rung; the record carries degraded_rung and the
    degraded counter ticks."""
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5)])

    def multi_broken_prover(dpk_, wits):
        if os.environ.get("ZKP2P_MSM_MULTI") != "0":
            raise ValueError("multi-column path broken")  # permanent
        return _prove_batch(dpk_, wits)

    multi_broken_prover.reads_msm_knobs = True  # the ladder gates on this
    d0 = _counter("zkp2p_service_degraded_total", rung="no-multi")
    stats = _mk(world, prover_fn=multi_broken_prover, batch_size=1).process_dir(spool)
    assert stats["done"] == 1
    assert _counter("zkp2p_service_degraded_total", rung="no-multi") - d0 == 1
    (rec,) = _records(spool)
    assert rec["state"] == "done" and rec["degraded_rung"] == "no-multi"
    # the overlay is restored: the env is not left degraded
    assert os.environ.get("ZKP2P_MSM_MULTI") != "0"


def test_ladder_skipped_for_knob_blind_prover(world, tmp_path):
    """A prover that never reads the MSM knobs (the default TPU batch
    prover, or any custom fn) must NOT get the ladder: every rung would
    re-run the identical prove — four wasted full proves — and a flaky
    success would be misattributed to the rung."""
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5)])
    calls = []

    def always_broken(dpk_, wits):
        calls.append(len(wits))
        raise ValueError("deterministic breakage")  # permanent, knob-blind

    stats = _mk(world, prover_fn=always_broken, batch_size=1).process_dir(spool)
    assert stats["error-failed-to-prove"] == 1
    assert len(calls) == 1  # no retries (permanent), NO ladder re-proves
    with open(os.path.join(spool, "r0.error.json")) as f:
        assert "deterministic breakage" in json.load(f)["error"]


def test_queued_batch_claims_stay_heartbeated(world, tmp_path):
    """Claims held by batches waiting in ready_q behind a slow prove
    must stay fresh: with only a per-batch heartbeat they age toward
    stale while queued, a peer takes them over, and both workers emit
    terminal records for the same rid — the duplicate the chaos
    invariant forbids."""
    import threading

    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5), (2, 7), (4, 3)])

    def slow_prover(dpk_, wits):
        time.sleep(0.6)  # each batch outlives stale_claim_s below
        return _prove_batch(dpk_, wits)

    svc = _mk(world, prover_fn=slow_prover, batch_size=1, prefetch=3, stale_claim_s=0.4)
    t = threading.Thread(target=svc.process_dir, args=(spool,))
    t.start()
    time.sleep(0.5)  # queued batches' claims are now older than stale_claim_s
    # a peer sweeping the same spool mid-run must find nothing stale
    peer = _mk(world, batch_size=1)
    peer_stats = peer.process_dir(spool)
    t.join()
    assert not any(peer_stats.values())  # nothing was takeover-eligible
    by_rid = {}
    for rec in _records(spool):
        by_rid[rec["request_id"]] = by_rid.get(rec["request_id"], 0) + 1
    assert by_rid == {"r0": 1, "r1": 1, "r2": 1}  # exactly one terminal each


def test_spool_cap_ignores_requests_claimed_by_peers(world, tmp_path):
    """Admission control must count the CLAIMABLE backlog: requests a
    peer is actively proving are not queue pressure, and shedding off
    the inflated number permanently fails viable requests while the
    fleet has spare capacity."""
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5), (2, 7), (4, 3)])
    # a peer holds r0 right now (fresh claim)
    with open(os.path.join(spool, "r0.claim"), "w") as f:
        json.dump({"pid": 99999999, "ts": time.time()}, f)
    svc = _mk(world, spool_cap=2)
    stats = svc.process_dir(spool)
    # claimable backlog = 2 = cap: nothing shed, both proven
    assert stats["error-shed"] == 0 and stats["done"] == 2
    os.unlink(os.path.join(spool, "r0.claim"))


# ------------------------------------------------------------ deadlines


def test_deadline_exceeded_at_claim(world, tmp_path):
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5)], prefix="old", deadline_s=5)
    _write_reqs(spool, [(2, 7)], prefix="fresh", deadline_s=3600)
    # age the first request past its payload deadline (mtime is the
    # spool arrival clock)
    old = os.path.join(spool, "old0.req.json")
    past = time.time() - 60
    os.utime(old, (past, past))
    d0 = _counter("zkp2p_service_deadline_total")
    stats = _mk(world).process_dir(spool)
    assert stats["error-deadline-exceeded"] == 1 and stats["done"] == 1
    assert _counter("zkp2p_service_deadline_total") - d0 == 1
    with open(os.path.join(spool, "old0.error.json")) as f:
        assert json.load(f)["state"] == "error-deadline-exceeded"
    assert os.path.exists(os.path.join(spool, "fresh0.proof.json"))


def test_deadline_exceeded_at_batch_assembly(world, tmp_path, monkeypatch):
    """Budget burned between claim and batch assembly (here: a witness
    hang fault) trips deadline gate #2 — no prove compute is spent on a
    request that is already dead."""
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5)], deadline_s=0.6)
    monkeypatch.setenv("ZKP2P_FAULTS", "witness:hang=1.2")
    faults.reset()
    calls = []

    def counting_prover(dpk_, wits):
        calls.append(len(wits))
        return _prove_batch(dpk_, wits)

    stats = _mk(world, prover_fn=counting_prover).process_dir(spool)
    assert stats["error-deadline-exceeded"] == 1
    assert calls == []  # the prover never ran


def test_service_default_deadline_applies_when_payload_has_none(world, tmp_path):
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5)])
    req = os.path.join(spool, "r0.req.json")
    past = time.time() - 60
    os.utime(req, (past, past))
    stats = _mk(world, deadline_s=5.0).process_dir(spool)
    assert stats["error-deadline-exceeded"] == 1
    # deadline_s=0 means NO deadline: same aged request proves fine
    spool2 = str(tmp_path / "nodeadline")
    os.makedirs(spool2)
    _write_reqs(spool2, [(3, 5)])
    req2 = os.path.join(spool2, "r0.req.json")
    os.utime(req2, (past, past))
    assert _mk(world, deadline_s=0.0).process_dir(spool2)["done"] == 1


# ----------------------------------------------------- admission control


def test_spool_cap_sheds_newest_visibly(world, tmp_path):
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5), (2, 7), (4, 4), (9, 2)])
    # make arrival order unambiguous: r0 oldest ... r3 newest
    now = time.time()
    for i in range(4):
        p = os.path.join(spool, f"r{i}.req.json")
        os.utime(p, (now - 40 + 10 * i, now - 40 + 10 * i))
    s0 = _counter("zkp2p_service_shed_total")
    stats = _mk(world, spool_cap=2).process_dir(spool)
    assert stats["done"] == 2 and stats["error-shed"] == 2
    assert _counter("zkp2p_service_shed_total") - s0 == 2
    # the OLDEST two are kept (closest to their deadlines), newest shed
    assert os.path.exists(os.path.join(spool, "r0.proof.json"))
    assert os.path.exists(os.path.join(spool, "r1.proof.json"))
    for i in (2, 3):
        with open(os.path.join(spool, f"r{i}.error.json")) as f:
            err = json.load(f)
        assert err["state"] == "error-shed"
    shed = [r for r in _records(spool) if r["state"] == "error-shed"]
    assert sorted(r["request_id"] for r in shed) == ["r2", "r3"]


# ------------------------------------------------------- emit deferral


def test_injected_enospc_at_emit_defers_and_next_sweep_completes(world, tmp_path, monkeypatch):
    """emit:enospc:once — the proof is valid but cannot land; the
    request stays NON-terminal (no half-terminal artifacts, no TERMINAL
    record) and the next sweep re-proves and completes it.
    At-least-once, exactly one terminal record — plus one `deferred`
    attempt record carrying the sweep's spans, so the prove the failed
    sweep paid for stays on the waterfall (PR 8)."""
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5)])
    monkeypatch.setenv("ZKP2P_FAULTS", "emit:enospc:once")
    faults.reset()
    svc = _mk(world)
    e0 = _counter("zkp2p_service_emit_failures_total")
    d0 = _counter("zkp2p_service_deferred_total")
    stats = svc.process_dir(spool)
    assert stats["done"] == 0 and not any(stats.values())
    assert _counter("zkp2p_service_emit_failures_total") - e0 == 1
    assert _counter("zkp2p_service_deferred_total") - d0 == 1
    assert not os.path.exists(os.path.join(spool, "r0.proof.json"))
    assert not os.path.exists(os.path.join(spool, "r0.error.json"))
    assert not os.path.exists(os.path.join(spool, "r0.claim"))
    # deferred = NOT terminal, but the attempt IS recorded: state
    # "deferred", a reason, and the spans of the prove it burned
    recs = _records(spool)
    assert [r["state"] for r in recs] == ["deferred"]
    assert recs[0]["deferred_reason"].startswith("transient emit failure")
    assert recs[0]["queue_wait_s"] >= 0
    assert any(s["name"] == "prove" for s in recs[0]["spans"])
    # the fault is spent: the retry sweep lands the proof — exactly one
    # TERMINAL record, the deferred attempt line preserved before it
    stats2 = svc.process_dir(spool)
    assert stats2["done"] == 1
    recs = _records(spool)
    assert [r["state"] for r in recs] == ["deferred", "done"]
    assert all(r["request_id"] == "r0" for r in recs)


def test_transient_witness_failure_defers_not_bad_input(world, tmp_path, monkeypatch):
    """witness:raise:once is an infrastructure failure, not the
    payload's fault — the request must NOT terminal error-bad-input; it
    defers and the next sweep completes it."""
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5)])
    monkeypatch.setenv("ZKP2P_FAULTS", "witness:raise:once")
    faults.reset()
    svc = _mk(world)
    stats = svc.process_dir(spool)
    assert not any(stats.values())
    assert not os.path.exists(os.path.join(spool, "r0.error.json"))
    assert svc.process_dir(spool)["done"] == 1


# -------------------------------------------------------- claim takeover


def test_stale_claim_takeover_rewrites_owner(world, tmp_path):
    """The satellite fix: takeover must leave the claim file naming the
    CURRENT owner (pid/ts/takeover marker), not the dead worker's
    identity with a refreshed mtime."""
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5)])
    base = os.path.join(spool, "r0")
    claim = base + ".claim"
    with open(claim, "w") as f:
        json.dump({"pid": 99999999, "ts": 0.0}, f)  # dead peer's claim
    past = time.time() - 3600
    os.utime(claim, (past, past))

    svc = _mk(world, stale_claim_s=10.0)
    assert svc._try_claim(base) is True
    with open(claim) as f:
        owner = json.load(f)
    assert owner["pid"] == os.getpid() and owner.get("takeover") is True
    ProvingService._release_claim(base)


def test_takeover_backs_off_when_owner_completed_mid_race(world, tmp_path, monkeypatch):
    """The 'dead' owner was merely slow: it completes INSIDE the
    stale-check -> steal window (it never re-checks its stolen claim).
    The takeover must fail closed — re-proving finished work would emit
    a duplicate terminal record, the exact violation the chaos
    invariant asserts against — and must sweep the claim away."""
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5)])
    base = os.path.join(spool, "r0")
    claim = base + ".claim"
    with open(claim, "w") as f:
        json.dump({"pid": 99999999, "ts": 0.0}, f)
    past = time.time() - 3600
    os.utime(claim, (past, past))
    svc = _mk(world, stale_claim_s=10.0)

    real_rename = os.rename

    def racing_rename(src, dst):
        # we win the steal — and the slow owner's terminal write lands
        # right after (its own claim unlink hits OUR re-created claim)
        out = real_rename(src, dst)
        with open(base + ".proof.json", "w") as f:
            f.write("{}")
        return out

    monkeypatch.setattr(os, "rename", racing_rename)
    assert svc._try_claim(base) is False
    assert not os.path.exists(claim)


def test_fresh_claim_backs_off_when_peer_completed_mid_claim(world, tmp_path, monkeypatch):
    """A peer emits + releases between our top-of-function artifact
    check and our O_EXCL create landing on the freed slot: the fresh
    claim must back off like the steal path does, not re-prove finished
    work into a duplicate terminal record."""
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5)])
    base = os.path.join(spool, "r0")
    svc = _mk(world)

    real_open = os.open

    def racing_open(path, flags, *a, **kw):
        if isinstance(path, str) and path.endswith(".claim"):
            with open(base + ".proof.json", "w") as f:  # peer completes now
                f.write("{}")
        return real_open(path, flags, *a, **kw)

    monkeypatch.setattr(os, "open", racing_open)
    assert svc._try_claim(base) is False
    assert not os.path.exists(base + ".claim")


def test_steal_aside_litter_is_scavenged(world, tmp_path):
    """A taker SIGKILLed between its rename-aside and its unlink leaves
    <name>.claim.stale.<pid> behind; the sweep must scavenge aged ones
    (no other path ever matches the name)."""
    spool = str(tmp_path)
    litter = os.path.join(spool, "r0.claim.stale.12345")
    with open(litter, "w") as f:
        f.write("{}")
    past = time.time() - 3600
    os.utime(litter, (past, past))
    _mk(world, stale_claim_s=10.0).process_dir(spool)
    assert not os.path.exists(litter)


def test_two_takers_race_loser_backs_off(world, tmp_path, monkeypatch):
    """Two survivors racing one stale claim reach the steal at the same
    moment: rename is atomic, the kernel hands the file to exactly one,
    and the other's rename gets ENOENT and backs off.  (The earlier
    replace-in-place scheme let both takers read back their own write
    and both 'win' -> duplicate proves + duplicate terminal records.)"""
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5)])
    base = os.path.join(spool, "r0")
    claim = base + ".claim"
    with open(claim, "w") as f:
        json.dump({"pid": 99999999, "ts": 0.0}, f)
    past = time.time() - 3600
    os.utime(claim, (past, past))
    a = _mk(world, stale_claim_s=10.0)

    real_rename = os.rename

    def peer_steals_first(src, dst):
        # the peer's atomic steal lands one instant before ours
        real_rename(src, src + ".stolen-by-peer")
        return real_rename(src, dst)  # ours: source gone -> ENOENT

    monkeypatch.setattr(os, "rename", peer_steals_first)
    assert a._try_claim(base) is False  # loser backs off cleanly
    os.unlink(claim + ".stolen-by-peer")


def test_error_terminal_releases_claim_immediately(world, tmp_path):
    """An error-terminal request must not leave a live .claim behind:
    an orphan claim reads as in-flight work (the chaos harness picks
    SIGKILL victims by that signal) and outlives the service when no
    later sweep runs to scavenge it."""
    spool = str(tmp_path)
    with open(os.path.join(spool, "r0.req.json"), "w") as f:
        json.dump({"x": "not-a-number", "y": 5}, f)  # witness_fn int() fails
    stats = _mk(world).process_dir(spool)
    assert stats["error-bad-input"] == 1
    assert os.path.exists(os.path.join(spool, "r0.error.json"))
    assert not os.path.exists(os.path.join(spool, "r0.claim"))


def test_stale_claim_takeover_completes_request_exactly_once(world, tmp_path):
    """Sweep-level takeover: an aged claim with no terminal output (the
    crashed-peer signature) is taken over and the request completes with
    exactly one terminal state."""
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5)])
    claim = os.path.join(spool, "r0.claim")
    with open(claim, "w") as f:
        json.dump({"pid": 99999999, "ts": 0.0}, f)
    past = time.time() - 3600
    os.utime(claim, (past, past))

    stats = _mk(world, stale_claim_s=10.0).process_dir(spool)
    assert stats["done"] == 1
    assert os.path.exists(os.path.join(spool, "r0.proof.json"))
    assert not os.path.exists(claim)
    recs = _records(spool)
    assert [r["request_id"] for r in recs] == ["r0"] and recs[0]["state"] == "done"


def test_fresh_claim_is_not_taken_over(world, tmp_path):
    """A live peer's claim (age < stale_claim_s) blocks this worker
    entirely: no prove, no artifacts, claim content untouched."""
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5)])
    claim = os.path.join(spool, "r0.claim")
    peer = {"pid": 424242, "ts": time.time()}
    with open(claim, "w") as f:
        json.dump(peer, f)

    stats = _mk(world, stale_claim_s=300.0).process_dir(spool)
    assert not any(stats.values())
    assert not os.path.exists(os.path.join(spool, "r0.proof.json"))
    assert not os.path.exists(os.path.join(spool, "r0.error.json"))
    with open(claim) as f:
        assert json.load(f) == peer  # untouched
    os.unlink(claim)


def test_terminal_output_wins_over_stale_claim(world, tmp_path):
    """A request with a .proof.json is DONE regardless of any leftover
    claim: never reprocessed, the orphan claim is swept away."""
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5)])
    assert _mk(world).process_dir(spool)["done"] == 1
    claim = os.path.join(spool, "r0.claim")
    with open(claim, "w") as f:
        json.dump({"pid": 99999999, "ts": 0.0}, f)
    past = time.time() - 3600
    os.utime(claim, (past, past))
    proof_mtime = os.path.getmtime(os.path.join(spool, "r0.proof.json"))
    stats = _mk(world, stale_claim_s=10.0).process_dir(spool)
    assert not any(stats.values())
    assert os.path.getmtime(os.path.join(spool, "r0.proof.json")) == proof_mtime
    assert not os.path.exists(claim)


# ------------------------------------------------------ the sample verify
#
# One check a prover call, by the path the service observes (the native
# library loaded, or not): snark.native_verify.  The Python function is
# the oracle on both.

PATHS = ["native", "python"]


@pytest.fixture
def verify_path(request, monkeypatch):
    """The path under test, and every call the Python oracle gets."""
    from zkp2p_tpu.snark import native_verify

    if request.param == "python":
        monkeypatch.setattr(native_verify, "_native", lambda: None)
    spoken = []
    oracle = native_verify.verify_python

    def listening(vk, proof, public):
        spoken.append(oracle(vk, proof, public))
        return spoken[-1]

    monkeypatch.setattr(native_verify, "verify_python", listening)
    return request.param, spoken


def _bad_first_proof(dpk_, wits):
    """Every proof good but the sample: A moved off by the generator."""
    from dataclasses import replace

    from zkp2p_tpu.curve.host import G1_GENERATOR, g1_add

    proofs = _prove_batch(dpk_, wits)
    return [replace(proofs[0], a=g1_add(proofs[0].a, G1_GENERATOR))] + proofs[1:]


def _verify_spans(spool):
    return [s for r in _records(spool) for s in r["spans"] if s["name"] == "verify"]


@pytest.mark.parametrize("verify_path", PATHS, indirect=True)
def test_sample_verify_says_which_path_ran(world, tmp_path, verify_path):
    path, spoken = verify_path
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5), (2, 7)])
    v0 = _counter("zkp2p_service_verify_total", path=path)
    d0 = _counter("zkp2p_service_verify_disagree_total")
    assert _mk(world).process_dir(spool)["done"] == 2
    assert _counter("zkp2p_service_verify_total", path=path) - v0 == 1  # one a prover call, not one a proof
    assert _counter("zkp2p_service_verify_disagree_total") == d0
    spans = _verify_spans(spool)
    assert len(spans) == 2 and all(s["path"] == path for s in spans)  # the batch's one span, on both records
    # a native True is accepted: the oracle is asked only where it is the check
    assert spoken == ([] if path == "native" else [True])


@pytest.mark.parametrize("verify_path", PATHS, indirect=True)
def test_bad_sample_is_refused_with_the_oracle_having_spoken(world, tmp_path, verify_path):
    """The parent's error and the parent's ladder: the pair fails, is
    bisected, each single fails, and nothing is written for either."""
    path, spoken = verify_path
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5), (2, 7)])
    b0 = _counter("zkp2p_service_bisections_total")
    d0 = _counter("zkp2p_service_verify_disagree_total")
    stats = _mk(world, prover_fn=_bad_first_proof).process_dir(spool)
    assert stats["error-failed-to-prove"] == 2 and stats["done"] == 0
    assert _counter("zkp2p_service_bisections_total") - b0 == 1
    assert _counter("zkp2p_service_verify_disagree_total") == d0
    assert spoken == [False, False, False]  # the pair, then each half
    for i in range(2):
        with open(os.path.join(spool, f"r{i}.error.json")) as f:
            assert "sample proof failed verification" in json.load(f)["error"]
        assert not os.path.exists(os.path.join(spool, f"r{i}.proof.json"))
    assert all(s["path"] == path for s in _verify_spans(spool))


@pytest.mark.parametrize("verify_path", PATHS, indirect=True)
def test_bad_sample_walks_the_degradation_ladder(world, tmp_path, verify_path):
    """A prover whose fast path yields a wrong sample is rescued on the
    rung that turns the fast path off, as a prover that raises is."""
    path, spoken = verify_path
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5)])

    def multi_wrong_prover(dpk_, wits):
        good = os.environ.get("ZKP2P_MSM_MULTI") == "0"
        return (_prove_batch if good else _bad_first_proof)(dpk_, wits)

    multi_wrong_prover.reads_msm_knobs = True
    g0 = _counter("zkp2p_service_degraded_total", rung="no-multi")
    v0 = _counter("zkp2p_service_verify_total", path=path)
    stats = _mk(world, prover_fn=multi_wrong_prover, batch_size=1).process_dir(spool)
    assert stats["done"] == 1
    assert _counter("zkp2p_service_degraded_total", rung="no-multi") - g0 == 1
    (rec,) = _records(spool)
    assert rec["state"] == "done" and rec["degraded_rung"] == "no-multi"
    # the plain call, the rung that changes nothing for this prover, the rung that passed
    assert _counter("zkp2p_service_verify_total", path=path) - v0 == 3
    assert spoken == ([False, False] if path == "native" else [False, False, True])
    assert os.path.exists(os.path.join(spool, "r0.proof.json"))


def test_native_false_the_oracle_overrules_is_counted_and_emitted(world, tmp_path, monkeypatch, capfd):
    from zkp2p_tpu.snark import native_verify

    monkeypatch.setattr(native_verify, "verify_native", lambda lib, vk, proof, public: False)
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5), (2, 7)])
    d0 = _counter("zkp2p_service_verify_disagree_total")
    assert _mk(world).process_dir(spool)["done"] == 2
    assert _counter("zkp2p_service_verify_disagree_total") - d0 == 1
    assert "the two disagree" in capfd.readouterr().err
    assert all(os.path.exists(os.path.join(spool, f"r{i}.proof.json")) for i in range(2))


@pytest.mark.parametrize("verify_path", PATHS, indirect=True)
def test_verify_fault_site_fires_before_the_check(world, tmp_path, monkeypatch, verify_path):
    path, spoken = verify_path
    spool = str(tmp_path)
    _write_reqs(spool, [(3, 5), (2, 7)])
    monkeypatch.setenv("ZKP2P_FAULTS", "verify:raise:n=1")
    faults.reset()
    r0 = _counter("zkp2p_service_retries_total")
    v0 = _counter("zkp2p_service_verify_total", path=path)
    assert _mk(world, retries=1).process_dir(spool)["done"] == 2
    assert _counter("zkp2p_service_retries_total") - r0 == 1
    # the attempt the fault took never reached the check
    assert _counter("zkp2p_service_verify_total", path=path) - v0 == 1
    spans = _verify_spans(spool)
    assert len(spans) == 4 and all(s["path"] == path for s in spans)
