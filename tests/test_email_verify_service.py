"""`ProvingService.for_email_verify`, the served entry of the EmailVerify
family (what `cli.cmd_service` and the benchmark's adapter call), at the
256/128 CI shape with the C++ prover standing in for the device: requests
end `done`, their proofs pass the benchmark's own pairing check, carry the
public signals their request asks for, and are the bytes the oracle prover
gives from the scalar witness tier under the same (r, s)."""

import json
import os
import random

import pytest

from benchmarks import worlds_email
from benchmarks.harness import check
from benchmarks.reference.email_signals import twitter_reset
from benchmarks.reference.public_signals import differing
from zkp2p_tpu.field.bn254 import R

CI_SIZES = {"max_header_bytes": 256, "max_body_bytes": 128, "n": 121, "k": 17}
PAYLOADS = [{"handle": "zk_pranker", "filler": 0}, {"handle": "Ab_9", "filler": 70}]


def _pinned(payload):
    rng = random.Random("pinned-" + payload["handle"])
    return rng.randrange(1, R), rng.randrange(1, R)


@pytest.fixture(scope="module")
def world():
    from zkp2p_tpu.native.lib import get_lib
    from zkp2p_tpu.prover.setup_device import setup_device

    if get_lib() is None:
        pytest.skip("native library unavailable")
    cs, make_service = worlds_email.email_verify(CI_SIZES)
    dpk, vk = setup_device(cs, seed="test-email-verify-service")
    return cs, make_service, dpk, vk


@pytest.fixture(scope="module")
def served(world, tmp_path_factory):
    """Both requests through one sweep of the service, the device prover
    stood in for by `prove_native` with (r, s) pinned from the witness's
    own handle words, so a test can ask the oracle for the same proof."""
    from zkp2p_tpu.prover import groth16_tpu
    from zkp2p_tpu.prover.native_prove import prove_native

    cs, make_service, dpk, vk = world
    by_words = {tuple(twitter_reset(p).values()): _pinned(p) for p in PAYLOADS}
    calls = []

    def fake(dpk_, witnesses, rs=None, ss=None):
        calls.append(len(witnesses))
        return [prove_native(dpk_, w, *by_words[tuple(int(v) for v in w[18:21])]) for w in witnesses]

    spool = str(tmp_path_factory.mktemp("spool"))
    for i, payload in enumerate(PAYLOADS):
        with open(os.path.join(spool, f"r{i}.req.json"), "w") as f:
            json.dump(payload, f)
    mp = pytest.MonkeyPatch()
    mp.setattr(groth16_tpu, "prove_tpu_batch", fake)
    try:
        svc = make_service(dpk, vk, batch_size=2)
        stats = svc.process_dir(spool)
    finally:
        mp.undo()
    return {"svc": svc, "stats": stats, "spool": spool, "calls": calls}


def _artifact(served, i, kind):
    with open(os.path.join(served["spool"], f"r{i}.{kind}.json")) as f:
        return json.load(f)


def test_the_entry_point_sets_the_batched_witness_tier(world, served):
    cs, _make, _dpk, _vk = world
    assert cs.num_public == 20 and cs.num_constraints == 461_148
    assert served["svc"].inputs_fn is not None  # witness_batch and the service/inputs spans, as venmo's
    assert served["stats"]["done"] == len(PAYLOADS) and served["calls"] == [len(PAYLOADS)]


@pytest.mark.parametrize("i", range(len(PAYLOADS)))
def test_a_served_proof_verifies_and_carries_its_requests_signals(world, served, i):
    vk_ints = check.vk_to_ints(world[3])
    proof, public = _artifact(served, i, "proof"), _artifact(served, i, "public")
    assert check.verify_many(vk_ints, [(proof, public)], workers=1) == [True]
    assert differing(twitter_reset(PAYLOADS[i]), public) == 0
    # the control: it does not answer the other request, and not under the other's signals
    other = PAYLOADS[1 - i]
    assert differing(twitter_reset(other), public) > 0
    assert check.verify_many(vk_ints, [(proof, _artifact(served, 1 - i, "public"))], workers=1) == [False]


@pytest.mark.parametrize("i", range(len(PAYLOADS)))
def test_a_served_proof_is_the_oracles_bytes_from_the_scalar_tier(world, served, i):
    """Served: inputs_fn -> witness_batch -> prover -> JSON.  Oracle: the
    service's scalar `witness_fn` -> prove_native, same (r, s)."""
    from zkp2p_tpu.formats.proof_json import proof_to_json, public_to_json
    from zkp2p_tpu.prover.native_prove import prove_native

    cs, _make, dpk, _vk = world
    svc = served["svc"]
    witness = svc.witness_fn(PAYLOADS[i])
    cs.check_witness(witness)
    oracle = proof_to_json(prove_native(dpk, witness, *_pinned(PAYLOADS[i])))
    assert check.bytes_differing(_artifact(served, i, "proof"), oracle) == 0
    assert [int(v) for v in _artifact(served, i, "public")] == [int(v) for v in public_to_json(svc.public_fn(witness))]


def test_the_payload_generator_is_seeded_and_in_the_regex_alphabet():
    import re

    gen = worlds_email.twitter_reset_email({}, None)
    a = [gen(random.Random(f"payload-7-{i}"), i) for i in range(64)]
    assert a == [gen(random.Random(f"payload-7-{i}"), i) for i in range(64)]
    assert all(re.fullmatch(r"[A-Za-z0-9_]{4,15}", p["handle"]) and 0 <= p["filler"] <= 4096 for p in a)
    assert len({p["handle"] for p in a}) == 64 and len({p["filler"] // 64 for p in a}) > 8  # the midstate cut moves
    narrowed = worlds_email.twitter_reset_email({"filler_bytes": [0, 8]}, None)
    assert all(narrowed(random.Random(i), i)["filler"] <= 8 for i in range(32))
