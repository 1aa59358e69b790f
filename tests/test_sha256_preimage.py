"""The fixed-length SHA-256 preimage circuit (`models.registry.
build_sha256_preimage`: the digest public, SHA-256's own padding wired as
constants) at the registry's CI shape, 64 message bytes and two blocks, and
its served entry `ProvingService.for_sha256_preimage` with the C++ prover
standing in for the device: the public signals are `hashlib`'s digest on
both witness tiers, no other padding satisfies the circuit, the audit
admits it with two public signals, and a malformed request is its own
error, not its batch's."""

import hashlib
import json
import os
import random
import time

import pytest

from benchmarks.harness import check
from benchmarks.reference.public_signals import differing
from benchmarks.reference.sha_signals import preimage_digest
from zkp2p_tpu.models import registry

N = 64


def _payload(seed):
    rng = random.Random(f"sha256-preimage-{seed}")
    return {"msg": [rng.randrange(256) for _ in range(N)]}


def _halves(digest: bytes):
    return [int.from_bytes(digest[:16], "big"), int.from_bytes(digest[16:], "big")]


@pytest.fixture(scope="module")
def circuit():
    return registry.build_sha256_preimage(N)


@pytest.mark.parametrize("tier", ["scalar", "batched"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_public_signals_are_hashlibs_digest_of_the_message(circuit, tier, seed):
    cs, msg = circuit
    payload = _payload(seed)
    pubs, private = registry.sha256_preimage_inputs(msg, payload)
    assert pubs == _halves(hashlib.sha256(bytes(payload["msg"])).digest())
    w = cs.witness(pubs, private) if tier == "scalar" else cs.witness_batch([(pubs, private), (pubs, private)])[1]
    cs.check_witness(w)
    assert [int(v) for v in w[1:3]] == pubs and list(preimage_digest(payload).values()) == pubs
    # the control: the circuit holds the signals to the digest, it does not just carry them
    with pytest.raises(AssertionError, match="digest/pack"):
        cs.check_witness(cs.witness([pubs[0], pubs[1] ^ 1], private))


@pytest.mark.parametrize("seed", [7, 8])
def test_a_message_in_hex_is_the_same_request_as_its_bytes(circuit, seed):
    """`msg_hex`, the form the benchmark's cell sends: the same signals and inputs, and the same tie."""
    payload = _payload(seed)
    in_hex = {"msg_hex": bytes(payload["msg"]).hex()}
    assert registry.sha256_preimage_inputs(circuit[1], in_hex) == registry.sha256_preimage_inputs(circuit[1], payload)
    assert preimage_digest(in_hex) == preimage_digest(payload)


def test_the_two_tiers_agree_wire_for_wire(circuit):
    cs, msg = circuit
    inputs = [registry.sha256_preimage_inputs(msg, _payload(seed)) for seed in (3, 4, 5)]
    for one, (pubs, private) in zip(cs.witness_batch(inputs), inputs):
        assert list(one) == list(cs.witness(pubs, private))


@pytest.mark.parametrize("byte,bit", [(0, 7), (17, 0), (62, 1)], ids=["the_0x80", "a_zero", "the_length"])
def test_a_padding_bit_altered_cannot_satisfy_the_circuit(circuit, byte, bit):
    cs, msg = circuit
    (wire,) = [w for w, label in cs.labels.items() if label == f"pad.{byte}.{bit}"]
    w = list(cs.witness(*registry.sha256_preimage_inputs(msg, _payload(6))))
    pad = (b"\x80" + b"\x00" * 55 + (8 * N).to_bytes(8, "big"))[byte]
    assert w[wire] == (pad >> bit) & 1
    w[wire] ^= 1
    with pytest.raises(AssertionError, match="pad/const"):
        cs.check_witness(w)


def test_the_digest_of_the_message_under_another_padding_is_refused(circuit):
    """`build_sha2b` takes 128 pre-padded bytes, so its prover may pad as it
    likes; here the chain over (message, some other second block) is not a
    digest the circuit accepts."""
    from zkp2p_tpu.inputs.sha_host import midstate

    cs, msg = circuit
    payload = _payload(7)
    _pubs, private = registry.sha256_preimage_inputs(msg, payload)
    other = midstate(bytes(payload["msg"]) + b"\x80" + b"\x00" * 63)
    forged = _halves(b"".join(v.to_bytes(4, "big") for v in other))
    with pytest.raises(AssertionError, match="digest/pack"):
        cs.check_witness(cs.witness(forged, private))


def test_the_registry_admits_the_ci_shape_with_two_public_signals():
    cs, report = registry.audited("sha256-64")
    assert report["unwaived"] == 0 and report["n_public"] == cs.num_public == registry.SPECS["sha256-64"].n_public == 2
    assert cs.num_constraints == 54_546 and len(cs.input_wires) == N
    assert "sha256-64" in registry.circuit_ids() and "sha256-4k" not in registry.circuit_ids()
    assert registry.SPECS["sha256-4k"].flagship and registry.SPECS["sha256-4k"].n_public == 2


@pytest.mark.parametrize("payload,why", [
    ({"msg": [1] * (N - 1)}, "carries 63"),
    ({"msg": [256] + [0] * (N - 1)}, "range"),
    ({}, "msg"),
    ({"msg_hex": "00" * (N - 1)}, "carries 63"),
    ({"msg_hex": "0g" * N}, "hex"),
], ids=["short", "not_a_byte", "no_message", "short_hex", "not_hex"])
def test_a_malformed_request_raises_before_any_witness(circuit, payload, why):
    with pytest.raises((ValueError, KeyError), match=why):
        registry.sha256_preimage_inputs(circuit[1], payload)


def test_the_cli_builds_and_witnesses_the_circuit():
    import argparse

    from zkp2p_tpu.pipeline import cli

    cs, meta = cli._build_circuit("sha256_preimage", 256, 192, N)
    args = argparse.Namespace(circuit="sha256_preimage", message="zkp2p")
    w, pub = cli._witness_for(args, cs, meta)
    cs.check_witness(w)
    assert pub == _halves(hashlib.sha256(b"zkp2p".ljust(N, b"\x00")).digest())  # a short message is zero-filled
    with pytest.raises(SystemExit, match="--message-bytes is 64"):
        cli._witness_for(argparse.Namespace(circuit="sha256_preimage", message="x" * (N + 1)), cs, meta)


# ------------------------------------------------------------------ served

GOOD = {"r0": _payload(10), "r2": {"msg_hex": bytes(_payload(11)["msg"]).hex()}}
BAD = {"r1": {"msg": [7] * (N + 1)}, "r3": {"msg": [300] + [0] * (N - 1)}}


@pytest.fixture(scope="module")
def key(circuit):
    from zkp2p_tpu.native.lib import get_lib
    from zkp2p_tpu.prover.setup_device import setup_device

    if get_lib() is None:
        pytest.skip("native library unavailable")
    return setup_device(circuit[0], seed="test-sha256-preimage")


def _sweep(circuit, key, spool, monkeypatch, requests, age_s=0.0, **patches):
    """`requests` dropped into `spool` and one sweep of a service of batches
    of four over it, the device prover stood in for by `prove_native`;
    returns (service, stats, the sizes the prover was called with)."""
    from zkp2p_tpu.pipeline import service
    from zkp2p_tpu.prover import groth16_tpu
    from zkp2p_tpu.prover.native_prove import prove_native

    calls = []

    def fake(dpk_, witnesses, rs=None, ss=None):
        calls.append(len(witnesses))
        return [prove_native(dpk_, w, 11 + i, 23 + i) for i, w in enumerate(witnesses)]

    with monkeypatch.context() as mp:
        mp.setattr(groth16_tpu, "prove_tpu_batch", fake)
        for name, value in patches.items():
            mp.setattr(service, name, value)
        svc = service.ProvingService.for_sha256_preimage(circuit[0], circuit[1], *key, batch_size=4)
        for rid, payload in requests.items():
            _drop(spool, rid, payload, age_s)
        return svc, svc.process_dir(spool), calls


def _drop(spool, rid, payload, age_s=0.0):
    path = os.path.join(spool, rid + ".req.json")
    with open(path, "w") as f:
        json.dump(payload, f)
    if age_s:
        os.utime(path, (time.time() - age_s,) * 2)


@pytest.fixture(scope="module")
def served(circuit, key, tmp_path_factory):
    """One batch of four through one sweep of the service, two of its
    requests malformed."""
    spool = str(tmp_path_factory.mktemp("spool"))
    with pytest.MonkeyPatch.context() as mp:
        svc, stats, calls = _sweep(circuit, key, spool, mp, {**GOOD, **BAD})
    return {"svc": svc, "stats": stats, "spool": spool, "calls": calls, "vk": key[1]}


@pytest.mark.parametrize("age_s", [0.0, 1.0], ids=["young", "settled"])
def test_a_sweep_lets_a_burst_that_is_still_arriving_land(circuit, key, tmp_path, monkeypatch, age_s):
    """Eight callers submit together and the sweep lists the spool after the
    fifth: it waits the youngest out and lists again, so the burst is two
    whole batches and not 4 + 1 with three left for the next sweep.  Where
    the youngest is older than the settle time nothing is waited for."""
    import types

    from zkp2p_tpu.pipeline import service

    spool, waits = str(tmp_path), []

    def sleep(seconds):  # the rest of the burst arrives while the sweep waits
        waits.append(seconds)
        for i in range(5, 8):
            _drop(spool, f"r{i}", _payload(20 + i))

    clock = types.SimpleNamespace(**{**vars(time), "sleep": sleep})
    first = {f"r{i}": _payload(20 + i) for i in range(5)}
    _svc, stats, calls = _sweep(circuit, key, spool, monkeypatch, first, age_s, time=clock)
    if age_s:
        assert waits == [] and stats["done"] == 5 and calls == [4, 4]  # the fifth alone, proved at a batch's size
    else:
        assert len(waits) == 1 and 0 < waits[0] <= service.BURST_SETTLE_S
        assert calls == [4, 4] and stats["done"] == 8


def _artifact(served, rid, kind):
    with open(os.path.join(served["spool"], f"{rid}.{kind}.json")) as f:
        return json.load(f)


def test_a_malformed_request_is_its_own_error_not_its_batchs(served):
    assert served["svc"].inputs_fn is not None  # whole batches take `witness_batch`
    assert served["stats"]["done"] == len(GOOD) and served["stats"]["error-bad-input"] == len(BAD)
    assert served["calls"] == [4]  # one batch, proved at the size it was claimed for: the two left fill it
    for rid in BAD:
        assert _artifact(served, rid, "error")["state"] == "error-bad-input"
        assert not os.path.exists(os.path.join(served["spool"], rid + ".proof.json"))


@pytest.mark.parametrize("rid", sorted(GOOD))
def test_a_served_proof_verifies_under_the_digest_of_its_own_request(served, rid):
    vk_ints = check.vk_to_ints(served["vk"])
    proof, public = _artifact(served, rid, "proof"), _artifact(served, rid, "public")
    assert check.verify_many(vk_ints, [(proof, public)], workers=1) == [True]
    assert differing(preimage_digest(GOOD[rid]), public) == 0
    (other,) = set(GOOD) - {rid}
    assert differing(preimage_digest(GOOD[other]), public) == 2
    assert check.verify_many(vk_ints, [(proof, _artifact(served, other, "public"))], workers=1) == [False]
