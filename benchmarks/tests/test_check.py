"""The checker CAN read false: one limb of a proof flipped, one public
signal changed, one byte of the pinned proof off the oracle's."""

import copy

import pytest

from benchmarks.harness import check


@pytest.fixture(scope="module")
def proved():
    from zkp2p_tpu.field.bn254 import R
    from zkp2p_tpu.formats.proof_json import proof_to_json, public_to_json
    from zkp2p_tpu.snark.groth16 import prove_host, setup
    from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem

    cs = ConstraintSystem("check-toy")
    out = cs.new_public("out")
    x, y, z = cs.new_wire(), cs.new_wire(), cs.new_wire()
    cs.enforce(LC.of(x), LC.of(y), LC.of(z))
    cs.enforce(LC.of(z), LC.of(z), LC.of(out))
    cs.compute(z, lambda a, b: a * b % R, [x, y])
    pk, vk = setup(cs)
    proofs = [proof_to_json(prove_host(pk, cs, cs.witness([pow(3 * k, 2, R)], {x: 3, y: k}))) for k in (5, 7, 11)]
    publics = [public_to_json([pow(3 * k, 2, R)]) for k in (5, 7, 11)]
    return check.vk_to_ints(vk), proofs, publics


def test_sound_proofs_pass_inline_and_in_worker_processes(proved):
    vk, proofs, publics = proved
    assert check.verify_many(vk, list(zip(proofs, publics)), workers=1) == [True] * 3
    assert check.verify_many(vk, list(zip(proofs, publics)), workers=3) == [True] * 3


@pytest.mark.parametrize("point,coord", [("pi_a", 0), ("pi_c", 1)])
def test_a_flipped_limb_fails_the_pairing(proved, point, coord):
    vk, proofs, publics = proved
    bad = copy.deepcopy(proofs[0])
    bad[point][coord] = str(int(bad[point][coord]) ^ (1 << 64))  # one bit of the second 64-bit limb
    assert check.verify_many(vk, [(bad, publics[0])], 1) == [False]
    bad = copy.deepcopy(proofs[0])
    bad["pi_b"][0][1] = str(int(bad["pi_b"][0][1]) ^ 1)
    assert check.verify_many(vk, [(bad, publics[0])], 1) == [False]


def test_a_changed_public_signal_fails_the_pairing(proved):
    vk, proofs, publics = proved
    assert check.verify_many(vk, [(proofs[0], [str(int(publics[0][0]) + 1)]), (proofs[0], publics[1]),
                                  (proofs[0], [])], 1) == [False] * 3


def test_malformed_artifacts_are_verdicts_not_crashes(proved, tmp_path):
    vk, proofs, publics = proved
    assert check.verify_many(vk, [({"pi_a": ["1", "2", "1"]}, publics[0])], 1) == [False]
    reqs = [{"rid": "r0", "state": "done"}, {"rid": "r1", "state": "error-shed"}]
    (tmp_path / "r0.proof.json").write_text("{torn")
    assert check.check_window(vk, str(tmp_path), reqs, 1) == 0
    assert reqs[0]["valid"] is False and "valid" not in reqs[1]


def test_a_valid_proof_that_answers_another_request_is_not_valid_for_this_one(proved, tmp_path):
    """Proofs swapped between two slots pass the pairing and fail the tie."""
    import json

    vk, proofs, publics = proved
    tie = lambda payload: {0: pow(3 * payload["k"], 2)}  # noqa: E731 — the circuit's one signal, from the request
    reqs = [{"rid": f"r{k}", "state": "done", "payload": {"k": k}} for k in (5, 7, 11)]
    for r, proof, public in zip(reqs, [proofs[1], proofs[0], proofs[2]], [publics[1], publics[0], publics[2]]):
        (tmp_path / (r["rid"] + ".proof.json")).write_text(json.dumps(proof))
        (tmp_path / (r["rid"] + ".public.json")).write_text(json.dumps(public))
    assert check.check_window(vk, str(tmp_path), reqs, 1, tie) == 2
    assert [r["valid"] for r in reqs] == [False, False, True]
    assert check.check_window(vk, str(tmp_path), reqs, 1) == 0 and all(r["valid"] for r in reqs)  # the pairing alone passes them


def test_venmo_signals_worked_out_from_the_request_are_the_programs():
    """The reference's amount words and ids against the program's own input generator and
    witness, on a request as set-up numbers them and on one with ids below zero (the
    witness holds those modulo the field)."""
    import random

    from benchmarks.harness import worlds
    from benchmarks.reference.public_signals import differing, venmo_receipt
    from benchmarks.run import SETUP_INDEX
    from zkp2p_tpu.inputs.email import generate_inputs, make_test_key, make_venmo_email
    from zkp2p_tpu.models.venmo import VenmoParams, build_venmo_circuit

    params = VenmoParams(max_header_bytes=256, max_body_bytes=192)
    cs, lay = build_venmo_circuit(params)
    key = make_test_key(1)
    payload = worlds.venmo_receipt({}, cs)(random.Random(5), SETUP_INDEX + 3)
    assert payload["order_id"] == SETUP_INDEX + 4 and payload["claim_id"] == SETUP_INDEX + 3
    for p in (payload, dict(payload, order_id=-2, claim_id=-3)):
        inputs = generate_inputs(make_venmo_email(key, raw_id=p["raw_id"], amount=p["amount"]), key.n,
                                 p["order_id"], p["claim_id"], params, lay)
        public = [str(x) for x in cs.witness(inputs.public_signals, inputs.seed)[1:cs.num_public + 1]]
        assert len(public) == 26 and differing(venmo_receipt(p), public) == 0
    assert differing(venmo_receipt(dict(payload, amount=str(int(payload["amount"]) + 1))), public) >= 1
    assert differing(venmo_receipt(dict(payload, order_id=9, claim_id=8)), public) >= 2
    assert differing(venmo_receipt(p), public[:20]) == 2 and differing(venmo_receipt(p), None) == 5


def test_the_pinned_batch_is_held_to_the_oracle_byte_for_byte(proved):
    vk, proofs, publics = proved
    same = check.check_pinned(vk, proofs, publics, copy.deepcopy(proofs), 1, [{0: int(p[0])} for p in publics])
    assert same == {"pinned_pairing_failures": 0, "pinned_signals_not_the_requests": 0,
                    "pinned_bytes_differing_from_native": 0}
    other = check.check_pinned(vk, proofs, publics, [proofs[0], proofs[2], proofs[1]], 1)  # two of three from another (witness, r, s)
    assert other["pinned_pairing_failures"] == 0 and other["pinned_bytes_differing_from_native"] > 400
    bad = copy.deepcopy(proofs)
    bad[2]["pi_c"][0] = str(int(bad[2]["pi_c"][0]) ^ 1)
    res = check.check_pinned(vk, bad, publics, proofs, 1, [{0: 1}] * 3)
    assert res["pinned_pairing_failures"] == 1 and res["pinned_bytes_differing_from_native"] == 1
    assert res["pinned_signals_not_the_requests"] == 3
