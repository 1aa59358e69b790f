"""Structural parity of the exported verifier vs the reference contract.

No EVM toolchain exists in this environment (no solc/node/hardhat, zero
egress), so the exported `verifier.sol` cannot be *executed* here; this
test pins the next-strongest property: structural identity with
`/root/reference/contracts/Verifier.sol` — the exact snarkjs export
shape `Ramp is Verifier` compiles against — plus the calldata contract
(`verifyProof(uint[2], uint[2][2], uint[2], uint[26])`, G2 limbs in the
EVM's reversed order).  See docs/EVM_PARITY.md for the full accounting.
"""

import json
import os
import re

import pytest

from zkp2p_tpu.field.tower import Fq2
from zkp2p_tpu.formats.solidity import export_verifier
from zkp2p_tpu.snark.groth16 import VerifyingKey

REF = "/root/reference/contracts/Verifier.sol"
REF_VKEY = "/root/reference/app/src/helpers/vkey.ts"


def _venmo_shaped_vk() -> VerifyingKey:
    """A 26-public VerifyingKey (the Ramp.sol uint[26] layout) with
    generator-derived points — export_verifier only reads coordinates."""
    from zkp2p_tpu.curve.host import G1_GENERATOR, G2_GENERATOR, g1_mul, g2_mul

    ic = [g1_mul(G1_GENERATOR, 3 + i) for i in range(27)]
    return VerifyingKey(
        n_public=26,
        alpha_1=g1_mul(G1_GENERATOR, 5),
        beta_2=g2_mul(G2_GENERATOR, 7),
        gamma_2=g2_mul(G2_GENERATOR, 11),
        delta_2=g2_mul(G2_GENERATOR, 13),
        ic=ic,
    )


def test_export_has_the_reference_interface():
    sol = export_verifier(_venmo_shaped_vk())
    # The exact pieces Ramp.sol and the reference deployment depend on.
    assert "function verifyProof(" in sol
    assert "uint[26] memory input" in sol
    assert "uint[2] memory a" in sol and "uint[2][2] memory b" in sol
    assert "public view returns (bool r)" in sol
    assert len(re.findall(r"vk\.IC\[\d+\] = Pairing\.G1Point", sol)) == 27
    # BN254 precompiles 6 (add), 7 (mul), 8 (pairing) via staticcall.
    for pre in (" 6,", " 7,", " 8,"):
        assert f"staticcall(sub(gas(), 2000),{pre}" in sol
    assert "21888242871839275222246405745257275088548364400416034343698204186575808495617" in sol


@pytest.mark.skipif(not os.path.exists(REF), reason="reference checkout not available")
def test_export_structurally_matches_reference_verifier():
    """Every function the reference Verifier exposes (that the onramp
    path uses) exists in our export with an identical signature, and the
    pairing-check call sequence is the same."""
    with open(REF) as f:
        ref = f.read()
    sol = export_verifier(_venmo_shaped_vk())

    def signatures(src):
        return set(re.findall(r"function\s+(\w+)\(", src))

    ours, theirs = signatures(sol), signatures(ref)
    # pairingProd2/3 and P2 are dead code in the reference (only Prod4 is
    # called by verify); everything the verify path touches must match.
    needed = {"negate", "addition", "scalar_mul", "pairing", "pairingProd4", "verifyingKey", "verify", "verifyProof"}
    assert needed <= ours
    assert needed <= theirs

    # Same pairing equation, same operand order.
    pat = re.compile(
        r"pairingProd4\(\s*Pairing\.negate\(proof\.A\),\s*proof\.B,\s*vk\.alfa1,\s*vk\.beta2,\s*vk_x,\s*vk\.gamma2,\s*proof\.C,\s*vk\.delta2", re.S
    )
    assert pat.search(sol) and pat.search(ref)

    # Identical scalar-field guard and IC accumulation loop shape.
    for frag in (
        'require(input[i] < snark_scalar_field',
        "vk_x = Pairing.addition(vk_x, Pairing.scalar_mul(vk.IC[i + 1], input[i]))",
        "vk_x = Pairing.addition(vk_x, vk.IC[0])",
    ):
        assert frag.replace(" ", "") in sol.replace(" ", "")
        assert frag.replace(" ", "") in ref.replace(" ", "")

    # Reference vkey has 27 IC points (26 publics + 1), ours likewise.
    n_ic = lambda src: len(re.findall(r"vk\.IC\[\d+\] = Pairing\.G1Point", src))
    assert n_ic(ref) == 27 == n_ic(sol)


def _verifying_key_constants(sol: str):
    """Every number snarkjs bakes into verifyingKey(), as an ordered map:
    the complete key-dependent content of the contract (all other lines
    are vkey-independent boilerplate)."""
    out = {}
    m = re.search(r"vk\.alfa1 = Pairing\.G1Point\(\s*(\d+),\s*(\d+)", sol)
    out["alfa1"] = (int(m.group(1)), int(m.group(2)))
    for name in ("beta2", "gamma2", "delta2"):
        m = re.search(
            rf"vk\.{name} = Pairing\.G2Point\(\s*\[(\d+),\s*(\d+)\],\s*\[(\d+),\s*(\d+)\]",
            sol,
        )
        out[name] = tuple(int(m.group(i)) for i in range(1, 5))
    for m in re.finditer(r"vk\.IC\[(\d+)\] = Pairing\.G1Point\(\s*(\d+),\s*(\d+)", sol):
        out[f"IC[{m.group(1)}]"] = (int(m.group(2)), int(m.group(3)))
    return out


@pytest.mark.skipif(
    not (os.path.exists(REF) and os.path.exists(REF_VKEY)),
    reason="reference checkout not available",
)
def test_reference_vkey_golden_constants():
    """Golden comparison against a REAL snarkjs export:
    feed the reference's shipped verification key (app/src/helpers/vkey.ts)
    through our exporter and require every constant embedded in the
    generated contract — alfa1, beta2/gamma2/delta2 with snarkjs's
    reversed G2 limb order, and all 27 IC points — to equal the ones in
    the reference's own snarkjs-generated contracts/Verifier.sol, plus
    the exact verifyProof ABI.  (The reference file is read in place, not
    vendored: the surrounding Pairing-library boilerplate is
    vkey-independent, so the constants + ABI are the entire key-derived
    content of the export.)"""
    from zkp2p_tpu.formats.proof_json import vkey_from_json

    from zkp2p_tpu.field.bn254 import P

    with open(REF_VKEY) as f:
        ts = f.read()
    vkey_json = json.loads(ts[ts.index("{"):ts.rindex("}") + 1])
    vk = vkey_from_json(vkey_json)
    sol = export_verifier(vk)
    ours = _verifying_key_constants(sol)
    with open(REF) as f:
        theirs = _verifying_key_constants(f.read())
    # delta2 is EXCLUDED by necessity: the reference's own two artifacts
    # disagree on it — vkey.ts and contracts/Verifier.sol were exported
    # from different phase-2 contribution counts, and a contribution
    # rerandomises exactly delta (alpha/beta/gamma and the gamma-divided
    # IC are contribution-invariant, and do match below, all 51 numbers).
    ours.pop("delta2")
    want_delta = theirs.pop("delta2")
    assert ours == theirs
    # our delta2 must still be the faithful rendering of vkey.ts's delta
    # (snarkjs reversed limb order), and a valid distinct ceremony value.
    m = re.search(
        r"vk\.delta2 = Pairing\.G2Point\(\s*\[(\d+),\s*(\d+)\],\s*\[(\d+),\s*(\d+)\]", sol
    )
    dx, dy = vk.delta_2
    assert tuple(int(m.group(i)) for i in range(1, 5)) == (dx.c1, dx.c0, dy.c1, dy.c0)
    assert all(0 < v < P for v in want_delta)
    assert "uint[26] memory input" in sol and "public view returns (bool r)" in sol
