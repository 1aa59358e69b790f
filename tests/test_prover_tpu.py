"""TPU Groth16 prover vs host oracle + pairing verifier.

The determinism contract: same (witness, r, s) -> byte-identical proof from
`prove_tpu` and `prove_host` (the build's analog of the reference pinning a
known-good proof vector in test/ramp.test.js:193-196)."""

import random

import pytest

from zkp2p_tpu.field.bn254 import R
from zkp2p_tpu.prover import device_pk, prove_tpu, prove_tpu_batch
from zkp2p_tpu.snark.groth16 import prove_host, setup, verify
from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem

# XLA-compile-heavy: opt-in via ZKP2P_RUN_SLOW=1 (default suite must stay
# minutes on a 1-core host; the dryrun/bench paths exercise this code too)
pytestmark = [pytest.mark.slow, pytest.mark.xslow]

rng = random.Random(42)


def build_toy():
    """public out; private x, y:  x*y = z,  z*z = out (test_groth16_host twin)."""
    cs = ConstraintSystem("toy")
    out = cs.new_public("out")
    x = cs.new_wire("x")
    y = cs.new_wire("y")
    z = cs.new_wire("z")
    cs.enforce(LC.of(x), LC.of(y), LC.of(z), "mul")
    cs.enforce(LC.of(z), LC.of(z), LC.of(out), "sq")
    cs.compute(z, lambda a, b: a * b % R, [x, y])
    return cs, out, x, y


def build_wide():
    """A fatter circuit: chain of muls + linear combos, 2 public inputs."""
    cs = ConstraintSystem("wide")
    pub_a = cs.new_public("a")
    pub_b = cs.new_public("b")
    wires = [pub_a, pub_b]
    for i in range(12):
        u, v = wires[-2], wires[-1]
        w = cs.new_wire(f"w{i}")
        cs.enforce(LC.of(u) + LC.of(v) * 3 + LC.const(i + 1), LC.of(v) + LC.const(2), LC.of(w))
        cs.compute(w, lambda x, y, k=i: (x + 3 * y + k + 1) * (y + 2) % R, [u, v])
        wires.append(w)
    return cs


def test_tpu_matches_host_prover():
    cs, out, x, y = build_toy()
    w = cs.witness([225], {x: 3, y: 5})
    pk, vk = setup(cs)
    dpk = device_pk(pk, cs)
    r, s = rng.randrange(1, R), rng.randrange(1, R)
    got = prove_tpu(dpk, w, r=r, s=s)
    want = prove_host(pk, cs, w, r=r, s=s)
    assert got == want
    assert verify(vk, got, [225])


def test_tpu_prover_wide_circuit():
    cs = build_wide()
    pub = [7, 11]
    w = cs.witness(pub)
    cs.check_witness(w)
    pk, vk = setup(cs, seed="wide")
    dpk = device_pk(pk, cs)
    proof = prove_tpu(dpk, w)
    assert verify(vk, proof, pub)
    assert not verify(vk, proof, [8, 11])


def test_tpu_batch_prove():
    cs, out, x, y = build_toy()
    pk, vk = setup(cs)
    dpk = device_pk(pk, cs)
    cases = [(3, 5), (2, 7), (10, 11), (1, 1)]
    wits, pubs = [], []
    for a, b in cases:
        z = a * b % R
        o = z * z % R
        wits.append(cs.witness([o], {x: a, y: b}))
        pubs.append([o])
    proofs = prove_tpu_batch(dpk, wits)
    for proof, pub in zip(proofs, pubs):
        assert verify(vk, proof, pub)


def test_tpu_batch_prove_chunked(monkeypatch):
    """Sub-chunked batch (ZKP2P_BATCH_CHUNK, the HBM-bounding path): a
    5-witness batch over chunks of 2 — uneven tail padded by repeating
    the last witness — must yield 5 independently-verifying proofs."""
    from zkp2p_tpu.prover import groth16_tpu

    cs, out, x, y = build_toy()
    pk, vk = setup(cs)
    dpk = device_pk(pk, cs)
    cases = [(3, 5), (2, 7), (10, 11), (1, 1), (6, 9)]
    wits, pubs = [], []
    for a, b in cases:
        z = a * b % R
        o = z * z % R
        wits.append(cs.witness([o], {x: a, y: b}))
        pubs.append([o])
    monkeypatch.setattr(groth16_tpu, "BATCH_CHUNK", "2")
    proofs = groth16_tpu.prove_tpu_batch(dpk, wits)
    assert len(proofs) == 5
    for proof, pub in zip(proofs, pubs):
        assert verify(vk, proof, pub)


def test_tpu_width_classed_prover():
    """Width-classed MSM split (narrow 3-plane w=4 vs wide): a circuit
    with num2bits bit wires + full-width products must produce the EXACT
    host-oracle proof with both classes live."""
    from zkp2p_tpu.gadgets.core import bits2num, num2bits

    cs = ConstraintSystem("classed")
    out = cs.new_public("out")
    x = cs.new_wire("x")
    bits = num2bits(cs, x, 16, "xb")        # 16 bool wires + width tag on x
    y = bits2num(cs, bits[:8], "ylow")      # width-8 wire
    z = cs.new_wire("z")                    # full-width product
    cs.enforce(LC.of(y), LC.of(x), LC.of(z), "mul")
    cs.enforce(LC.of(z) + LC.const(3), LC.of(z), LC.of(out), "fin")
    cs.compute(z, lambda a, b: a * b % R, [y, x])
    cs.compute(out, lambda a: (a + 3) * a % R, [z])

    xv = 0xBEEF
    yv = xv & 0xFF
    zv = yv * xv
    w = cs.witness([(zv + 3) * zv % R], {x: xv})
    cs.check_witness(w)
    pk, vk = setup(cs, seed="classed")
    dpk = device_pk(pk, cs)
    # both classes must be populated for this test to mean anything
    assert int(dpk.a_nsel.shape[0]) > 16 and int(dpk.a_wsel.shape[0]) >= 2
    r, s = rng.randrange(1, R), rng.randrange(1, R)
    got = prove_tpu(dpk, w, r=r, s=s)
    want = prove_host(pk, cs, w, r=r, s=s)
    assert got == want
    assert verify(vk, got, [(zv + 3) * zv % R])
