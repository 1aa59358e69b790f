"""snarkjs .zkey format round-trip (monolithic + b..k chunks).

The environment has no node/snarkjs (zero egress), so true differential
validation against the reference toolchain is impossible here; these
tests pin the byte-level format discipline instead: Montgomery LE
encodings, section layout, coeff rows including the public binding rows,
and that a key surviving the round trip proves + verifies identically.
"""

import os

import pytest

from zkp2p_tpu.field.bn254 import R
from zkp2p_tpu.formats.zkey import CHUNK_SUFFIXES, read_zkey, split_zkey, write_zkey
from zkp2p_tpu.snark.groth16 import prove_host, qap_rows, setup, verify
from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem


def _toy():
    cs = ConstraintSystem("toy")
    out = cs.new_public("out")
    x = cs.new_wire("x")
    y = cs.new_wire("y")
    z = cs.new_wire("z")
    cs.enforce(LC.of(x), LC.of(y), LC.of(z), "mul")
    cs.enforce(LC.of(z) + LC.const(2), LC.of(z), LC.of(out), "sq")
    cs.compute(z, lambda a, b: a * b % R, [x, y])
    return cs, x, y


def test_zkey_roundtrip(tmp_path):
    cs, x, y = _toy()
    pk, vk = setup(cs, seed="zkey-test")
    path = os.path.join(tmp_path, "circuit_final.zkey")
    write_zkey(path, pk, vk, qap_rows(cs))
    zk = read_zkey(path)

    assert zk.n_vars == cs.num_wires
    assert zk.n_public == 1
    assert zk.domain_size == pk.domain_size
    assert zk.alpha_1 == pk.alpha_1
    assert zk.beta_2 == pk.beta_2
    assert zk.gamma_2 == vk.gamma_2
    assert zk.ic == vk.ic
    assert zk.a_query == pk.a_query
    assert zk.b1_query == pk.b1_query
    assert zk.b2_query == pk.b2_query
    assert zk.c_query == pk.c_query
    assert zk.h_query == pk.h_query

    # coeff section reproduces the QAP rows (incl. binding rows)
    a_rows, b_rows = zk.qap_row_arrays()
    rows = qap_rows(cs)
    assert len(a_rows) == len(rows)
    for j, (a, b, _c) in enumerate(rows):
        assert a_rows[j] == {w: v % R for w, v in a.items()}
        assert b_rows[j] == {w: v % R for w, v in b.items()}

    # the imported key proves and verifies
    w = cs.witness([255], {x: 3, y: 5})
    pk2 = zk.to_proving_key()
    vk2 = zk.to_verifying_key()
    proof = prove_host(pk2, cs, w, r=11, s=13)
    assert proof == prove_host(pk, cs, w, r=11, s=13)
    assert verify(vk2, proof, [255])
    assert not verify(vk2, proof, [256])


def test_zkey_chunked(tmp_path):
    cs, x, y = _toy()
    pk, vk = setup(cs, seed="zkey-test")
    path = os.path.join(tmp_path, "circuit.zkey")
    write_zkey(path, pk, vk, qap_rows(cs))
    chunks = split_zkey(path, n_chunks=10)
    assert [c[-1] for c in chunks] == list(CHUNK_SUFFIXES)
    zk = read_zkey(chunks)
    assert zk.a_query == pk.a_query
    assert zk.h_query == pk.h_query


@pytest.mark.slow
@pytest.mark.xslow
def test_zkey_device_prove(tmp_path):
    """device_pk_from_zkey: the zkey-import path drives the TPU prover to
    the same proof as the ConstraintSystem path."""
    from zkp2p_tpu.prover.groth16_tpu import device_pk, device_pk_from_zkey, prove_tpu

    cs, x, y = _toy()
    pk, vk = setup(cs, seed="zkey-test")
    path = os.path.join(tmp_path, "circuit_final.zkey")
    write_zkey(path, pk, vk, qap_rows(cs))
    zk = read_zkey(path)
    w = cs.witness([255], {x: 3, y: 5})
    got = prove_tpu(device_pk_from_zkey(zk), w, r=21, s=22)
    want = prove_tpu(device_pk(pk, cs), w, r=21, s=22)
    assert got == want
    assert verify(vk, got, [255])


def test_zkey_width_inference(tmp_path):
    """infer_zkey_widths recovers the bit wires (circom Num2Bits pattern
    x*(x-1)=0) from the coeff section alone, the imported key proves
    identically through the narrow-classed native path, and a witness
    violating an inferred bound is rejected instead of silently proving
    wrong (the zkey has no C matrix, so x*(x-1)=y is indistinguishable
    from a bit row at import time)."""
    import numpy as np

    from zkp2p_tpu.gadgets.core import num2bits
    from zkp2p_tpu.prover.groth16_tpu import (
        NARROW_WIDTH,
        device_pk,
        device_pk_from_zkey,
        infer_zkey_widths,
        widths_array,
    )
    from zkp2p_tpu.prover.native_prove import prove_native

    cs = ConstraintSystem("bits")
    out = cs.new_public("out")
    x = cs.new_wire("x")
    bits = num2bits(cs, x, 8)
    cs.enforce(LC.of(x), LC.of(x), LC.of(out), "sq")
    pk, vk = setup(cs, seed="width-infer")
    path = os.path.join(tmp_path, "bits.zkey")
    write_zkey(path, pk, vk, qap_rows(cs))
    zk = read_zkey(path)

    inferred = infer_zkey_widths(zk)
    tagged = widths_array(cs)
    # every cs-tagged BIT wire is recovered as narrow from the file alone
    bit_wires = np.flatnonzero(tagged == 1)
    assert len(bit_wires) >= 8
    assert (inferred[bit_wires] == 1).all()
    # and nothing untagged-narrow got widened into the narrow class
    assert (inferred[tagged > NARROW_WIDTH] > NARROW_WIDTH).all()

    dpk_imported = device_pk_from_zkey(zk)
    assert int(dpk_imported.a_nsel.shape[0]) > 0  # the fast path engaged
    dpk_cs = device_pk(pk, cs)
    w = cs.witness([169 % R], {x: 13})
    got = prove_native(dpk_imported, w, r=31, s=37)
    want = prove_native(dpk_cs, w, r=31, s=37)
    assert got == want
    assert verify(vk, got, [169])


def test_zkey_width_inference_guard(tmp_path):
    """The ambiguous pattern: x*(x-1) = y (NOT a bit constraint) — the
    importer will class x narrow, and the prove-time guard must reject a
    witness where x is actually wide."""
    from zkp2p_tpu.prover.groth16_tpu import device_pk_from_zkey
    from zkp2p_tpu.prover.native_prove import prove_native

    cs = ConstraintSystem("trap")
    out = cs.new_public("out")
    x = cs.new_wire("x")
    y = cs.new_wire("y")
    cs.enforce(LC.of(x), LC.of(x) - 1, LC.of(y), "not-a-bit")
    cs.enforce(LC.of(y), LC.const(1), LC.of(out), "bind")
    cs.compute(y, lambda v: v * (v - 1) % R, [x])
    pk, vk = setup(cs, seed="width-trap")
    path = os.path.join(tmp_path, "trap.zkey")
    write_zkey(path, pk, vk, qap_rows(cs))
    zk = read_zkey(path)
    dpk = device_pk_from_zkey(zk)

    xv = 5000  # > 2^11: breaks the inferred narrow bound
    w = cs.witness([xv * (xv - 1) % R], {x: xv})
    with pytest.raises(ValueError, match="width bound inferred"):
        prove_native(dpk, w, r=3, s=5)
    # opting out of inference proves fine (wide class)
    dpk_wide = device_pk_from_zkey(zk, infer_widths=False)
    proof = prove_native(dpk_wide, w, r=3, s=5)
    assert verify(vk, proof, [xv * (xv - 1) % R])
