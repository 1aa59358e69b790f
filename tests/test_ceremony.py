"""Phase-2 ceremony ops: contribute -> beacon -> verify, end to end.

Mirrors the reference's MPC flow
(`/root/reference/dizkus-scripts/3_gen_both_zkeys.sh:18-65`: contribute
x2 + beacon + `zkey verify`), over our zkey wire format: every
contribution must keep the key PROVING (proofs under the final key
verify against the final vkey), the chain must verify from the trusted
initial zkey, and any tamper — forged delta, skipped PoK, edited
queries — must be rejected.
"""

import hashlib
import os

import pytest

from zkp2p_tpu.field.bn254 import R
from zkp2p_tpu.formats.zkey import read_zkey, write_zkey, write_zkey_data
from zkp2p_tpu.snark import ceremony
from zkp2p_tpu.snark.groth16 import prove_host, qap_rows, setup, verify
from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    td = tmp_path_factory.mktemp("ceremony")
    cs = ConstraintSystem("ceremony-demo")
    out = cs.new_public("out")
    x, y, z = cs.new_wire(), cs.new_wire(), cs.new_wire()
    cs.enforce(LC.of(x), LC.of(y), LC.of(z))
    cs.enforce(LC.of(z), LC.of(z), LC.of(out))
    cs.compute(z, lambda a, b: a * b % R, [x, y])
    pk, vk = setup(cs, seed="ceremony-test")
    z0 = str(td / "initial.zkey")
    write_zkey(z0, pk, vk, qap_rows(cs))

    z1 = str(td / "c1.zkey")
    z2 = str(td / "c2.zkey")
    zf = str(td / "final.zkey")
    ceremony.contribute(z0, z1, b"first contributor entropy", name="alice")
    ceremony.contribute(z1, z2, b"second contributor entropy", name="bob")
    ceremony.beacon(z2, zf, hashlib.sha256(b"public drand round").digest(), iter_exp=6)
    return (cs, x, y), z0, z1, z2, zf


def test_hash_to_g2_lands_in_subgroup():
    from zkp2p_tpu.curve.host import g2_is_on_curve, g2_mul
    from zkp2p_tpu.field.bn254 import R as FR

    for seed in (b"a", b"b", b"longer seed value"):
        pt = ceremony.hash_to_g2(seed)
        assert g2_is_on_curve(pt)
        assert g2_mul(pt, FR) is None
    # determinism
    assert ceremony.hash_to_g2(b"a") == ceremony.hash_to_g2(b"a")


def test_chain_verifies(world):
    _, z0, _, _, zf = world
    ok, log = ceremony.verify_chain(z0, zf)
    assert ok, log
    assert any("beacon re-derived" in line for line in log)
    assert sum("PoK + delta link verified" in line for line in log) == 2


def test_final_key_still_proves(world):
    """The whole point of phase 2: the contributed key must produce
    proofs that verify against its own (delta-updated) vkey — and the
    original pre-ceremony vkey must now REJECT them."""
    (cs, x, y), z0, _, _, zf = world
    zd = read_zkey(zf)
    pk2, vk2 = zd.to_proving_key(), zd.to_verifying_key()
    w = cs.witness([1849], {x: 43, y: 1})
    proof = prove_host(pk2, cs, w)
    assert verify(vk2, proof, [1849])
    vk0 = read_zkey(z0).to_verifying_key()
    assert not verify(vk0, proof, [1849])


def test_intermediate_prefix_also_verifies(world):
    _, z0, z1, z2, _ = world
    ok, _ = ceremony.verify_chain(z0, z1)
    assert ok
    ok, _ = ceremony.verify_chain(z0, z2)
    assert ok


def test_forged_delta_rejected(world, tmp_path):
    """Replacing the final delta without a matching contribution record
    (the classic key-swap attack) must fail the chain."""
    from dataclasses import replace

    from zkp2p_tpu.curve.host import g1_mul, g2_mul

    _, z0, _, _, zf = world
    zd = read_zkey(zf)
    forged = replace(zd, delta_1=g1_mul(zd.delta_1, 3), delta_2=g2_mul(zd.delta_2, 3))
    bad = str(tmp_path / "forged.zkey")
    write_zkey_data(bad, forged)
    ok, log = ceremony.verify_chain(z0, bad)
    assert not ok and "chain head" in log[-1]


def test_tampered_query_rejected(world, tmp_path):
    """A single edited c_query point (a soundness backdoor) must fail
    the randomized scaling check even when deltas are untouched."""
    from dataclasses import replace

    from zkp2p_tpu.curve.host import G1_GENERATOR, g1_add

    _, z0, _, _, zf = world
    zd = read_zkey(zf)
    cq = list(zd.c_query)
    for i, pt in enumerate(cq):
        if pt is not None:
            cq[i] = g1_add(pt, G1_GENERATOR)
            break
    bad = str(tmp_path / "backdoor.zkey")
    write_zkey_data(bad, replace(zd, c_query=cq))
    ok, log = ceremony.verify_chain(z0, bad)
    assert not ok and "C query" in log[-1]


def test_tampered_transcript_rejected(world, tmp_path):
    from dataclasses import replace

    _, z0, _, _, zf = world
    zd = read_zkey(zf)
    c0 = zd.mpc.contributions[0]
    forged = replace(c0, transcript=bytes(64))
    mpc = replace(zd.mpc, contributions=[forged] + zd.mpc.contributions[1:])
    bad = str(tmp_path / "badtranscript.zkey")
    write_zkey_data(bad, replace(zd, mpc=mpc))
    ok, log = ceremony.verify_chain(z0, bad)
    assert not ok


def test_beacon_value_is_binding(world, tmp_path):
    """Rewriting the recorded beacon hash must be caught by the exact
    re-derivation check."""
    from dataclasses import replace

    _, z0, _, _, zf = world
    zd = read_zkey(zf)
    last = zd.mpc.contributions[-1]
    forged = replace(last, beacon_hash=hashlib.sha256(b"rigged").digest())
    mpc = replace(zd.mpc, contributions=zd.mpc.contributions[:-1] + [forged])
    bad = str(tmp_path / "riggedbeacon.zkey")
    write_zkey_data(bad, replace(zd, mpc=mpc))
    ok, log = ceremony.verify_chain(z0, bad)
    assert not ok


def test_mpc_section_roundtrips(world):
    _, _, _, _, zf = world
    zd = read_zkey(zf)
    assert zd.mpc is not None and len(zd.mpc.contributions) == 3
    assert zd.mpc.contributions[0].name == "alice"
    assert zd.mpc.contributions[2].kind == 1


def test_foreign_mpc_section_imports_as_opaque():
    """A section 10 in a layout we don't understand (e.g. stock
    snarkjs's TLV contribution records) must not break key import —
    the parser returns None and the key loads without MPC data."""
    from zkp2p_tpu.formats.zkey import _mpc_from_bytes

    garbage = b"\x00" * 64 + (3).to_bytes(4, "little") + b"\x17" * 200
    assert _mpc_from_bytes(garbage) is None
    huge_count = b"\x00" * 64 + (2**31).to_bytes(4, "little")
    assert _mpc_from_bytes(huge_count) is None


def test_cli_ceremony_roundtrip(world, tmp_path):
    """The CLI surface: contribute + verify through `ceremony` commands."""
    import subprocess
    import sys

    _, z0, _, _, zf = world
    out = str(tmp_path / "cli.zkey")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r1 = subprocess.run(
        [sys.executable, "-m", "zkp2p_tpu.pipeline.cli", "ceremony", "contribute", z0, out, "--entropy", "cli-test", "--name", "cli"],
        capture_output=True, text=True, env=env, cwd=root, timeout=300,
    )
    assert r1.returncode == 0, r1.stderr[-500:]
    r2 = subprocess.run(
        [sys.executable, "-m", "zkp2p_tpu.pipeline.cli", "ceremony", "verify", z0, out],
        capture_output=True, text=True, env=env, cwd=root, timeout=300,
    )
    assert r2.returncode == 0, r2.stdout + r2.stderr[-500:]
    assert "ZKEY OK" in r2.stdout


def test_offcurve_pok_point_rejected(world, tmp_path):
    """Invalid-curve attack: an off-curve g2_spx must be rejected by
    point validation BEFORE any pairing computes over it."""
    from dataclasses import replace

    from zkp2p_tpu.field.tower import Fq2

    _, z0, _, _, zf = world
    zd = read_zkey(zf)
    c0 = zd.mpc.contributions[0]
    bad_pt = (Fq2(1, 2), Fq2(3, 4))  # not on the twist
    mpc = replace(zd.mpc, contributions=[replace(c0, pok_g2_spx=bad_pt)] + zd.mpc.contributions[1:])
    bad = str(tmp_path / "offcurve.zkey")
    write_zkey_data(bad, replace(zd, mpc=mpc))
    ok, log = ceremony.verify_chain(z0, bad)
    assert not ok and "off-curve" in log[-1]


def test_huge_beacon_iter_exp_rejected_fast(world, tmp_path):
    """A file-controlled iter_exp of 63 must fail the cap check, not
    hang the verifier for 2^63 hashes."""
    import time as _t
    from dataclasses import replace

    _, z0, _, _, zf = world
    zd = read_zkey(zf)
    last = zd.mpc.contributions[-1]
    mpc = replace(zd.mpc, contributions=zd.mpc.contributions[:-1] + [replace(last, beacon_iter_exp=63)])
    bad = str(tmp_path / "dos.zkey")
    write_zkey_data(bad, replace(zd, mpc=mpc))
    t0 = _t.time()
    ok, log = ceremony.verify_chain(z0, bad)
    assert not ok and _t.time() - t0 < 30
    assert any("over cap" in line for line in log)


def test_truncated_h_query_rejected(world, tmp_path):
    """zip() must not silently truncate: a final key with a shorter
    h_query (padding poisoning vector) fails the scaling check."""
    from dataclasses import replace

    _, z0, _, _, zf = world
    zd = read_zkey(zf)
    bad = str(tmp_path / "short_h.zkey")
    write_zkey_data(bad, replace(zd, h_query=zd.h_query[:-2], domain_size=zd.domain_size))
    # the shorter section changes domain_size on read; rebuild via bytes
    zd2 = read_zkey(bad)
    ok, _ = ceremony.verify_chain(z0, bad)
    assert not ok
