"""Native C++ BN254 library vs the Python oracle (skips without g++)."""

import random

import pytest

from zkp2p_tpu.curve.host import G1_GENERATOR, g1_mul
from zkp2p_tpu.field.bn254 import P, R
from zkp2p_tpu.native import lib as native

rng = random.Random(9)


pytestmark = pytest.mark.skipif(native.get_lib() is None, reason="native toolchain unavailable")


def test_fp_mul_std_matches_python():
    import ctypes

    import numpy as np

    lib = native.get_lib()
    u64p = ctypes.POINTER(ctypes.c_uint64)
    for _ in range(20):
        a, b = rng.randrange(P), rng.randrange(P)
        av, bv = native._int_to_u64x4(a), native._int_to_u64x4(b)
        cv = np.zeros(4, dtype=np.uint64)
        lib.fp_mul_std(av.ctypes.data_as(u64p), bv.ctypes.data_as(u64p), cv.ctypes.data_as(u64p))
        assert native._u64x4_to_int(cv) == a * b % P


def test_fixed_base_batch_matches_oracle():
    ks = [rng.randrange(R) for _ in range(50)] + [0, 1, 2, R - 1]
    res = native.g1_fixed_base_batch(G1_GENERATOR, ks)
    assert res is not None
    for k, pt in zip(ks, res):
        assert pt == g1_mul(G1_GENERATOR, k), k


def test_g1_mont_limbs_matches_oracle():
    """The Montgomery-limb fast path (batch-inverted normalization) emits
    exactly what g1_to_affine_arrays(host points) would."""
    import numpy as np

    from zkp2p_tpu.field.jfield import FQ

    ks = [rng.randrange(R) for _ in range(40)] + [0, 1, R - 1]
    res = native.g1_fixed_base_batch_mont_limbs(G1_GENERATOR, ks)
    assert res is not None
    xs, ys = res
    for i, k in enumerate(ks):
        pt = g1_mul(G1_GENERATOR, k)
        if pt is None:
            assert not xs[i].any() and not ys[i].any()
        else:
            assert np.array_equal(xs[i], FQ.to_mont_host(pt[0])), k
            assert np.array_equal(ys[i], FQ.to_mont_host(pt[1])), k


def test_g2_mont_limbs_matches_oracle():
    import numpy as np

    from zkp2p_tpu.curve.host import G2_GENERATOR, g2_mul
    from zkp2p_tpu.field.jfield import FQ

    ks = [rng.randrange(R) for _ in range(15)] + [0, 1, R - 1]
    res = native.g2_fixed_base_batch_mont_limbs(G2_GENERATOR, ks)
    assert res is not None
    xs, ys = res
    for i, k in enumerate(ks):
        pt = g2_mul(G2_GENERATOR, k)
        if pt is None:
            assert not xs[i].any() and not ys[i].any()
        else:
            x, y = pt
            assert np.array_equal(xs[i, 0], FQ.to_mont_host(x.c0)), k
            assert np.array_equal(xs[i, 1], FQ.to_mont_host(x.c1)), k
            assert np.array_equal(ys[i, 0], FQ.to_mont_host(y.c0)), k
            assert np.array_equal(ys[i, 1], FQ.to_mont_host(y.c1)), k


def test_setup_uses_native_and_matches():
    """setup must produce identical keys whether or not the native path is
    active (same seed -> same tau -> same points)."""
    from zkp2p_tpu.curve import host
    from zkp2p_tpu.snark.groth16 import setup
    from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem

    cs = ConstraintSystem("n")
    a = cs.new_public("a")
    w = cs.new_wire("w")
    cs.enforce(LC.of(a), LC.of(a), LC.of(w), "sq")
    cs.compute(w, lambda v: v * v % R, [a])
    pk1, vk1 = setup(cs, seed="native-test")

    # force the Python fallback
    import zkp2p_tpu.native.lib as nl

    saved = nl._lib, nl._tried
    nl._lib, nl._tried = None, True
    try:
        pk2, vk2 = setup(cs, seed="native-test")
    finally:
        nl._lib, nl._tried = saved
    assert pk1.a_query == pk2.a_query
    assert vk1.ic == vk2.ic
    assert pk1.h_query == pk2.h_query


# ------------------------------------------------ the sample verify, native
#
# csrc groth16_verify_bn254 against snark.groth16.verify (the oracle): the
# library's answer alone (`verify_native`), so an agreement here is not the
# oracle agreeing with itself.


def _toy(seed: str):
    """(vk, a valid proof, its public inputs) of a two-public toy circuit."""
    from zkp2p_tpu.snark.groth16 import prove_host, setup
    from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem

    r = random.Random(seed)
    cs = ConstraintSystem("verify-" + seed)
    out, sq = cs.new_public("out"), cs.new_public("sq")
    x, y = cs.new_wire("x"), cs.new_wire("y")
    cs.enforce(LC.of(x), LC.of(y), LC.of(out), "mul")
    cs.enforce(LC.of(x), LC.of(x), LC.of(sq), "sq")
    xv, yv = r.randrange(R), r.randrange(R)
    public = [xv * yv % R, xv * xv % R]
    w = cs.witness(public, {x: xv, y: yv})
    pk, vk = setup(cs, seed=seed)
    return vk, prove_host(pk, cs, w, r=r.randrange(1, R), s=r.randrange(1, R)), public


@pytest.fixture(scope="module")
def toy():
    return _toy("toy")


def _off_subgroup_twist_point():
    """A point of the twist that [R] does not kill (the cofactor is 2p - r:
    almost every point of the twist is one)."""
    from zkp2p_tpu.curve.host import TWIST_B, g2_mul
    from zkp2p_tpu.field.tower import Fq2
    from zkp2p_tpu.snark.ceremony import _fq2_sqrt

    x0 = 1
    while True:
        x = Fq2(x0, 1)
        y = _fq2_sqrt(x * x * x + TWIST_B)
        if y is not None and g2_mul((x, y), R) is not None:
            return (x, y)
        x0 += 1


def _tampered(case, vk, proof, public):
    from dataclasses import replace

    from zkp2p_tpu.curve.host import G2_GENERATOR, g1_add, g2_add
    from zkp2p_tpu.field.tower import Fq2

    if case == "valid":
        return proof, public
    if case == "A+G":
        return replace(proof, a=g1_add(proof.a, G1_GENERATOR)), public
    if case == "B+G":
        return replace(proof, b=g2_add(proof.b, G2_GENERATOR)), public
    if case == "C+G":
        return replace(proof, c=g1_add(proof.c, G1_GENERATOR)), public
    if case == "public+1":
        return proof, [public[0], public[1] + 1]
    if case == "public+R":  # the same input mod R, as the oracle reduces it
        return proof, [public[0] + R, public[1] - R]
    if case == "A-off-curve":
        return replace(proof, a=(proof.a[0], (proof.a[1] + 1) % P)), public
    if case == "C-off-curve":
        return replace(proof, c=((proof.c[0] + 1) % P, proof.c[1])), public
    if case == "B-off-twist":
        return replace(proof, b=(proof.b[0], proof.b[1] + Fq2(1, 0))), public
    if case == "B-off-subgroup":
        return replace(proof, b=_off_subgroup_twist_point()), public
    if case == "A-infinity":
        return replace(proof, a=None), public
    if case == "B-infinity":
        return replace(proof, b=None), public
    if case == "C-infinity":
        return replace(proof, c=None), public
    if case == "A-zero-zero":  # not the point at infinity: (0, 0) is off the curve
        return replace(proof, a=(0, 0)), public
    if case == "A-unreduced":  # the same point to the oracle, a coordinate the library refuses
        return replace(proof, a=(proof.a[0] + P, proof.a[1])), public
    if case == "too-few":
        return proof, public[:1]
    if case == "too-many":
        return proof, public + [1]
    raise AssertionError(case)


_CASES = {
    "valid": True, "A+G": False, "B+G": False, "C+G": False, "public+1": False, "public+R": True,
    "A-off-curve": False, "C-off-curve": False, "B-off-twist": False, "B-off-subgroup": False,
    "A-infinity": False, "B-infinity": False, "C-infinity": False, "A-zero-zero": False,
    "A-unreduced": True, "too-few": False, "too-many": False,
}


@pytest.mark.parametrize("case", list(_CASES))
def test_native_verify_answers_as_the_oracle(toy, case):
    """Every way a sample can be wrong: the library refuses it (it never
    raises), and the chooser hands the caller the oracle's answer."""
    from zkp2p_tpu.snark import native_verify
    from zkp2p_tpu.snark.groth16 import verify

    vk, proof, public = toy
    proof, public = _tampered(case, vk, proof, public)
    want = verify(vk, proof, public)
    assert want is _CASES[case]
    got = native_verify.verify_native(native.get_lib(), vk, proof, public)
    # the one case the library leaves to the oracle though the proof is good
    assert got is (want and case != "A-unreduced")
    assert native_verify.verify(vk, proof, public, "native") == (want, want and not got)
    assert native_verify.verify(vk, proof, public, "python") == (want, False)


@pytest.mark.parametrize("case", ["alpha-off-curve", "gamma-off-twist", "ic-off-curve", "ic-short"])
def test_native_verify_leaves_a_bad_key_to_the_oracle(toy, case):
    from dataclasses import replace

    from zkp2p_tpu.field.tower import Fq2
    from zkp2p_tpu.snark import native_verify

    vk, proof, public = toy
    if case == "alpha-off-curve":
        bad = replace(vk, alpha_1=(vk.alpha_1[0], (vk.alpha_1[1] + 1) % P))
    elif case == "gamma-off-twist":
        bad = replace(vk, gamma_2=(vk.gamma_2[0], vk.gamma_2[1] + Fq2(0, 1)))
    elif case == "ic-off-curve":
        bad = replace(vk, ic=[vk.ic[0], vk.ic[1], (vk.ic[2][0], (vk.ic[2][1] + 1) % P)])
    else:
        bad = replace(vk, ic=vk.ic[:2])
    assert native_verify.verify_native(native.get_lib(), bad, proof, public) is False


@pytest.mark.parametrize("seed", range(16))
def test_native_verify_agrees_over_seeded_triples(seed):
    """A fresh (vk, proof, public) a seed, valid and tampered one of four
    ways: thirty-two triples, the two functions asked each."""
    from zkp2p_tpu.snark import native_verify
    from zkp2p_tpu.snark.groth16 import verify

    vk, proof, public = _toy(f"triple-{seed}")
    lib = native.get_lib()
    assert native_verify.verify_native(lib, vk, proof, public) is True
    assert verify(vk, proof, public) is True
    case = ("A+G", "B+G", "C+G", "public+1")[seed % 4]
    bad_proof, bad_public = _tampered(case, vk, proof, public)
    assert native_verify.verify_native(lib, vk, bad_proof, bad_public) is False, case
    assert verify(vk, bad_proof, bad_public) is False, case


def test_verify_key_of_another_setup_is_refused(toy):
    from zkp2p_tpu.snark import native_verify

    vk, proof, public = toy
    other_vk, _, _ = _toy("another")
    assert native_verify.verify_native(native.get_lib(), other_vk, proof, public) is False


def test_load_time_self_check_refuses_a_wrong_tower(monkeypatch):
    """`get_lib` asks the library one pairing identity both ways: a
    library whose product check answers wrongly is no library."""
    import zkp2p_tpu.native.lib as nl
    from zkp2p_tpu.snark import native_verify

    for wrong in (lambda lib, pairs: True, lambda lib, pairs: False):
        monkeypatch.setattr(native_verify, "pairing_product_is_one", wrong)
        monkeypatch.setattr(nl, "_lib", None)
        monkeypatch.setattr(nl, "_tried", False)
        assert nl.get_lib() is None
    monkeypatch.undo()
    assert nl.get_lib() is not None
