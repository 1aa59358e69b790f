"""GLV endomorphism decomposition: host oracle and native C runtime
diffed integer-for-integer, plus the group-law property
k*P == k1*P + k2*phi(P) that the native arm rests on.

The two implementations share derived constants (field.bn254 computes
the cube roots, the lattice basis, and the Barrett mus at import), so
these tests pin both the math and the plumbing: a drifted constant or a
limb-arithmetic bug in the kernel breaks a parity assert here before it
can reach a prover MSM."""

import random

import numpy as np
import pytest

from zkp2p_tpu.curve.host import G1_GENERATOR, g1_add, g1_mul, g1_neg
from zkp2p_tpu.field import bn254 as b
from zkp2p_tpu.field.bn254 import (
    GLV_BETA,
    GLV_LAMBDA,
    GLV_MAX_BITS,
    P,
    R,
    glv_decompose,
    glv_num_planes,
)

rng = random.Random(17)

# the satellite-mandated edge scalars plus values that exercise negative
# half-scalars and the Barrett floor boundary
EDGE_SCALARS = [0, 1, 2, R - 1, R - 2, GLV_LAMBDA, R - GLV_LAMBDA, GLV_LAMBDA - 1,
                (1 << 128) - 1, 1 << 128, (1 << 200) + 7, R >> 1]


def _random_scalars(n):
    return [rng.randrange(R) for _ in range(n)]


def test_glv_constants_are_nontrivial_roots():
    assert GLV_LAMBDA != 1 and pow(GLV_LAMBDA, 3, R) == 1
    assert (GLV_LAMBDA * GLV_LAMBDA + GLV_LAMBDA + 1) % R == 0
    assert GLV_BETA != 1 and pow(GLV_BETA, 3, P) == 1
    # half-scalars must be genuinely half-length: the whole win
    assert GLV_MAX_BITS <= 130
    assert glv_num_planes(4) < 64 // 2 + 2


def test_glv_decompose_identity_and_bounds():
    for k in EDGE_SCALARS + _random_scalars(300):
        k1, k2 = glv_decompose(k)
        assert (k1 + k2 * GLV_LAMBDA - k) % R == 0, k
        assert abs(k1) < (1 << GLV_MAX_BITS) and abs(k2) < (1 << GLV_MAX_BITS), k


def test_glv_negative_half_scalars_occur():
    """The sign handling is load-bearing: with the floor-Barrett
    quotients and a positive-column basis, k1 is structurally
    nonnegative (it is the floored residual of positive terms) while k2
    comes out negative for essentially every scalar — so the negation
    plumbing in every kernel IS exercised by random data.  Pin that
    shape: if a basis change flipped it, the kernels' sign paths would
    silently swap coverage."""
    seen_neg = False
    for k in _random_scalars(200):
        k1, k2 = glv_decompose(k)
        assert k1 >= 0  # floor residual of positive columns
        seen_neg |= k2 < 0
    assert seen_neg


def test_glv_endomorphism_group_law():
    """k*P == k1*P + k2*phi(P) on the host curve, random and edge
    scalars (the property the satellite checklist names)."""
    pts = [G1_GENERATOR, g1_mul(G1_GENERATOR, rng.randrange(1, R))]
    for pt in pts:
        phi = (GLV_BETA * pt[0] % P, pt[1])
        for k in [0, 1, R - 1, GLV_LAMBDA] + _random_scalars(4):
            k1, k2 = glv_decompose(k)
            t1 = g1_mul(pt, abs(k1))
            t1 = g1_neg(t1) if k1 < 0 else t1
            t2 = g1_mul(phi, abs(k2))
            t2 = g1_neg(t2) if k2 < 0 else t2
            assert g1_add(t1, t2) == g1_mul(pt, k), k


# ---------------------------------------------------------------- native


def _native_lib():
    from zkp2p_tpu.native.lib import get_lib

    return get_lib()


@pytest.mark.skipif(_native_lib() is None, reason="native toolchain unavailable")
def test_native_decompose_matches_host():
    import ctypes

    from zkp2p_tpu.native.lib import _scalars_to_u64
    from zkp2p_tpu.prover.native_prove import _glv_consts, _lib, _p

    lib = _lib()
    ks = EDGE_SCALARS + _random_scalars(60)
    n = len(ks)
    sc = np.ascontiguousarray(_scalars_to_u64(ks))
    out = np.zeros((2 * n, 4), dtype=np.uint64)
    negs = np.zeros(2 * n, dtype=np.uint8)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.glv_decompose_batch.argtypes = [
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_long,
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64), u8p,
    ]
    lib.glv_decompose_batch(_p(sc), n, _p(_glv_consts()), _p(out), negs.ctypes.data_as(u8p))
    for i, k in enumerate(ks):
        k1 = int.from_bytes(out[i].tobytes(), "little")
        k2 = int.from_bytes(out[n + i].tobytes(), "little")
        got = (-k1 if negs[i] else k1, -k2 if negs[n + i] else k2)
        assert got == glv_decompose(k), k


@pytest.mark.skipif(_native_lib() is None, reason="native toolchain unavailable")
def test_native_glv_msm_matches_plain():
    """g1_msm_pippenger_glv_mt == g1_msm_pippenger on the same inputs —
    infinity holes, 0/+-1 scalars (the tree-sum classification), and
    both thread arms."""
    import ctypes

    from zkp2p_tpu.native.lib import _pack_affine, _scalars_to_u64
    from zkp2p_tpu.prover.native_prove import _glv_consts, _lib, _p

    lib = _lib()
    u64p = ctypes.POINTER(ctypes.c_uint64)
    n = 200
    pts = [g1_mul(G1_GENERATOR, rng.randrange(1, R)) for _ in range(n)]
    scalars = [rng.randrange(R) for _ in range(n)]
    pts[3] = None
    scalars[5] = 0
    scalars[6] = 1
    scalars[7] = R - 1
    bases = _pack_affine(pts)
    bm = np.zeros_like(bases)
    lib.fp_to_mont.argtypes = [u64p, u64p, ctypes.c_int]
    lib.fp_to_mont(_p(bases), _p(bm), 2 * n)
    sc = np.ascontiguousarray(_scalars_to_u64(scalars))
    want = np.zeros(8, dtype=np.uint64)
    lib.g1_msm_pippenger.argtypes = [u64p, u64p, ctypes.c_long, ctypes.c_int, u64p]
    lib.g1_msm_pippenger(_p(bm), _p(sc), n, 8, _p(want))

    phi = np.zeros_like(bm)
    lib.g1_glv_phi_bases(_p(bm), n, _p(_glv_consts()), _p(phi))
    b2 = np.ascontiguousarray(np.concatenate([bm, phi]))
    for threads in (1, 2):
        got = np.zeros(8, dtype=np.uint64)
        lib.g1_msm_pippenger_glv_mt(
            _p(b2), _p(sc), n, n, 8, threads, _p(_glv_consts()), GLV_MAX_BITS, _p(got)
        )
        assert (got == want).all(), threads

    # fewer scalars than cached bases: the phi half still sits at offset
    # nb in the doubled set, NOT at the scalar count — a regression here
    # silently reads plain bases as endomorphism bases
    n_short = n - 7
    want_s = np.zeros(8, dtype=np.uint64)
    lib.g1_msm_pippenger(_p(bm), _p(sc), n_short, 8, _p(want_s))
    got_s = np.zeros(8, dtype=np.uint64)
    lib.g1_msm_pippenger_glv_mt(
        _p(b2), _p(sc), n_short, n, 8, 1, _p(_glv_consts()), GLV_MAX_BITS, _p(got_s)
    )
    assert (got_s == want_s).all()


@pytest.mark.skipif(_native_lib() is None, reason="native toolchain unavailable")
def test_native_prove_glv_parity(monkeypatch):
    """prove_native with ZKP2P_MSM_GLV=1 emits the exact same proof as
    the GLV-off path for the same (witness, r, s) — the determinism
    contract the bench A/B depends on."""
    from zkp2p_tpu.prover import device_pk
    from zkp2p_tpu.prover.native_prove import prove_native
    from zkp2p_tpu.snark.groth16 import setup, verify
    from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem

    cs = ConstraintSystem("glv-toy")
    out = cs.new_public("out")
    x = cs.new_wire("x")
    y = cs.new_wire("y")
    z = cs.new_wire("z")
    cs.enforce(LC.of(x), LC.of(y), LC.of(z), "mul")
    cs.enforce(LC.of(z), LC.of(z), LC.of(out), "sq")
    cs.compute(z, lambda a, bb: a * bb % R, [x, y])
    w = cs.witness([225], {x: 3, y: 5})
    pk, vk = setup(cs)
    dpk = device_pk(pk, cs)
    r, s = rng.randrange(1, R), rng.randrange(1, R)
    monkeypatch.delenv("ZKP2P_MSM_GLV", raising=False)
    plain = prove_native(dpk, w, r=r, s=s)
    monkeypatch.setenv("ZKP2P_MSM_GLV", "1")
    glv = prove_native(dpk, w, r=r, s=s)
    assert plain == glv
    assert verify(vk, glv, [225])


def test_pick_window_thread_clamp():
    """ADVICE r5 #1: the vectorized cross-window suffix only engages
    single-threaded, so multi-threaded IFMA runs must keep the serial-
    suffix c=14 optimum instead of the single-thread c=15/16 curve."""
    from zkp2p_tpu.prover.native_prove import _lib, _pick_window

    lib = _lib()
    if lib is None or not lib.zkp2p_ifma_available():
        pytest.skip("IFMA unavailable: the wide-window curve is not active")
    assert _pick_window(1 << 19, threads=1) >= 15
    assert _pick_window(1 << 19, threads=2) <= 14
    assert _pick_window(1 << 21, threads=4) <= 14
