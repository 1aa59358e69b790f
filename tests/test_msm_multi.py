"""Cross-proof multi-column MSM (csrc g1_msm_pippenger_multi /
g1_msm_pippenger_glv_multi): one sweep over a fixed base array fills S
independent bucket sets per window, sharing the batch-affine inversion
rounds across columns.

The parity oracle is the SEQUENTIAL single-column driver (itself diffed
against the pure-python host curve in test_msm_native_edge): every
column of a multi call must be byte-identical to its own sequential MSM
across {GLV on/off} x {batch-affine on/off} x {S=1, ragged S=3, S=8},
zero/infinity columns included.  The same contract one level up:
`prove_native_batch` emits the exact proof bytes of N sequential
`prove_native` calls for the same (witness, r, s) — that is what lets
the service feed whole claimed batches into one prove without changing
a single emitted artifact.

The scalar (non-IFMA) batch-affine tier runs in a ZKP2P_NATIVE_IFMA=0
subprocess (the env is latched at first native use — the test_ifma
pattern).
"""

import ctypes
import os
import random
import subprocess
import sys

import numpy as np
import pytest

from zkp2p_tpu.curve.host import G1_GENERATOR, g1_msm, g1_mul
from zkp2p_tpu.field.bn254 import GLV_MAX_BITS, P, R
from zkp2p_tpu.native import lib as native
from zkp2p_tpu.native.lib import _pack_affine, _scalars_to_u64

pytestmark = pytest.mark.skipif(native.get_lib() is None, reason="native toolchain unavailable")

rng = random.Random(23)
_u64p = ctypes.POINTER(ctypes.c_uint64)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _p(a: np.ndarray):
    return a.ctypes.data_as(_u64p)


def _lib():
    from zkp2p_tpu.prover.native_prove import _lib as pl

    return pl()


def _mont_bases(pts) -> np.ndarray:
    lib = _lib()
    bases = _pack_affine(pts)
    bm = np.zeros_like(bases)
    lib.fp_to_mont.argtypes = [_u64p, _u64p, ctypes.c_int]
    lib.fp_to_mont(_p(bases), _p(bm), 2 * len(pts))
    return bm


def _cols_to_u64(cols, n) -> np.ndarray:
    sc = np.zeros((len(cols), n, 4), dtype=np.uint64)
    for s, col in enumerate(cols):
        if col:
            sc[s, : len(col)] = _scalars_to_u64(col)
    return np.ascontiguousarray(sc)


def _multi(bm: np.ndarray, cols, c: int, threads: int = 1) -> np.ndarray:
    lib = _lib()
    n = bm.shape[0]
    S = len(cols)
    sc = _cols_to_u64(cols, n)
    out = np.zeros((S, 8), dtype=np.uint64)
    lib.g1_msm_pippenger_multi(_p(bm), _p(sc), n, S, c, threads, _p(out))
    return out


def _seq(bm: np.ndarray, cols, c: int, threads: int = 1) -> np.ndarray:
    lib = _lib()
    n = bm.shape[0]
    out = np.zeros((len(cols), 8), dtype=np.uint64)
    for s, col in enumerate(cols):
        sc = np.zeros((n, 4), dtype=np.uint64)
        if col:
            sc[: len(col)] = _scalars_to_u64(col)
        sc = np.ascontiguousarray(sc)
        lib.g1_msm_pippenger_mt(_p(bm), _p(sc), n, c, threads, _p(out[s]))
    return out


def _glv_doubled(bm: np.ndarray) -> np.ndarray:
    from zkp2p_tpu.prover.native_prove import _glv_consts

    lib = _lib()
    n = bm.shape[0]
    phi = np.zeros_like(bm)
    lib.g1_glv_phi_bases(_p(bm), n, _p(_glv_consts()), _p(phi))
    return np.ascontiguousarray(np.concatenate([bm, phi]))


def _multi_glv(b2: np.ndarray, nb: int, cols, c: int, threads: int = 1) -> np.ndarray:
    from zkp2p_tpu.prover.native_prove import _glv_consts

    lib = _lib()
    S = len(cols)
    sc = _cols_to_u64(cols, nb)
    out = np.zeros((S, 8), dtype=np.uint64)
    lib.g1_msm_pippenger_glv_multi(
        _p(b2), _p(sc), nb, nb, S, c, threads, _p(_glv_consts()), GLV_MAX_BITS, _p(out)
    )
    return out


def _seq_glv(b2: np.ndarray, nb: int, cols, c: int, threads: int = 1) -> np.ndarray:
    from zkp2p_tpu.prover.native_prove import _glv_consts

    lib = _lib()
    out = np.zeros((len(cols), 8), dtype=np.uint64)
    for s, col in enumerate(cols):
        sc = np.zeros((nb, 4), dtype=np.uint64)
        if col:
            sc[: len(col)] = _scalars_to_u64(col)
        sc = np.ascontiguousarray(sc)
        lib.g1_msm_pippenger_glv_mt(
            _p(b2), _p(sc), nb, nb, c, threads, _p(_glv_consts()), GLV_MAX_BITS, _p(out[s])
        )
    return out


def _bases_and_cols(n=420, S=8):
    """Shared fixture data: bases with infinity holes + duplicate points,
    columns exercising zeros, +-1 classification, full-width scalars,
    same-bucket doubling/cancellation pairs, and an all-zero column."""
    pts = [g1_mul(G1_GENERATOR, rng.randrange(1, 1 << 28)) for _ in range(n)]
    pts[3] = None
    pts[n - 2] = None
    pts[10] = pts[11]          # duplicate base: same-bucket P+P shapes
    x, y = pts[12]
    pts[13] = (x, P - y)       # negated base: P+(-P) cancellation shapes
    cols = []
    for s in range(S):
        col = [rng.randrange(1 << 14, 1 << 20) for _ in range(n)]
        col[0] = 0
        col[1] = 1
        col[2] = R - 1
        col[5] = rng.randrange(R)          # full-width lane
        col[10] = col[11]                  # dup (point, scalar) -> doubling
        col[12] = col[13]                  # negated pair, same scalar -> cancel
        cols.append(col)
    cols[S // 2] = [0] * n                 # a whole zero column
    return pts, cols


@pytest.fixture
def both_arms(monkeypatch):
    """Run the wrapped check under each ZKP2P_MSM_BATCH_AFFINE arm (the
    csrc gate is fresh-read per MSM, so one process can diff both)."""

    def runner(check):
        for arm in ("1", "0"):
            monkeypatch.setenv("ZKP2P_MSM_BATCH_AFFINE", arm)
            check(arm)

    yield runner


def test_multi_vs_sequential_plain(both_arms):
    pts, cols = _bases_and_cols()
    bm = _mont_bases(pts)

    def check(arm):
        for S in (1, 8):
            sub = cols[:S]
            for c, threads in ((14, 1), (14, 2), (8, 1)):
                got = _multi(bm, sub, c, threads)
                want = _seq(bm, sub, c, threads)
                assert np.array_equal(got, want), (arm, S, c, threads)

    both_arms(check)


def test_multi_vs_sequential_glv(both_arms):
    pts, cols = _bases_and_cols()
    bm = _mont_bases(pts)
    b2 = _glv_doubled(bm)
    nb = len(pts)

    def check(arm):
        for S in (1, 8):
            sub = cols[:S]
            for c, threads in ((14, 1), (14, 2)):
                got = _multi_glv(b2, nb, sub, c, threads)
                want = _seq_glv(b2, nb, sub, c, threads)
                assert np.array_equal(got, want), (arm, S, c, threads)

    both_arms(check)


def test_multi_ragged_columns_and_oracle(both_arms):
    """S=3 ragged (columns shorter than the base set are zero-padded)
    through the lib.py wrapper, diffed against the pure-python host
    oracle — small scalars keep g1_mul cheap."""
    from zkp2p_tpu.native.lib import g1_msm_multi

    n = 96
    pts = [g1_mul(G1_GENERATOR, rng.randrange(1, 1 << 24)) for _ in range(n)]
    pts[7] = None
    cols = [
        [rng.randrange(1, 1 << 18) for _ in range(n)],      # full column
        [rng.randrange(1, 1 << 18) for _ in range(n // 3)],  # ragged
        [],                                                  # empty = zero column
    ]

    def check(arm):
        got = g1_msm_multi(pts, cols)
        assert got is not False, "native lib vanished mid-test"
        for s, col in enumerate(cols):
            want = g1_msm(pts[: len(col)], col) if col else None
            assert got[s] == want, (arm, s)

    both_arms(check)


def test_multi_zero_and_infinity_only_columns(both_arms):
    pts = [g1_mul(G1_GENERATOR, rng.randrange(1, 1 << 24)) for _ in range(40)]
    holes = [None] * 40
    bm = _mont_bases(pts)
    bm_holes = _mont_bases(holes)

    def check(arm):
        # all-zero scalars in every column -> every output is infinity
        out = _multi(bm, [[0] * 40] * 3, 8)
        assert not out.any(), arm
        # all-infinity bases -> infinity even with live scalars
        out = _multi(bm_holes, [[rng.randrange(R) for _ in range(40)]] * 2, 8)
        assert not out.any(), arm

    both_arms(check)


def test_multi_scalar_tier_subprocess():
    """The non-IFMA scalar batch-affine multi tier (csrc
    g1_window_sum_multi): parity vs sequential in a ZKP2P_NATIVE_IFMA=0
    subprocess (the csrc gate is latched at first use per process)."""
    code = r"""
import ctypes, random, sys
sys.path.insert(0, %r)
import numpy as np
from zkp2p_tpu.curve.host import G1_GENERATOR, g1_mul
from zkp2p_tpu.field.bn254 import P, R
from zkp2p_tpu.native.lib import _pack_affine, _scalars_to_u64, get_lib

lib = get_lib()
assert lib is not None
assert lib.zkp2p_ifma_available() == 0, "IFMA gate did not latch off"
u64p = ctypes.POINTER(ctypes.c_uint64)
lib.fp_to_mont.argtypes = [u64p, u64p, ctypes.c_int]
lib.g1_msm_pippenger_mt.argtypes = [u64p, u64p, ctypes.c_long, ctypes.c_int, ctypes.c_int, u64p]
lib.g1_msm_pippenger_multi.argtypes = [u64p, u64p, ctypes.c_long, ctypes.c_int, ctypes.c_int, ctypes.c_int, u64p]

rng = random.Random(5)
n = 260
pts = [g1_mul(G1_GENERATOR, rng.randrange(1, 1 << 24)) for _ in range(n)]
pts[4] = None
pts[10] = pts[11]
x, y = pts[12]; pts[13] = (x, P - y)
bases = _pack_affine(pts)
bm = np.zeros_like(bases)
lib.fp_to_mont(bases.ctypes.data_as(u64p), bm.ctypes.data_as(u64p), 2 * n)
cols = [[rng.randrange(1 << 14, 1 << 20) for _ in range(n)] for _ in range(3)]
cols[0][10] = cols[0][11]
cols[0][12] = cols[0][13]
cols[1] = [0] * n
cols[2][0] = 0; cols[2][1] = 1; cols[2][2] = R - 1
sc = np.ascontiguousarray(np.stack([_scalars_to_u64(c) for c in cols]))
for c, threads in ((14, 1), (14, 2)):
    out = np.zeros((3, 8), dtype=np.uint64)
    lib.g1_msm_pippenger_multi(bm.ctypes.data_as(u64p), sc.ctypes.data_as(u64p), n, 3, c, threads, out.ctypes.data_as(u64p))
    for s in range(3):
        ref = np.zeros(8, dtype=np.uint64)
        scs = np.ascontiguousarray(_scalars_to_u64(cols[s]))
        lib.g1_msm_pippenger_mt(bm.ctypes.data_as(u64p), scs.ctypes.data_as(u64p), n, c, threads, ref.ctypes.data_as(u64p))
        assert np.array_equal(out[s], ref), (c, threads, s)
print("SCALAR-MULTI-OK")
""" % (REPO,)
    env = dict(os.environ, ZKP2P_NATIVE_IFMA="0", JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=600
    )
    assert r.returncode == 0, f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
    assert "SCALAR-MULTI-OK" in r.stdout


def test_multi_stats_counters():
    """The multi driver ticks its own stat slots (the PR-3 stats-block
    extension the observability docs name)."""
    from zkp2p_tpu.native.lib import stats_reset, stats_snapshot

    pts = [g1_mul(G1_GENERATOR, rng.randrange(1, 1 << 24)) for _ in range(64)]
    bm = _mont_bases(pts)
    assert stats_reset()
    _multi(bm, [[rng.randrange(R) for _ in range(64)] for _ in range(3)], 8)
    snap = stats_snapshot()
    assert snap["msm_multi_calls"] == 1
    assert snap["msm_multi_cols"] == 3
    assert snap["msm_multi_cols_last"] == 3
    assert snap["msm_multi_prep_ns"] > 0
    assert snap["msm_points"] == 3 * 64


def _toy_circuit():
    from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem

    cs = ConstraintSystem("multi-toy")
    out = cs.new_public("out")
    x = cs.new_wire("x")
    y = cs.new_wire("y")
    z = cs.new_wire("z")
    cs.enforce(LC.of(x), LC.of(y), LC.of(z), "mul")
    cs.enforce(LC.of(z), LC.of(z), LC.of(out), "sq")
    cs.compute(z, lambda a, b: a * b % R, [x, y])
    return cs, (out, x, y, z)


def test_prove_native_batch_matches_sequential(monkeypatch):
    """prove_native_batch == N x prove_native, byte for byte, for the
    same (witness, r, s) — under BOTH msm_multi arms and both GLV arms.
    This is the acceptance contract the service fast path rides on."""
    from zkp2p_tpu.prover import device_pk
    from zkp2p_tpu.prover.native_prove import prove_native, prove_native_batch
    from zkp2p_tpu.snark.groth16 import setup, verify

    cs, (out, x, y, z) = _toy_circuit()
    wits = [
        cs.witness([(3 * 5) ** 2 % R], {x: 3, y: 5}),
        cs.witness([(3 * 10) ** 2 % R], {x: 3, y: 10}),
        cs.witness([(7 * 11) ** 2 % R], {x: 7, y: 11}),
    ]
    pk, vk = setup(cs)
    dpk = device_pk(pk, cs)
    rs = [rng.randrange(1, R) for _ in wits]
    ss = [rng.randrange(1, R) for _ in wits]
    for glv in ("0", "1"):
        monkeypatch.setenv("ZKP2P_MSM_GLV", glv)
        seq = [prove_native(dpk, w, r=r, s=s) for w, r, s in zip(wits, rs, ss)]
        monkeypatch.setenv("ZKP2P_MSM_MULTI", "1")
        assert prove_native_batch(dpk, wits, rs=rs, ss=ss) == seq, f"glv={glv}"
        monkeypatch.setenv("ZKP2P_MSM_MULTI", "0")
        assert prove_native_batch(dpk, wits, rs=rs, ss=ss) == seq, f"glv={glv} (gate off)"
        monkeypatch.delenv("ZKP2P_MSM_MULTI", raising=False)
    assert verify(vk, seq[2], [(7 * 11) ** 2 % R])


def test_prove_native_batch_floor_arms(monkeypatch):
    """PR-20 floor arms on the batch path: prove_native_batch under
    {interleave, radix-8, witness-u64 all-on / all-off} x {threads 1,2}
    emits the exact bytes of the committed-old sequential proves — the
    multi-column apply interleave and the builder-u64 hand-off are pure
    scheduling/serialization changes."""
    from zkp2p_tpu.prover import device_pk
    from zkp2p_tpu.prover.native_prove import prove_native, prove_native_batch
    from zkp2p_tpu.snark.groth16 import setup

    cs, (out, x, y, z) = _toy_circuit()
    wits = [
        cs.witness([(3 * 5) ** 2 % R], {x: 3, y: 5}),
        cs.witness([(3 * 10) ** 2 % R], {x: 3, y: 10}),
        cs.witness([(7 * 11) ** 2 % R], {x: 7, y: 11}),
    ]
    pk, _vk = setup(cs)
    dpk = device_pk(pk, cs)
    rs = [rng.randrange(1, R) for _ in wits]
    ss = [rng.randrange(1, R) for _ in wits]
    for knob in ("ZKP2P_MSM_INTERLEAVE", "ZKP2P_NTT_RADIX8", "ZKP2P_WITNESS_U64"):
        monkeypatch.setenv(knob, "0")
    monkeypatch.setenv("ZKP2P_NATIVE_THREADS", "1")
    seq = [prove_native(dpk, w, r=r, s=s) for w, r, s in zip(wits, rs, ss)]
    for arm in ("1", "0"):
        for knob in ("ZKP2P_MSM_INTERLEAVE", "ZKP2P_NTT_RADIX8", "ZKP2P_WITNESS_U64"):
            monkeypatch.setenv(knob, arm)
        for threads in ("1", "2"):
            monkeypatch.setenv("ZKP2P_NATIVE_THREADS", threads)
            got = prove_native_batch(dpk, wits, rs=rs, ss=ss)
            assert got == seq, f"floor arm={arm} threads={threads}"


def test_prove_native_batch_edges():
    from zkp2p_tpu.prover import device_pk
    from zkp2p_tpu.prover.native_prove import prove_native, prove_native_batch
    from zkp2p_tpu.snark.groth16 import setup

    cs, (out, x, y, z) = _toy_circuit()
    w = cs.witness([225], {x: 3, y: 5})
    pk, _vk = setup(cs)
    dpk = device_pk(pk, cs)
    assert prove_native_batch(dpk, []) == []
    # S=1 rides the sequential path (nothing to amortize)
    assert prove_native_batch(dpk, [w], rs=[7], ss=[9]) == [prove_native(dpk, w, r=7, s=9)]
    with pytest.raises(ValueError):
        prove_native_batch(dpk, [w, w], rs=[1], ss=[2, 3])
