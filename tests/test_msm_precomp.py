"""Fixed-base precomputed-window MSM (csrc g1_precomp_build /
g1_msm_pippenger_fixed / _fixed_multi + prover.precomp).

The parity oracle is the VARIABLE-BASE driver (itself diffed against
the pure-python host curve in test_msm_native_edge): the fixed tier's
result must be byte-identical to g1_msm_pippenger_mt for the same
(bases, scalars) across {batch-affine on/off} x {single, multi S=4,
ragged}, zero/infinity columns included.  One level up, the proof
contract: ZKP2P_MSM_PRECOMP=1 emits the exact proof bytes of the =0 arm
across {GLV on/off} x {single prove, batch prove} — the fixed tier
bypasses GLV, so parity across the GLV arms is what pins "same group
element, same canonical bytes".

The persistence layer is covered tier-1-resident (the Makefile
`precomp-cache` smoke): build -> persist -> reload -> identical proof,
warm start skips the build (native precomp_build_ns stat unchanged),
and a corrupt or foreign cache file is rejected by the level-0
integrity check and rebuilt.
"""

import ctypes
import os
import random

import numpy as np
import pytest

from zkp2p_tpu.curve.host import G1_GENERATOR, g1_msm, g1_mul
from zkp2p_tpu.field.bn254 import P, R
from zkp2p_tpu.native import lib as native
from zkp2p_tpu.native.lib import _pack_affine, _scalars_to_u64

pytestmark = pytest.mark.skipif(native.get_lib() is None, reason="native toolchain unavailable")

rng = random.Random(29)
_u64p = ctypes.POINTER(ctypes.c_uint64)


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


def _build_tables(bm: np.ndarray, c: int, q: int, levels: int):
    lib = _lib()
    n = bm.shape[0]
    table = np.zeros((levels * n, 8), dtype=np.uint64)
    lib.g1_precomp_build(_p(bm), n, c, q, levels, 2, _p(table))
    t52 = np.zeros((levels * n, 10), dtype=np.uint64)
    p52 = _p(t52) if lib.g1_precomp_to52(_p(table), levels * n, _p(t52)) else None
    return table, t52, p52


def _fixed(table, p52, cols, n, c, q, levels, threads=1) -> np.ndarray:
    lib = _lib()
    S = len(cols)
    sc = np.zeros((S, n, 4), dtype=np.uint64)
    for s, col in enumerate(cols):
        if col:
            sc[s, : len(col)] = _scalars_to_u64(col)
    sc = np.ascontiguousarray(sc)
    out = np.zeros((S, 8), dtype=np.uint64)
    if S == 1:
        lib.g1_msm_pippenger_fixed(
            _p(table), p52, _p(sc), n, n, levels, c, q, threads, _p(out[0])
        )
    else:
        lib.g1_msm_pippenger_fixed_multi(
            _p(table), p52, _p(sc), n, n, S, levels, c, q, threads, _p(out)
        )
    return out


def _oracle(bm, cols, c=14, threads=1) -> np.ndarray:
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


def _bases_and_cols(n=300, S=4):
    """Infinity holes, duplicate/negated bases, zero / +-1 / full-width
    scalars, same-bucket doubling + cancellation pairs, a zero column —
    the test_msm_multi fixture shapes, reused for the fixed tier."""
    pts = [g1_mul(G1_GENERATOR, rng.randrange(1, 1 << 28)) for _ in range(n)]
    pts[3] = None
    pts[n - 1] = None
    pts[10] = pts[11]
    x, y = pts[12]
    pts[13] = (x, P - y)
    cols = []
    for _ in range(S):
        col = [rng.randrange(1 << 14, 1 << 20) for _ in range(n)]
        col[0] = 0
        col[1] = 1
        col[2] = R - 1
        col[5] = rng.randrange(R)
        col[10] = col[11]
        col[12] = col[13]
        cols.append(col)
    cols[S // 2] = [0] * n
    return pts, cols


@pytest.fixture
def both_arms(monkeypatch):
    def runner(check):
        for arm in ("1", "0"):
            monkeypatch.setenv("ZKP2P_MSM_BATCH_AFFINE", arm)
            check(arm)

    yield runner


GEOMS = ((16, 2, 8), (8, 4, 8), (6, 43, 1))  # deep, mid, degenerate L=1


def test_fixed_vs_variable_base_oracle(both_arms):
    pts, cols = _bases_and_cols()
    bm = _mont_bases(pts)
    n = bm.shape[0]

    def check(arm):
        want = _oracle(bm, cols[:1])
        for c, q, levels in GEOMS:
            table, t52, p52 = _build_tables(bm, c, q, levels)
            for threads in (1, 2):
                got = _fixed(table, p52, cols[:1], n, c, q, levels, threads)
                assert np.array_equal(got, want), (arm, c, q, levels, threads)
            # scalar-path arm of the same tables: mont256 reads, no 52-limb
            got = _fixed(table, None, cols[:1], n, c, q, levels)
            assert np.array_equal(got, want), (arm, c, q, levels, "no52")

    both_arms(check)


def test_fixed_multi_vs_sequential(both_arms):
    pts, cols = _bases_and_cols()
    bm = _mont_bases(pts)
    n = bm.shape[0]
    c, q, levels = 10, 3, 9
    table, t52, p52 = _build_tables(bm, c, q, levels)

    def check(arm):
        want = _oracle(bm, cols)
        for threads in (1, 2):
            got = _fixed(table, p52, cols, n, c, q, levels, threads)
            assert np.array_equal(got, want), (arm, threads)
        # ragged: short + empty columns zero-pad like the multi driver
        ragged = [cols[0], cols[1][: n // 3], []]
        want = _oracle(bm, ragged)
        got = _fixed(table, p52, ragged, n, c, q, levels)
        assert np.array_equal(got, want), arm

    both_arms(check)


def test_fixed_zero_and_infinity_only(both_arms):
    pts = [g1_mul(G1_GENERATOR, rng.randrange(1, 1 << 24)) for _ in range(48)]
    holes = [None] * 48
    c, q, levels = 8, 4, 8

    def check(arm):
        table, t52, p52 = _build_tables(_mont_bases(pts), c, q, levels)
        out = _fixed(table, p52, [[0] * 48], 48, c, q, levels)
        assert not out.any(), arm
        table, t52, p52 = _build_tables(_mont_bases(holes), c, q, levels)
        out = _fixed(table, p52, [[rng.randrange(R) for _ in range(48)]] * 2, 48, c, q, levels)
        assert not out.any(), arm

    both_arms(check)


def test_fixed_vs_host_oracle():
    """Ground truth: the pure-python host curve, small scalars."""
    n = 64
    pts = [g1_mul(G1_GENERATOR, rng.randrange(1, 1 << 22)) for _ in range(n)]
    pts[5] = None
    scalars = [rng.randrange(1 << 18) for _ in range(n)]
    want = g1_msm(pts, scalars)
    bm = _mont_bases(pts)
    c, q, levels = 6, 7, 7
    table, t52, p52 = _build_tables(bm, c, q, levels)
    out = _fixed(table, p52, [scalars], n, c, q, levels)
    x = int.from_bytes(out[0, :4].tobytes(), "little")
    y = int.from_bytes(out[0, 4:].tobytes(), "little")
    assert (None if x == 0 and y == 0 else (x, y)) == want


def test_fixed_stats_counters():
    from zkp2p_tpu.native.lib import stats_reset, stats_snapshot

    pts = [g1_mul(G1_GENERATOR, rng.randrange(1, 1 << 24)) for _ in range(64)]
    bm = _mont_bases(pts)
    assert stats_reset()
    table, t52, p52 = _build_tables(bm, 8, 4, 8)
    snap = stats_snapshot()
    assert snap["precomp_build_ns"] > 0
    assert snap["precomp_table_bytes"] == 8 * 64 * 64
    _fixed(table, p52, [[rng.randrange(R) for _ in range(64)]], 64, 8, 4, 8)
    snap = stats_snapshot()
    assert snap["msm_fixed_calls"] == 1
    assert snap["msm_fixed_prep_ns"] > 0


# ------------------------------------------------------------ geometry


def test_geometry_resolution_and_budget():
    from zkp2p_tpu.prover.precomp import _resolve_geometry, fixed_nwin

    for c in range(4, 22):
        W = fixed_nwin(c)
        assert W * c >= 255
        assert (W - 1) * c < 255 or (254 + c - 1) // c == W
    # unconstrained: depth 8 at the bench shape -> c=16, q=2, L=8
    assert _resolve_geometry(1 << 19, 8, 1 << 62) == (16, 2, 8)
    # depth 1 degrades to a single level (q = W)
    c, q, levels = _resolve_geometry(1 << 19, 1, 1 << 62)
    assert levels == 1 and q == fixed_nwin(c)
    # budget squeeze: shallower tables, cover bound levels*q >= W kept
    c, q, levels = _resolve_geometry(1 << 19, 8, 300 << 20)
    assert levels * q >= fixed_nwin(c)
    assert (levels << 19) * 144 <= 300 << 20
    assert levels < 8
    # impossible budget: family skipped
    assert _resolve_geometry(1 << 19, 8, 1 << 20) is None


# ------------------------------------------------- prove-level parity


def _toy_circuit():
    from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem

    cs = ConstraintSystem("precomp-toy")
    out = cs.new_public("out")
    x = cs.new_wire("x")
    y = cs.new_wire("y")
    z = cs.new_wire("z")
    cs.enforce(LC.of(x), LC.of(y), LC.of(z), "mul")
    cs.enforce(LC.of(z), LC.of(z), LC.of(out), "sq")
    cs.compute(z, lambda a, b: a * b % R, [x, y])
    return cs, (out, x, y, z)


@pytest.fixture
def toy_dpk():
    from zkp2p_tpu.prover import device_pk
    from zkp2p_tpu.snark.groth16 import setup

    cs, (out, x, y, z) = _toy_circuit()
    pk, vk = setup(cs)
    return cs, (x, y), device_pk(pk, cs), vk


@pytest.fixture(autouse=True)
def _fresh_precomp(monkeypatch, tmp_path):
    """Every test gets an isolated table cache + cleared memo so proves
    here never litter (or trust) the shared .bench_cache."""
    from zkp2p_tpu.prover import precomp

    monkeypatch.setenv("ZKP2P_MSM_PRECOMP_CACHE", str(tmp_path / "precomp"))
    precomp.reset()
    yield
    precomp.reset()


def test_prove_parity_across_arms(monkeypatch, toy_dpk):
    """Precomp on == off, byte for byte, across {GLV on/off} x {single,
    batch S=3 incl. multi-column} — and the proof verifies."""
    from zkp2p_tpu.prover.native_prove import prove_native, prove_native_batch
    from zkp2p_tpu.snark.groth16 import verify

    cs, (x, y), dpk, vk = toy_dpk
    wits = [
        cs.witness([(3 * 5) ** 2 % R], {x: 3, y: 5}),
        cs.witness([(3 * 10) ** 2 % R], {x: 3, y: 10}),
        cs.witness([(7 * 11) ** 2 % R], {x: 7, y: 11}),
    ]
    rs = [rng.randrange(1, R) for _ in wits]
    ss = [rng.randrange(1, R) for _ in wits]
    for glv in ("0", "1"):
        monkeypatch.setenv("ZKP2P_MSM_GLV", glv)
        monkeypatch.setenv("ZKP2P_MSM_PRECOMP", "0")
        base = [prove_native(dpk, w, r=r, s=s) for w, r, s in zip(wits, rs, ss)]
        monkeypatch.setenv("ZKP2P_MSM_PRECOMP", "1")
        got = [prove_native(dpk, w, r=r, s=s) for w, r, s in zip(wits, rs, ss)]
        assert got == base, f"glv={glv} single"
        assert prove_native_batch(dpk, wits, rs=rs, ss=ss) == base, f"glv={glv} batch"
    assert verify(vk, base[2], [(7 * 11) ** 2 % R])


def test_partial_families_fall_through(monkeypatch, toy_dpk):
    """A families subset (h off the tables) mixes fixed + variable-base
    paths in one prove and still matches the oracle byte-for-byte."""
    from zkp2p_tpu.prover.native_prove import prove_native
    from zkp2p_tpu.prover.precomp import precomputed_for

    cs, (x, y), dpk, _vk = toy_dpk
    w = cs.witness([225], {x: 3, y: 5})
    monkeypatch.setenv("ZKP2P_MSM_PRECOMP", "0")
    want = prove_native(dpk, w, r=11, s=13)
    monkeypatch.setenv("ZKP2P_MSM_PRECOMP", "1")
    monkeypatch.setenv("ZKP2P_MSM_PRECOMP_FAMILIES", "a,c")
    assert prove_native(dpk, w, r=11, s=13) == want
    pk = precomputed_for(dpk)
    assert set(pk.families) == {"a", "c"}
    assert pk.skipped.get("h") == "config" and pk.skipped.get("b1") == "config"


# ----------------------------------------------- cache build + reload
# (the tier-1-resident smoke behind `make precomp-cache`)


def test_cache_roundtrip_and_warm_start(monkeypatch, toy_dpk, tmp_path):
    """build -> persist -> reload -> identical proof; the warm start
    runs ZERO native table builds (precomp_build_ns stat unchanged) and
    reports source=cache in the manifest."""
    from zkp2p_tpu.native.lib import stats_reset, stats_snapshot
    from zkp2p_tpu.prover import precomp
    from zkp2p_tpu.prover.native_prove import prove_native

    cs, (x, y), dpk, _vk = toy_dpk
    w = cs.witness([225], {x: 3, y: 5})
    monkeypatch.setenv("ZKP2P_MSM_PRECOMP_PERSIST_MIN", "1")
    monkeypatch.setenv("ZKP2P_MSM_PRECOMP", "1")
    cold = prove_native(dpk, w, r=5, s=7)
    man = precomp.precomp_manifest()
    assert man and all(f["source"] == "built" for f in man["families"].values())
    cache_dir = os.environ["ZKP2P_MSM_PRECOMP_CACHE"]
    # the cache dir is shared with the matvec segment plans
    # (prover.matvec_plan) — count only the precomp tables here
    files = sorted(f for f in os.listdir(cache_dir) if f.startswith("precomp_g1_") and f.endswith(".npy"))
    assert len(files) == len(man["families"])
    assert man["total_bytes"] > 0

    # warm start: drop the in-RAM memo, prove again — tables must come
    # from disk (source=cache) with no build work in the C runtime
    precomp.reset()
    assert stats_reset()
    warm = prove_native(dpk, w, r=5, s=7)
    assert warm == cold
    snap = stats_snapshot()
    assert snap["precomp_build_ns"] == 0, "warm start re-ran the table build"
    man = precomp.precomp_manifest()
    assert all(f["source"] == "cache" for f in man["families"].values())
    assert sorted(f for f in os.listdir(cache_dir) if f.startswith("precomp_g1_") and f.endswith(".npy")) == files


@pytest.mark.parametrize("level", [0, 1])
def test_stale_cache_rejected(monkeypatch, toy_dpk, level):
    """A corrupt (or foreign-key) cache file fails the integrity check
    and rebuilds instead of proving garbage — whether the flipped bit is
    in the verbatim level 0 or in a HIGHER doubled level (caught by the
    sampled host-curve chain walk); the rebuilt file replaces it and the
    proof stays byte-identical."""
    from zkp2p_tpu.prover import precomp
    from zkp2p_tpu.prover.native_prove import prove_native

    cs, (x, y), dpk, _vk = toy_dpk
    w = cs.witness([225], {x: 3, y: 5})
    monkeypatch.setenv("ZKP2P_MSM_PRECOMP_PERSIST_MIN", "1")
    monkeypatch.setenv("ZKP2P_MSM_PRECOMP", "1")
    cold = prove_native(dpk, w, r=5, s=7)
    man = precomp.precomp_manifest()
    cache_dir = os.environ["ZKP2P_MSM_PRECOMP_CACHE"]
    for name in os.listdir(cache_dir):
        if not name.startswith("precomp_g1_") or not name.endswith(".npy"):
            continue  # matvec segment plans + flock sidecars share this dir
        path = os.path.join(cache_dir, name)
        t = np.load(path)
        fam = name.split("_")[2]
        n = man["families"][fam]["n"]
        t[level * n] ^= np.uint64(0xDEAD)  # flipped bits: torn/rotted file
        with open(path, "wb") as f:
            np.save(f, t)
    precomp.reset()
    assert prove_native(dpk, w, r=5, s=7) == cold
    man = precomp.precomp_manifest()
    assert all(f["source"] == "built" for f in man["families"].values()), (
        "tampered cache was trusted"
    )


def test_key_hash_partitions_cache(monkeypatch, toy_dpk):
    """A different key resolves to different cache files — the key hash
    in the filename IS the invalidation mechanism."""
    from zkp2p_tpu.prover import device_pk, precomp
    from zkp2p_tpu.prover.native_prove import prove_native
    from zkp2p_tpu.snark.groth16 import setup

    cs, (x, y), dpk, _vk = toy_dpk
    w = cs.witness([225], {x: 3, y: 5})
    monkeypatch.setenv("ZKP2P_MSM_PRECOMP_PERSIST_MIN", "1")
    monkeypatch.setenv("ZKP2P_MSM_PRECOMP", "1")
    prove_native(dpk, w, r=5, s=7)
    cache_dir = os.environ["ZKP2P_MSM_PRECOMP_CACHE"]
    first = {f for f in os.listdir(cache_dir) if f.startswith("precomp_g1_") and f.endswith(".npy")}
    # a different setup seed = different toxic waste = different bases
    cs2, (out2, x2, y2, z2) = _toy_circuit()
    pk2, _ = setup(cs2, seed="zkp2p-tpu-dev-precomp-b")
    dpk2 = device_pk(pk2, cs2)
    prove_native(dpk2, cs2.witness([225], {x2: 3, y2: 5}), r=5, s=7)
    second = {f for f in os.listdir(cache_dir) if f.startswith("precomp_g1_") and f.endswith(".npy")}
    assert first < second and len(second) == 2 * len(first)


def test_witness_reduce_native_matches_python():
    """The native fr_reduce_batch path == the
    Python `w % R` loop, including >= r values and the big-int
    fallback for negatives."""
    from zkp2p_tpu.prover.native_prove import _lib, _witness_std_u64

    lib = _lib()
    vals = [0, 1, R - 1, R, R + 12345, 2 * R + 7, (1 << 256) - 1, 5 * R - 1,
            rng.randrange(1 << 256), rng.randrange(R)]
    want = np.ascontiguousarray(_scalars_to_u64([v % R for v in vals]))
    got = _witness_std_u64(lib, vals)
    assert np.array_equal(got, want)
    # negative values take the exact python fallback
    got = _witness_std_u64(lib, [-1, -R, 7])
    want = np.ascontiguousarray(_scalars_to_u64([(-1) % R, (-R) % R, 7]))
    assert np.array_equal(got, want)
