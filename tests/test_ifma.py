"""AVX-512 IFMA fast paths vs the scalar native paths / Python oracle.

The IFMA tier (csrc `mont52_mul8` + `fr_ntt_ifma` + `g1_chunk_apply_ifma`)
is the single-core SIMD counterpart of rapidsnark's x86-64 asm field
layer (SURVEY.md §2.2): 5x52-bit Montgomery limbs (R = 2^260), 8
independent elements per vector, lazy [0,2p) reduction.  Every test here
is a differential against either Python bignums or the scalar CIOS
path, which the r4 suite already pins to the host oracle.

Skips cleanly when the native lib or the IFMA instructions are absent —
the scalar paths remain the covenant.
"""

import ctypes
import random

import numpy as np
import pytest

from zkp2p_tpu.field.bn254 import R
from zkp2p_tpu.native import lib as native
from zkp2p_tpu.prover import native_prove as npv

rng = random.Random(77)

_lib = npv._lib()
pytestmark = pytest.mark.skipif(
    _lib is None or not _lib.zkp2p_ifma_available(),
    reason="native lib or AVX-512 IFMA unavailable",
)


def _setup():
    lib = npv._lib()
    lib.fr52_mul_std_batch.argtypes = [npv._u64p, npv._u64p, npv._u64p, ctypes.c_long]
    lib.fr_ntt_ifma.argtypes = [npv._u64p, ctypes.c_long, npv._u64p, npv._u64p]
    return lib


def test_mont52_kernel_differential():
    """8-wide kernel vs Python bignum, adversarial operands included."""
    lib = _setup()
    special = [0, 1, 2, R - 1, R - 2, (1 << 52) - 1, 1 << 52, 1 << 208, R >> 1]
    va = special + [rng.randrange(R) for _ in range(119)]
    vb = list(reversed(special)) + [rng.randrange(R) for _ in range(119)]
    n = len(va)
    a = npv._scalars_to_u64(va).copy()
    b = npv._scalars_to_u64(vb).copy()
    c = np.zeros((n, 4), dtype=np.uint64)
    lib.fr52_mul_std_batch(npv._p(a), npv._p(b), npv._p(c), n)
    for i in range(n):
        assert int.from_bytes(c[i].tobytes(), "little") == va[i] * vb[i] % R, i


def test_ntt_ifma_matches_scalar():
    """fr_ntt_ifma must be byte-identical to fr_ntt (vector stages +
    scalar len<16 stages + scale path)."""
    lib = _setup()
    for k in (6, 9, 12):
        m = 1 << k
        root = pow(7, (R - 1) // m, R)
        vals = [rng.randrange(R) for _ in range(m)]
        d1 = npv._scalars_to_u64(vals).copy()
        d2 = d1.copy()
        rv = npv._scalars_to_u64([root]).copy()
        sc = npv._scalars_to_u64([98765]).copy()
        lib.fr_ntt(npv._p(d1), m, npv._p(rv), npv._p(sc))
        lib.fr_ntt_ifma(npv._p(d2), m, npv._p(rv), npv._p(sc))
        assert np.array_equal(d1, d2), f"m={m}"


def test_msm_ifma_matches_scalar_env_toggle():
    """g1_msm_pippenger with the IFMA chunk apply vs ZKP2P_NATIVE_IFMA=0
    scalar run in a subprocess (the env is latched at first use, so the
    scalar reference must be a fresh process)."""
    import json
    import os
    import subprocess
    import sys
    import tempfile

    lib = _setup()
    n = 1 << 12
    ks = [rng.randrange(R) for _ in range(n)]
    from zkp2p_tpu.curve.host import G1_GENERATOR

    pts = native.g1_fixed_base_batch(G1_GENERATOR, ks)
    scs = [rng.randrange(R) for _ in range(n)]
    bases = np.zeros((n, 8), dtype=np.uint64)
    for i, p in enumerate(pts):
        if p is None:
            continue
        bases[i, :4] = np.frombuffer(p[0].to_bytes(32, "little"), dtype=np.uint64)
        bases[i, 4:] = np.frombuffer(p[1].to_bytes(32, "little"), dtype=np.uint64)
    bm = np.zeros_like(bases)
    lib.fp_to_mont(bases.ctypes.data_as(npv._u64p), bm.ctypes.data_as(npv._u64p), 2 * n)
    sc = npv._scalars_to_u64(scs).copy()
    out = np.zeros((3, 4), dtype=np.uint64)
    lib.g1_msm_pippenger(bm.ctypes.data_as(npv._u64p), npv._p(sc), n, 13, npv._p(out))

    with tempfile.TemporaryDirectory() as td:
        np.save(os.path.join(td, "b.npy"), bm)
        np.save(os.path.join(td, "s.npy"), sc)
        code = (
            "import sys, numpy as np, json;"
            f"sys.path.insert(0, {str(npv.__file__.rsplit('/zkp2p_tpu', 1)[0])!r});"
            "from zkp2p_tpu.prover import native_prove as npv;"
            "lib = npv._lib();"
            f"bm = np.load({os.path.join(td, 'b.npy')!r}); sc = np.load({os.path.join(td, 's.npy')!r});"
            "out = np.zeros((3, 4), dtype=np.uint64);"
            "lib.g1_msm_pippenger(bm.ctypes.data_as(npv._u64p), npv._p(sc), bm.shape[0], 13, npv._p(out));"
            "print(json.dumps(out.tolist()))"
        )
        env = dict(os.environ, ZKP2P_NATIVE_IFMA="0", JAX_PLATFORMS="cpu")
        ref = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=300)
        assert ref.returncode == 0, ref.stderr[-800:]
        want = np.array(json.loads(ref.stdout.strip().splitlines()[-1]), dtype=np.uint64)
    assert np.array_equal(out, want)


def test_msm_ifma_exceptional_lanes():
    """Doubling lanes (same point scheduled into a bucket that already
    holds it), +/- cancellation (P then -P in one bucket) and installs
    must all survive the VECTOR path.  Scalars stay below 2^13 with
    c=13 so everything lands in one full-width window (vector-eligible:
    2^13 >= 4B), and duplicates are kept under the bail threshold."""
    lib = _setup()
    from zkp2p_tpu.curve.host import G1_GENERATOR, g1_add, g1_mul, g1_neg

    n = 4096
    ks = [rng.randrange(1, R) for _ in range(n)]
    uniq = native.g1_fixed_base_batch(G1_GENERATOR, ks)
    base_pts = list(uniq)
    scs = [rng.randrange(1, 1 << 12) for _ in range(n)]
    # 128 doubling pairs: same point, same scalar -> same bucket twice
    for j in range(128):
        base_pts[2 * j + 1] = base_pts[2 * j]
        scs[2 * j + 1] = scs[2 * j]
    # 64 cancellation pairs: same point, negated digit (d and 2^13-... use
    # s and -s mod R: digit -s hits bucket s with negated y)
    for j in range(64):
        i1, i2 = 1024 + 2 * j, 1024 + 2 * j + 1
        base_pts[i2] = base_pts[i1]
        scs[i2] = R - scs[i1]
    bases = np.zeros((n, 8), dtype=np.uint64)
    for i, p in enumerate(base_pts):
        bases[i, :4] = np.frombuffer(p[0].to_bytes(32, "little"), dtype=np.uint64)
        bases[i, 4:] = np.frombuffer(p[1].to_bytes(32, "little"), dtype=np.uint64)
    bm = np.zeros_like(bases)
    lib.fp_to_mont(bases.ctypes.data_as(npv._u64p), bm.ctypes.data_as(npv._u64p), 2 * n)
    sc = npv._scalars_to_u64(scs).copy()
    # out: affine STANDARD form (x, y), all-zero = infinity
    out = np.zeros((2, 4), dtype=np.uint64)
    lib.g1_msm_pippenger(bm.ctypes.data_as(npv._u64p), npv._p(sc), n, 13, npv._p(out))
    ax, ay = native._u64x4_to_int(out[0]), native._u64x4_to_int(out[1])
    want = None
    for p, s in zip(base_pts, scs):
        want = g1_add(want, g1_mul(p, s))
    got = None if ax == 0 and ay == 0 else (ax, ay)
    assert got == want


def test_msm_bit_scalar_fast_path():
    """The witness-MSM shape: ~90% scalars in {0, 1, r-1} (bit wires and
    negated bits) + a few wide ones.  The classifier must route the
    ones through the vectorized tree sum and still match the oracle."""
    lib = _setup()
    from zkp2p_tpu.curve.host import G1_GENERATOR, g1_add, g1_mul

    n = 2048
    ks = [rng.randrange(1, R) for _ in range(n)]
    pts = native.g1_fixed_base_batch(G1_GENERATOR, ks)
    scs = []
    for i in range(n):
        r_ = i % 10
        if r_ < 4:
            scs.append(1)
        elif r_ < 6:
            scs.append(0)
        elif r_ < 8:
            scs.append(R - 1)
        else:
            scs.append(rng.randrange(2, R - 1))
    # holes survive the ones path too
    pts[7] = None
    pts[17] = None
    bases = np.zeros((n, 8), dtype=np.uint64)
    for i, p in enumerate(pts):
        if p is None:
            continue
        bases[i, :4] = np.frombuffer(p[0].to_bytes(32, "little"), dtype=np.uint64)
        bases[i, 4:] = np.frombuffer(p[1].to_bytes(32, "little"), dtype=np.uint64)
    bm = np.zeros_like(bases)
    lib.fp_to_mont(bases.ctypes.data_as(npv._u64p), bm.ctypes.data_as(npv._u64p), 2 * n)
    sc = npv._scalars_to_u64(scs).copy()
    out = np.zeros((2, 4), dtype=np.uint64)
    lib.g1_msm_pippenger(bm.ctypes.data_as(npv._u64p), npv._p(sc), n, 13, npv._p(out))
    ax, ay = native._u64x4_to_int(out[0]), native._u64x4_to_int(out[1])
    want = None
    for p, s in zip(pts, scs):
        if p is None or s == 0:
            continue
        want = g1_add(want, g1_mul(p, s))
    got = None if ax == 0 and ay == 0 else (ax, ay)
    assert got == want


def test_msm_all_ones_duplicate_points():
    """Pure sum with duplicated points: every tree level hits doubling
    lanes; must still match the oracle."""
    lib = _setup()
    from zkp2p_tpu.curve.host import G1_GENERATOR, g1_add, g1_mul

    n = 512
    base = g1_mul(G1_GENERATOR, 11)
    pts = [base] * (n // 2) + [g1_mul(G1_GENERATOR, 13)] * (n // 2)
    scs = [1] * n
    bases = np.zeros((n, 8), dtype=np.uint64)
    for i, p in enumerate(pts):
        bases[i, :4] = np.frombuffer(p[0].to_bytes(32, "little"), dtype=np.uint64)
        bases[i, 4:] = np.frombuffer(p[1].to_bytes(32, "little"), dtype=np.uint64)
    bm = np.zeros_like(bases)
    lib.fp_to_mont(bases.ctypes.data_as(npv._u64p), bm.ctypes.data_as(npv._u64p), 2 * n)
    sc = npv._scalars_to_u64(scs).copy()
    out = np.zeros((2, 4), dtype=np.uint64)
    lib.g1_msm_pippenger(bm.ctypes.data_as(npv._u64p), npv._p(sc), n, 13, npv._p(out))
    ax, ay = native._u64x4_to_int(out[0]), native._u64x4_to_int(out[1])
    want = None
    for p in pts:
        want = g1_add(want, p)
    assert (ax, ay) == want


def test_msm_ones_cancel_to_infinity():
    """P with scalar 1 and the same P with scalar r-1 cancel: the tree
    must emit infinity, encoded (0,0)."""
    lib = _setup()
    from zkp2p_tpu.curve.host import G1_GENERATOR, g1_mul

    pts = [g1_mul(G1_GENERATOR, 5)] * 2 + [g1_mul(G1_GENERATOR, 9)] * 2
    scs = [1, R - 1, 1, R - 1]
    n = 4
    bases = np.zeros((n, 8), dtype=np.uint64)
    for i, p in enumerate(pts):
        bases[i, :4] = np.frombuffer(p[0].to_bytes(32, "little"), dtype=np.uint64)
        bases[i, 4:] = np.frombuffer(p[1].to_bytes(32, "little"), dtype=np.uint64)
    bm = np.zeros_like(bases)
    lib.fp_to_mont(bases.ctypes.data_as(npv._u64p), bm.ctypes.data_as(npv._u64p), 2 * n)
    sc = npv._scalars_to_u64(scs).copy()
    out = np.ones((2, 4), dtype=np.uint64)
    lib.g1_msm_pippenger(bm.ctypes.data_as(npv._u64p), npv._p(sc), n, 13, npv._p(out))
    assert not out.any()


def test_g2_msm_bit_scalar_fast_path():
    """G2 mirror: ones/negated-ones through the Fq2 tree sum (with
    duplicates forcing doubling lanes), rest through Pippenger."""
    lib = _setup()
    from zkp2p_tpu.curve.host import G2_GENERATOR, g2_add, g2_mul
    from zkp2p_tpu.field.tower import Fq2

    n = 512
    pts = [g2_mul(G2_GENERATOR, 3 + (i % 37)) for i in range(n)]  # dups -> doublings
    scs = []
    for i in range(n):
        r_ = i % 8
        scs.append(1 if r_ < 3 else (R - 1 if r_ < 5 else (0 if r_ < 6 else rng.randrange(2, R - 1))))
    bases = np.zeros((n, 16), dtype=np.uint64)
    for i, p in enumerate(pts):
        x, y = p
        for j, v in enumerate((x.c0, x.c1, y.c0, y.c1)):
            bases[i, 4 * j : 4 * j + 4] = np.frombuffer(v.to_bytes(32, "little"), dtype=np.uint64)
    bm = np.zeros_like(bases)
    lib.fp_to_mont(bases.ctypes.data_as(npv._u64p), bm.ctypes.data_as(npv._u64p), 4 * n)
    sc = npv._scalars_to_u64(scs).copy()
    out = np.zeros(16, dtype=np.uint64)
    lib.g2_msm_pippenger(bm.ctypes.data_as(npv._u64p), npv._p(sc), n, 8, npv._p(out))
    xc0, xc1, yc0, yc1 = (native._u64x4_to_int(out[4 * j : 4 * j + 4]) for j in range(4))
    got = None if xc0 == xc1 == yc0 == yc1 == 0 else (Fq2(xc0, xc1), Fq2(yc0, yc1))
    want = None
    for p, s in zip(pts, scs):
        if s == 0:
            continue
        want = g2_add(want, g2_mul(p, s))
    assert got == want


def test_g2_msm_affine_fill_matches_scalar():
    """The batch-affine G2 window fill (c>=13 engages it) vs the
    Jacobian path in a ZKP2P_NATIVE_IFMA=0 subprocess."""
    import json
    import os
    import subprocess
    import sys
    import tempfile

    lib = _setup()
    from zkp2p_tpu.curve.host import G2_GENERATOR, g2_mul

    n = 1 << 12
    pts = [g2_mul(G2_GENERATOR, 3 + i) for i in range(64)] * (n // 64)
    scs = [rng.randrange(2, R - 1) for _ in range(n)]
    bases = np.zeros((n, 16), dtype=np.uint64)
    for i, p in enumerate(pts):
        x, y = p
        for j, v in enumerate((x.c0, x.c1, y.c0, y.c1)):
            bases[i, 4 * j : 4 * j + 4] = np.frombuffer(v.to_bytes(32, "little"), dtype=np.uint64)
    bm = np.zeros_like(bases)
    lib.fp_to_mont(bases.ctypes.data_as(npv._u64p), bm.ctypes.data_as(npv._u64p), 4 * n)
    sc = npv._scalars_to_u64(scs).copy()
    out = np.zeros(16, dtype=np.uint64)
    lib.g2_msm_pippenger(bm.ctypes.data_as(npv._u64p), npv._p(sc), n, 13, npv._p(out))

    with tempfile.TemporaryDirectory() as td:
        np.save(os.path.join(td, "b.npy"), bm)
        np.save(os.path.join(td, "s.npy"), sc)
        code = (
            "import sys, numpy as np, json;"
            f"sys.path.insert(0, {str(npv.__file__.rsplit('/zkp2p_tpu', 1)[0])!r});"
            "from zkp2p_tpu.prover import native_prove as npv;"
            "lib = npv._lib();"
            f"bm = np.load({os.path.join(td, 'b.npy')!r}); sc = np.load({os.path.join(td, 's.npy')!r});"
            "out = np.zeros(16, dtype=np.uint64);"
            "lib.g2_msm_pippenger(bm.ctypes.data_as(npv._u64p), npv._p(sc), bm.shape[0], 13, npv._p(out));"
            "print(json.dumps(out.tolist()))"
        )
        env = dict(os.environ, ZKP2P_NATIVE_IFMA="0", JAX_PLATFORMS="cpu")
        ref = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=600)
        assert ref.returncode == 0, ref.stderr[-800:]
        want = np.array(json.loads(ref.stdout.strip().splitlines()[-1]), dtype=np.uint64)
    assert np.array_equal(out, want)


def test_g2_msm_affine_bail_path_matches_scalar():
    """Constant non-±1 scalars pile every point into ONE bucket per
    window: the affine fill defers nearly the whole chunk and must BAIL
    to the mixed-Jacobian merge — diffed against the pure-Jacobian
    subprocess reference."""
    import json
    import os
    import subprocess
    import sys
    import tempfile

    lib = _setup()
    from zkp2p_tpu.curve.host import G2_GENERATOR, g2_mul

    n = 1 << 12
    pts = [g2_mul(G2_GENERATOR, 5 + i) for i in range(128)] * (n // 128)
    scs = [12345] * n  # constant wire: every digit identical
    bases = np.zeros((n, 16), dtype=np.uint64)
    for i, p in enumerate(pts):
        x, y = p
        for j, v in enumerate((x.c0, x.c1, y.c0, y.c1)):
            bases[i, 4 * j : 4 * j + 4] = np.frombuffer(v.to_bytes(32, "little"), dtype=np.uint64)
    bm = np.zeros_like(bases)
    lib.fp_to_mont(bases.ctypes.data_as(npv._u64p), bm.ctypes.data_as(npv._u64p), 4 * n)
    sc = npv._scalars_to_u64(scs).copy()
    out = np.zeros(16, dtype=np.uint64)
    lib.g2_msm_pippenger(bm.ctypes.data_as(npv._u64p), npv._p(sc), n, 13, npv._p(out))

    with tempfile.TemporaryDirectory() as td:
        np.save(os.path.join(td, "b.npy"), bm)
        np.save(os.path.join(td, "s.npy"), sc)
        code = (
            "import sys, numpy as np, json;"
            f"sys.path.insert(0, {str(npv.__file__.rsplit('/zkp2p_tpu', 1)[0])!r});"
            "from zkp2p_tpu.prover import native_prove as npv;"
            "lib = npv._lib();"
            f"bm = np.load({os.path.join(td, 'b.npy')!r}); sc = np.load({os.path.join(td, 's.npy')!r});"
            "out = np.zeros(16, dtype=np.uint64);"
            "lib.g2_msm_pippenger(bm.ctypes.data_as(npv._u64p), npv._p(sc), bm.shape[0], 13, npv._p(out));"
            "print(json.dumps(out.tolist()))"
        )
        env = dict(os.environ, ZKP2P_NATIVE_IFMA="0", JAX_PLATFORMS="cpu")
        ref = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=600)
        assert ref.returncode == 0, ref.stderr[-800:]
        want = np.array(json.loads(ref.stdout.strip().splitlines()[-1]), dtype=np.uint64)
    assert np.array_equal(out, want)


def test_msm_suffix_vector_exceptional_lanes():
    """Exceptional cases INSIDE the 8-lane vector suffix walk (not the
    fill): run == bucket forces the doubling patch (scalar 5 and 6 on
    the SAME point -> run = P after bucket 6, then P + P at bucket 5),
    and run == -bucket forces the infinity transition (P at 6, -P at 5
    via the negated-digit encoding).  Scalars < 2^12 keep every higher
    window empty, so the walk's state is exactly these lanes."""
    lib = _setup()
    from zkp2p_tpu.curve.host import G1_GENERATOR, g1_add, g1_mul

    cases = []
    # doubling inside the suffix: same point in buckets 5 and 6
    P = g1_mul(G1_GENERATOR, 11)
    cases.append(([P, P], [5, 6]))
    # cancellation to infinity mid-walk, then a later bucket revives run
    Q = g1_mul(G1_GENERATOR, 23)
    cases.append(([Q, Q, g1_mul(G1_GENERATOR, 7)], [6, R - 6, 3]))
    # wsum-side equality: buckets arranged so wsum == run at some step
    cases.append(([P, P, P], [2, 1, 3]))
    for base_pts, scs in cases:
        n = len(base_pts)
        bases = np.zeros((n, 8), dtype=np.uint64)
        for i, pt in enumerate(base_pts):
            bases[i, :4] = np.frombuffer(pt[0].to_bytes(32, "little"), dtype=np.uint64)
            bases[i, 4:] = np.frombuffer(pt[1].to_bytes(32, "little"), dtype=np.uint64)
        bm = np.zeros_like(bases)
        lib.fp_to_mont(bases.ctypes.data_as(npv._u64p), bm.ctypes.data_as(npv._u64p), 2 * n)
        sc = npv._scalars_to_u64(scs).copy()
        out = np.zeros((2, 4), dtype=np.uint64)
        lib.g1_msm_pippenger(bm.ctypes.data_as(npv._u64p), npv._p(sc), n, 13, npv._p(out))
        ax, ay = native._u64x4_to_int(out[0]), native._u64x4_to_int(out[1])
        want = None
        for pt, s in zip(base_pts, scs):
            want = g1_add(want, g1_mul(pt, s % R))
        got = None if ax == 0 and ay == 0 else (ax, ay)
        assert got == want, (scs, got, want)
