"""ASan/UBSan smoke of the native MSM tiers (`make native-asan`).

Builds the sanitizer-instrumented library (csrc libzkp2p_native_asan.so)
and runs a small-but-representative G1 MSM parity check against the host
oracle INSIDE it: enough points and window width to drive the
batch-affine bucket fill (its shared-inversion scratch buffers are the
new-code risk this guards), the Jacobian A/B arm, the GLV driver, and
the persistent worker pool, the service's sample verify
(`groth16_verify_bn254`: the Fq12 tower and the Miller loops) and a
proof's assembly (`groth16_assemble_bn254`) — all
under `-fno-sanitize-recover`, so any ASan/UBSan report aborts the
subprocess and fails the test.

The python interpreter is NOT instrumented, so the library must be
loaded with libasan LD_PRELOADed — hence the subprocess (slow tier; run
via `make native-asan` or ZKP2P_RUN_SLOW=1).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ASAN_SO = os.path.join(REPO, "csrc", "libzkp2p_native_asan.so")

# The check script runs in a fresh interpreter with libasan preloaded.
# It computes the oracle with the pure-python host curve and diffs the
# instrumented library's MSM output bit-for-bit, covering: the
# batch-affine fill (c=14 => the affine tier engages even at small n),
# the jac arm (ZKP2P_MSM_BATCH_AFFINE=0), GLV, threads via the pool, and
# the edge scalars 0 / 1 / r-1.
_CHECK = r"""
import ctypes, os, random, sys
sys.path.insert(0, os.environ["ZKP2P_REPO"])
import numpy as np
from zkp2p_tpu.curve.host import G1_GENERATOR, g1_msm, g1_mul
from zkp2p_tpu.field.bn254 import GLV_MAX_BITS, R
from zkp2p_tpu.native.lib import _pack_affine, _scalars_to_u64

lib = ctypes.CDLL(os.environ["ZKP2P_ASAN_SO"])
u64p = ctypes.POINTER(ctypes.c_uint64)
lib.fp_to_mont.argtypes = [u64p, u64p, ctypes.c_int]
lib.g1_msm_pippenger_mt.argtypes = [u64p, u64p, ctypes.c_long, ctypes.c_int, ctypes.c_int, u64p]
lib.g1_glv_phi_bases.argtypes = [u64p, ctypes.c_long, u64p, u64p]
lib.g1_msm_pippenger_glv_mt.argtypes = [
    u64p, u64p, ctypes.c_long, ctypes.c_long, ctypes.c_int, ctypes.c_int,
    u64p, ctypes.c_int, u64p,
]

rng = random.Random(5)
n = 300
pts = [g1_mul(G1_GENERATOR, rng.randrange(1, R)) for _ in range(n)]
pts[7] = None  # infinity hole
scalars = [rng.randrange(R) for _ in range(n)]
scalars[0] = 0
scalars[1] = 1
scalars[2] = R - 1
# duplicate point+scalar pairs: same-bucket P+P / P+(-P) shapes
pts[10] = pts[11]
scalars[10] = scalars[11]
pts[12] = pts[13]
scalars[13] = R - scalars[12]

want = g1_msm(pts, scalars)
bases = _pack_affine(pts)
bm = np.zeros_like(bases)
lib.fp_to_mont(bases.ctypes.data_as(u64p), bm.ctypes.data_as(u64p), 2 * n)
sc = np.ascontiguousarray(_scalars_to_u64(scalars))

def check(tag, got):
    x = int.from_bytes(got[:4].tobytes(), "little")
    y = int.from_bytes(got[4:].tobytes(), "little")
    g = None if x == 0 and y == 0 else (x, y)
    assert g == want, tag
    print("ok", tag, flush=True)

for ba in ("1", "0"):
    os.environ["ZKP2P_MSM_BATCH_AFFINE"] = ba  # fresh-read per MSM in csrc
    for c, threads in ((8, 1), (14, 1), (14, 2)):
        out = np.zeros(8, dtype=np.uint64)
        lib.g1_msm_pippenger_mt(
            bm.ctypes.data_as(u64p), sc.ctypes.data_as(u64p), n, c, threads,
            out.ctypes.data_as(u64p))
        check(f"plain ba={ba} c={c} t={threads}", out)

# GLV x batch-affine composed.  The consts are packed inline from the
# pure-python field.bn254 constants (same layout as native_prove's
# _glv_consts) — importing the prover module would pull in jaxlib, whose
# pybind exception machinery trips ASan's interceptors under LD_PRELOAD.
from zkp2p_tpu.field.bn254 import GLV_BETA, GLV_K1_TERMS, GLV_K2_TERMS, GLV_MU1, GLV_MU2, P, to_mont
mask = (1 << 64) - 1
u64x4 = lambda v: [(v >> (64 * i)) & mask for i in range(4)]
flags, mags = 0, []
for j, (mag, sub) in enumerate(GLV_K1_TERMS):
    mags += u64x4(mag); flags |= int(sub) << j
for j, (mag, sub) in enumerate(GLV_K2_TERMS):
    mags += u64x4(mag); flags |= int(sub) << (2 + j)
gc = np.ascontiguousarray(np.array(
    u64x4(to_mont(GLV_BETA, P)) + u64x4(GLV_MU1) + u64x4(GLV_MU2) + mags + [flags],
    dtype=np.uint64))
phi = np.zeros_like(bm)
lib.g1_glv_phi_bases(bm.ctypes.data_as(u64p), n, gc.ctypes.data_as(u64p),
                     phi.ctypes.data_as(u64p))
b2 = np.ascontiguousarray(np.concatenate([bm, phi]))
for ba in ("1", "0"):
    os.environ["ZKP2P_MSM_BATCH_AFFINE"] = ba
    out = np.zeros(8, dtype=np.uint64)
    lib.g1_msm_pippenger_glv_mt(
        b2.ctypes.data_as(u64p), sc.ctypes.data_as(u64p), n, n, 14, 2,
        gc.ctypes.data_as(u64p), GLV_MAX_BITS, out.ctypes.data_as(u64p))
    check(f"glv ba={ba}", out)

# multi-column drivers (plain + GLV): 3 scalar columns — the original
# vector, an all-zero column, and a shuffled-support column — over the
# same base set; every column diffed against its own host-oracle MSM.
# The S-wide bucket/stamp blocks, the shared-chunk inversion scratch,
# and the lane-encoded defer lists are the new-allocation risk here.
lib.g1_msm_pippenger_multi.argtypes = [
    u64p, u64p, ctypes.c_long, ctypes.c_int, ctypes.c_int, ctypes.c_int, u64p,
]
lib.g1_msm_pippenger_glv_multi.argtypes = [
    u64p, u64p, ctypes.c_long, ctypes.c_long, ctypes.c_int, ctypes.c_int,
    ctypes.c_int, u64p, ctypes.c_int, u64p,
]
cols = [scalars, [0] * n, list(reversed(scalars))]
cols[2][5] = 0
cols[2][6] = 1
wants = [g1_msm(pts, col) for col in cols]
scm = np.ascontiguousarray(np.stack([_scalars_to_u64(col) for col in cols]))

def check_multi(tag, got):
    for s in range(3):
        x = int.from_bytes(got[s, :4].tobytes(), "little")
        y = int.from_bytes(got[s, 4:].tobytes(), "little")
        g = None if x == 0 and y == 0 else (x, y)
        assert g == wants[s], (tag, s)
    print("ok", tag, flush=True)

for ba in ("1", "0"):
    os.environ["ZKP2P_MSM_BATCH_AFFINE"] = ba
    for c, threads in ((14, 1), (14, 2)):
        outm = np.zeros((3, 8), dtype=np.uint64)
        lib.g1_msm_pippenger_multi(
            bm.ctypes.data_as(u64p), scm.ctypes.data_as(u64p), n, 3, c, threads,
            outm.ctypes.data_as(u64p))
        check_multi(f"multi ba={ba} c={c} t={threads}", outm)
    outm = np.zeros((3, 8), dtype=np.uint64)
    lib.g1_msm_pippenger_glv_multi(
        b2.ctypes.data_as(u64p), scm.ctypes.data_as(u64p), n, n, 3, 14, 2,
        gc.ctypes.data_as(u64p), GLV_MAX_BITS, outm.ctypes.data_as(u64p))
    check_multi(f"glv multi ba={ba}", outm)

# fixed-base precomputed-table tier: build the level tables (the
# Jacobian doubling chains + batched normalization are fresh allocation
# surface), convert to the 52-limb form, and run the fixed single- and
# multi-column drivers — each diffed against the same host oracles.
# Covers both batch-affine arms and the scalar (p52=NULL) read path.
lib.g1_precomp_build.argtypes = [u64p, ctypes.c_long, ctypes.c_int, ctypes.c_int,
                                 ctypes.c_int, ctypes.c_int, u64p]
lib.g1_precomp_to52.argtypes = [u64p, ctypes.c_long, u64p]
lib.g1_precomp_to52.restype = ctypes.c_int
lib.g1_msm_pippenger_fixed.argtypes = [u64p, u64p, u64p, ctypes.c_long, ctypes.c_long,
                                       ctypes.c_int, ctypes.c_int, ctypes.c_int,
                                       ctypes.c_int, u64p]
lib.g1_msm_pippenger_fixed_multi.argtypes = [u64p, u64p, u64p, ctypes.c_long,
                                             ctypes.c_long, ctypes.c_int, ctypes.c_int,
                                             ctypes.c_int, ctypes.c_int, ctypes.c_int, u64p]
cq, qq, Lq = 8, 4, 8
table = np.zeros((Lq * n, 8), dtype=np.uint64)
lib.g1_precomp_build(bm.ctypes.data_as(u64p), n, cq, qq, Lq, 2,
                     table.ctypes.data_as(u64p))
t52 = np.zeros((Lq * n, 10), dtype=np.uint64)
has52 = lib.g1_precomp_to52(table.ctypes.data_as(u64p), Lq * n, t52.ctypes.data_as(u64p))
for ba in ("1", "0"):
    os.environ["ZKP2P_MSM_BATCH_AFFINE"] = ba
    for threads in (1, 2):
        out = np.zeros(8, dtype=np.uint64)
        lib.g1_msm_pippenger_fixed(
            table.ctypes.data_as(u64p), t52.ctypes.data_as(u64p) if has52 else None,
            sc.ctypes.data_as(u64p), n, n, Lq, cq, qq, threads, out.ctypes.data_as(u64p))
        check(f"fixed ba={ba} t={threads}", out)
    outm = np.zeros((3, 8), dtype=np.uint64)
    lib.g1_msm_pippenger_fixed_multi(
        table.ctypes.data_as(u64p), t52.ctypes.data_as(u64p) if has52 else None,
        scm.ctypes.data_as(u64p), n, n, 3, Lq, cq, qq, 2, outm.ctypes.data_as(u64p))
    check_multi(f"fixed multi ba={ba}", outm)
    # scalar read path (no 52-limb table)
    out = np.zeros(8, dtype=np.uint64)
    lib.g1_msm_pippenger_fixed(
        table.ctypes.data_as(u64p), None, sc.ctypes.data_as(u64p), n, n, Lq, cq, qq, 1,
        out.ctypes.data_as(u64p))
    check(f"fixed no52 ba={ba}", out)

# non-MSM kernels (segmented matvec + pooled/fused NTT ladder): the
# per-chunk product-slice scratch, the mont260 plan pack, the SoA stage
# planes, and the gpow260 table are the new-allocation surface.  Parity
# vs fr_matvec / the knob-off ladder arm inside the instrumented lib.
import hashlib
u32p = ctypes.POINTER(ctypes.c_uint32)
i64p = ctypes.POINTER(ctypes.c_longlong)
lib.fr_to_mont_batch.argtypes = [u64p, u64p, ctypes.c_long]
lib.fr_matvec.argtypes = [u64p, u32p, u32p, ctypes.c_long, u64p, ctypes.c_long, u64p]
lib.fr_matvec_pack52.argtypes = [u64p, ctypes.c_long, u64p]
lib.fr_matvec_pack52.restype = ctypes.c_int
lib.fr_matvec_seg.argtypes = [u64p, u64p, u32p, i64p, u32p, ctypes.c_long,
                              u64p, ctypes.c_long, ctypes.c_int, u64p]
lib.fr_h_ladder.argtypes = [u64p, u64p, u64p, ctypes.c_long, u64p, u64p, u64p]
m_mv, nw, nnz = 128, 90, 700
w_std = _scalars_to_u64([rng.randrange(R) for _ in range(nw)]).copy()
w_m = np.zeros_like(w_std)
lib.fr_to_mont_batch(w_std.ctypes.data_as(u64p), w_m.ctypes.data_as(u64p), nw)
cf_std = _scalars_to_u64([rng.randrange(R) for _ in range(nnz)]).copy()
cf = np.zeros_like(cf_std)
lib.fr_to_mont_batch(cf_std.ctypes.data_as(u64p), cf.ctypes.data_as(u64p), nnz)
wires = np.array([rng.randrange(nw) for _ in range(nnz)], dtype=np.uint32)
rows = np.array([rng.randrange(m_mv) for _ in range(nnz)], dtype=np.uint32)
rows[:150] = 9  # hot segment crossing the product-slice boundary shape
mv_want = np.zeros((m_mv, 4), dtype=np.uint64)
lib.fr_matvec(cf.ctypes.data_as(u64p), wires.ctypes.data_as(u32p),
              rows.ctypes.data_as(u32p), nnz, w_m.ctypes.data_as(u64p), m_mv,
              mv_want.ctypes.data_as(u64p))
perm = np.argsort(rows, kind="stable")
rsort = rows[perm]
cp = np.ascontiguousarray(cf[perm]); wp = np.ascontiguousarray(wires[perm])
bnd = np.flatnonzero(np.diff(rsort)) + 1
seg_starts = np.ascontiguousarray(np.concatenate([[0], bnd, [nnz]]).astype(np.int64))
seg_rows = np.ascontiguousarray(rsort[seg_starts[:-1]].astype(np.uint32))
c52 = np.zeros(((nnz + 7) // 8) * 40, dtype=np.uint64)
mv52 = lib.fr_matvec_pack52(cp.ctypes.data_as(u64p), nnz, c52.ctypes.data_as(u64p))
for threads in (1, 2):
    for p52 in ([c52.ctypes.data_as(u64p), None] if mv52 else [None]):
        got = np.zeros((m_mv, 4), dtype=np.uint64)
        lib.fr_matvec_seg(p52, cp.ctypes.data_as(u64p), wp.ctypes.data_as(u32p),
                          seg_starts.ctypes.data_as(i64p), seg_rows.ctypes.data_as(u32p),
                          len(seg_rows), w_m.ctypes.data_as(u64p), m_mv, threads,
                          got.ctypes.data_as(u64p))
        assert np.array_equal(got, mv_want), ("matvec_seg", threads, p52 is not None)
print("ok matvec_seg", flush=True)

from zkp2p_tpu.field.bn254 import fr_domain_root
from zkp2p_tpu.snark.groth16 import coset_gen
log_lm = 7; M = 1 << log_lm
wroot = _scalars_to_u64([fr_domain_root(log_lm)]).copy()
gcosv = _scalars_to_u64([coset_gen(log_lm)]).copy()
abc0 = _scalars_to_u64([rng.randrange(R) for _ in range(3 * M)]).reshape(3, M, 4).copy()
lad = {}
for knob in ("1", "0"):
    os.environ["ZKP2P_NTT_POOL"] = knob
    os.environ["ZKP2P_NATIVE_THREADS"] = "2"
    abc = [np.ascontiguousarray(abc0[i].copy()) for i in range(3)]
    d = np.zeros((M, 4), dtype=np.uint64)
    lib.fr_h_ladder(abc[0].ctypes.data_as(u64p), abc[1].ctypes.data_as(u64p),
                    abc[2].ctypes.data_as(u64p), M, wroot.ctypes.data_as(u64p),
                    gcosv.ctypes.data_as(u64p), d.ctypes.data_as(u64p))
    lad[knob] = d
assert np.array_equal(lad["1"], lad["0"]), "pooled ladder != unfused ladder"
print("ok ladder_pool", flush=True)

# PR-20 interleaved apply arm (fresh-read per MSM): both arms at
# threads 1 and 2 across the bucket drivers.  The down-stream prefetch
# issues (schedule walk, gather/y2, bail-fill, writeback) and the
# two-chain mul8x2 accumulators are the new surface — a prefetch off
# the end of a table or bucket block is exactly what ASan would catch.
for ilv in ("1", "0"):
    os.environ["ZKP2P_MSM_INTERLEAVE"] = ilv
    os.environ["ZKP2P_MSM_BATCH_AFFINE"] = "1"
    for threads in (1, 2):
        out = np.zeros(8, dtype=np.uint64)
        lib.g1_msm_pippenger_mt(
            bm.ctypes.data_as(u64p), sc.ctypes.data_as(u64p), n, 14, threads,
            out.ctypes.data_as(u64p))
        check(f"ilv={ilv} plain t={threads}", out)
        out = np.zeros(8, dtype=np.uint64)
        lib.g1_msm_pippenger_glv_mt(
            b2.ctypes.data_as(u64p), sc.ctypes.data_as(u64p), n, n, 14, threads,
            gc.ctypes.data_as(u64p), GLV_MAX_BITS, out.ctypes.data_as(u64p))
        check(f"ilv={ilv} glv t={threads}", out)
        outm = np.zeros((3, 8), dtype=np.uint64)
        lib.g1_msm_pippenger_multi(
            bm.ctypes.data_as(u64p), scm.ctypes.data_as(u64p), n, 3, 14, threads,
            outm.ctypes.data_as(u64p))
        check_multi(f"ilv={ilv} multi t={threads}", outm)
        out = np.zeros(8, dtype=np.uint64)
        lib.g1_msm_pippenger_fixed(
            table.ctypes.data_as(u64p), t52.ctypes.data_as(u64p) if has52 else None,
            sc.ctypes.data_as(u64p), n, n, Lq, cq, qq, threads, out.ctypes.data_as(u64p))
        check(f"ilv={ilv} fixed t={threads}", out)
print("ok msm_interleave", flush=True)

# PR-20 radix-8 fused NTT stages: both arms x threads 1/2 through the
# ladder at a domain deep enough for whole radix-8 passes (the fused
# stage's wider twiddle strides and in-place SoA planes are the risk).
log_r8 = 10; M8 = 1 << log_r8
wroot8 = _scalars_to_u64([fr_domain_root(log_r8)]).copy()
gcos8 = _scalars_to_u64([coset_gen(log_r8)]).copy()
abc8 = _scalars_to_u64([rng.randrange(R) for _ in range(3 * M8)]).reshape(3, M8, 4).copy()
os.environ["ZKP2P_NTT_POOL"] = "1"
r8lad = {}
for r8 in ("1", "0"):
    os.environ["ZKP2P_NTT_RADIX8"] = r8
    for t in ("1", "2"):
        os.environ["ZKP2P_NATIVE_THREADS"] = t
        abc = [np.ascontiguousarray(abc8[i].copy()) for i in range(3)]
        d = np.zeros((M8, 4), dtype=np.uint64)
        lib.fr_h_ladder(abc[0].ctypes.data_as(u64p), abc[1].ctypes.data_as(u64p),
                        abc[2].ctypes.data_as(u64p), M8, wroot8.ctypes.data_as(u64p),
                        gcos8.ctypes.data_as(u64p), d.ctypes.data_as(u64p))
        r8lad[(r8, t)] = d
ref8 = r8lad[("0", "1")]
for key, d in r8lad.items():
    assert np.array_equal(d, ref8), ("radix8 ladder diverged", key)
print("ok ntt_radix8", flush=True)

# the sample verify (groth16_verify_bn254): the Fq12 tower, the Miller
# loops' per-step vectors and the lazily built curve constants are its
# allocation surface.  An instance made from known exponents, so nothing
# but the host curve oracle is imported: A = r G1, B = s G2 and
# C = (r s - alpha beta - vk_x gamma) / delta G1 satisfy the equation.
from zkp2p_tpu.curve.host import G2_GENERATOR, g1_add, g2_mul
from zkp2p_tpu.snark.groth16 import Proof, VerifyingKey
from zkp2p_tpu.snark.native_verify import pairing_product_is_one, verify_native
lib.bn254_pairing_product_is_one.argtypes = [u64p, u64p, ctypes.c_int]
lib.groth16_verify_bn254.argtypes = [u64p, ctypes.c_int, ctypes.c_int, u64p, u64p, ctypes.c_int]
va, vb, vg, vd, vr, vs = (rng.randrange(1, R) for _ in range(6))
v_ic = [rng.randrange(1, R) for _ in range(3)]
v_pub = [rng.randrange(R), 0]
v_x = (v_ic[0] + v_pub[0] * v_ic[1] + v_pub[1] * v_ic[2]) % R
v_c = (vr * vs - va * vb - v_x * vg) * pow(vd, -1, R) % R
v_vk = VerifyingKey(
    n_public=2, alpha_1=g1_mul(G1_GENERATOR, va), beta_2=g2_mul(G2_GENERATOR, vb),
    gamma_2=g2_mul(G2_GENERATOR, vg), delta_2=g2_mul(G2_GENERATOR, vd),
    ic=[g1_mul(G1_GENERATOR, k) for k in v_ic])
v_good = Proof(a=g1_mul(G1_GENERATOR, vr), b=g2_mul(G2_GENERATOR, vs), c=g1_mul(G1_GENERATOR, v_c))
v_bad = Proof(a=v_good.a, b=v_good.b, c=g1_add(v_good.c, G1_GENERATOR))
v_inf = Proof(a=None, b=None, c=None)
assert verify_native(lib, v_vk, v_good, v_pub) is True
assert verify_native(lib, v_vk, v_bad, v_pub) is False
assert verify_native(lib, v_vk, v_inf, v_pub) is False
assert verify_native(lib, v_vk, v_good, v_pub[:1]) is False
assert verify_native(lib, v_vk, v_good, [v_pub[0] + 1, 0]) is False
assert pairing_product_is_one(lib, [])
assert pairing_product_is_one(lib, [(None, G2_GENERATOR), (G1_GENERATOR, None)])
assert not pairing_product_is_one(lib, [(G1_GENERATOR, G2_GENERATOR)])
print("ok groth16_verify", flush=True)

# a proof's assembly (groth16_assemble_bn254): stack points and scalars
# alone; full-width blinding, then every accumulator at infinity, then an
# accumulator off its curve (no answer), each against the Python form
from types import SimpleNamespace
from zkp2p_tpu.snark.native_assemble import assemble_native, assemble_python
lib.groth16_assemble_bn254.argtypes = [u64p, u64p, u64p, u64p]
a_key = SimpleNamespace(alpha_1=v_vk.alpha_1, beta_1=g1_mul(G1_GENERATOR, vb), delta_1=g1_mul(G1_GENERATOR, vd),
                        beta_2=v_vk.beta_2, delta_2=v_vk.delta_2)
a_acc = [v_good.a, v_good.c, v_good.b, v_bad.c, v_vk.ic[0]]
for acc in (a_acc, [None] * 5, [a_key.alpha_1] + a_acc[1:]):
    assert assemble_native(lib, a_key, acc, vr, vs) == assemble_python(a_key, acc, vr, vs)
assert assemble_native(lib, a_key, [(1, 1)] + a_acc[1:], vr, vs) is None
print("ok groth16_assemble", flush=True)

lib.zkp2p_pool_shutdown()
print("ASAN-PARITY-GREEN", flush=True)
"""


@pytest.mark.slow
def test_asan_msm_parity_smoke():
    if not os.path.exists(ASAN_SO):
        r = subprocess.run(
            ["make", "-C", os.path.join(REPO, "csrc"), "libzkp2p_native_asan.so"],
            capture_output=True, text=True,
        )
        if r.returncode != 0:
            pytest.skip(f"asan build unavailable: {r.stderr[-300:]}")
    # locate the asan runtime the instrumented .so links against
    asan_rt = subprocess.run(
        ["g++", "-print-file-name=libasan.so"], capture_output=True, text=True
    ).stdout.strip()
    if not asan_rt or not os.path.exists(asan_rt):
        pytest.skip("libasan runtime not found")
    env = dict(
        os.environ,
        ZKP2P_REPO=REPO,
        ZKP2P_ASAN_SO=ASAN_SO,
        LD_PRELOAD=asan_rt,
        # CPython leaks by design at interpreter teardown; leak reports
        # would drown real findings.  Everything else stays fatal
        # (-fno-sanitize-recover + abort_on_error).
        ASAN_OPTIONS="detect_leaks=0:abort_on_error=1",
        UBSAN_OPTIONS="halt_on_error=1:abort_on_error=1",
    )
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", _CHECK], env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert r.returncode == 0, f"sanitizer run failed:\n{r.stdout[-2000:]}\n{r.stderr[-4000:]}"
    assert "ASAN-PARITY-GREEN" in r.stdout, r.stdout[-2000:]
