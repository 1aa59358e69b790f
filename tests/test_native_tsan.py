"""ThreadSanitizer smoke of the native concurrency tier (`make native-tsan`).

ASan (tests/test_native_asan.py) proves the BUFFERS of the native MSM /
NTT / matvec tiers; this proves the SYNCHRONIZATION.  The WorkPool and
everything scheduled on it — pool-parallel NTT stages, segmented
matvec, the multi-column MSM's shared bucket blocks — is a
relaxed-atomics MPMC design (the layer ZKProphet/SZKP call the
synchronization-sensitive core of accelerated Groth16, PAPERS.md), and
until this test it had NO race detector coverage: a missing
happens-before edge on the job queue or a torn non-atomic counter
would pass every parity test until a chaos run (or production) lost a
proof.

Driven under TSan, threads=2, with parity asserts against the host
oracle so a silently-wrong result fails even where no race is reported:

  * WorkPool MPMC: TWO python submitter threads issue pooled MSMs
    concurrently (ctypes releases the GIL), so enqueue/claim/complete
    race windows are real, not simulated;
  * the relaxed-atomics stats block: a reader thread hammers
    zkp2p_stats_snapshot while the MSMs run (the documented contract:
    purely observational, never synchronizing);
  * pool-parallel NTT stages + fused coset ladder (ZKP2P_NTT_POOL=1);
  * segmented matvec at threads=2 (conflict-free by construction — the
    claim TSan now checks);
  * multi-column MSM from two concurrent submitters;
  * the sample verify (`groth16_verify_bn254`) from two concurrent
    submitters: four replicas of one process call it at once, and its
    curve constants are built on first use;
  * a proof's assembly (`groth16_assemble_bn254`) from four threads at
    once, as those replicas' proving threads call it after every batch.

The python interpreter is NOT instrumented, so libtsan must be
LD_PRELOADed (same pattern as the ASan smoke; TSan only tracks
instrumented code plus intercepted pthread/libc calls, which is exactly
the native library + its threading).  Suppressions: csrc/tsan.supp,
policy in docs/STATIC_ANALYSIS.md — currently EMPTY, and any new entry
needs a written benign-race argument.  Slow tier; run via
`make native-tsan` or ZKP2P_RUN_SLOW=1.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TSAN_SO = os.path.join(REPO, "csrc", "libzkp2p_native_tsan.so")
SUPP = os.path.join(REPO, "csrc", "tsan.supp")

_CHECK = r"""
import ctypes, os, random, sys, threading
sys.path.insert(0, os.environ["ZKP2P_REPO"])
import numpy as np
from zkp2p_tpu.curve.host import G1_GENERATOR, g1_msm, g1_mul
from zkp2p_tpu.field.bn254 import R, fr_domain_root
from zkp2p_tpu.native.lib import _pack_affine, _scalars_to_u64
from zkp2p_tpu.snark.groth16 import coset_gen

lib = ctypes.CDLL(os.environ["ZKP2P_TSAN_SO"])
u64p = ctypes.POINTER(ctypes.c_uint64)
u32p = ctypes.POINTER(ctypes.c_uint32)
i64p = ctypes.POINTER(ctypes.c_longlong)
lib.fp_to_mont.argtypes = [u64p, u64p, ctypes.c_int]
lib.g1_msm_pippenger_mt.argtypes = [u64p, u64p, ctypes.c_long, ctypes.c_int, ctypes.c_int, u64p]
lib.g1_msm_pippenger_multi.argtypes = [
    u64p, u64p, ctypes.c_long, ctypes.c_int, ctypes.c_int, ctypes.c_int, u64p,
]
lib.zkp2p_stats_snapshot.argtypes = [i64p]

rng = random.Random(11)
n = 160
pts = [g1_mul(G1_GENERATOR, rng.randrange(1, R)) for _ in range(n)]
pts[5] = None  # infinity hole through the pooled fill
scalars = [rng.randrange(R) for _ in range(n)]
scalars[0] = 0
scalars[1] = 1
scalars[2] = R - 1
want = g1_msm(pts, scalars)
bases = _pack_affine(pts)
bm = np.zeros_like(bases)
lib.fp_to_mont(bases.ctypes.data_as(u64p), bm.ctypes.data_as(u64p), 2 * n)
sc = np.ascontiguousarray(_scalars_to_u64(scalars))

def as_pt(got):
    x = int.from_bytes(got[:4].tobytes(), "little")
    y = int.from_bytes(got[4:].tobytes(), "little")
    return None if x == 0 and y == 0 else (x, y)

# ---- 1+2: WorkPool MPMC from two submitters, stats reader alongside --
stop = threading.Event()
def stats_reader():
    buf = np.zeros(64, dtype=np.int64)
    while not stop.is_set():
        lib.zkp2p_stats_snapshot(buf.ctypes.data_as(i64p))

errors = []
def submitter(tag, reps):
    try:
        for _ in range(reps):
            out = np.zeros(8, dtype=np.uint64)
            lib.g1_msm_pippenger_mt(
                bm.ctypes.data_as(u64p), sc.ctypes.data_as(u64p), n, 11, 2,
                out.ctypes.data_as(u64p))
            assert as_pt(out) == want, tag
    except Exception as e:  # noqa: BLE001 — surfaced below
        errors.append((tag, e))

rd = threading.Thread(target=stats_reader)
rd.start()
ts = [threading.Thread(target=submitter, args=(f"mpmc{i}", 4)) for i in range(2)]
for t in ts: t.start()
for t in ts: t.join()
assert not errors, errors
print("ok mpmc+stats", flush=True)

# ---- 5: multi-column MSM from two concurrent submitters -------------
cols = [scalars, list(reversed(scalars)), [0] * n]
wants = [g1_msm(pts, col) for col in cols]
scm = np.ascontiguousarray(np.stack([_scalars_to_u64(col) for col in cols]))
def multi_submitter(tag):
    try:
        for _ in range(3):
            outm = np.zeros((3, 8), dtype=np.uint64)
            lib.g1_msm_pippenger_multi(
                bm.ctypes.data_as(u64p), scm.ctypes.data_as(u64p), n, 3, 11, 2,
                outm.ctypes.data_as(u64p))
            for s in range(3):
                assert as_pt(outm[s]) == wants[s], (tag, s)
    except Exception as e:  # noqa: BLE001
        errors.append((tag, e))

ts = [threading.Thread(target=multi_submitter, args=(f"multi{i}",)) for i in range(2)]
for t in ts: t.start()
for t in ts: t.join()
assert not errors, errors
print("ok multi", flush=True)

# ---- 4: segmented matvec, threads=2, parity vs the scatter oracle ---
lib.fr_to_mont_batch.argtypes = [u64p, u64p, ctypes.c_long]
lib.fr_matvec.argtypes = [u64p, u32p, u32p, ctypes.c_long, u64p, ctypes.c_long, u64p]
lib.fr_matvec_pack52.argtypes = [u64p, ctypes.c_long, u64p]
lib.fr_matvec_pack52.restype = ctypes.c_int
lib.fr_matvec_seg.argtypes = [u64p, u64p, u32p, i64p, u32p, ctypes.c_long,
                              u64p, ctypes.c_long, ctypes.c_int, u64p]
m_mv, nw, nnz = 64, 48, 400
w_std = _scalars_to_u64([rng.randrange(R) for _ in range(nw)]).copy()
w_m = np.zeros_like(w_std)
lib.fr_to_mont_batch(w_std.ctypes.data_as(u64p), w_m.ctypes.data_as(u64p), nw)
cf_std = _scalars_to_u64([rng.randrange(R) for _ in range(nnz)]).copy()
cf = np.zeros_like(cf_std)
lib.fr_to_mont_batch(cf_std.ctypes.data_as(u64p), cf.ctypes.data_as(u64p), nnz)
wires = np.array([rng.randrange(nw) for _ in range(nnz)], dtype=np.uint32)
rows = np.array([rng.randrange(m_mv) for _ in range(nnz)], dtype=np.uint32)
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
for p52 in ([c52.ctypes.data_as(u64p), None] if mv52 else [None]):
    got = np.zeros((m_mv, 4), dtype=np.uint64)
    lib.fr_matvec_seg(p52, cp.ctypes.data_as(u64p), wp.ctypes.data_as(u32p),
                      seg_starts.ctypes.data_as(i64p), seg_rows.ctypes.data_as(u32p),
                      len(seg_rows), w_m.ctypes.data_as(u64p), m_mv, 2,
                      got.ctypes.data_as(u64p))
    assert np.array_equal(got, mv_want), ("matvec_seg", p52 is not None)
print("ok matvec_seg", flush=True)

# ---- 3: pool-parallel NTT stages + fused ladder, threads=2 ----------
lib.fr_h_ladder.argtypes = [u64p, u64p, u64p, ctypes.c_long, u64p, u64p, u64p]
log_lm = 7; M = 1 << log_lm
wroot = _scalars_to_u64([fr_domain_root(log_lm)]).copy()
gcosv = _scalars_to_u64([coset_gen(log_lm)]).copy()
abc0 = _scalars_to_u64([rng.randrange(R) for _ in range(3 * M)]).reshape(3, M, 4).copy()
lad = {}
for knob in ("1", "0"):
    os.environ["ZKP2P_NTT_POOL"] = knob  # fresh-read per call in csrc
    abc = [np.ascontiguousarray(abc0[i].copy()) for i in range(3)]
    d = np.zeros((M, 4), dtype=np.uint64)
    lib.fr_h_ladder(abc[0].ctypes.data_as(u64p), abc[1].ctypes.data_as(u64p),
                    abc[2].ctypes.data_as(u64p), M, wroot.ctypes.data_as(u64p),
                    gcosv.ctypes.data_as(u64p), d.ctypes.data_as(u64p))
    lad[knob] = d
assert np.array_equal(lad["1"], lad["0"]), "pooled ladder != unfused ladder"
print("ok ladder_pool", flush=True)

# ---- PR-20 floor arms under the race detector ------------------------
# Interleaved apply: two submitters again, now with the prefetch-issuing
# interleave arm on — the prefetches walk shared read-only schedule /
# bucket memory while another worker fills its own chunk, which must
# stay happens-before-clean.  Then both radix-8 ladder arms at
# threads=2 (the fused stage splits planes across pool workers).
for ilv in ("1", "0"):
    os.environ["ZKP2P_MSM_INTERLEAVE"] = ilv  # fresh-read per MSM
    ts = [threading.Thread(target=submitter, args=(f"ilv{ilv}-{i}", 2)) for i in range(2)]
    for t in ts: t.start()
    for t in ts: t.join()
    assert not errors, errors
print("ok msm_interleave", flush=True)

r8lad = {}
os.environ["ZKP2P_NTT_POOL"] = "1"
for r8 in ("1", "0"):
    os.environ["ZKP2P_NTT_RADIX8"] = r8
    abc = [np.ascontiguousarray(abc0[i].copy()) for i in range(3)]
    d = np.zeros((M, 4), dtype=np.uint64)
    lib.fr_h_ladder(abc[0].ctypes.data_as(u64p), abc[1].ctypes.data_as(u64p),
                    abc[2].ctypes.data_as(u64p), M, wroot.ctypes.data_as(u64p),
                    gcosv.ctypes.data_as(u64p), d.ctypes.data_as(u64p))
    r8lad[r8] = d
assert np.array_equal(r8lad["1"], r8lad["0"]), "radix-8 ladder != radix-4 ladder"
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
# two submitters at once: the first calls race to build the constants (a
# C++11 static) and every call after shares them read-only
def verifier(tag):
    try:
        for _ in range(3):
            assert verify_native(lib, v_vk, v_good, v_pub) is True, tag
            assert verify_native(lib, v_vk, v_bad, v_pub) is False, tag
    except Exception as e:  # noqa: BLE001
        errors.append((tag, e))

ts = [threading.Thread(target=verifier, args=(f"verify{i}",)) for i in range(2)]
for t in ts: t.start()
for t in ts: t.join()
assert not errors, errors
print("ok groth16_verify", flush=True)

# a proof's assembly (groth16_assemble_bn254) from four threads at once,
# as four replicas' proving threads call it: it shares nothing but the
# curve constants the verify above built
from types import SimpleNamespace
from zkp2p_tpu.snark.native_assemble import assemble_native, assemble_python
lib.groth16_assemble_bn254.argtypes = [u64p, u64p, u64p, u64p]
a_key = SimpleNamespace(alpha_1=v_vk.alpha_1, beta_1=g1_mul(G1_GENERATOR, vb), delta_1=g1_mul(G1_GENERATOR, vd),
                        beta_2=v_vk.beta_2, delta_2=v_vk.delta_2)
a_acc = [v_good.a, v_good.c, v_good.b, v_bad.c, None]
a_want = assemble_python(a_key, a_acc, vr, vs)
def assembler(tag):
    try:
        for _ in range(3):
            assert assemble_native(lib, a_key, a_acc, vr, vs) == a_want, tag
    except Exception as e:  # noqa: BLE001
        errors.append((tag, e))

ts = [threading.Thread(target=assembler, args=(f"assemble{i}",)) for i in range(4)]
for t in ts: t.start()
for t in ts: t.join()
assert not errors, errors
print("ok groth16_assemble", flush=True)

stop.set()
rd.join()
lib.zkp2p_stats_reset()
lib.zkp2p_pool_shutdown()
print("TSAN-CONCURRENCY-GREEN", flush=True)
"""


@pytest.mark.slow
def test_tsan_concurrency_smoke():
    if not os.path.exists(TSAN_SO):
        r = subprocess.run(
            ["make", "-C", os.path.join(REPO, "csrc"), "libzkp2p_native_tsan.so"],
            capture_output=True, text=True,
        )
        if r.returncode != 0:
            pytest.skip(f"tsan build unavailable: {r.stderr[-300:]}")
    tsan_rt = subprocess.run(
        ["g++", "-print-file-name=libtsan.so"], capture_output=True, text=True
    ).stdout.strip()
    if not tsan_rt or not os.path.exists(tsan_rt):
        pytest.skip("libtsan runtime not found")
    env = dict(
        os.environ,
        ZKP2P_REPO=REPO,
        ZKP2P_TSAN_SO=TSAN_SO,
        LD_PRELOAD=tsan_rt,
        # halt_on_error + abort_on_error: the FIRST race report kills the
        # subprocess, so a green run means zero findings.  Thread-leak
        # reporting off: the driver is an uninstrumented python whose
        # daemon threads TSan cannot attribute.  Suppressions wired even
        # while the file is empty — the wiring itself is under test.
        TSAN_OPTIONS=(
            f"halt_on_error=1:abort_on_error=1:report_thread_leaks=0:"
            f"suppressions={SUPP}"
        ),
        ZKP2P_NATIVE_THREADS="2",
    )
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", _CHECK], env=env, capture_output=True, text=True,
        timeout=600,
    )
    if r.returncode != 0 and "unexpected memory mapping" in r.stderr:
        # gcc-10's libtsan predates high-entropy mmap ASLR; a host whose
        # kernel randomizes outside TSan's shadow layout cannot run it
        # at all — that is an environment limitation, not a race
        pytest.skip("TSan incompatible with this kernel's ASLR layout")
    assert r.returncode == 0, f"tsan run failed:\n{r.stdout[-2000:]}\n{r.stderr[-4000:]}"
    assert "TSAN-CONCURRENCY-GREEN" in r.stdout, r.stdout[-2000:]
    assert "WARNING: ThreadSanitizer" not in r.stderr, r.stderr[-4000:]
