"""Native-CPU Groth16 prover: the rapidsnark analog of the framework.

Same dataflow as `prover.groth16_tpu.prove_tpu` (sparse matvec -> iNTT/
coset/NTT ladder -> 4 G1 + 1 G2 variable-base MSMs -> host blind and
assemble), executed by the C++ runtime (csrc/zkp2p_native.cpp: Fr
Montgomery field, precomputed-twiddle NTT, Pippenger bucket MSM) instead
of XLA.  The reference ships exactly this split: a browser/wasm prover
plus the native rapidsnark fast path (`dizkus-scripts/
6_gen_proof_rapidsnark.sh`); here the TPU prover is the accelerator path
and this is the portable native one — the first prover in this repo
that can prove the FULL-SIZE flagship circuit on a 1-core host.

Determinism contract: identical proof bytes to `prove_host`/`prove_tpu`
for the same (witness, r, s) — differentially tested in
tests/test_native_prover.py.
"""

from __future__ import annotations

import ctypes
import secrets
import threading
from typing import Optional, Sequence

import numpy as np

from ..field.bn254 import (
    GLV_BETA,
    GLV_K1_TERMS,
    GLV_K2_TERMS,
    GLV_MAX_BITS,
    GLV_MU1,
    GLV_MU2,
    P,
    R,
    fr_domain_root,
    to_mont,
)
from ..field.tower import Fq2
from ..native.lib import _scalars_to_u64, get_lib
from ..snark.groth16 import Proof, coset_gen
from ..snark.witness_check import rows_of
from .groth16_tpu import DeviceProvingKey, _assemble_host, _check_inferred_widths

_u64p = ctypes.POINTER(ctypes.c_uint64)
_u32p = ctypes.POINTER(ctypes.c_uint32)
_configured = False


def _lib():
    """Native library with the prover entry points configured (lazily —
    get_lib() already built and self-tested the .so)."""
    global _configured
    lib = get_lib()
    if lib is None:
        return None
    if not _configured:
        lib.fr_to_mont_batch.argtypes = [_u64p, _u64p, ctypes.c_long]
        lib.fr_from_mont_batch.argtypes = [_u64p, _u64p, ctypes.c_long]
        lib.fr_mul_batch.argtypes = [_u64p, _u64p, _u64p, ctypes.c_long]
        lib.fr_mul_std.argtypes = [_u64p, _u64p, _u64p]
        lib.fr_matvec.argtypes = [_u64p, _u32p, _u32p, ctypes.c_long, _u64p, ctypes.c_long, _u64p]
        lib.fr_ntt.argtypes = [_u64p, ctypes.c_long, _u64p, _u64p]
        lib.fr_h_ladder.argtypes = [_u64p, _u64p, _u64p, ctypes.c_long, _u64p, _u64p, _u64p]
        lib.g1_msm_pippenger.argtypes = [_u64p, _u64p, ctypes.c_long, ctypes.c_int, _u64p]
        lib.g2_msm_pippenger.argtypes = [_u64p, _u64p, ctypes.c_long, ctypes.c_int, _u64p]
        lib.g1_msm_pippenger_mt.argtypes = [_u64p, _u64p, ctypes.c_long, ctypes.c_int, ctypes.c_int, _u64p]
        lib.g2_msm_pippenger_mt.argtypes = [_u64p, _u64p, ctypes.c_long, ctypes.c_int, ctypes.c_int, _u64p]
        lib.g1_glv_phi_bases.argtypes = [_u64p, ctypes.c_long, _u64p, _u64p]
        lib.g1_msm_pippenger_glv_mt.argtypes = [
            _u64p, _u64p, ctypes.c_long, ctypes.c_long, ctypes.c_int, ctypes.c_int,
            _u64p, ctypes.c_int, _u64p,
        ]
        lib.g1_msm_pippenger_multi.argtypes = [
            _u64p, _u64p, ctypes.c_long, ctypes.c_int, ctypes.c_int, ctypes.c_int, _u64p,
        ]
        lib.g1_msm_pippenger_glv_multi.argtypes = [
            _u64p, _u64p, ctypes.c_long, ctypes.c_long, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, _u64p, ctypes.c_int, _u64p,
        ]
        lib.fr_reduce_batch.argtypes = [_u64p, ctypes.c_long]
        # segmented matvec tier (prover.matvec_plan)
        lib.fr_matvec_pack52.argtypes = [_u64p, ctypes.c_long, _u64p]
        lib.fr_matvec_pack52.restype = ctypes.c_int
        lib.fr_matvec_seg.argtypes = [
            _u64p, _u64p, _u32p, ctypes.POINTER(ctypes.c_longlong), _u32p,
            ctypes.c_long, _u64p, ctypes.c_long, ctypes.c_int, _u64p,
        ]
        # fixed-base precomputed-window tier (prover.precomp)
        lib.g1_precomp_build.argtypes = [
            _u64p, ctypes.c_long, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, _u64p,
        ]
        lib.g1_precomp_to52.argtypes = [_u64p, ctypes.c_long, _u64p]
        lib.g1_precomp_to52.restype = ctypes.c_int
        lib.g1_msm_pippenger_fixed.argtypes = [
            _u64p, _u64p, _u64p, ctypes.c_long, ctypes.c_long, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, _u64p,
        ]
        lib.g1_msm_pippenger_fixed_multi.argtypes = [
            _u64p, _u64p, _u64p, ctypes.c_long, ctypes.c_long, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int, _u64p,
        ]
        # Self-test the Fr multiplier before trusting proofs to it (the
        # same covenant native/lib.py applies to the Fq side).
        a, b = R - 987654321, 0xFEDCBA9876543210 << 128 | 0x42
        av = _scalars_to_u64([a]).copy()
        bv = _scalars_to_u64([b]).copy()
        cv = np.zeros((1, 4), dtype=np.uint64)
        lib.fr_mul_std(_p(av), _p(bv), _p(cv))
        if int.from_bytes(cv.tobytes(), "little") != a * b % R:
            raise RuntimeError("native fr_mul self-test failed")
        _configured = True
    return lib


def _p(a: np.ndarray):
    return a.ctypes.data_as(_u64p)


def _p32(a: np.ndarray):
    return a.ctypes.data_as(_u32p)


def _limbs16_to_u64(a: np.ndarray) -> np.ndarray:
    """(..., 16) u32 16-bit-limb layout (jfield) -> (..., 4) u64."""
    a = np.asarray(a)
    a16 = np.ascontiguousarray(a.astype(np.uint16))
    return a16.view("<u8").reshape(*a.shape[:-1], 4)


# Base conversions are pure functions of the (immutable) key arrays:
# memoized per AffPoint identity so a service proving many requests
# against one DeviceProvingKey converts each MSM's bases ONCE (at full
# size the five conversions cost seconds per proof otherwise).  Each
# entry pins the source arrays, so an id() key cannot be reused while
# its entry is alive; a small cap bounds test-suite churn.  Guarded by a
# lock: the stage task-graph converts the a/b1/b2/c bases from worker
# threads concurrently, and a racing evict+insert must not corrupt the
# dict (worst case under the lock is a duplicate convert, never a wrong
# entry).
_bases_cache: dict = {}
_BASES_CACHE_CAP = 16
_bases_lock = threading.Lock()


def _bases_memo(bases, convert, tag: str = ""):
    key = (id(bases[0]), id(bases[1]), tag)
    with _bases_lock:
        hit = _bases_cache.get(key)
        if hit is not None and hit[0] is bases[0] and hit[1] is bases[1]:
            return hit[2]
    out = convert(bases)
    with _bases_lock:
        if len(_bases_cache) >= _BASES_CACHE_CAP:
            _bases_cache.pop(next(iter(_bases_cache)))
        _bases_cache[key] = (bases[0], bases[1], out)
    return out


def _g1_bases_u64(bases) -> np.ndarray:
    """AffPoint ((n,16),(n,16)) Montgomery limbs -> (n, 8) u64."""

    def convert(b):
        x, y = (np.asarray(c) for c in b)
        return np.ascontiguousarray(
            np.concatenate([_limbs16_to_u64(x), _limbs16_to_u64(y)], axis=-1)
        )

    return _bases_memo(bases, convert)


_glv_consts_arr: Optional[np.ndarray] = None


def _glv_consts() -> np.ndarray:
    """GLV constants packed for the C runtime (csrc glv_split layout):
    beta (Montgomery), the two Barrett mus, the four lattice-term
    magnitudes, and the subtract-flag word — all DERIVED in field.bn254,
    nothing hardcoded on either side."""
    global _glv_consts_arr
    if _glv_consts_arr is None:
        mask = (1 << 64) - 1

        def u64x4(v: int):
            return [(v >> (64 * i)) & mask for i in range(4)]

        flags = 0
        mags = []
        for j, (mag, sub) in enumerate(GLV_K1_TERMS):
            mags += u64x4(mag)
            flags |= int(sub) << j
        for j, (mag, sub) in enumerate(GLV_K2_TERMS):
            mags += u64x4(mag)
            flags |= int(sub) << (2 + j)
        _glv_consts_arr = np.array(
            u64x4(to_mont(GLV_BETA, P)) + u64x4(GLV_MU1) + u64x4(GLV_MU2) + mags + [flags],
            dtype=np.uint64,
        )
    return _glv_consts_arr


def _g1_bases_glv_u64(bases) -> np.ndarray:
    """AffPoint Montgomery limbs -> the GLV-doubled (2n, 8) u64 base set
    [P, phi(P)] (csrc g1_glv_phi_bases).  Key-dependent only: memoized
    beside the plain conversion so a service pays the n Fq muls once."""

    def convert(b):
        plain = _g1_bases_u64(b)
        n = plain.shape[0]
        phi = np.zeros_like(plain)
        _lib().g1_glv_phi_bases(_p(plain), n, _p(_glv_consts()), _p(phi))
        return np.ascontiguousarray(np.concatenate([plain, phi]))

    return _bases_memo(bases, convert, tag="glv")


def _glv_arm() -> bool:
    from ..utils.audit import record_arm
    from ..utils.config import load_config

    return record_arm("native_msm_glv", load_config().msm_glv)


def _use_batch_affine() -> bool:
    from ..utils.audit import record_arm
    from ..utils.config import load_config

    return record_arm("native_batch_affine", load_config().msm_batch_affine)


def _use_msm_multi() -> bool:
    """Cross-proof multi-column MSM gate (ZKP2P_MSM_MULTI, default ON):
    prove_native_batch issues each G1 MSM family as ONE multi-column
    Pippenger call across the batch; =0 falls back to sequential
    per-proof proves — the byte-parity oracle arm.  Fresh-read per batch
    and record_arm-audited, so A/B digests distinguish the arms."""
    from ..utils.audit import record_arm
    from ..utils.config import load_config

    return record_arm("native_msm_multi", load_config().msm_multi)


def _use_msm_precomp() -> bool:
    """Fixed-base precomputed-window MSM gate (ZKP2P_MSM_PRECOMP,
    default ON): the frozen G1 families prove from offline level tables
    (prover.precomp) instead of re-running the GLV split + base
    conversion + variable-base fill; =0 falls back to the existing
    drivers — the byte-parity oracle arm.  Fresh-read per prove and
    record_arm-audited, so A/B digests distinguish the arms."""
    from ..utils.audit import record_arm
    from ..utils.config import load_config

    return record_arm("native_msm_precomp", load_config().msm_precomp)


def _use_matvec_seg() -> bool:
    """Segmented-plan matvec gate (ZKP2P_MATVEC_SEG, default ON): the
    A/B matvecs run through the presorted per-key segment plan
    (prover.matvec_plan + csrc fr_matvec_seg — 8-wide IFMA products,
    pool-parallel conflict-free segments); =0 falls back to the scatter
    oracle `fr_matvec` — the byte-parity arm.  Fresh-read per prove and
    record_arm-audited, so A/B digests distinguish the arms."""
    from ..utils.audit import record_arm
    from ..utils.config import load_config

    return record_arm("native_matvec_seg", load_config().matvec_seg)


def _use_msm_overlap() -> bool:
    """Stage task-graph gate (ZKP2P_MSM_OVERLAP, default ON): the
    witness-dependent MSMs run on worker threads overlapping the H
    ladder; =0 runs the strict sequential schedule — the byte-parity
    arm.  Fresh-read per prove and record_arm-audited (the one armable
    knob that historically lacked an arm record: a flip was invisible
    to the execution digest until zkp2p-lint's gate-arm rule caught
    it)."""
    from ..utils.audit import record_arm
    from ..utils.config import load_config

    return record_arm("native_msm_overlap", load_config().msm_overlap)


def _ntt_pool_arm() -> bool:
    """NTT stage-pool + fused-ladder gate (ZKP2P_NTT_POOL, default ON).
    The arm itself is resolved IN the C runtime (fresh getenv per
    ladder/NTT call, like ZKP2P_MSM_BATCH_AFFINE); this mirror records
    it into the execution digest so pool-NTT A/Bs are
    digest-distinguishable.  apply_env keeps the env and the typed
    config coherent, so the recorded arm is the arm C takes."""
    from ..utils.audit import record_arm
    from ..utils.config import load_config

    return record_arm("native_ntt_pool", load_config().ntt_pool)


def _msm_interleave_arm() -> bool:
    """MSM apply interleave gate (ZKP2P_MSM_INTERLEAVE, default ON).
    Resolved IN the C runtime (fresh getenv per apply/window-sum call):
    =1 runs the batched affine apply as two independent chunk groups
    through one mont52_mul8x2 register schedule plus software prefetch
    down the known bucket/point schedules; =0 is the single-chain
    byte-parity oracle arm.  This mirror records the arm into the
    execution digest."""
    from ..utils.audit import record_arm
    from ..utils.config import load_config

    return record_arm("native_msm_interleave", load_config().msm_interleave)


def _ntt_radix8_arm() -> bool:
    """NTT radix-8 pass gate (ZKP2P_NTT_RADIX8, default OFF on narrow
    hosts — measured 0.95x at 2^19 on the 1-core box, see
    docs/TUNING.md).  Resolved IN the C runtime (fresh getenv per
    stage-batch call): =1 fuses three butterfly stages per load/store
    pass in fr_ntt_soa_stages; unset/=0 keeps the radix-4 pairs — the
    byte-parity oracle arm.  Mirror-recorded into the execution
    digest."""
    from ..utils.audit import record_arm
    from ..utils.config import load_config

    return record_arm("native_ntt_radix8", load_config().ntt_radix8)


def _use_witness_u64() -> bool:
    """Witness-at-builder gate (ZKP2P_WITNESS_U64, default ON): when the
    witness object carries a build-time standard-form `u64` array
    (snark.r1cs.Witness / WitnessRow), the witness_convert stage hands
    it off instead of re-serializing Python ints every prove; =0 (or a
    plain witness sequence) re-serializes — the byte-parity oracle
    arm.  Fresh-read per prove and record_arm-audited so A/B digests
    distinguish the arms."""
    from ..utils.audit import record_arm
    from ..utils.config import load_config

    return record_arm("native_witness_u64", load_config().witness_u64)


# ONE process-wide executor for the prover's Python-side task graphs
# (stage overlap + oracle-arm matvec jobs).  The per-prove, per-matvec
# `ThreadPoolExecutor(max_workers=2)` constructions this replaces
# spawned and joined 2-6 threads per proof — tens of thread spawns per
# batch, pure overhead on the hot path (tests/test_nonmsm.py counts
# constructions per batch now).  Sized for the widest acyclic task set:
# 4 overlap tasks + 2 oracle matvec leaves; leaves are only ever
# submitted from the MAIN thread, so the graph cannot deadlock on pool
# exhaustion.
_executor = None
_executor_lock = threading.Lock()


def _shared_executor():
    global _executor
    with _executor_lock:
        if _executor is None:
            from concurrent.futures import ThreadPoolExecutor

            _executor = ThreadPoolExecutor(
                max_workers=6, thread_name_prefix="zkp2p-native"
            )
        return _executor


def _witness_std_u64(
    lib, witness: Sequence[int], fast: bool = False, builder_u64: bool = False
) -> np.ndarray:
    """Witness ints -> standard-form (n, 4) u64 MSM scalars, reduced
    mod r IN THE NATIVE LIBRARY: raw 256-bit
    serialization here, `fr_reduce_batch` there — the per-element
    Python `w % R` this replaces was ~half the witness_convert stage.
    Values a 256-bit window cannot hold (negative or >= 2^256 — no
    in-tree witness builder emits them) fall back to the exact Python
    reduction.

    builder_u64=True (the ZKP2P_WITNESS_U64 arm): a witness built by
    snark.r1cs already carries its standard-form serialization (`u64`
    attribute, emitted at build time from the same bulk/exact split),
    so the whole stage collapses to an array hand-off.  The arm is
    resolved by the caller per prove, so an in-process A/B exercises
    both paths on the identical witness object; a plain sequence (no
    `u64`) falls through to the serializing arms regardless.

    fast=True (the ZKP2P_MATVEC_SEG arm — witness-side leg of the same
    vectorized-floor tier, so the knob-off arm reproduces the full
    pre-tier path): real witnesses are overwhelmingly sub-64-bit wires
    (99.2% on the venmo shape — bits, bytes, bignum limbs), so chunks
    bulk-assign into the u64 column at numpy C speed (already < r, no
    reduction needed); a chunk holding any >= 2^64 value raises
    OverflowError and takes the exact serialize+reduce path for that
    chunk alone.  Byte-identical to the slow path by construction
    (pinned in tests/test_nonmsm.py)."""
    n = len(witness)
    if builder_u64:
        rows = rows_of(witness, getattr(witness, "u64", None))
        if rows is not None:
            return rows
    if fast and n:
        try:
            arr = np.zeros((n, 4), dtype=np.uint64)
            col = arr[:, 0]
            CH = 8192
            for lo in range(0, n, CH):
                hi = min(n, lo + CH)
                chunk = witness[lo:hi]
                try:
                    col[lo:hi] = chunk  # raises on >= 2^64 / negative / non-int
                except (OverflowError, TypeError, ValueError):
                    sub = np.frombuffer(
                        b"".join(int(w).to_bytes(32, "little") for w in chunk),
                        dtype="<u8",
                    ).reshape(hi - lo, 4)
                    view = arr[lo:hi]
                    view[:] = sub
                    lib.fr_reduce_batch(_p(view), hi - lo)
            return arr
        except (OverflowError, ValueError, TypeError):
            pass  # exotic values (negative / >= 2^256) or a non-sliceable
            # sequence: the exact paths below handle them
    try:
        buf = b"".join(int(w).to_bytes(32, "little") for w in witness)
    except (OverflowError, ValueError):
        return np.ascontiguousarray(_scalars_to_u64([w % R for w in witness]))
    arr = np.frombuffer(buf, dtype="<u8").reshape(len(witness), 4).copy()
    lib.fr_reduce_batch(_p(arr), arr.shape[0])
    return np.ascontiguousarray(arr)


def _native_ifma_tier() -> bool:
    """The 52-bit AVX512-IFMA batch-affine tier gate for G1 windows —
    the native mirror of the device prover's impl gates, reported to the
    execution audit per consultation (one per MSM via _pick_window).
    False routes through the scalar Montgomery tier."""
    from ..native.lib import ifma_available
    from ..utils.audit import record_arm

    v = _use_batch_affine() and ifma_available()
    record_arm("native_tier", "ifma" if v else "scalar")
    return v


def _g2_bases_u64(bases) -> np.ndarray:
    """AffPoint ((n,2,16),(n,2,16)) -> (n, 16) u64 (x.c0 x.c1 y.c0 y.c1)."""

    def convert(b):
        x, y = (np.asarray(c) for c in b)
        n = x.shape[0]
        return np.ascontiguousarray(
            np.concatenate(
                [_limbs16_to_u64(x).reshape(n, 8), _limbs16_to_u64(y).reshape(n, 8)], axis=-1
            )
        )

    return _bases_memo(bases, convert)


def _u64x4_to_int_arr(a: np.ndarray) -> list:
    """(k, 4) u64 -> python ints."""
    return [int.from_bytes(a[i].tobytes(), "little") for i in range(a.shape[0])]


def _tuned_window(tag: str, bl: int, threads: int):
    """Host-profile window resolution for the variable-base G1 curves
    (the tune window arm, APPLIED): the measured-best
    c when the profile recorded one at this exact (shape, threads)
    context, else None -> the committed curve below.  A tuned value
    bypasses the multi-thread clamp: the sweep measured it AT that
    thread count, so the clamp's serial-suffix reasoning is already in
    the number.  The source is recorded per consultation (the precomp
    manifest's geometry_source discipline, on the audit rail) so a
    profile-resolved prove never shares a digest with a curve-resolved
    one."""
    from ..utils.audit import record_arm
    from ..utils.hostprof import tuned_window

    c = tuned_window(tag, bl, threads)
    record_arm("window_source", "profile" if c is not None else "fallback")
    return c


def _pick_window(n: int, g2: bool = False, threads: int = 1) -> int:
    """Pippenger window: ~log2(n) - 4 with SIGNED digits — the signed
    recoding halves the bucket count at a given c, so the sweet spot
    sits one window wider than the unsigned sweep (n=2^19: unsigned
    c=13 3.49s, c=15 3.34s, c=16 3.52s) — same bucket count and
    chunk-conflict rate as unsigned c-1, one fewer window of fill adds.
    At full size (2^23) signed c=16 regressed the prove 125.6->138.7 s
    purely from doubled batch-affine conflicts; the raised clamp lets
    the big domains reach c=17 while the bench shape keeps its
    measured-best c=15 (signed sweep at 2^19: c=15 6.3s, c=16 7.6s)."""
    if not g2 and _native_ifma_tier():  # batch-affine off: wide-window curve n/a
        tuned = _tuned_window("plain", n.bit_length(), threads)
        if tuned is not None:
            return tuned
        # IFMA regime (G1 only) with the 8-lane vector suffix (csrc
        # g1_suffix8): the serial per-window reduction that clamped the
        # r5 sweep at c=14 is vectorized across windows, so wider
        # windows win again (fill scales with ceil(254/c)).  Measured
        # on the vector-suffix build, random full-width scalars:
        #   2^15: c15 166 ms vs c14 189;  2^17: c15 404 vs c14 495;
        #   2^19: c16 1456 vs c14 1808 (c17 equal — keep the smaller).
        # The vector suffix only engages SINGLE-threaded (csrc gates the
        # deferred-bucket pass on n_threads <= 1: each worker already
        # runs its own serial suffix concurrently) — so multi-threaded
        # runs keep the r5 serial-suffix optimum of c=14 instead of
        # paying a 4x longer per-window serial tail at c=15/16
        # (ADVICE r5 #1).  The whole IFMA curve also rides the
        # batch-affine tier: with ZKP2P_MSM_BATCH_AFFINE=0 (the
        # Jacobian A/B arm) both the 52-limb fill and the vector suffix
        # are gated off, so the generic curve below applies instead.
        bl = n.bit_length()
        if bl >= 20:
            c = 16
        elif bl >= 16:  # sweep coverage starts at 2^15; below it the old curve
            c = 15
        else:
            c = max(4, bl - 5)
        return min(c, 14) if threads > 1 else c
    return max(4, min(17, n.bit_length() - 5))


def _pick_window_glv(n: int, threads: int = 1) -> int:
    """Pippenger window for the GLV shape: 2n points of ~129-bit
    half-scalars, nwin = ceil((GLV_MAX_BITS+1)/c).  Swept on the IFMA
    build (min-of-reps, random full-width scalars, GLV arm):
      2^15: c16 225 ms vs c15 253 / c17 533
      2^17: c16 796 vs c15 933
      2^19: c15 3173 vs c16 4383 — at 2^19 the c=16 deferred-suffix
            bucket block (nwin x 2^15 x 80 B = 23 MB) falls out of LLC,
            so the curve steps DOWN a window at the domain shape.
    Multi-threaded keeps the same c=14 serial-suffix clamp as the plain
    curve (the vector suffix is gated off there)."""
    bl = (2 * n).bit_length()
    if _native_ifma_tier():
        tuned = _tuned_window("glv", bl, threads)
        if tuned is not None:
            return tuned
        if bl >= 20:
            c = 15
        elif bl >= 14:
            c = 16
        else:
            c = max(4, bl - 5)
        return min(c, 14) if threads > 1 else c
    return max(4, min(17, bl - 5))


def _pick_window_multi(n: int, S: int, threads: int, glv: bool) -> int:
    """Window for the MULTI-COLUMN drivers.  The single-column curves
    apply unchanged: the S-wide bucket block (S x nbuckets x 80 B per
    window) argues for NARROWER windows, the shared inversion rounds
    for wider ones, and the interleaved prove A/B measured the existing
    threads-clamped curves best on the driver box (a wide-window sweep
    with the t=1 curve + vector suffix at threads=2 regressed the
    whole batch ~15% — see the csrc multi-core comment).  Kept as a
    separate hook so a box with a bigger LLC can retune multi alone."""
    del S
    return _pick_window_glv(n, threads=threads) if glv else _pick_window(n, threads=threads)


def _n_threads() -> int:
    """MSM worker threads: the typed config's native_threads
    (ZKP2P_NATIVE_THREADS) always wins; unset, the tuned host profile's
    topology-aware default applies when one is loaded (physical cores,
    not SMT siblings — the measured-best width from `zkp2p-tpu tune`);
    else the logical core count as before — the parallel axis is
    per-window (rapidsnark's split); on the 1-core build host this
    resolves to 1 and the code path stays sequential."""
    import os

    from ..utils.config import load_config
    from ..utils.hostprof import tuned_threads

    v = load_config().native_threads
    if v:
        return v
    t = tuned_threads()  # records the host_profile gate
    return t if t else max(1, os.cpu_count() or 1)


def _run_matvecs(lib, dpk, w_mont: np.ndarray, m: int, threads: int, a_ev, b_ev, plans):
    """The A/B QAP matvecs into a_ev/b_ev.  With a segment plan armed,
    each matrix is ONE `fr_matvec_seg` call — 8-wide IFMA products,
    segments partitioned across the C pool with no scatter conflicts
    (the pool is the parallel axis; no Python threads needed).  The
    oracle arm keeps the scatter `fr_matvec` with the two matrices on
    the shared executor."""
    if plans is not None:
        for matrix, out in (("a", a_ev), ("b", b_ev)):
            p52, pcf, pwi, pss, psr, nseg = plans[matrix].pointers()
            lib.fr_matvec_seg(
                p52, pcf, pwi, pss, psr, nseg, _p(w_mont), m, threads, _p(out)
            )
        return

    def matvec(coeff, wire, row, out):
        cf = _bases_memo(
            (coeff, coeff),
            lambda b: np.ascontiguousarray(_limbs16_to_u64(np.asarray(b[0]))),
        )
        wi = np.ascontiguousarray(np.asarray(wire, dtype=np.uint32))
        ro = np.ascontiguousarray(np.asarray(row, dtype=np.uint32))
        lib.fr_matvec(_p(cf), _p32(wi), _p32(ro), cf.shape[0], _p(w_mont), m, _p(out))

    jobs = [
        (dpk.a_coeff, dpk.a_wire, dpk.a_row, a_ev),
        (dpk.b_coeff, dpk.b_wire, dpk.b_row, b_ev),
    ]
    if threads > 1:
        # futures, not bare Threads: a worker exception must abort the
        # prove, not leave a zeroed evaluation vector behind.  Shared
        # executor — the per-matvec ThreadPoolExecutor construction this
        # replaces spawned threads on every proof.
        ex = _shared_executor()
        for f in [ex.submit(matvec, *j) for j in jobs]:
            f.result()
    else:
        for j in jobs:
            matvec(*j)


def _seg_plans(dpk):
    """The memoized segment plans when ZKP2P_MATVEC_SEG arms (and the
    native lib is up); None otherwise — callers fall back to the
    scatter oracle."""
    if not _use_matvec_seg():
        return None
    from .matvec_plan import plans_for

    return plans_for(dpk)


def prove_native(
    dpk: DeviceProvingKey,
    witness: Sequence[int],
    r: Optional[int] = None,
    s: Optional[int] = None,
) -> Proof:
    """Prove with the native C++ runtime.  Emits the exact proof
    `prove_host` / `prove_tpu` produce for the same (witness, r, s)."""
    from ..utils.faults import fault_point
    from ..utils.trace import trace

    # chaos/fault-injection site for the CLI/bench prove path (the
    # service's batch prove has its own `prove` site one level up) —
    # a single env-read no-op when ZKP2P_FAULTS is unset
    fault_point("native_prove")
    lib = _lib()
    if lib is None:
        raise RuntimeError("native library unavailable (csrc build failed?)")
    if r is None:
        r = 1 + secrets.randbelow(R - 1)
    if s is None:
        s = 1 + secrets.randbelow(R - 1)
    m = 1 << dpk.log_m
    threads = _n_threads()
    plans = _seg_plans(dpk)  # memoized; resolves the matvec_seg gate
    _ntt_pool_arm()  # C-side gate; recorded here for the digest
    _msm_interleave_arm()  # C-side gate; recorded here for the digest
    _ntt_radix8_arm()  # C-side gate; recorded here for the digest
    wit_u64 = _use_witness_u64()

    # Witness: standard-form u64x4 (MSM scalars) + Montgomery (matvec).
    with trace("native/witness_convert"):
        w_std = _witness_std_u64(
            lib, witness, fast=plans is not None, builder_u64=wit_u64
        )
        n_wires = w_std.shape[0]
        # inferred-width guard, vectorized over the limb view
        _check_inferred_widths(dpk, witness, w_std=w_std)
        w_mont = np.zeros_like(w_std)
        lib.fr_to_mont_batch(_p(w_std), _p(w_mont), n_wires)

    # Az/Bz/Cz evaluations on the domain (Cz = Az . Bz pointwise, valid
    # for a satisfying witness — same shortcut as abc_evals).  The A and
    # B matvecs are independent and ctypes releases the GIL, so they run
    # on two Python threads when the host has cores.
    a_ev = np.zeros((m, 4), dtype=np.uint64)
    b_ev = np.zeros((m, 4), dtype=np.uint64)
    c_ev = np.zeros((m, 4), dtype=np.uint64)
    with trace("native/matvec"):
        _run_matvecs(lib, dpk, w_mont, m, threads, a_ev, b_ev, plans)
        lib.fr_mul_batch(_p(a_ev), _p(b_ev), _p(c_ev), m)

    b_sel = np.asarray(dpk.b_sel)
    c_sel = np.asarray(dpk.c_sel)

    glv = _glv_arm()
    # Fixed-base precomputed tables for the frozen G1 families: resolved
    # ONCE per key (built or cache-loaded on first prove), then each
    # family's MSM is pure digit scatter + gather/add — the GLV split
    # and base conversion leave the hot loop entirely.  Families the
    # budget guard skipped fall through to the variable-base path below.
    from .precomp import precomputed_for

    ptables = precomputed_for(dpk) if _use_msm_precomp() else None

    def msm_g1(bases, scalars: np.ndarray, tag: str):
        fam = ptables.families.get(tag) if ptables is not None else None
        with trace(f"native/msm_{tag}"):
            out = np.zeros(8, dtype=np.uint64)
            if fam is not None:
                n = min(fam.n, scalars.shape[0])
                sc = np.ascontiguousarray(scalars[:n])
                lib.g1_msm_pippenger_fixed(
                    _p(fam.table), fam.p52(), _p(sc), n, fam.n, fam.levels,
                    fam.c, fam.q, threads, _p(out),
                )
            elif glv:
                b = _g1_bases_glv_u64(bases)
                nb = b.shape[0] // 2  # phi half offset in the cached doubled set
                n = min(nb, scalars.shape[0])
                sc = np.ascontiguousarray(scalars[:n])
                c = _pick_window_glv(n, threads=threads)
                lib.g1_msm_pippenger_glv_mt(
                    _p(b), _p(sc), n, nb, c, threads, _p(_glv_consts()), GLV_MAX_BITS, _p(out)
                )
            else:
                b = _g1_bases_u64(bases)
                n = min(b.shape[0], scalars.shape[0])
                sc = np.ascontiguousarray(scalars[:n])
                lib.g1_msm_pippenger_mt(
                    _p(b), _p(sc), n, _pick_window(n, threads=threads), threads, _p(out)
                )
        x, y = _u64x4_to_int_arr(out.reshape(2, 4))
        return None if x == 0 and y == 0 else (x, y)

    def msm_g2(bases, scalars: np.ndarray, tag: str):
        with trace(f"native/msm_{tag}"):
            b = _g2_bases_u64(bases)
            n = min(b.shape[0], scalars.shape[0])
            sc = np.ascontiguousarray(scalars[:n])
            out = np.zeros(16, dtype=np.uint64)
            lib.g2_msm_pippenger_mt(_p(b), _p(sc), n, _pick_window(n, g2=True), threads, _p(out))
        xc0, xc1, yc0, yc1 = _u64x4_to_int_arr(out.reshape(4, 4))
        if xc0 == xc1 == yc0 == yc1 == 0:
            return None
        return (Fq2(xc0, xc1), Fq2(yc0, yc1))

    def h_ladder_and_d():
        # H ladder: d_j = (A.B - C)(g . w^j), Montgomery -> std scalars.
        d = np.zeros((m, 4), dtype=np.uint64)
        with trace("native/h_ladder"):
            w_root = _scalars_to_u64([fr_domain_root(dpk.log_m)]).copy()
            g_cos = _scalars_to_u64([coset_gen(dpk.log_m)]).copy()
            lib.fr_h_ladder(_p(a_ev), _p(b_ev), _p(c_ev), m, _p(w_root), _p(g_cos), _p(d))
            d_std = np.zeros_like(d)
            lib.fr_from_mont_batch(_p(d), _p(d_std), m)
        return d_std

    # Stage task-graph (ZKP2P_MSM_OVERLAP, default on): the a/b1/b2/c
    # MSMs depend only on the witness scalars, while msm_h sits behind
    # the H ladder — so the four independent MSMs run on worker threads
    # (ctypes releases the GIL; the C pool's per-region width caps bound
    # total MSM-window concurrency) and OVERLAP the ladder and msm_h on
    # this thread instead of queuing behind them.  Gated on threads > 1:
    # a ZKP2P_NATIVE_THREADS=1 pin means "at most one busy core", and
    # Python-level concurrency would quietly break that promise.
    # Results are gathered in the fixed assembly order, so proof bytes
    # are identical to the sequential schedule (pinned by
    # tests/test_msm_native_edge.py parity).
    if _use_msm_overlap() and threads > 1:
        from ..utils.trace import adopt_context, adopt_stack, current_context, current_stack

        # worker-thread trace records keep this thread's stage prefix
        # (e.g. bench.py's prove_native_N span) — without it the four
        # submitted MSMs log under a bare root and per-rep stage
        # attribution in the bench trace is lost.  The ambient context
        # (the service's request_id) rides along the same way.
        stack = current_stack()
        ctx = current_context()

        def seeded(fn, *fargs):
            adopt_stack(stack)
            adopt_context(ctx)
            return fn(*fargs)

        ex = _shared_executor()
        fut_a = ex.submit(seeded, msm_g1, dpk.a_bases, w_std, "a")
        fut_b1 = ex.submit(seeded, msm_g1, dpk.b1_bases, np.ascontiguousarray(w_std[b_sel]), "b1")
        fut_b2 = ex.submit(seeded, msm_g2, dpk.b2_bases, np.ascontiguousarray(w_std[b_sel]), "b2")
        fut_c = ex.submit(seeded, msm_g1, dpk.c_bases, np.ascontiguousarray(w_std[c_sel]), "c")
        d_std = h_ladder_and_d()
        h_acc = msm_g1(dpk.h_bases, d_std, "h")
        a_acc, b1_acc, b2_acc, c_acc = (
            fut_a.result(), fut_b1.result(), fut_b2.result(), fut_c.result()
        )
    else:
        d_std = h_ladder_and_d()
        a_acc = msm_g1(dpk.a_bases, w_std, "a")
        b1_acc = msm_g1(dpk.b1_bases, np.ascontiguousarray(w_std[b_sel]), "b1")
        b2_acc = msm_g2(dpk.b2_bases, np.ascontiguousarray(w_std[b_sel]), "b2")
        c_acc = msm_g1(dpk.c_bases, np.ascontiguousarray(w_std[c_sel]), "c")
        h_acc = msm_g1(dpk.h_bases, d_std, "h")
    proof = _assemble_host(dpk, (a_acc, b1_acc, b2_acc, c_acc, h_acc), r, s)
    # publish into the process registry: prove count + a refresh of the
    # native runtime's counter block (one ctypes read of ~20 slots —
    # noise next to a prove), so a Prometheus scrape or the service's
    # per-sweep flush always sees current MSM/pool stats
    from ..utils.metrics import REGISTRY, publish_native_stats

    REGISTRY.counter("zkp2p_proves_total", {"prover": "native"}).inc()
    publish_native_stats()
    return proof


def prove_native_batch(
    dpk: DeviceProvingKey,
    witnesses: Sequence[Sequence[int]],
    rs: Optional[Sequence[int]] = None,
    ss: Optional[Sequence[int]] = None,
) -> list:
    """Prove a whole batch with the native runtime, amortizing the fixed
    proving-key bases across proofs: witness-convert / matvec / H-ladder
    run per proof, but each of the four G1 MSM families (a, b1, c, h) is
    issued as ONE multi-column Pippenger call — one base sweep, S scalar
    columns, batch-affine inversion rounds shared across columns (csrc
    g1_msm_pippenger_multi).  The G2 b2 MSM stays per proof (no
    multi-column G2 tier yet).  Gated by ZKP2P_MSM_MULTI (default ON);
    off — or S <= 1 — falls back to sequential `prove_native` calls,
    which remain the byte-parity oracle: every proof here is
    byte-identical to its sequential counterpart for the same
    (witness, r, s), pinned by tests/test_msm_multi.py."""
    from ..utils.faults import fault_point
    from ..utils.trace import trace

    fault_point("native_prove")
    lib = _lib()
    if lib is None:
        raise RuntimeError("native library unavailable (csrc build failed?)")
    S = len(witnesses)
    if S == 0:
        return []
    rs = list(rs) if rs is not None else [1 + secrets.randbelow(R - 1) for _ in range(S)]
    ss = list(ss) if ss is not None else [1 + secrets.randbelow(R - 1) for _ in range(S)]
    if len(rs) != S or len(ss) != S:
        raise ValueError(f"prove_native_batch: {S} witnesses but {len(rs)}/{len(ss)} blinds")
    if not _use_msm_multi() or S == 1:
        return [prove_native(dpk, w, r=r, s=s) for w, r, s in zip(witnesses, rs, ss)]

    m = 1 << dpk.log_m
    threads = _n_threads()
    glv = _glv_arm()
    b_sel = np.asarray(dpk.b_sel)
    c_sel = np.asarray(dpk.c_sel)

    # Resolved once per batch (not per proof): the segment plans + both
    # arm recordings — ladder constants are hoisted further down.
    plans = _seg_plans(dpk)
    _ntt_pool_arm()
    _msm_interleave_arm()
    _ntt_radix8_arm()
    wit_u64 = _use_witness_u64()

    # Phase 1: witness conversion for EVERY proof first — it is cheap
    # and unlocks all three witness-column multi MSMs (a/b1/c) plus the
    # per-proof b2 G2 MSMs, which the overlap arm below launches before
    # the expensive per-proof matvec/H-ladder work runs on this thread.
    w_cols, w_monts = [], []
    for witness in witnesses:
        with trace("native/witness_convert"):
            w_std = _witness_std_u64(
                lib, witness, fast=plans is not None, builder_u64=wit_u64
            )
            n_wires = w_std.shape[0]
            _check_inferred_widths(dpk, witness, w_std=w_std)
            w_mont = np.zeros_like(w_std)
            lib.fr_to_mont_batch(_p(w_std), _p(w_mont), n_wires)
        w_cols.append(w_std)
        w_monts.append(w_mont)

    # Hoisted out of the per-proof ladder loop: the domain root and
    # coset generator are key-shape constants, yet were re-derived (a
    # Python bigint pow chain each) S times per batch.
    w_root = _scalars_to_u64([fr_domain_root(dpk.log_m)]).copy()
    g_cos = _scalars_to_u64([coset_gen(dpk.log_m)]).copy()

    # Concurrency cap for the pipelined d-column tasks: each live
    # ladder body holds ~5 m-row buffers (a/b/c/d/d_std) plus the fused
    # ladder's 5-plane SoA scratch — letting all 6 executor workers run
    # ladders would multiply transient memory ~6x over the old serial
    # walk (≈8 GB at 2^23).  Two concurrent bodies keep the b2-overlap
    # win while bounding the peak at ~2x serial; the gate is INSIDE the
    # task so a capped task parks its worker, never deadlocks (the
    # tasks holding the permits always progress and release).
    d_gate = threading.Semaphore(2)

    def ladder_one_col(w_mont):
        # one proof: A/B matvecs, Cz = Az . Bz, H ladder -> d column
        # (evaluation buffers freed on return)
        with d_gate:
            a_ev = np.zeros((m, 4), dtype=np.uint64)
            b_ev = np.zeros((m, 4), dtype=np.uint64)
            c_ev = np.zeros((m, 4), dtype=np.uint64)
            with trace("native/matvec"):
                _run_matvecs(lib, dpk, w_mont, m, threads, a_ev, b_ev, plans)
                lib.fr_mul_batch(_p(a_ev), _p(b_ev), _p(c_ev), m)
            with trace("native/h_ladder"):
                d = np.zeros((m, 4), dtype=np.uint64)
                lib.fr_h_ladder(_p(a_ev), _p(b_ev), _p(c_ev), m, _p(w_root), _p(g_cos), _p(d))
                d_std = np.zeros_like(d)
                lib.fr_from_mont_batch(_p(d), _p(d_std), m)
            return d_std

    def ladder_cols():
        return [ladder_one_col(w_mont) for w_mont in w_monts]

    # Phase 2: the MSMs.  a/b1/c/h each ride ONE multi-column call over
    # the fixed (memoized) bases; b2 stays a per-proof G2 MSM.  With
    # precomp armed, a family's call is the fixed-table multi driver —
    # S digit scatters over ONE persistent table, sharing the same
    # batch-affine inversion rounds the variable-base multi path built.
    from .precomp import precomputed_for

    ptables = precomputed_for(dpk) if _use_msm_precomp() else None

    def msm_g1_multi(bases, cols, tag: str):
        fam = ptables.families.get(tag) if ptables is not None else None
        with trace(f"native/msm_{tag}", cols=len(cols)):
            out = np.zeros((S, 8), dtype=np.uint64)
            if fam is not None:
                n = min(fam.n, cols[0].shape[0])
                sc = np.ascontiguousarray(np.stack([np.asarray(col[:n]) for col in cols]))
                lib.g1_msm_pippenger_fixed_multi(
                    _p(fam.table), fam.p52(), _p(sc), n, fam.n, S, fam.levels,
                    fam.c, fam.q, threads, _p(out),
                )
            elif glv:
                b = _g1_bases_glv_u64(bases)
                nb = b.shape[0] // 2
                n = min(nb, cols[0].shape[0])
                sc = np.ascontiguousarray(np.stack([np.asarray(col[:n]) for col in cols]))
                c = _pick_window_multi(n, S, threads, glv=True)
                lib.g1_msm_pippenger_glv_multi(
                    _p(b), _p(sc), n, nb, S, c, threads,
                    _p(_glv_consts()), GLV_MAX_BITS, _p(out),
                )
            else:
                b = _g1_bases_u64(bases)
                n = min(b.shape[0], cols[0].shape[0])
                sc = np.ascontiguousarray(np.stack([np.asarray(col[:n]) for col in cols]))
                lib.g1_msm_pippenger_multi(
                    _p(b), _p(sc), n, S, _pick_window_multi(n, S, threads, glv=False),
                    threads, _p(out)
                )
        res = []
        for s in range(S):
            x, y = _u64x4_to_int_arr(out[s].reshape(2, 4))
            res.append(None if x == 0 and y == 0 else (x, y))
        return res

    def msm_g2_one(bases, scalars: np.ndarray, tag: str):
        with trace(f"native/msm_{tag}"):
            b = _g2_bases_u64(bases)
            n = min(b.shape[0], scalars.shape[0])
            sc = np.ascontiguousarray(scalars[:n])
            out = np.zeros(16, dtype=np.uint64)
            lib.g2_msm_pippenger_mt(_p(b), _p(sc), n, _pick_window(n, g2=True), threads, _p(out))
        xc0, xc1, yc0, yc1 = _u64x4_to_int_arr(out.reshape(4, 4))
        if xc0 == xc1 == yc0 == yc1 == 0:
            return None
        return (Fq2(xc0, xc1), Fq2(yc0, yc1))

    b_cols = [np.ascontiguousarray(w[b_sel]) for w in w_cols]
    c_cols = [np.ascontiguousarray(w[c_sel]) for w in w_cols]
    if _use_msm_overlap() and threads > 1:
        # Same stage task-graph contract as prove_native, one level up:
        # everything witness-dependent — the three witness-column multi
        # MSMs and the S per-proof G2 MSMs — runs on worker threads
        # (ctypes releases the GIL; the C pool's region width caps bound
        # window concurrency) while the per-proof matvec/H-ladder
        # pipeline produces d columns, then the h multi MSM (which sits
        # behind ALL of them) runs on this thread.  Assembly order stays
        # fixed, so proof bytes match the sequential schedule.
        from ..utils.trace import adopt_context, adopt_stack, current_context, current_stack

        stack = current_stack()
        ctx = current_context()

        def seeded(fn, *fargs):
            adopt_stack(stack)
            adopt_context(ctx)
            return fn(*fargs)

        ex = _shared_executor()
        fut_a = ex.submit(seeded, msm_g1_multi, dpk.a_bases, w_cols, "a")
        fut_b1 = ex.submit(seeded, msm_g1_multi, dpk.b1_bases, b_cols, "b1")
        fut_c = ex.submit(seeded, msm_g1_multi, dpk.c_bases, c_cols, "c")
        if plans is not None:
            # PIPELINED arm: per-proof b2 tasks (not one serialized
            # list — a free worker starts proof k's G2 MSM while k-1's
            # runs) interleaved with ladder d-column tasks, so the h
            # multi MSM starts when the LAST column lands instead of
            # after a serial ladder walk.  Segment-plan arm only: its
            # matvec parallelism lives in the C pool, so a d task never
            # submits executor work (workers submitting-and-blocking
            # could exhaust the shared pool).
            b2_futs = [
                ex.submit(seeded, msm_g2_one, dpk.b2_bases, col, "b2") for col in b_cols
            ]
            d_cols = [f.result() for f in [
                ex.submit(seeded, ladder_one_col, w_mont) for w_mont in w_monts
            ]]
        else:
            # oracle arm: ONE serialized b2 task (the pre-tier
            # schedule) — S individual b2 tasks would FIFO-queue ahead
            # of the main-thread ladder's matvec leaves on the shared
            # executor and stall the d-column pipeline the h MSM waits
            # on.
            b2_futs = [ex.submit(
                seeded, lambda: [msm_g2_one(dpk.b2_bases, col, "b2") for col in b_cols]
            )]
            d_cols = ladder_cols()
        h_accs = msm_g1_multi(dpk.h_bases, d_cols, "h")
        a_accs, b1_accs, c_accs = (fut_a.result(), fut_b1.result(), fut_c.result())
        gathered = [f.result() for f in b2_futs]
        b2_accs = gathered if plans is not None else gathered[0]
    else:
        d_cols = ladder_cols()
        a_accs = msm_g1_multi(dpk.a_bases, w_cols, "a")
        b1_accs = msm_g1_multi(dpk.b1_bases, b_cols, "b1")
        b2_accs = [msm_g2_one(dpk.b2_bases, col, "b2") for col in b_cols]
        c_accs = msm_g1_multi(dpk.c_bases, c_cols, "c")
        h_accs = msm_g1_multi(dpk.h_bases, d_cols, "h")

    proofs = [
        _assemble_host(dpk, (a_accs[s], b1_accs[s], b2_accs[s], c_accs[s], h_accs[s]), rs[s], ss[s])
        for s in range(S)
    ]
    from ..utils.metrics import REGISTRY, publish_native_stats

    REGISTRY.counter("zkp2p_proves_total", {"prover": "native_batch"}).inc(S)
    publish_native_stats()
    return proofs


# The service's degradation ladder (pipeline.service) only makes sense
# for provers that actually READ the MSM knobs it flips per rung
# (ZKP2P_MSM_PRECOMP/MULTI/BATCH_AFFINE/OVERLAP are fresh-read here,
# per prove) — mark them so the ladder can tell.
prove_native.reads_msm_knobs = True
prove_native_batch.reads_msm_knobs = True
