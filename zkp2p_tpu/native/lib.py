"""ctypes bridge to the native BN254 library (csrc/zkp2p_native.cpp).

The C++ runtime layer of the framework (the role rapidsnark's native
field library plays in the reference, SURVEY.md §2.2) — loaded lazily
and built on demand with make.  `get_lib()` returns None when the build
fails, so imports never hard-fail and gadget-scale callers keep their
pure-Python paths; a caller for whom those paths would take hours (the
array-path setup, the native prover, chip_smoke.py) treats None as an
error.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import List, Optional, Sequence, Tuple

import numpy as np

_CSRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "csrc")
_SO = os.path.join(_CSRC, "libzkp2p_native.so")

_lib = None
_tried = False


def _int_to_u64x4(x: int) -> np.ndarray:
    return np.array([(x >> (64 * i)) & ((1 << 64) - 1) for i in range(4)], dtype=np.uint64)


def _u64x4_to_int(a) -> int:
    return int(a[0]) | int(a[1]) << 64 | int(a[2]) << 128 | int(a[3]) << 192


def get_lib() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    # Always (re)build from the committed source: a stale or prebuilt .so
    # must never be loaded in preference to the reviewed C++ (the binary is
    # gitignored; `make` is a no-op when the .so is already newer than the
    # source, so this costs one stat on the warm path).  A failed build
    # means no library — whatever .so is on disk was built from other
    # source or for another CPU (-march=native).
    try:
        subprocess.run(["make", "-C", _CSRC], check=True, capture_output=True)
    except (OSError, subprocess.CalledProcessError):
        return None
    try:
        lib = ctypes.CDLL(_SO)
    except OSError:
        return None
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.g1_fixed_base_batch.argtypes = [u64p, u64p, ctypes.c_int, u64p]
    lib.g1_fixed_base_batch_mont.argtypes = [u64p, u64p, ctypes.c_int, u64p]
    lib.g2_fixed_base_batch_mont.argtypes = [u64p, u64p, ctypes.c_int, u64p]
    lib.fp_mul_std.argtypes = [u64p, u64p, u64p]
    lib.bn254_pairing_product_is_one.argtypes = [u64p, u64p, ctypes.c_int]
    lib.bn254_pairing_product_is_one.restype = ctypes.c_int
    lib.groth16_verify_bn254.argtypes = [u64p, ctypes.c_int, ctypes.c_int, u64p, u64p, ctypes.c_int]
    lib.groth16_verify_bn254.restype = ctypes.c_int
    lib.groth16_assemble_bn254.argtypes = [u64p, u64p, u64p, u64p]
    lib.groth16_assemble_bn254.restype = ctypes.c_int
    # Self-check before trusting it: one field mul against Python ints, one
    # fixed-base scalar mul against the host curve oracle, one pairing
    # identity AND one proof's assembly, so a library with subtly wrong
    # curve ops (used for trusted-setup point generation and for every
    # device proof's pi_a, pi_b, pi_c) or a wrong tower (used for the
    # service's sample verify) is rejected, not just one with a broken
    # multiplier.
    from ..field.bn254 import P, R

    a, b = 0x1234567890ABCDEF << 120 | 0x42, P - 12345
    av, bv, cv = _int_to_u64x4(a), _int_to_u64x4(b), np.zeros(4, dtype=np.uint64)
    lib.fp_mul_std(
        av.ctypes.data_as(u64p), bv.ctypes.data_as(u64p), cv.ctypes.data_as(u64p)
    )
    if _u64x4_to_int(cv) != a * b % P:
        return None
    _lib = lib
    from ..curve.host import G1_GEN, g1_mul

    k = 0xDEADBEEFCAFEF00D1234567890ABCDEF
    got = g1_fixed_base_batch(G1_GEN, [k])
    if got is None or got[0] != g1_mul(G1_GEN, k):
        _lib = None
        return None
    # e(a G1, b G2) e(-ab G1, G2) = 1, and not with ab + 1
    from ..curve.host import G2_GENERATOR, g1_neg, g2_mul
    from ..snark.native_verify import pairing_product_is_one

    a, b = 0xA5A5, 0x5A5B
    left = (g1_mul(G1_GEN, a), g2_mul(G2_GENERATOR, b))
    if not pairing_product_is_one(lib, [left, (g1_neg(g1_mul(G1_GEN, a * b)), G2_GENERATOR)]) or (
        pairing_product_is_one(lib, [left, (g1_neg(g1_mul(G1_GEN, a * b + 1)), G2_GENERATOR)])
    ):
        _lib = None
        return None
    # one assembly at full-width blinding, an accumulator at infinity among
    # the five, against the Python form: its bytes or no library
    from types import SimpleNamespace

    from ..snark import native_assemble

    key = SimpleNamespace(
        alpha_1=g1_mul(G1_GEN, 3), beta_1=g1_mul(G1_GEN, 5), delta_1=g1_mul(G1_GEN, 7),
        beta_2=g2_mul(G2_GENERATOR, 5), delta_2=g2_mul(G2_GENERATOR, 7),
    )
    acc = (g1_mul(G1_GEN, 11), g1_mul(G1_GEN, 13), g2_mul(G2_GENERATOR, 13), g1_mul(G1_GEN, 17), None)
    r, s = R - 0xDEADBEEFCAFEF00D, R - 0x1234567890ABCDEF
    if native_assemble.assemble_native(lib, key, acc, r, s) != native_assemble.assemble_python(key, acc, r, s):
        _lib = None
        return None
    return _lib


# Slot order of the C runtime's always-on stats block (csrc StatSlot) —
# the ctypes ABI: index i here reads g_stats[i] there.  Append-only on
# both sides; zkp2p_stats_count() guards against drift at runtime.
STATS_FIELDS = (
    "msm_g1_calls",
    "msm_g2_calls",
    "msm_glv_calls",
    "msm_batch_affine_calls",
    "msm_points",
    "msm_wall_ns",
    "msm_fill_ns",
    "msm_apply_ns",
    "msm_suffix_ns",
    "msm_bailfill_ns",
    "msm_window_last",
    "msm_dbl_lanes",
    "msm_cancel_lanes",
    "msm_defer_hits",
    "pool_jobs",
    "pool_tasks",
    "pool_wait_ns",
    "pool_run_ns",
    "pool_depth_peak",
    "pool_workers",
    "msm_multi_calls",
    "msm_multi_cols",
    "msm_multi_cols_last",
    "msm_multi_prep_ns",
    "msm_fixed_calls",
    "msm_fixed_prep_ns",
    "precomp_build_ns",
    "precomp_table_bytes",
    "matvec_ns",
    "matvec_seg_calls",
    "ntt_stage_ns",
    "msm_inflight",
)


def stats_snapshot() -> Optional[dict]:
    """Read the native runtime's lock-free counter block as a dict
    (field -> int); None if the native lib is unavailable.  Purely
    observational — counters keep accumulating."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "zkp2p_stats_count"):
        # a stale pre-stats .so (toolchain gone, rebuild failed) still
        # passes get_lib's self-checks — observation must degrade to
        # None, never AttributeError a finished prove
        return None
    n = int(lib.zkp2p_stats_count())
    buf = np.zeros(max(n, len(STATS_FIELDS)), dtype=np.int64)
    lib.zkp2p_stats_snapshot.argtypes = [ctypes.POINTER(ctypes.c_longlong)]
    lib.zkp2p_stats_snapshot(buf.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)))
    # a lib ahead of this bridge exposes extra slots we cannot name; a
    # lib behind it reads 0 for the missing names (buf is zero-filled
    # past n) — either way every STATS_FIELDS key is present, so
    # consumers never KeyError on version skew
    return {name: int(buf[i]) for i, name in enumerate(STATS_FIELDS)}


def ifma_available() -> bool:
    """True when the loaded native lib's AVX512-IFMA 52-bit tier is
    usable (hardware present AND not disabled via ZKP2P_NATIVE_IFMA —
    `zkp2p_ifma_available` applies the C runtime's own gate, so this
    mirrors exactly the arm the drivers will take).  False when the lib
    is unavailable."""
    lib = get_lib()
    try:
        return bool(lib is not None and lib.zkp2p_ifma_available())
    except Exception:  # noqa: BLE001 — a stale pre-IFMA .so must not crash callers
        return False


def cache_sizes() -> Optional[dict]:
    """Detected data-cache capacities in bytes via the C runtime's
    sysconf probe: {"l1d": int, "l2": int, "l3": int}, 0 = that level is
    unknown to the kernel/libc.  None when the native lib is unavailable
    or predates the probe (stale .so — degrade, never AttributeError;
    the host-profile layer falls back to sysfs, then to documented
    constants)."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "zkp2p_cache_size"):
        return None
    lib.zkp2p_cache_size.argtypes = [ctypes.c_int]
    lib.zkp2p_cache_size.restype = ctypes.c_long
    return {
        "l1d": int(lib.zkp2p_cache_size(1)),
        "l2": int(lib.zkp2p_cache_size(2)),
        "l3": int(lib.zkp2p_cache_size(3)),
    }


def native_cpu_count() -> Optional[int]:
    """Online logical CPU count as the C runtime's WorkPool sees it;
    None when the lib is unavailable/stale, 0 when the libc cannot say."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "zkp2p_cpu_count"):
        return None
    lib.zkp2p_cpu_count.restype = ctypes.c_long
    return int(lib.zkp2p_cpu_count())


def stats_reset() -> bool:
    """Zero the native counter block; False if the lib is unavailable
    (or predates the stats block — see stats_snapshot)."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "zkp2p_stats_reset"):
        return False
    lib.zkp2p_stats_reset()
    return True


def g1_fixed_base_batch(base: Tuple[int, int], scalars: Sequence[int]) -> Optional[List]:
    """Batch k_i * base over G1; None if the native lib is unavailable.
    Returns affine (x, y) int tuples, None entries for infinity."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(scalars)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    base_arr = np.concatenate([_int_to_u64x4(base[0]), _int_to_u64x4(base[1])])
    sc = np.zeros((n, 4), dtype=np.uint64)
    for i, s in enumerate(scalars):
        sc[i] = _int_to_u64x4(int(s))
    out = np.zeros((n, 8), dtype=np.uint64)
    lib.g1_fixed_base_batch(
        base_arr.ctypes.data_as(u64p),
        sc.ctypes.data_as(u64p),
        n,
        out.ctypes.data_as(u64p),
    )
    res = []
    for i in range(n):
        x = _u64x4_to_int(out[i, :4])
        y = _u64x4_to_int(out[i, 4:])
        res.append(None if x == 0 and y == 0 else (x, y))
    return res


def _pack_affine(points: Sequence) -> np.ndarray:
    """Affine (x, y) int tuples (None = infinity -> all-zero hole) to the
    (n, 8) u64 layout every g1 native entry point consumes — ONE shared
    encoder so the infinity convention cannot drift between callers."""
    n = len(points)
    bases = np.zeros((n, 8), dtype=np.uint64)
    for i, p in enumerate(points):
        if p is None:
            continue
        bases[i, :4] = _int_to_u64x4(p[0])
        bases[i, 4:] = _int_to_u64x4(p[1])
    return bases


def g1_scale_batch(points: Sequence, scalar: int) -> Optional[List]:
    """out[i] = scalar * points[i] over G1 (shared scalar — the ceremony
    delta-rescale); None if the native lib is unavailable.  Points are
    affine (x, y) int tuples with None = infinity, same out."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(points)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.g1_scale_batch.argtypes = [u64p, ctypes.c_long, u64p, u64p]
    bases = _pack_affine(points)
    sc = _int_to_u64x4(int(scalar))
    out = np.zeros((n, 8), dtype=np.uint64)
    lib.g1_scale_batch(bases.ctypes.data_as(u64p), n, sc.ctypes.data_as(u64p), out.ctypes.data_as(u64p))
    res = []
    for i in range(n):
        x = _u64x4_to_int(out[i, :4])
        y = _u64x4_to_int(out[i, 4:])
        res.append(None if x == 0 and y == 0 else (x, y))
    return res


def g1_msm(points: Sequence, scalars: Sequence[int]) -> Optional[object]:
    """Native variable-base MSM, std-form affine tuples in/out ("sentinel
    False" when the lib is unavailable so callers can distinguish the
    infinity result None from no-lib)."""
    lib = get_lib()
    if lib is None or not points:
        return False if lib is None else None
    n = len(points)
    if len(scalars) != n:
        raise ValueError(f"g1_msm: {n} points but {len(scalars)} scalars")
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.fp_to_mont.argtypes = [u64p, u64p, ctypes.c_int]
    lib.g1_msm_pippenger.argtypes = [u64p, u64p, ctypes.c_long, ctypes.c_int, u64p]
    bases = _pack_affine(points)
    bm = np.zeros_like(bases)
    lib.fp_to_mont(bases.ctypes.data_as(u64p), bm.ctypes.data_as(u64p), 2 * n)
    sc = _scalars_to_u64([int(s) for s in scalars])
    out = np.zeros(8, dtype=np.uint64)
    # the ONE window policy (IFMA-aware clamp included) lives in
    # native_prove; late import avoids the module cycle
    from ..prover.native_prove import _pick_window

    c = _pick_window(n)
    lib.g1_msm_pippenger(bm.ctypes.data_as(u64p), sc.ctypes.data_as(u64p), n, c, out.ctypes.data_as(u64p))
    x, y = _u64x4_to_int(out[:4]), _u64x4_to_int(out[4:])
    return None if x == 0 and y == 0 else (x, y)


def g1_msm_multi(points: Sequence, scalar_cols: Sequence[Sequence[int]]) -> Optional[object]:
    """Multi-column native MSM: ONE sweep over the shared base array, S
    scalar columns, S results (csrc g1_msm_pippenger_multi).  Columns
    shorter than the base set are zero-padded (a zero scalar contributes
    nothing, so the result matches the truncated sequential MSM).
    Returns a list of affine (x, y) tuples — None entries for infinity
    columns — or the "sentinel False" when the lib is unavailable."""
    lib = get_lib()
    if lib is None:
        return False
    S = len(scalar_cols)
    if S == 0:
        return []
    if not points:
        return [None] * S  # every column of an empty MSM is infinity
    n = len(points)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.fp_to_mont.argtypes = [u64p, u64p, ctypes.c_int]
    lib.g1_msm_pippenger_multi.argtypes = [
        u64p, u64p, ctypes.c_long, ctypes.c_int, ctypes.c_int, ctypes.c_int, u64p,
    ]
    bases = _pack_affine(points)
    bm = np.zeros_like(bases)
    lib.fp_to_mont(bases.ctypes.data_as(u64p), bm.ctypes.data_as(u64p), 2 * n)
    sc = np.zeros((S, n, 4), dtype=np.uint64)
    for s, col in enumerate(scalar_cols):
        if len(col) > n:
            raise ValueError(f"g1_msm_multi: column {s} has {len(col)} scalars for {n} points")
        if col:
            sc[s, : len(col)] = _scalars_to_u64([int(k) for k in col])
    sc = np.ascontiguousarray(sc)
    out = np.zeros((S, 8), dtype=np.uint64)
    from ..prover.native_prove import _pick_window

    c = _pick_window(n)
    lib.g1_msm_pippenger_multi(
        bm.ctypes.data_as(u64p), sc.ctypes.data_as(u64p), n, S, c, 1,
        out.ctypes.data_as(u64p),
    )
    res = []
    for s in range(S):
        x, y = _u64x4_to_int(out[s, :4]), _u64x4_to_int(out[s, 4:])
        res.append(None if x == 0 and y == 0 else (x, y))
    return res


def _scalars_to_u64(scalars: Sequence[int]) -> np.ndarray:
    """(n, 4) u64 little-endian — via one bytes join, not a Python limb
    loop (to_bytes is C-speed; this path handles millions of scalars)."""
    buf = b"".join(int(s).to_bytes(32, "little") for s in scalars)
    return np.frombuffer(buf, dtype="<u8").reshape(len(scalars), 4)


def _u64_to_limbs16(a: np.ndarray, out: Optional[np.ndarray] = None) -> np.ndarray:
    """(..., 4) u64 -> (..., 16) u32 of 16-bit limbs (the jfield layout),
    widened straight into `out` where the caller has the place for them."""
    limbs = np.ascontiguousarray(a).view("<u2").reshape(*a.shape[:-1], 16)
    if out is None:
        return limbs.astype(np.uint32)
    out[...] = limbs
    return out


# rows a call of a fixed-base batch kernel: each call builds its own window
# table (8,160 additions) and inverts once, so a slice this long repays both
_FIXED_BASE_MIN_ROWS = 1 << 14


def _fixed_base_rows(kernel, base_arr: np.ndarray, sc: np.ndarray, out: np.ndarray) -> None:
    """`kernel(base, scalars, n, out)` over the rows of `sc`, in slices on
    a thread each: the kernels are single-threaded, keep their table on
    the heap a call and run without the GIL, and every row of `out` is
    the affine point of its own scalar whatever slice it falls in.  A key
    of 2^22 domain points is 15 million rows (prover.setup_device)."""
    import concurrent.futures

    from ..utils.config import load_config

    u64p = ctypes.POINTER(ctypes.c_uint64)
    n = len(sc)
    threads = load_config().native_threads or os.cpu_count() or 1
    step = max(_FIXED_BASE_MIN_ROWS, -(-n // threads))

    def run(lo: int) -> None:
        hi = min(n, lo + step)
        kernel(base_arr.ctypes.data_as(u64p), sc[lo:hi].ctypes.data_as(u64p), hi - lo, out[lo:hi].ctypes.data_as(u64p))

    starts = range(0, n, step)
    if len(starts) <= 1:
        run(0)
        return
    with concurrent.futures.ThreadPoolExecutor(max_workers=len(starts)) as pool:
        list(pool.map(run, starts))


def g1_fixed_base_batch_mont_limbs(base: Tuple[int, int], scalars: Sequence[int]):
    """Batch k_i * base over G1, emitted directly as Montgomery (n, 16)
    u32 limb arrays (the DeviceProvingKey base layout) — skips every
    per-point Python conversion.  None if the native lib is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(scalars)
    base_arr = np.concatenate([_int_to_u64x4(base[0]), _int_to_u64x4(base[1])])
    sc = np.ascontiguousarray(_scalars_to_u64(scalars))
    out = np.zeros((n, 8), dtype=np.uint64)
    _fixed_base_rows(lib.g1_fixed_base_batch_mont, base_arr, sc, out)
    limbs = _u64_to_limbs16(out.reshape(n, 2, 4))  # (n, 2, 16)
    return limbs[:, 0], limbs[:, 1]


def g2_fixed_base_batch_mont_limbs(base, scalars: Sequence[int]):
    """Batch k_i * base over G2 -> Montgomery (n, 2, 16) u32 limb arrays
    (x, y as Fq2 pairs).  `base` is a host G2Point ((Fq2, Fq2) affine).
    None if the native lib is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(scalars)
    x, y = base
    base_arr = np.concatenate(
        [_int_to_u64x4(x.c0), _int_to_u64x4(x.c1), _int_to_u64x4(y.c0), _int_to_u64x4(y.c1)]
    )
    sc = np.ascontiguousarray(_scalars_to_u64(scalars))
    out = np.zeros((n, 16), dtype=np.uint64)
    _fixed_base_rows(lib.g2_fixed_base_batch_mont, base_arr, sc, out)
    limbs = _u64_to_limbs16(out.reshape(n, 4, 4))  # (n, 4, 16): x0 x1 y0 y1
    return limbs[:, 0:2], limbs[:, 2:4]
