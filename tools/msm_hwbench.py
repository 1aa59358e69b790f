#!/usr/bin/env python
"""Hardware A/B microbench: XLA vs Pallas curve kernels inside the MSM.

Round-4 follow-up to docs/ROOFLINE.md: the fused Montgomery mul measured
136.5 M muls/s (7.9x XLA) on the chip; this script measures what that
buys at the POINT and MSM level, which is what the prover actually runs
(SURVEY.md §3.1 hot loop 2 — the reference's rapidsnark MSMs).

Selects the implementation via the existing env flags (read at import
time, so each arm runs in its own process).  The defaults are "auto"
(= pallas on TPU), so the XLA arm must PIN BOTH flags:

  ZKP2P_CURVE_KERNEL=xla ZKP2P_FIELD_MUL=xla python tools/msm_hwbench.py \
      [--n 131072] [--window 4] [--lanes ...]

Prints per-stage rates: the six batched point ops (G1 and G2 add_mixed,
add and double: the MSM inner ops) at `--adds` lanes, and a full G1
msm_windowed at the requested size.

`--native` benches the C++ Pippenger tier (csrc zkp2p_native) instead of
the JAX path — the native prover's arm (oracle and `cpu` row).  The
batch-affine bucket knob is A/B-able there:

  python tools/msm_hwbench.py --native --n 524288 --glv --batch-affine
  python tools/msm_hwbench.py --native --n 524288 --glv --no-batch-affine

`--columns S` (native arm) benches the cross-proof multi-column kernel —
one base sweep filling S independent bucket sets, batch-affine inversion
rounds shared across columns — against S sequential MSMs, min-of-reps,
with a result-hash parity echo:

  python tools/msm_hwbench.py --native --n 131072 --columns 4 [--glv]

`--precomp` (native arm) benches the fixed-base precomputed-table tier
(csrc g1_msm_pippenger_fixed / _fixed_multi with --columns) against the
variable-base oracle arm (--glv picks which), building the level tables
in-process first; `--table-depth` sets the level count (q derives as
ceil(W/depth)); parity hash echoed like the --columns convention:

  python tools/msm_hwbench.py --native --n 524288 --precomp --glv
  python tools/msm_hwbench.py --native --n 524288 --precomp --table-depth 4
  python tools/msm_hwbench.py --native --n 131072 --precomp --columns 4

Each arm runs in its own process anyway (import-time constants on the
JAX side; one clean env per arm on the native side).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# --json accumulator: every bench arm appends one record (arm, shape,
# min-of-reps seconds, parity hash where the arm has an oracle), and
# main() emits ONE JSON document after all text output.  The text lines
# above it stay byte-stable — existing docs/scripts scrape them; the
# tune pass (zkp2p_tpu.pipeline.tune) consumes the records.
_RESULTS = []


def _rec(**kw):
    _RESULTS.append(kw)


def _native_bench(args):
    """The C++ Pippenger arm: random full-width scalars over a tiled
    base set, min-of-reps wall time (this box is ±30% noisy), result
    x-coordinate echoed so A/B arms can be cross-checked for parity."""
    import ctypes
    import random

    import numpy as np

    from zkp2p_tpu.field.bn254 import GLV_MAX_BITS, R
    from zkp2p_tpu.curve.host import G1_GENERATOR, g1_mul
    from zkp2p_tpu.native.lib import _pack_affine, _scalars_to_u64
    from zkp2p_tpu.prover.native_prove import (
        _glv_consts,
        _lib,
        _p,
        _pick_window,
        _pick_window_glv,
    )
    from zkp2p_tpu.utils.config import load_config

    lib = _lib()
    assert lib is not None, "native library unavailable"
    load_config()  # resolve + validate env the same way the prover does
    from zkp2p_tpu.prover.native_prove import _n_threads

    # the PROVER's thread resolution (env else core count), so the bench
    # measures the arm the native prover actually runs; pin
    # ZKP2P_NATIVE_THREADS=1 for single-worker microbenches
    threads = _n_threads()
    if args.window is not None and args.window <= 0:
        args.window = None  # 0 = auto, same as omitting the flag
    ba = bool(lib.zkp2p_batch_affine_enabled())
    print(
        f"native arm: n={args.n} ifma={'on' if lib.zkp2p_ifma_available() else 'off'} "
        f"threads={threads} glv={'on' if args.glv else 'off'} "
        f"batch_affine={'on' if ba else 'off'}",
        flush=True,
    )
    rng = np.random.default_rng(7)
    host_pts = [g1_mul(G1_GENERATOR, int(k)) for k in rng.integers(1, 1 << 30, 64)]
    n = args.n
    bases = _pack_affine(host_pts)
    bm64 = np.zeros_like(bases)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.fp_to_mont.argtypes = [u64p, u64p, ctypes.c_int]
    lib.fp_to_mont(_p(bases), _p(bm64), 2 * 64)
    bm = np.ascontiguousarray(np.tile(bm64, ((n + 63) // 64, 1))[:n])
    py_rng = random.Random(11)
    sc = np.ascontiguousarray(_scalars_to_u64([py_rng.randrange(R) for _ in range(n)]))
    out = np.zeros(8, dtype=np.uint64)
    reps = args.reps
    if args.precomp:
        _native_precomp_bench(args, lib, bm, sc, threads)
        return
    if args.columns > 1:
        _native_multi_bench(args, lib, bm, threads)
        return
    if args.glv:
        c = args.window if args.window is not None else _pick_window_glv(n, threads=threads)
        phi = np.zeros_like(bm)
        lib.g1_glv_phi_bases(_p(bm), n, _p(_glv_consts()), _p(phi))
        b2 = np.ascontiguousarray(np.concatenate([bm, phi]))

        def run():
            lib.g1_msm_pippenger_glv_mt(
                _p(b2), _p(sc), n, n, c, threads, _p(_glv_consts()), GLV_MAX_BITS, _p(out)
            )
    else:
        c = args.window if args.window is not None else _pick_window(n, threads=threads)

        def run():
            lib.g1_msm_pippenger_mt(_p(bm), _p(sc), n, c, threads, _p(out))

    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        run()
        times.append(time.perf_counter() - t0)
    best = min(times)
    x = int.from_bytes(out[:4].tobytes(), "little")
    print(
        f"native msm: n={n} c={c} reps={reps} min={best*1e3:.0f} ms "
        f"(all: {' '.join(f'{t*1e3:.0f}' for t in times)}) -> {n/best/1e6:.3f} M pts/s "
        f"result_x={x % (1 << 64):#x}",
        flush=True,
    )
    import hashlib

    _rec(
        arm="native_msm", tag="glv" if args.glv else "plain", n=n, c=c,
        threads=threads, reps=reps, min_s=best, times_s=times,
        result_hash=hashlib.sha256(out.tobytes()).hexdigest()[:16],
    )


def _native_apply_prof_bench(args):
    """--apply-prof arm: isolated fill/apply/suffix/bailfill attribution
    for the MSM apply-interleave lever, riding the csrc `g_prof_*`
    counters (ZKP2P_MSM_PROF is latched ON in main() BEFORE the native
    lib loads).  Interleaved same-process A/B — ZKP2P_MSM_INTERLEAVE=1
    vs =0 alternate every rep (the C side fresh-reads the env per call),
    min-of-reps per arm, counters drained before each rep so every
    split belongs to exactly one call — with the usual result-hash
    parity echo.  NOTE the fill window ENCLOSES the apply window
    (sched = fill - apply), so the columns do not sum to the wall."""
    import ctypes
    import hashlib
    import random

    import numpy as np

    from zkp2p_tpu.field.bn254 import GLV_MAX_BITS, R
    from zkp2p_tpu.curve.host import G1_GENERATOR, g1_mul
    from zkp2p_tpu.native.lib import _pack_affine, _scalars_to_u64
    from zkp2p_tpu.prover.native_prove import (
        _glv_consts,
        _lib,
        _n_threads,
        _p,
        _pick_window,
        _pick_window_glv,
    )

    lib = _lib()
    assert lib is not None, "native library unavailable"
    lib.zkp2p_msm_prof_dump.argtypes = [ctypes.POINTER(ctypes.c_longlong)]
    threads = _n_threads()
    n = args.n
    rng = np.random.default_rng(7)
    host_pts = [g1_mul(G1_GENERATOR, int(k)) for k in rng.integers(1, 1 << 30, 64)]
    bases = _pack_affine(host_pts)
    bm64 = np.zeros_like(bases)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.fp_to_mont.argtypes = [u64p, u64p, ctypes.c_int]
    lib.fp_to_mont(_p(bases), _p(bm64), 2 * 64)
    bm = np.ascontiguousarray(np.tile(bm64, ((n + 63) // 64, 1))[:n])
    py_rng = random.Random(11)
    sc = np.ascontiguousarray(_scalars_to_u64([py_rng.randrange(R) for _ in range(n)]))
    out = np.zeros(8, dtype=np.uint64)
    if args.glv:
        c = args.window if args.window is not None else _pick_window_glv(n, threads=threads)
        phi = np.zeros_like(bm)
        lib.g1_glv_phi_bases(_p(bm), n, _p(_glv_consts()), _p(phi))
        b2 = np.ascontiguousarray(np.concatenate([bm, phi]))

        def run():
            lib.g1_msm_pippenger_glv_mt(
                _p(b2), _p(sc), n, n, c, threads, _p(_glv_consts()), GLV_MAX_BITS, _p(out)
            )
    else:
        c = args.window if args.window is not None else _pick_window(n, threads=threads)

        def run():
            lib.g1_msm_pippenger_mt(_p(bm), _p(sc), n, c, threads, _p(out))

    def drain():
        buf = (ctypes.c_longlong * 4)()
        lib.zkp2p_msm_prof_dump(buf)
        return [int(v) for v in buf]

    print(
        f"apply-prof: n={n} c={c} threads={threads} "
        f"glv={'on' if args.glv else 'off'} reps={args.reps} "
        "(interleaved ZKP2P_MSM_INTERLEAVE=1/0 per rep)",
        flush=True,
    )
    best = {}  # arm -> (wall_s, [fill, apply, suffix, bailfill] ns)
    hashes = {}
    for rep in range(args.reps):
        for arm in ("1", "0"):
            os.environ["ZKP2P_MSM_INTERLEAVE"] = arm
            drain()
            t0 = time.perf_counter()
            run()
            wall = time.perf_counter() - t0
            split = drain()
            if arm not in best or wall < best[arm][0]:
                best[arm] = (wall, split)
            hashes.setdefault(arm, hashlib.sha256(out.tobytes()).hexdigest()[:16])
    os.environ.pop("ZKP2P_MSM_INTERLEAVE", None)
    names = ("fill", "apply", "suffix", "bailfill")
    for arm in ("0", "1"):
        wall, split = best[arm]
        cols = " ".join(f"{nm}={v / 1e6:.1f}ms" for nm, v in zip(names, split))
        print(
            f"  interleave={arm}: wall={wall * 1e3:.1f}ms {cols} "
            f"(sched={ (split[0] - split[1]) / 1e6:.1f}ms) "
            f"result_hash={hashes[arm]}",
            flush=True,
        )
    w1, s1 = best["1"]
    w0, s0 = best["0"]
    parity = hashes["1"] == hashes["0"]
    print(
        f"  speedup: wall {w0 / w1:.3f}x  apply "
        f"{(s0[1] / s1[1]) if s1[1] else float('nan'):.3f}x  "
        f"parity={'OK' if parity else 'MISMATCH'}",
        flush=True,
    )
    assert parity, "apply-prof arms disagree on the MSM result"
    _rec(
        arm="native_apply_prof", tag="glv" if args.glv else "plain", n=n, c=c,
        threads=threads, reps=args.reps,
        interleave_on={"wall_s": w1, **{nm + "_ns": v for nm, v in zip(names, s1)}},
        interleave_off={"wall_s": w0, **{nm + "_ns": v for nm, v in zip(names, s0)}},
        result_hash=hashes["1"],
    )


def _native_precomp_bench(args, lib, bm, sc, threads):
    """--precomp arm: fixed-base precomputed-table drivers vs the
    variable-base oracle (GLV when --glv, plain otherwise) — tables
    built in-process at the prover's fixed-tier window, min-of-reps per
    arm, speedup ratio, and a result-hash parity echo matching the
    --columns convention.  --table-depth sets the level count (the
    ZKP2P_MSM_PRECOMP_DEPTH dial); --columns S runs the _fixed_multi
    driver against S sequential oracle MSMs."""
    import hashlib
    import random

    import numpy as np

    from zkp2p_tpu.field.bn254 import GLV_MAX_BITS, R
    from zkp2p_tpu.native.lib import _scalars_to_u64
    from zkp2p_tpu.prover.native_prove import (
        _glv_consts,
        _p,
        _pick_window,
        _pick_window_glv,
    )
    from zkp2p_tpu.prover.precomp import _resolve_geometry

    n, S, reps = bm.shape[0], max(1, args.columns), args.reps
    # the prover's own geometry resolver (uncapped budget: the bench
    # measures the requested depth, the prover's RAM guard is its own
    # concern) — so the tool can never drift from what the prover runs.
    # No argtype declarations here: the `lib` handle comes from
    # native_prove._lib(), which already configures the precomp ABI.
    cf, q, levels = _resolve_geometry(n, args.table_depth, 1 << 62)
    t0 = time.perf_counter()
    table = np.zeros((levels * n, 8), dtype=np.uint64)
    lib.g1_precomp_build(_p(bm), n, cf, q, levels, threads, _p(table))
    t_build = time.perf_counter() - t0
    table52 = np.zeros((levels * n, 10), dtype=np.uint64)
    p52 = _p(table52) if lib.g1_precomp_to52(_p(table), levels * n, _p(table52)) else None
    print(
        f"precomp tables: c={cf} q={q} levels={levels} "
        f"({table.nbytes + (table52.nbytes if p52 else 0):,} B resident) "
        f"built in {t_build:.1f}s",
        flush=True,
    )

    py_rng = random.Random(13)
    if S > 1:
        cols = [[py_rng.randrange(R) for _ in range(n)] for _ in range(S)]
        scm = np.ascontiguousarray(np.stack([_scalars_to_u64(col) for col in cols]))
    else:
        scm = np.ascontiguousarray(sc.reshape(1, n, 4))
    out_fixed = np.zeros((S, 8), dtype=np.uint64)
    out_ref = np.zeros((S, 8), dtype=np.uint64)

    def run_fixed():
        if S > 1:
            lib.g1_msm_pippenger_fixed_multi(
                _p(table), p52, _p(scm), n, n, S, levels, cf, q, threads, _p(out_fixed)
            )
        else:
            lib.g1_msm_pippenger_fixed(
                _p(table), p52, _p(scm), n, n, levels, cf, q, threads, _p(out_fixed)
            )

    if args.glv:
        c_ref = args.window if args.window is not None else _pick_window_glv(n, threads=threads)
        phi = np.zeros_like(bm)
        lib.g1_glv_phi_bases(_p(bm), n, _p(_glv_consts()), _p(phi))
        b2 = np.ascontiguousarray(np.concatenate([bm, phi]))

        def run_ref():
            for s in range(S):
                col = np.ascontiguousarray(scm[s])
                lib.g1_msm_pippenger_glv_mt(
                    _p(b2), _p(col), n, n, c_ref, threads, _p(_glv_consts()),
                    GLV_MAX_BITS, _p(out_ref[s]),
                )
    else:
        c_ref = args.window if args.window is not None else _pick_window(n, threads=threads)

        def run_ref():
            for s in range(S):
                col = np.ascontiguousarray(scm[s])
                lib.g1_msm_pippenger_mt(_p(bm), _p(col), n, c_ref, threads, _p(out_ref[s]))

    t_fixed, t_ref = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        run_fixed()
        t_fixed.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_ref()
        t_ref.append(time.perf_counter() - t0)
    bf, br = min(t_fixed), min(t_ref)
    parity = "OK" if np.array_equal(out_fixed, out_ref) else "MISMATCH"
    h = hashlib.sha256(out_fixed.tobytes()).hexdigest()[:16]
    tag = "glv" if args.glv else "plain"
    print(
        f"native msm precomp[vs {tag}]: n={n} S={S} c={cf} q={q} L={levels} reps={reps} "
        f"fixed min={bf*1e3:.0f} ms vs oracle(c={c_ref}) min={br*1e3:.0f} ms "
        f"-> {br/bf:.2f}x ({S*n/bf/1e6:.3f} M col-pts/s) "
        f"parity={parity} result_hash={h}",
        flush=True,
    )
    _rec(
        arm="native_msm_precomp", tag=tag, n=n, S=S, c=cf, q=q, levels=levels,
        threads=threads, reps=reps, build_s=t_build, min_s=bf,
        oracle_min_s=br, oracle_c=c_ref, parity=parity, result_hash=h,
    )
    assert parity == "OK", "precomp result diverged from the variable-base oracle"


def _native_multi_bench(args, lib, bm, threads):
    """--columns S sweep: the multi-column kernel (one base sweep, S
    scalar columns) vs S sequential single-column MSMs — min-of-reps
    wall per arm, speedup ratio, and a result-hash parity check (the
    sequential driver is the byte oracle)."""
    import ctypes
    import hashlib
    import random

    import numpy as np

    from zkp2p_tpu.field.bn254 import GLV_MAX_BITS, R
    from zkp2p_tpu.native.lib import _scalars_to_u64
    from zkp2p_tpu.prover.native_prove import (
        _glv_consts,
        _p,
        _pick_window,
        _pick_window_glv,
    )

    u64p = ctypes.POINTER(ctypes.c_uint64)
    n, S, reps = bm.shape[0], args.columns, args.reps
    py_rng = random.Random(13)
    cols = [[py_rng.randrange(R) for _ in range(n)] for _ in range(S)]
    sc = np.ascontiguousarray(np.stack([_scalars_to_u64(col) for col in cols]))
    out_multi = np.zeros((S, 8), dtype=np.uint64)
    out_seq = np.zeros((S, 8), dtype=np.uint64)
    if args.glv:
        c = args.window if args.window is not None else _pick_window_glv(n, threads=threads)
        phi = np.zeros_like(bm)
        lib.g1_glv_phi_bases.argtypes = [u64p, ctypes.c_long, u64p, u64p]
        lib.g1_glv_phi_bases(_p(bm), n, _p(_glv_consts()), _p(phi))
        b2 = np.ascontiguousarray(np.concatenate([bm, phi]))
        lib.g1_msm_pippenger_glv_multi.argtypes = [
            u64p, u64p, ctypes.c_long, ctypes.c_long, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, u64p, ctypes.c_int, u64p,
        ]

        def run_multi():
            lib.g1_msm_pippenger_glv_multi(
                _p(b2), _p(sc), n, n, S, c, threads, _p(_glv_consts()),
                GLV_MAX_BITS, _p(out_multi),
            )

        def run_seq():
            for s in range(S):
                col = np.ascontiguousarray(sc[s])
                lib.g1_msm_pippenger_glv_mt(
                    _p(b2), _p(col), n, n, c, threads, _p(_glv_consts()),
                    GLV_MAX_BITS, _p(out_seq[s]),
                )
    else:
        c = args.window if args.window is not None else _pick_window(n, threads=threads)
        lib.g1_msm_pippenger_multi.argtypes = [
            u64p, u64p, ctypes.c_long, ctypes.c_int, ctypes.c_int, ctypes.c_int, u64p,
        ]

        def run_multi():
            lib.g1_msm_pippenger_multi(_p(bm), _p(sc), n, S, c, threads, _p(out_multi))

        def run_seq():
            for s in range(S):
                col = np.ascontiguousarray(sc[s])
                lib.g1_msm_pippenger_mt(_p(bm), _p(col), n, c, threads, _p(out_seq[s]))

    t_multi, t_seq = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        run_multi()
        t_multi.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_seq()
        t_seq.append(time.perf_counter() - t0)
    bm_multi, bm_seq = min(t_multi), min(t_seq)
    parity = "OK" if np.array_equal(out_multi, out_seq) else "MISMATCH"
    h = hashlib.sha256(out_multi.tobytes()).hexdigest()[:16]
    tag = "glv" if args.glv else "plain"
    print(
        f"native msm multi[{tag}]: n={n} S={S} c={c} reps={reps} "
        f"multi min={bm_multi*1e3:.0f} ms vs {S}x sequential min={bm_seq*1e3:.0f} ms "
        f"-> {bm_seq/bm_multi:.2f}x ({S*n/bm_multi/1e6:.3f} M col-pts/s) "
        f"parity={parity} result_hash={h}",
        flush=True,
    )
    _rec(
        arm="native_msm_multi", tag=tag, n=n, S=S, c=c, threads=threads,
        reps=reps, min_s=bm_multi, seq_min_s=bm_seq, parity=parity,
        result_hash=h,
    )
    assert parity == "OK", "multi-column result diverged from the sequential oracle"


def _ladder_bench(args):
    """--ladder: the non-MSM floor in isolation (docs/TUNING.md
    §non-MSM) — the segmented matvec vs the scatter `fr_matvec` oracle,
    and the H ladder with the pool-parallel fused NTT pipeline vs the
    3-wide unfused arm.  Interleaved same-process A/B (both knobs are
    fresh-read in csrc), min-of-reps, parity asserted on output bytes.

      python tools/msm_hwbench.py --ladder --n 524288 [--reps 5]
    """
    import ctypes

    import numpy as np

    from zkp2p_tpu.field.bn254 import fr_domain_root
    from zkp2p_tpu.prover import matvec_plan
    from zkp2p_tpu.prover.native_prove import _lib, _n_threads, _p
    from zkp2p_tpu.snark.groth16 import coset_gen

    lib = _lib()
    assert lib is not None, "native library unavailable"
    u64p = ctypes.POINTER(ctypes.c_uint64)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    i64p = ctypes.POINTER(ctypes.c_longlong)
    threads = _n_threads()
    m = args.n
    log_m = m.bit_length() - 1
    assert 1 << log_m == m, "--ladder needs a power-of-two --n (the NTT domain)"
    print(
        f"ladder arm: m=2^{log_m} threads={threads} "
        f"ifma={'on' if lib.zkp2p_ifma_available() else 'off'} reps={args.reps}",
        flush=True,
    )
    g = np.random.default_rng(17)

    def rand_fr(n):
        a = g.integers(0, 1 << 64, size=(n, 4), dtype=np.uint64)
        a[:, 3] &= np.uint64((1 << 60) - 1)  # < 2^252 < r
        return np.ascontiguousarray(a)

    def mont(std):
        out = np.zeros_like(std)
        lib.fr_to_mont_batch(_p(std), _p(out), std.shape[0])
        return out

    # ---- matvec: venmo-like density (~4 nnz/row), random wires/rows
    nnz = 4 * m
    coeff = mont(rand_fr(nnz))
    wire = g.integers(0, m, size=nnz, dtype=np.uint32)
    row = g.integers(0, m, size=nnz, dtype=np.uint32)
    w_mont = mont(rand_fr(m))
    cp, wp, _perm, seg_starts, seg_rows = matvec_plan._build(coeff, wire, row)
    c52 = matvec_plan._pack52(lib, cp)
    outs = {}
    times = {"oracle": [], "seg": []}
    for _ in range(args.reps):
        for arm in ("oracle", "seg"):  # interleaved
            out = np.zeros((m, 4), dtype=np.uint64)
            t0 = time.perf_counter()
            if arm == "oracle":
                lib.fr_matvec(
                    _p(coeff), wire.ctypes.data_as(u32p), row.ctypes.data_as(u32p),
                    nnz, _p(w_mont), m, _p(out),
                )
            else:
                lib.fr_matvec_seg(
                    _p(c52) if c52 is not None else None, _p(cp),
                    wp.ctypes.data_as(u32p), seg_starts.ctypes.data_as(i64p),
                    seg_rows.ctypes.data_as(u32p), seg_rows.shape[0],
                    _p(w_mont), m, threads, _p(out),
                )
            times[arm].append(time.perf_counter() - t0)
            outs[arm] = out
    assert np.array_equal(outs["oracle"], outs["seg"]), "segmented matvec diverged"
    mo, ms = min(times["oracle"]), min(times["seg"])
    print(
        f"matvec nnz={nnz}: oracle min={mo*1e3:.1f} ms seg min={ms*1e3:.1f} ms "
        f"-> {mo/ms:.2f}x parity=OK",
        flush=True,
    )
    _rec(
        arm="ladder_matvec", m=m, nnz=nnz, threads=threads, reps=args.reps,
        min_s=ms, oracle_min_s=mo, parity="OK",
    )

    # ---- H ladder: pool-fused arm vs the 3-wide unfused arm
    wroot = np.ascontiguousarray(
        np.frombuffer(int(fr_domain_root(log_m)).to_bytes(32, "little"), dtype="<u8")
    )
    gcos = np.ascontiguousarray(
        np.frombuffer(int(coset_gen(log_m)).to_bytes(32, "little"), dtype="<u8")
    )
    base = mont(rand_fr(3 * m)).reshape(3, m, 4)
    lt = {"pool": [], "unfused": []}
    louts = {}
    for _ in range(args.reps):
        for arm, knob in (("pool", "1"), ("unfused", "0")):
            os.environ["ZKP2P_NTT_POOL"] = knob
            abc = [np.ascontiguousarray(base[i].copy()) for i in range(3)]
            d = np.zeros((m, 4), dtype=np.uint64)
            t0 = time.perf_counter()
            lib.fr_h_ladder(
                _p(abc[0]), _p(abc[1]), _p(abc[2]), m, _p(wroot), _p(gcos), _p(d)
            )
            lt[arm].append(time.perf_counter() - t0)
            louts[arm] = d
    os.environ.pop("ZKP2P_NTT_POOL", None)
    assert np.array_equal(louts["pool"], louts["unfused"]), "pooled ladder diverged"
    lp, lu = min(lt["pool"]), min(lt["unfused"])
    print(
        f"h_ladder m=2^{log_m}: unfused min={lu*1e3:.0f} ms pool-fused min={lp*1e3:.0f} ms "
        f"-> {lu/lp:.2f}x parity=OK",
        flush=True,
    )
    _rec(
        arm="ladder_h", m=m, threads=threads, reps=args.reps,
        min_s=lp, unfused_min_s=lu, parity="OK",
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1 << 17)
    ap.add_argument(
        "--window", type=int, default=None,
        help="digit/window width; default: 4 on the JAX path, the prover's "
        "_pick_window choice on --native (an explicit value always wins)",
    )
    ap.add_argument("--lanes", type=int, default=0, help="0 = default_lanes(n)")
    ap.add_argument("--adds", type=int, default=1 << 20, help="batch size for the raw add bench")
    ap.add_argument("--skip-msm", action="store_true")
    ap.add_argument("--skip-adds", action="store_true")
    ap.add_argument("--signed", action="store_true", help="signed digit recoding (half-size table)")
    ap.add_argument(
        "--native", action="store_true",
        help="bench the native C++ Pippenger tier (csrc) instead of the JAX path; "
        "omit --window (or pass 0) for the prover's _pick_window choice",
    )
    ap.add_argument("--reps", type=int, default=5, help="native arm: min-of-reps (noisy box)")
    ap.add_argument(
        "--ladder", action="store_true",
        help="bench the NON-MSM floor in isolation: segmented matvec vs the "
        "scatter oracle + the pool-fused H ladder vs the 3-wide unfused arm, "
        "interleaved same-process A/B at domain size --n (power of two)",
    )
    ap.add_argument(
        "--columns", type=int, default=1,
        help="native arm: S > 1 benches the multi-column kernel (one base sweep, "
        "S scalar columns) against S sequential MSMs, with a parity hash",
    )
    glv_grp = ap.add_mutually_exclusive_group()
    glv_grp.add_argument(
        "--glv", action="store_true",
        help="native arms: the GLV endomorphism MSM (half the Pippenger windows "
        "over the endomorphism-doubled [P, phi(P)] base axis)",
    )
    glv_grp.add_argument(
        "--no-glv", action="store_true",
        help="explicit non-GLV arm (the default; named so A/B run logs are self-labelling)",
    )
    pc_grp = ap.add_mutually_exclusive_group()
    pc_grp.add_argument(
        "--precomp", action="store_true",
        help="native arm: fixed-base precomputed-table tier (tables built "
        "in-process) vs the variable-base oracle, with a parity hash",
    )
    pc_grp.add_argument(
        "--no-precomp", action="store_true",
        help="explicit variable-base arm (the default; named so A/B run logs "
        "are self-labelling)",
    )
    ap.add_argument(
        "--table-depth", type=int, default=8,
        help="--precomp: table levels per family (the ZKP2P_MSM_PRECOMP_DEPTH "
        "dial; q = ceil(W/depth) hot-loop windows remain)",
    )
    ba_grp = ap.add_mutually_exclusive_group()
    ba_grp.add_argument(
        "--batch-affine", action="store_true",
        help="native tier: batch-affine Pippenger buckets (one shared Montgomery "
        "inversion per chunk of bucket adds) — the default arm",
    )
    ba_grp.add_argument(
        "--no-batch-affine", action="store_true",
        help="native tier: plain mixed-Jacobian bucket fill (the A/B baseline)",
    )
    ap.add_argument(
        "--apply-prof", action="store_true",
        help="native arm: isolated fill/apply/suffix/bailfill split via the "
        "csrc g_prof_* counters (ZKP2P_MSM_PROF latched before lib load), "
        "interleaved ZKP2P_MSM_INTERLEAVE=1/0 A/B with a parity hash — the "
        "measurable surface for the apply-interleave lever (docs/TUNING.md)",
    )
    ap.add_argument(
        "--json", action="store_true",
        help="after all text output, emit ONE JSON document of structured "
        "per-arm records (arm, shape, min-of-reps seconds, parity hash) — "
        "the machine-readable surface the tune pass consumes; the text "
        "lines above it are unchanged",
    )
    args = ap.parse_args()
    if args.glv:
        args.signed = True
    # The knob rides the env so the C runtime (and any child) sees it;
    # set BEFORE the native lib is loaded/called.
    if args.batch_affine:
        os.environ["ZKP2P_MSM_BATCH_AFFINE"] = "1"
    elif args.no_batch_affine:
        os.environ["ZKP2P_MSM_BATCH_AFFINE"] = "0"
    if args.apply_prof:
        # the C prof gate is latched at first use — arm it before ANY
        # native call so every counter add is live for the whole run
        os.environ["ZKP2P_MSM_PROF"] = "1"

    try:
        _dispatch(args)
    finally:
        if args.json:
            print(json.dumps({"schema": 1, "records": _RESULTS}, sort_keys=True), flush=True)


def _dispatch(args):
    if args.ladder:
        _ladder_bench(args)
        return
    if args.apply_prof:
        if args.window is not None and args.window <= 0:
            args.window = None
        _native_apply_prof_bench(args)
        return
    if args.native:
        _native_bench(args)
        return
    if args.window is None:
        args.window = 4

    import jax
    import jax.numpy as jnp
    import numpy as np

    from zkp2p_tpu.utils.jaxcfg import enable_cache

    enable_cache()
    dev = jax.devices()[0]
    # Print the RESOLVED implementations (the "auto" default resolves by
    # backend), not the raw env — a bare run on TPU measures pallas.
    from zkp2p_tpu.curve.jcurve import G1J
    from zkp2p_tpu.field.jfield import field_mul_impl

    curve_impl = "pallas" if G1J._pallas() else "xla"
    from zkp2p_tpu.utils.config import load_config

    print(
        f"device={dev} curve={curve_impl} fieldmul={field_mul_impl()} "
        f"glv={'on' if args.glv else 'off'} "
        f"batch_affine={'on' if load_config().msm_batch_affine else 'off'} (native tier knob)",
        flush=True,
    )

    from zkp2p_tpu.curve.host import G1_GENERATOR, g1_mul
    from zkp2p_tpu.curve.jcurve import g1_to_affine_arrays
    from zkp2p_tpu.ops.msm import (
        default_lanes,
        digit_planes_from_limbs,
        msm_windowed,
        msm_windowed_signed,
        signed_digit_planes_from_limbs,
    )

    curve = G1J
    rng = np.random.default_rng(7)

    # random-ish affine bases: k*G for 64 distinct k, tiled to n
    host_pts = [g1_mul(G1_GENERATOR, int(k)) for k in rng.integers(1, 1 << 30, 64)]
    ax_np, ay_np = (np.asarray(c) for c in g1_to_affine_arrays(host_pts))
    n = args.n
    reps = (n + 63) // 64
    bx = jnp.asarray(np.tile(ax_np, (reps, 1))[:n])
    by = jnp.asarray(np.tile(ay_np, (reps, 1))[:n])
    bases = (bx, by)

    # ---- raw batched point-op rates (the MSM inner ops), the six kernels ----
    if not args.skip_adds:
        from zkp2p_tpu.curve.host import G2_GENERATOR, g2_mul
        from zkp2p_tpu.curve.jcurve import G2J, g2_to_affine_arrays

        B, iters = args.adds, 8
        reps_b = (B + 63) // 64
        g2_pts = [g2_mul(G2_GENERATOR, int(k)) for k in rng.integers(1, 1 << 30, 64)]
        for tag, crv, aff in (("g1", curve, (ax_np, ay_np)), ("g2", G2J, g2_to_affine_arrays(g2_pts))):
            a = tuple(jnp.asarray(np.tile(np.asarray(c), (reps_b,) + (1,) * (c.ndim - 1))[:B]) for c in aff)
            q = tuple(jnp.roll(c, 1, axis=0) for c in a)
            # Z != 1 on the left, as the accumulators have it
            P = jax.jit(crv.double)(crv.from_affine(a))
            for op, fn, rhs in (
                ("add_mixed", crv.add_mixed, (q,)),
                ("add", crv.add, (crv.from_affine(q),)),
                ("double", crv.double, ()),
            ):
                f = jax.jit(fn)
                jax.block_until_ready(f(P, *rhs))
                t0 = time.perf_counter()
                for _ in range(iters):
                    out = f(P, *rhs)
                jax.block_until_ready(out)
                dt = (time.perf_counter() - t0) / iters
                print(f"{tag}_{op}: B={B} {dt*1e3:.1f} ms -> {B/dt/1e6:.2f} M ops/s", flush=True)
                _rec(arm=f"jax_{tag}_{op}", n=B, min_s=dt, reps=iters)

    if args.skip_msm:
        return

    # ---- full windowed MSM ----
    limbs_np = rng.integers(0, 1 << 16, size=(n, 16), dtype=np.uint32)
    limbs_np[:, 15] &= 0x3FFF  # < 2^254, like Fr scalars (signed recoding bound)
    lanes = args.lanes or default_lanes(n)
    tag = f"n={n} lanes={lanes} w={args.window}"
    if args.signed:
        mags, negs = signed_digit_planes_from_limbs(jnp.asarray(limbs_np), args.window)
        f = jax.jit(lambda b, m, s: msm_windowed_signed(curve, b, m, s, lanes=lanes, window=args.window))
        fargs = (bases, mags, negs)
        tag += " signed"
    else:
        planes = digit_planes_from_limbs(jnp.asarray(limbs_np), window=args.window)
        f = jax.jit(lambda b, p: msm_windowed(curve, b, p, lanes=lanes, window=args.window))
        fargs = (bases, planes)
    t0 = time.perf_counter()
    r = f(*fargs)
    jax.block_until_ready(r)
    compile_and_first = time.perf_counter() - t0
    print(f"msm first (incl compile): {compile_and_first:.1f}s", flush=True)
    t0 = time.perf_counter()
    r = f(*fargs)
    jax.block_until_ready(r)
    dt = time.perf_counter() - t0
    print(f"msm_windowed: {tag} {dt:.2f} s -> {n/dt/1e6:.3f} M pts/s", flush=True)
    _rec(
        arm="jax_msm_windowed", n=n, window=args.window, min_s=dt, reps=1,
        compile_s=compile_and_first,
    )


if __name__ == "__main__":
    main()
