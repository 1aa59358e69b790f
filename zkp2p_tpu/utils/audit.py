"""Execution-path audit + device flight recorder.

The metrics layer records how LONG stages took but not WHICH ARM
executed: a fast-path gate that silently resolves to its fallback, or
a batched prover that walks off the top of HBM with no memory
telemetry, is invisible in the numbers.  This module closes that blind
spot:

  1. **Arm recording** (`record_arm`): every backend/impl gate —
     `jaxcfg.on_tpu`, the prover's batch chunk and mesh, the
     pallas-vs-XLA field mul and curve kernel, the native
     GLV / batch-affine / IFMA-vs-scalar tiers — reports `(gate, arm)`
     at its call site into `zkp2p_path_taken_total{gate,arm}` counters
     and a process-wide gate→arm map.

  2. **Execution digest** (`execution_digest`): a stable hash of the
     sorted gate→arm map, stamped into the run manifest, every BENCH
     JSON and every service request record — two runs whose digests
     match are PROVEN to have exercised identical code paths before
     their numbers are compared; a silently-disarmed run is one digest
     diff away from being caught.

  3. **Flight recorder**: HBM watermarks via `device.memory_stats()`
     (`sample_device_memory`, gauges + per-request peak — the next OOM
     is predicted, not discovered) and jit compile events (count +
     seconds per trace stage via `jax.monitoring`; this box has
     measured 20-minute XLA:CPU prover compiles).

  4. **Preflight** (`preflight`): arm every gate, collect mis-arm
     warnings ("pallas requested but not armed off a TPU"), and emit a
     machine-readable report — the payload behind `zkp2p-tpu doctor`
     and the bench/service startup hooks.

Design constraints match utils.metrics: stdlib-only at import,
observation must never fail the prove around it, and the hot-path cost
(record_arm) is two dict operations + one counter add — measured on the
native prove path as noise (see docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import hashlib
import json
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from .metrics import REGISTRY

# gate -> latest arm string (GIL-atomic dict writes; cumulative per
# process, so a gate consulted only at jit-trace time keeps its arm in
# the digest across later proves that reuse the compiled executable).
_arms: Dict[str, str] = {}

# (gate, arm) -> Counter, cached so the registry lock is only taken on
# first sight of an arm; generation-keyed like trace._stage_hists so a
# REGISTRY.reset() never feeds an orphaned instrument.
_counters: Dict[Any, Any] = {}
_counters_gen = -1


def _arm_str(arm) -> str:
    if isinstance(arm, bool):
        return "on" if arm else "off"
    return str(arm)


def record_arm(gate: str, arm):
    """Report that `gate` resolved to `arm` at its call site.

    Returns `arm` unchanged so gate resolvers can
    `return record_arm("native_msm_glv", value)`.  Cost: two dict ops + a
    float add — cheap enough for resolvers consulted per-MSM or at
    jit-trace time (thousands of calls per trace)."""
    global _counters_gen
    s = _arm_str(arm)
    _arms[gate] = s
    if REGISTRY.generation != _counters_gen:
        _counters.clear()
        _counters_gen = REGISTRY.generation
    key = (gate, s)
    c = _counters.get(key)
    if c is None:
        c = _counters[key] = REGISTRY.counter("zkp2p_path_taken_total", {"gate": gate, "arm": s})
    c.inc()
    return arm


def gate_arms() -> Dict[str, str]:
    """Snapshot of the gate→arm map observed so far this process."""
    return dict(_arms)


def execution_digest(arms: Optional[Dict[str, str]] = None) -> str:
    """Stable 16-hex-char digest of the (sorted) gate→arm map.  Two
    processes that resolved every gate to the same arm produce the same
    digest regardless of resolution order; one flipped arm changes it."""
    if arms is None:
        arms = _arms
    blob = json.dumps(sorted(arms.items()), separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def reset() -> None:
    """Clear the gate→arm map (tests)."""
    _arms.clear()


# Summary of the most recent preflight() this process ran (None until
# one has).  The /status health route fails CLOSED on None: a scrape
# must never report "healthy" for a process whose gates nobody armed —
# the round-2 silent disarm applied to the health surface.
_preflight_report: Optional[Dict] = None


def last_preflight() -> Optional[Dict]:
    """{ts, backend, warnings, execution_digest} of the latest preflight
    run in this process, or None when none has run."""
    return _preflight_report


# ---------------------------------------------------------------------------
# Flight recorder, part 1: HBM watermarks.  `device.memory_stats()` is
# a cheap C call on TPU and None on CPU — the device list is probed once
# and a stats-less backend degrades to a no-op list scan per sample.

_mem_devices: Optional[list] = None
_peak_lock = threading.Lock()


def _memory_devices() -> list:
    global _mem_devices
    if _mem_devices is None:
        try:
            import jax

            devs = []
            for d in jax.devices():
                try:
                    if d.memory_stats():
                        devs.append(d)
                except Exception:  # noqa: BLE001 — stats are optional per PJRT backend
                    pass
            _mem_devices = devs
        except Exception:  # noqa: BLE001 — no backend at all
            _mem_devices = []
    return _mem_devices


def sample_device_memory(stage: str = "") -> Optional[Dict]:
    """Sample per-device HBM watermarks into gauges; returns the
    highest-use device's `{device, bytes_in_use, peak_bytes_in_use,
    bytes_limit}` (None when no device exposes memory stats — XLA:CPU).

    Call sites bracket prove/batch/sub-chunk boundaries so the
    `zkp2p_hbm_*` gauges track the allocation staircase a batched prove
    climbs; `stage` additionally keeps a max-semantics per-stage peak
    (`zkp2p_hbm_stage_peak_bytes{stage=...}`)."""
    best = None
    for i, d in enumerate(_memory_devices()):
        try:
            st = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 — observation only
            continue
        used = int(st.get("bytes_in_use", 0))
        peak = int(st.get("peak_bytes_in_use", used))
        limit = int(st.get("bytes_limit", 0))
        lab = {"device": str(i)}
        REGISTRY.gauge("zkp2p_hbm_bytes_in_use", lab).set(used)
        REGISTRY.gauge("zkp2p_hbm_peak_bytes", lab).set(peak)
        if limit:
            REGISTRY.gauge("zkp2p_hbm_bytes_limit", lab).set(limit)
        if best is None or used > best["bytes_in_use"]:
            best = {
                "device": i,
                "bytes_in_use": used,
                "peak_bytes_in_use": peak,
                "bytes_limit": limit,
            }
    if best is not None and stage:
        g = REGISTRY.gauge("zkp2p_hbm_stage_peak_bytes", {"stage": stage})
        # locked max-update: a bare read-then-set from two concurrent
        # samplers of one stage label could regress the recorded peak
        with _peak_lock:
            g.set(max(g.value, best["peak_bytes_in_use"]))
    return best


# ---------------------------------------------------------------------------
# Flight recorder, part 2: lower and compile events.  jax.monitoring
# publishes '/jax/core/compile/jaxpr_to_mlir_module_duration' and
# '.../backend_compile_duration' per jit cache miss; the listener
# attributes each to the calling thread's CURRENT trace span (both run
# synchronously inside the first dispatch), so a 20-minute
# cold prover compile shows up as lowering and compile seconds under its
# stage instead of silently inflating the stage's own latency histogram,
# and a replica that re-lowers while it serves says in which stage.

_compile_installed = False

# event-name suffix -> (what the log line calls it, events counter, seconds counter)
_JIT_EVENTS = {
    "jaxpr_to_mlir_module_duration": ("lowering", "zkp2p_lower_events_total", "zkp2p_lower_seconds_total"),
    "backend_compile_duration": ("compile", "zkp2p_compile_events_total", "zkp2p_compile_seconds_total"),
}


def _zero_jit_counters() -> None:
    """0 is a reading: a warmed window that lowered nothing shows the
    counters at zero (get-or-create, so also after a REGISTRY.reset())."""
    for _what, events, seconds in _JIT_EVENTS.values():
        REGISTRY.counter(events)
        REGISTRY.counter(seconds)


def install_compile_listener() -> bool:
    """Idempotently register the jit lower/compile event listener;
    False when the jax.monitoring API is unavailable."""
    global _compile_installed
    if _compile_installed:
        _zero_jit_counters()
        return True
    try:
        from jax import monitoring
    except Exception:  # noqa: BLE001 — jax absent or too old
        return False

    from .trace import current_path

    def _on_event(name: str, secs: float, **_kw) -> None:
        kind = _JIT_EVENTS.get(name.rsplit("/", 1)[-1])
        if kind is None:
            return
        what, events, seconds = kind
        try:
            stage = current_path() or "(none)"
            REGISTRY.counter(events, {"stage": stage}).inc()
            REGISTRY.counter(seconds, {"stage": stage}).inc(secs)
            if stage.startswith("service/"):
                # a replica lowering or compiling while it serves
                print(f"[service] {what} under {stage}: {secs:.3f}s", file=sys.stderr, flush=True)
        except Exception:  # noqa: BLE001 — observation must never fail a compile
            pass

    try:
        monitoring.register_event_duration_secs_listener(_on_event)
    except Exception:  # noqa: BLE001
        return False
    _zero_jit_counters()
    _compile_installed = True
    return True


def compile_totals() -> tuple:
    """(events, seconds) the compile listener has counted so far, summed
    over stages — what warm-cache and chip_smoke.py difference around a
    window to tell fresh compiles from cache loads."""
    events = seconds = 0.0
    for m in REGISTRY.snapshot():
        if m["name"] == "zkp2p_compile_events_total":
            events += m.get("value", 0.0)
        elif m["name"] == "zkp2p_compile_seconds_total":
            seconds += m.get("value", 0.0)
    return events, seconds


# ---------------------------------------------------------------------------
# Preflight: the doctor payload.  Arms every gate by calling the real
# resolvers (the same functions the provers consult — no parallel
# reimplementation that could drift), collects mis-arm warnings, and
# returns a machine-readable report.


def _mis_arm_warnings(cfg, backend: str, arms: Dict[str, str], native_ok: bool) -> List[str]:
    """Config-vs-resolution contradictions: an operator asked for an arm
    the gates did not (or could not) take.  Expected degradations (auto
    gates off on a host backend) are NOT warnings."""
    w: List[str] = []
    if cfg.field_mul == "pallas" and arms.get("field_mul") != "pallas":
        w.append(
            f"field_mul=pallas requested but the gate did not arm (backend={backend} "
            "is not a TPU): running the XLA field multiply"
        )
    if cfg.curve_kernel == "pallas" and arms.get("curve_kernel") != "pallas":
        w.append(
            f"curve_kernel=pallas requested but the gate did not arm (backend={backend} "
            "is not a TPU): running the XLA curve path"
        )
    if not native_ok:
        w.append(
            "native library unavailable (csrc toolchain/build failed?): native prover "
            "gates report 'unavailable'"
        )
    elif (
        cfg.native_ifma
        and cfg.provenance.get("native_ifma") != "default"
        and arms.get("native_tier") == "scalar"
    ):
        # only when EXPLICITLY requested (env): the default-True knob on
        # a non-IFMA host is an expected degradation, not a mis-arm —
        # warning there would fail a --strict doctor gate on every
        # healthy machine nobody configured for IFMA
        w.append(
            "native_ifma explicitly enabled but the 52-bit IFMA tier did not arm "
            "(CPU lacks AVX512-IFMA, or msm_batch_affine=0 gates it off): scalar tier"
        )
    return w


def stamp_preflight(stamp: Dict) -> None:
    """Publish `stamp` (a preflight report's `stamp`) as this process's
    latest preflight: `preflight` does it itself unless told not to,
    and a replica set (pipeline.replicas) does it once every replica's
    loop is up, so whoever waits on `last_preflight` waits for all."""
    global _preflight_report
    _preflight_report = dict(stamp, ts=round(time.time(), 3))


def preflight(workload: bool = True, log=None, cfg=None, stamp: bool = True) -> Dict:
    """Arm every gate, sample the backend, and return the preflight
    report (the `zkp2p-tpu doctor` payload; also hooked into bench.py
    and ProvingService.run so a mis-armed run warns before it proves
    anything).  Initialises the JAX backend in THIS process — on a TPU
    host that takes the chip, unless the process pinned its JAX to the
    host platform first (the CLI does, for `--prover native`).

    workload: run one tiny jitted op so the backend is proven to
    execute and the compile listener ticks (skipped by lightweight
    startup hooks).
    cfg: a pre-resolved ProverConfig — pass it when the caller has
    already run cfg.apply_env(): apply_env writes every knob back into
    the env, so a fresh load here would see every provenance as "env"
    and the explicit-request-only warning gates would fire on
    defaults.
    stamp: publish the report as `last_preflight`; False leaves that to
    the caller (`stamp_preflight(report["stamp"])`)."""
    import jax

    from .config import load_config
    from .jaxcfg import on_tpu
    from .metrics import run_id
    from .trace import trace

    install_compile_listener()
    if cfg is None:
        cfg = load_config()
    report: Dict = {"type": "doctor", "ts": round(time.time(), 3), "run_id": run_id()}
    backend = jax.default_backend()
    report["backend"] = backend

    # Arm every gate through its REAL resolver (each records itself).
    on_tpu()
    from ..curve.jcurve import G1J
    from ..field.jfield import field_mul_impl
    from ..prover.groth16_tpu import _batch_chunk_size, _shard_mesh

    field_mul_impl()
    G1J._pallas()
    _batch_chunk_size()
    # sharded-batch gate: "off" | "BxS" mesh shape | "fallback" — a
    # pjit-sharded batch prove must never share a digest with the
    # single-device loop (arms "off"/shape here; prove_tpu_batch
    # re-arms "fallback" when a batch can't split across the mesh)
    _shard_mesh()

    from ..native.lib import get_lib
    from ..prover.native_prove import (
        _msm_interleave_arm,
        _ntt_pool_arm,
        _ntt_radix8_arm,
        _use_batch_affine,
        _glv_arm,
        _use_matvec_seg,
        _use_msm_multi,
        _use_msm_overlap,
        _use_msm_precomp,
        _use_witness_u64,
    )

    _glv_arm()
    _use_batch_affine()
    _use_msm_multi()
    _use_msm_overlap()
    _use_msm_precomp()
    _use_matvec_seg()
    _ntt_pool_arm()
    _msm_interleave_arm()
    _ntt_radix8_arm()
    _use_witness_u64()
    native_ok = False
    try:
        native_ok = get_lib() is not None
    except Exception:  # noqa: BLE001 — a broken toolchain is a finding, not a crash
        pass
    if native_ok:
        from ..prover.native_prove import _native_ifma_tier, _pick_window

        if _native_ifma_tier():
            # arms window_source ("profile" when the host profile holds
            # tuned MSM geometry for this context, else "fallback") via
            # a representative single-thread pick — the same resolver
            # every real MSM consults
            _pick_window(1 << 12, threads=1)
    else:
        record_arm("native_tier", "unavailable")

    # fault-injection gate (utils.faults): "off" or the 8-hex spec
    # digest — a chaos run and a clean run must never share a digest
    from .faults import faults_arm

    faults_arm()

    # service observability gates (utils.slo): the SLO objective and the
    # time-series sampler interval — an A/B with the sampler off must be
    # digest-distinguishable from one with it on, like the fault gate
    from .slo import slo_arm, timeseries_arm

    slo_arm()
    timeseries_arm()

    # fleet gates (pipeline.fleet): membership ("worker" when a
    # supervisor stamped ZKP2P_WORKER_ID, else "off") and the resource
    # governor budgets — a degraded fleet worker must never share a
    # digest with a clean solo service
    from ..pipeline.fleet import fleet_member_arm, governor_arm

    fleet_member_arm()
    governor_arm()

    # scheduler gate (pipeline.sched): the adaptive batching/shedding
    # controller vs the static oracle arm — an adaptive run must never
    # share a digest with a static one
    from ..pipeline.sched import sched_arm, worker_tier_arm

    sched_arm()
    # worker-tier gate: "native" | "sharded" — heterogeneous-fleet
    # routing decisions must be attributable to the tier this worker
    # advertised (a bulk batch served by the wrong tier is an A/B
    # confound, not just a perf blip)
    worker_tier_arm()

    # host-profile gate (utils.hostprof): off | tuned | fallback — a run
    # steered by a tune-produced profile (geometry, thread default,
    # seeded amortization) must never share a digest with a
    # hand-picked-constants run
    from .hostprof import profile_arm

    profile_arm()

    if workload:
        # one tiny jitted op: proves the backend executes and ticks the
        # compile listener
        import jax.numpy as jnp

        t0 = time.perf_counter()
        with trace("doctor/workload"):
            jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()
        report["workload_s"] = round(time.perf_counter() - t0, 3)

    from .metrics import serialize_knobs

    arms = gate_arms()
    report["gates"] = arms
    report["knobs"] = serialize_knobs(cfg)
    report["provenance"] = dict(cfg.provenance)
    report["warnings"] = _mis_arm_warnings(cfg, backend, arms, native_ok)
    report["device_memory"] = sample_device_memory("preflight")
    report["execution_digest"] = execution_digest()
    report["stamp"] = {
        "backend": backend,
        "warnings": len(report["warnings"]),
        "execution_digest": report["execution_digest"],
    }
    if stamp:
        stamp_preflight(report["stamp"])
    if log is not None:
        for msg in report["warnings"]:
            log(f"PREFLIGHT WARNING: {msg}")
    return report
