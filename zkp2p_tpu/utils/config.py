"""One typed configuration for the prover stack (SURVEY.md §5).

Every tuning knob the prover/bench/service read lives HERE, as a frozen
dataclass with per-field provenance — not as ad-hoc `os.environ` reads
scattered across modules.

Resolution order per knob:

  1. built-in default (the committed, tested configuration),
  2. explicit environment variable — always wins (operator intent).

`provenance` records which layer produced each value, so a bench record
or bug report can say "batch_chunk=2 (env)" instead of guessing.

The environment remains the TRANSPORT (child processes, the C runtime's
getenv, jit-time module constants) — `apply_env()` writes the resolved
config back so every consumer, Python or C++, sees one consistent view.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

# knob -> (env var, parser, default) — THE registry; the test asserts
# every ZKP2P_* read in the tree maps through it.  Parsers REPRODUCE the
# semantics of the reader each knob steers (they predate this module and
# other consumers — notably the C runtime — still read the env):
_BOOL = lambda s: s == "1"  # noqa: E731 — readers compare == "1"


def _not_zero(s: str) -> bool:
    # the C runtime's rule for ZKP2P_NATIVE_IFMA: off ONLY when the
    # value starts with '0' (csrc ifma_enabled) — "true"/"yes" stay on
    return not s.startswith("0")


def _starts_one(s: str) -> bool:
    # the C runtime's opt-IN rule for default-off native arms: on ONLY
    # when the value starts with '1' (csrc ntt_radix8_enabled)
    return s.startswith("1")


def _opt_int(s: str) -> Optional[int]:
    if not s:
        return None  # empty string = unset (shell-style), not 1 thread
    try:
        return max(1, int(s))
    except ValueError:
        # malformed degrades to sequential — matching the C++ runtime's
        # atoi() on the same variable, so Python- and C-side threading
        # agree
        return 1


def _opt_port(s: str) -> Optional[int]:
    # metrics exposition port: unset/empty/malformed mean OFF (a typo
    # must fail closed — no listener), "auto" or "0" mean EPHEMERAL (the
    # OS picks a free port, recorded in the run manifest and the fleet
    # heartbeat so scrapes stay discoverable — N workers on one host
    # cannot share one fixed port), anything else is the fixed port
    if s.strip().lower() == "auto":
        return 0
    try:
        v = int(s)
    except ValueError:
        return None
    if v == 0:
        return 0
    return v if 0 < v < 65536 else None


def _pos_int(default: int):
    # bounded positive int with a safe fallback (ring-buffer sizes):
    # malformed keeps the committed default rather than crashing import
    def parse(s: str) -> int:
        try:
            return max(1, int(s))
        except ValueError:
            return default

    return parse


def _nonneg_int(default: int):
    # 0 is meaningful here ("unlimited" / "no retries"); malformed keeps
    # the committed default rather than crashing a running service
    def parse(s: str) -> int:
        try:
            return max(0, int(s))
        except ValueError:
            return default

    return parse


def _nonneg_float(default: float):
    # seconds knobs (deadlines, backoff): 0 = disabled; malformed keeps
    # the committed default
    def parse(s: str) -> float:
        try:
            return max(0.0, float(s))
        except ValueError:
            return default

    return parse


def _fraction(default: float):
    # SLO target fraction: must land strictly inside (0, 1) — a target
    # of 0 or 1 makes the burn-rate denominator meaningless; malformed
    # or out-of-range keeps the committed default
    def parse(s: str) -> float:
        try:
            v = float(s)
        except ValueError:
            return default
        return v if 0.0 < v < 1.0 else default

    return parse


KNOBS: Dict[str, Tuple[str, object, object]] = {
    # GLV endomorphism scalar decomposition for the G1 MSMs of the
    # NATIVE prover only (native_prove `_glv_arm`, arm `native_msm_glv`;
    # the device prover has no such arm): every Fr scalar splits into
    # two ~128-bit halves, halving the Pippenger windows at the cost of
    # doubling the base axis.  Off by default (the existing path is the
    # pinned fallback).
    "msm_glv": ("ZKP2P_MSM_GLV", _BOOL, False),
    # Stage task-graph in prove_native: the a/b1/b2/c MSMs run on worker
    # threads overlapping the H ladder + msm_h ("1"), or strictly
    # sequentially ("0").  Only engages when the resolved thread count
    # is > 1 (a ZKP2P_NATIVE_THREADS=1 pin means one busy core, which
    # Python-side concurrency must not break).  Overlap wins when cores
    # outnumber the per-region pool width or per-MSM serial glue
    # dominates; where the C tier already saturates every core per
    # stage it is neutral — hence a knob, so the arm is attributable
    # and host-tunable.
    "msm_overlap": ("ZKP2P_MSM_OVERLAP", _BOOL, True),
    # Batch-affine Pippenger bucket accumulation in the NATIVE (C++) MSM
    # tiers: buckets live as affine points, every chunk of bucket adds
    # shares ONE Montgomery batch inversion (~7 muls/add vs ~12 for the
    # mixed-Jacobian add).  Default ON (the measured-fastest arm and the
    # long-standing behavior); off routes every window through the plain
    # Jacobian fill — the honest A/B arm.  The C runtime re-reads the env
    # per MSM (csrc batch_affine_enabled), so flips apply immediately.
    "msm_batch_affine": ("ZKP2P_MSM_BATCH_AFFINE", _not_zero, True),
    # Cross-proof multi-column MSM in prove_native_batch: the a/b1/c/h
    # G1 MSM families each ride ONE native Pippenger call per batch (one
    # sweep over the fixed key bases, S scalar columns, batch-affine
    # inversion rounds shared across columns).  Default ON; "0" falls
    # back to sequential per-proof proves — the byte-parity oracle arm.
    # Fresh-read per batch (the gate resolves through load_config at the
    # prove_native_batch call site), so one process can A/B both arms.
    "msm_multi": ("ZKP2P_MSM_MULTI", _not_zero, True),
    # Fixed-base precomputed-window MSM tier (prover.precomp): the
    # frozen proving-key G1 families resolve to offline level tables at
    # first prove (persisted under .bench_cache/, keyed by key hash +
    # geometry), and the per-prove hot loop becomes pure table gather +
    # batch-affine bucket adds — no GLV split, no base conversion.
    # Default ON (the measured-faster arm at the bench shape); "0"
    # falls back to the variable-base drivers — the byte-parity oracle
    # arm.  Fresh-read per prove, so one process can A/B both arms.
    "msm_precomp": ("ZKP2P_MSM_PRECOMP", _not_zero, True),
    # table depth: max level copies per family (levels = ceil(W/q);
    # deeper tables = fewer hot-loop windows, more RAM — each level is
    # n x 144 B resident / n x 64 B on disk per family).  Build COST is
    # depth-invariant (~(W-q)*c doublings per point either way), so the
    # dial trades only memory against hot-loop windows.
    "precomp_depth": ("ZKP2P_MSM_PRECOMP_DEPTH", _pos_int(8), 8),
    # RAM budget guard for the resident tables (mont256 + 52-limb forms,
    # summed over families, in MiB).  A family that exceeds the budget
    # degrades to a shallower table; one that cannot fit even one level
    # falls through to the variable-base path and is recorded as
    # "skipped: budget" in the run manifest.
    "precomp_max_mb": ("ZKP2P_MSM_PRECOMP_MAX_MB", _pos_int(6144), 6144),
    # persistence root for built tables ("" = <repo>/.bench_cache,
    # "0" = never persist) and the minimum family size that persists at
    # all — tiny test keys rebuild in microseconds and must not litter
    # the shared cache dir.
    "precomp_cache": ("ZKP2P_MSM_PRECOMP_CACHE", str, ""),
    "precomp_persist_min": ("ZKP2P_MSM_PRECOMP_PERSIST_MIN", _pos_int(65536), 65536),
    # which G1 families ride tables.  h included by default: the
    # full-width ladder scalars still measure ~1.25x over the GLV
    # variable-base arm at the bench shape (docs/TUNING.md sweep).
    "precomp_families": ("ZKP2P_MSM_PRECOMP_FAMILIES", str, "a,b1,c,h"),
    # Segmented-plan matvec in the native prover (prover.matvec_plan +
    # csrc fr_matvec_seg): the A/B QAP matvecs run over a per-key
    # presorted plan — 8-wide IFMA coeff·wire products across segment
    # boundaries, segments partitioned over the C worker pool with no
    # scatter conflicts by construction; plans persist beside the
    # precomp tables keyed by matrix hash.  Default ON; "0" falls back
    # to the scatter `fr_matvec` oracle — the byte-parity A/B arm.
    # Fresh-read per prove, so one process can A/B both arms.
    "matvec_seg": ("ZKP2P_MATVEC_SEG", _not_zero, True),
    # Pool-parallel NTT stage splitting + fused coset ladder + Fr
    # vector batch passes in the C runtime: each NTT stage's butterfly
    # blocks fan out across the persistent WorkPool (ONE transform uses
    # every core, vs the old 3-wide whole-transform ladder split), the
    # H ladder keeps data in 52-limb SoA form across iNTT -> coset-mul
    # -> forward NTT (the coset+1/m pass vectorized, two full memory
    # passes dropped), and the fr_mul_batch / to-mont / from-mont
    # passes run 8-wide.  Default ON; "0" restores the full scalar
    # 3-wide unfused path — the byte-parity A/B arm.  The C runtime
    # re-reads the env per call (csrc ntt_pool_enabled), so flips apply
    # immediately.
    "ntt_pool": ("ZKP2P_NTT_POOL", _not_zero, True),
    # MSM apply interleave in the C batch-affine pipeline: the chunk
    # apply splits its block range in two and drives both halves'
    # prefix/inverse/apply mont52 chains through ONE fused register
    # schedule (mont52_mul8x2 — the second chain fills the first's
    # madd52 latency bubbles), plus software prefetch down the known
    # (bucket, point) index streams in the schedule/fill/bail loops.
    # Default ON; "0" restores the single-chain no-prefetch schedule —
    # the byte-parity A/B arm.  Fresh-read per call (csrc
    # msm_interleave_enabled), so flips apply immediately.
    "msm_interleave": ("ZKP2P_MSM_INTERLEAVE", _not_zero, True),
    # Radix-8 NTT stage fusion: three butterfly stages per load/store
    # pass in fr_ntt_soa_stages (vs the radix-4 stage pairs).  Default
    # OFF — measured 0.95x at 2^19 on the 1-core IFMA box (register
    # spills; the muls are throughput-bound, so the saved memory pass
    # does not pay there) — the knob stays for wider hosts; "1" arms it.
    # Fresh-read per transform (csrc ntt_radix8_enabled).
    "ntt_radix8": ("ZKP2P_NTT_RADIX8", _starts_one, False),
    # Witness-at-builder hand-off: snark.r1cs witness builders attach
    # the prover's standard-form (n, 4) u64 serialization at build time
    # and the witness_convert stage hands it off instead of
    # re-serializing Python ints every prove.  Default ON; "0"
    # re-serializes — the byte-parity oracle arm.  Fresh-read per prove
    # at the _witness_std_u64 call site.
    "witness_u64": ("ZKP2P_WITNESS_U64", _not_zero, True),
    # proof-batch sub-chunking: "auto" (on a real TPU a function of the
    # key's size and the device's memory — groth16_tpu.batch_chunk_for:
    # as many proofs a chunk, up to 4, as fit beside the key; 4 up to a
    # 2^20 domain, 2 at 2^21, 1 from 2^22 on a 16 GB chip; whole batch
    # elsewhere), "0" (never chunk), or an explicit chunk size, which
    # overrides the rule.  r5 bench1 on-chip: the batched h-evals stage
    # materialises a (batch, rows, 16, 16) partial-product tensor on the
    # XLA field path — 18 GB at batch=16 against 15.75 G HBM.
    "batch_chunk": ("ZKP2P_BATCH_CHUNK", str, "auto"),
    # device field/curve kernel selection — see field.jfield, curve.jcurve
    "field_conv": ("ZKP2P_FIELD_CONV", str, "matmul"),
    "field_mul": ("ZKP2P_FIELD_MUL", str, "auto"),
    "curve_kernel": ("ZKP2P_CURVE_KERNEL", str, "auto"),
    # native (C++) runtime
    "native_ifma": ("ZKP2P_NATIVE_IFMA", _not_zero, True),
    "native_threads": ("ZKP2P_NATIVE_THREADS", _opt_int, None),
    # compilation-cache opt-out (read by tests/conftest.py at process
    # start as well — the env var is authoritative there by necessity)
    "no_cache": ("ZKP2P_NO_CACHE", _BOOL, False),
    # debug: native MSM phase counters (csrc zkp2p_msm_prof_dump)
    "msm_prof": ("ZKP2P_MSM_PROF", _BOOL, False),
    # observability (utils.metrics / utils.trace): Prometheus exposition
    # port (unset/0 = off), JSONL metrics-sink path ("" = the consumer's
    # default: stderr for bench dumps, <spool>.metrics.jsonl for the
    # service), and the trace ring-buffer bound (records kept in memory
    # between dumps; overflow increments zkp2p_trace_dropped_total).
    "metrics_port": ("ZKP2P_METRICS_PORT", _opt_port, None),
    # bind address for the exposition endpoint: localhost by default —
    # /metrics discloses host facts and knob config, so reaching it from
    # another machine (a real Prometheus) is an explicit opt-in
    # (ZKP2P_METRICS_ADDR=0.0.0.0)
    "metrics_addr": ("ZKP2P_METRICS_ADDR", str, "127.0.0.1"),
    "metrics_sink": ("ZKP2P_METRICS_SINK", str, ""),
    "trace_max": ("ZKP2P_TRACE_MAX", _pos_int(65536), 65536),
    # fault injection (utils.faults): named injection sites through the
    # witness/prove/verify/emit/claim/sink paths, e.g.
    # "seed=7,prove:raise:p=0.2,emit:enospc:once,witness:hang=3".
    # Empty = off (the no-op fast path).  The spec grammar and
    # determinism contract live in utils/faults.py + docs/ROBUSTNESS.md;
    # the knob stays a raw string here (faults.parse_faults is THE
    # parser) so a malformed spec fails loudly at arm time, not silently
    # at config time.
    "faults": ("ZKP2P_FAULTS", str, ""),
    # service fault-tolerance knobs (pipeline.service; constructor args
    # override per instance — these are the fleet-wide defaults):
    # default per-request deadline in seconds (payload deadline_s wins;
    # 0 = no deadline), spool backlog cap (pending requests beyond it
    # are shed as error-shed; 0 = unlimited), bounded transient-failure
    # retries per batch prove, and the exponential-backoff base.
    "deadline_s": ("ZKP2P_DEADLINE_S", _nonneg_float(0.0), 0.0),
    "spool_cap": ("ZKP2P_SPOOL_CAP", _nonneg_int(0), 0),
    "prove_retries": ("ZKP2P_PROVE_RETRIES", _nonneg_int(2), 2),
    "retry_backoff_s": ("ZKP2P_RETRY_BACKOFF_S", _nonneg_float(0.25), 0.25),
    # service-level SLO (utils.slo; docs/OBSERVABILITY.md §SLO): the
    # p95 latency objective in seconds over the request's FULL life
    # (spool arrival -> terminal; 0 = no objective, the tracker still
    # records window latencies), the attainment target fraction behind
    # the burn-rate math, and the rolling-window length the tracker
    # aggregates over.
    "slo_p95_s": ("ZKP2P_SLO_P95_S", _nonneg_float(0.0), 0.0),
    "slo_target": ("ZKP2P_SLO_TARGET", _fraction(0.95), 0.95),
    "slo_window_s": ("ZKP2P_SLO_WINDOW_S", _nonneg_float(300.0), 300.0),
    # time-series sampler interval (pipeline.service.TimeseriesSampler):
    # every interval the service loop appends a `zkp2p_timeseries` line
    # (arrival rate, claimable backlog, in-flight fill, rescue counters,
    # native stats deltas, HBM gauges) to the JSONL sink.  0 = off.
    "ts_sample_s": ("ZKP2P_TS_SAMPLE_S", _nonneg_float(10.0), 10.0),
    # fleet identity + plumbing (pipeline.fleet): the supervisor stamps
    # these into each worker's environment — worker_id/fleet_id land on
    # every service record and time-series line so trace_report can
    # attribute rows to workers across a fleet run, and fleet_dir is
    # where the worker writes heartbeats / reads governor control files.
    # Empty = not a fleet member (solo service).
    "worker_id": ("ZKP2P_WORKER_ID", str, ""),
    "fleet_id": ("ZKP2P_FLEET_ID", str, ""),
    "fleet_dir": ("ZKP2P_FLEET_DIR", str, ""),
    # fleet policy knobs (pipeline.fleet; CLI flags override): worker
    # count, the bounded wait between SIGTERM (drain) and SIGKILL
    # escalation, per-worker RSS budgets for the resource governor
    # (0 = off; soft = ctl-file degradation, hard = drain + restart),
    # the crash-loop circuit breaker (K failures inside W seconds parks
    # the worker; the fleet degrades to N-1 instead of flapping), and
    # the exponential restart-backoff base.
    "fleet_workers": ("ZKP2P_FLEET_WORKERS", _pos_int(2), 2),
    "drain_timeout_s": ("ZKP2P_DRAIN_TIMEOUT_S", _nonneg_float(30.0), 30.0),
    "rss_soft_mb": ("ZKP2P_RSS_SOFT_MB", _nonneg_int(0), 0),
    "rss_hard_mb": ("ZKP2P_RSS_HARD_MB", _nonneg_int(0), 0),
    "breaker_k": ("ZKP2P_BREAKER_K", _pos_int(5), 5),
    "breaker_window_s": ("ZKP2P_BREAKER_WINDOW_S", _nonneg_float(60.0), 60.0),
    "restart_backoff_s": ("ZKP2P_RESTART_BACKOFF_S", _nonneg_float(0.5), 0.5),
    # fleet observability plane (pipeline.fleet_obs; docs/OBSERVABILITY
    # §fleet plane): the supervisor's STABLE aggregated endpoint
    # (/metrics /status /healthz; unset = plane off, "auto"/"0" =
    # ephemeral with the bound port in status.json — port semantics
    # identical to metrics_port), the worker-scrape/merge cadence, and
    # the fast sub-window for the multi-window burn-rate pair.
    "fleet_metrics_port": ("ZKP2P_FLEET_METRICS_PORT", _opt_port, None),
    "fleet_scrape_s": ("ZKP2P_FLEET_SCRAPE_S", _nonneg_float(2.0), 2.0),
    "slo_fast_window_s": ("ZKP2P_SLO_FAST_WINDOW_S", _nonneg_float(60.0), 60.0),
    # adaptive scheduler (pipeline.sched; docs/SCHEDULING.md): the
    # controller gate ("off" = the static batch_size/newest-first-shed
    # oracle arm, byte-for-byte today's behavior; "adaptive" = SLO-
    # driven batch sizing + expected-deadline-miss shedding + priority
    # lanes; anything else fails CLOSED to off), the headroom fraction
    # of the deadline/objective budget batches are planned to, the
    # amortization-curve calibration ("S:sec,S:sec,..."; "" = the
    # built-in conservative venmo curve; malformed raises LOUDLY at
    # controller creation), and the default priority lane for requests
    # whose payload carries none ("interactive" | anything-else=bulk).
    "sched": ("ZKP2P_SCHED", str, "off"),
    "sched_target_fill": ("ZKP2P_SCHED_TARGET_FILL", _fraction(0.8), 0.8),
    "sched_amort": ("ZKP2P_SCHED_AMORT", str, ""),
    "sched_priority_default": ("ZKP2P_SCHED_PRIORITY_DEFAULT", str, "bulk"),
    # fleet autoscaling (pipeline.sched.AutoscalePolicy, driven by the
    # FleetSupervisor off the fleet plane's merged signals): live-worker
    # bounds (workers_max 0 = autoscale off; min clamps to >= 1 when
    # on) and the hysteresis windows — how long the scale-up condition
    # (backlog growth / slo burn) and the scale-down condition (idle)
    # must hold CONTINUOUSLY before a step.
    "workers_min": ("ZKP2P_WORKERS_MIN", _nonneg_int(0), 0),
    "workers_max": ("ZKP2P_WORKERS_MAX", _nonneg_int(0), 0),
    "scale_up_s": ("ZKP2P_SCALE_UP_S", _nonneg_float(10.0), 10.0),
    "scale_down_s": ("ZKP2P_SCALE_DOWN_S", _nonneg_float(30.0), 30.0),
    # alert-engine thresholds (utils.alerts; the rule table lives in
    # docs/OBSERVABILITY.md): burn-rate multiple that pages when BOTH
    # the fast and slow merged windows exceed it, supervisor restarts
    # inside the breaker window that count as a storm, how long a
    # condition must hold to fire (for_s) and how long it must be
    # clean to clear (clear_s — the hysteresis damper), and the
    # heartbeat age that counts as a gap.
    "alert_burn_rate": ("ZKP2P_ALERT_BURN_RATE", _nonneg_float(2.0), 2.0),
    "alert_restarts": ("ZKP2P_ALERT_RESTARTS", _pos_int(3), 3),
    "alert_for_s": ("ZKP2P_ALERT_FOR_S", _nonneg_float(5.0), 5.0),
    "alert_clear_s": ("ZKP2P_ALERT_CLEAR_S", _nonneg_float(30.0), 30.0),
    "alert_hb_gap_s": ("ZKP2P_ALERT_HB_GAP_S", _nonneg_float(15.0), 15.0),
    # host auto-tune profile (utils.hostprof + pipeline.tune;
    # docs/TUNING.md §Host profiles): the profile-load gate ("0" =
    # ignore any profile on disk — the hand-picked-constants oracle arm
    # for tuned-vs-fallback A/Bs), an explicit profile path override
    # ("" = <precomp cache dir>/host_profile_<fingerprint>.json beside
    # .bench_cache; a copied profile whose embedded fingerprint doesn't
    # match this host is REJECTED, never loaded), the `zkp2p-tpu tune`
    # sweep's wall-clock budget in seconds, and a comma filter over the
    # sweep arms ("" = all of threads,ladder,window,geometry,columns).
    "profile": ("ZKP2P_PROFILE", _not_zero, True),
    "profile_path": ("ZKP2P_PROFILE_PATH", str, ""),
    "tune_budget_s": ("ZKP2P_TUNE_BUDGET_S", _nonneg_float(120.0), 120.0),
    "tune_arms": ("ZKP2P_TUNE_ARMS", str, ""),
    # sharded TPU arm (prover.groth16_tpu._prove_batch_sharded;
    # docs/TPU.md): the batch-axis pjit gate ("on" = route prove_tpu_batch
    # chunks through the pod-mesh program — batch data-parallel over the
    # mesh's outer axis, MSM bucket partial sums allreduced over the inner
    # ICI axis; anything else fails CLOSED to the single-device vmap),
    # the mesh shape ("BxS" = B batch-parallel groups of S base-axis
    # shards; a bare int N = "1xN"; "" = auto 1x<all devices>), and the
    # fleet worker tier this process advertises in heartbeats ("sharded"
    # = the wide-batch mesh tier the scheduler routes the bulk lane to;
    # anything else = "native").  (The persistent compile cache is
    # placed by the standard JAX_COMPILATION_CACHE_DIR — utils.jaxcfg.)
    "tpu_shard": ("ZKP2P_TPU_SHARD", str, "off"),
    "tpu_mesh": ("ZKP2P_TPU_MESH", str, ""),
    "worker_tier": ("ZKP2P_WORKER_TIER", str, ""),
}

# The A/B arm switches: a function that branches on one of these must
# record which arm it took (tools/lint gate-arm rule, audit.record_arm).
ARMABLE = (
    "msm_glv", "msm_batch_affine", "msm_overlap",
    "msm_multi", "msm_precomp", "matvec_seg", "ntt_pool", "sched",
    "profile", "tpu_shard", "worker_tier",
    "msm_interleave", "ntt_radix8", "witness_u64",
)


@dataclass(frozen=True)
class ProverConfig:
    msm_glv: bool = False
    msm_overlap: bool = True
    msm_batch_affine: bool = True
    msm_multi: bool = True
    msm_precomp: bool = True
    matvec_seg: bool = True
    ntt_pool: bool = True
    msm_interleave: bool = True
    ntt_radix8: bool = False
    witness_u64: bool = True
    precomp_depth: int = 8
    precomp_max_mb: int = 6144
    precomp_cache: str = ""
    precomp_persist_min: int = 65536
    precomp_families: str = "a,b1,c,h"
    batch_chunk: str = "auto"
    field_conv: str = "matmul"
    field_mul: str = "auto"
    curve_kernel: str = "auto"
    native_ifma: bool = True
    native_threads: Optional[int] = None
    no_cache: bool = False
    msm_prof: bool = False
    metrics_port: Optional[int] = None
    metrics_addr: str = "127.0.0.1"
    metrics_sink: str = ""
    trace_max: int = 65536
    faults: str = ""
    deadline_s: float = 0.0
    spool_cap: int = 0
    prove_retries: int = 2
    retry_backoff_s: float = 0.25
    slo_p95_s: float = 0.0
    slo_target: float = 0.95
    slo_window_s: float = 300.0
    ts_sample_s: float = 10.0
    worker_id: str = ""
    fleet_id: str = ""
    fleet_dir: str = ""
    fleet_workers: int = 2
    drain_timeout_s: float = 30.0
    rss_soft_mb: int = 0
    rss_hard_mb: int = 0
    breaker_k: int = 5
    breaker_window_s: float = 60.0
    restart_backoff_s: float = 0.5
    fleet_metrics_port: Optional[int] = None
    fleet_scrape_s: float = 2.0
    slo_fast_window_s: float = 60.0
    sched: str = "off"
    sched_target_fill: float = 0.8
    sched_amort: str = ""
    sched_priority_default: str = "bulk"
    workers_min: int = 0
    workers_max: int = 0
    scale_up_s: float = 10.0
    scale_down_s: float = 30.0
    alert_burn_rate: float = 2.0
    alert_restarts: int = 3
    alert_for_s: float = 5.0
    alert_clear_s: float = 30.0
    alert_hb_gap_s: float = 15.0
    profile: bool = True
    profile_path: str = ""
    tune_budget_s: float = 120.0
    tune_arms: str = ""
    tpu_shard: str = "off"
    tpu_mesh: str = ""
    worker_tier: str = ""
    # knob -> "default" | "env"
    provenance: Dict[str, str] = field(default_factory=dict, compare=False)

    def describe(self) -> str:
        return " ".join(
            f"{k}={getattr(self, k)}({self.provenance.get(k, 'default')})" for k in KNOBS
        )

    def apply_env(self, environ=None) -> None:
        """Write the resolved values back into the environment so child
        processes, import-time module constants, and the C runtime's
        getenv() all see the same configuration."""
        env = os.environ if environ is None else environ
        for attr, (var, _parse, _default) in KNOBS.items():
            v = getattr(self, attr)
            if v is None:
                env.pop(var, None)
            elif isinstance(v, bool):
                env[var] = "1" if v else "0"
            else:
                env[var] = str(v)


# the registry and the dataclass must never drift: a retuned default in
# one place only is an import-time error, not a silent divergence
for _attr, (_var, _parse, _default) in KNOBS.items():
    assert ProverConfig.__dataclass_fields__[_attr].default == _default, (
        f"default drift for {_attr}: KNOBS says {_default!r}, "
        f"ProverConfig says {ProverConfig.__dataclass_fields__[_attr].default!r}"
    )


def load_config(environ=None) -> ProverConfig:
    """Resolve the full configuration (default -> env)."""
    env = os.environ if environ is None else environ
    values: Dict[str, object] = {k: default for k, (_v, _p, default) in KNOBS.items()}
    prov: Dict[str, str] = {k: "default" for k in KNOBS}
    for attr, (var, parse, _default) in KNOBS.items():
        raw = env.get(var)
        if raw is not None:
            values[attr] = parse(raw)
            prov[attr] = "env"

    return ProverConfig(provenance=prov, **values)
