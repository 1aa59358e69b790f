"""The typed prover config (SURVEY.md §5: one config, env as override).

Pins the resolution order (default -> env), provenance labeling, and —
via a source scan — that every ZKP2P_* variable read anywhere in the
tree is registered in the config's knob table (no knob may bypass the
single source of truth)."""

import os
import re

from zkp2p_tpu.utils.config import ARMABLE, KNOBS, load_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_defaults():
    cfg = load_config(environ={})
    assert cfg.batch_chunk == "auto"
    assert cfg.tpu_shard == "off"
    assert cfg.msm_glv is False
    assert cfg.native_ifma is True
    # the native batch-affine bucket tier is the committed-on arm; its
    # parser follows the C runtime's leading-'0' rule like native_ifma
    assert cfg.msm_batch_affine is True
    assert load_config(environ={"ZKP2P_MSM_BATCH_AFFINE": "true"}).msm_batch_affine is True
    assert load_config(environ={"ZKP2P_MSM_BATCH_AFFINE": "0"}).msm_batch_affine is False
    assert all(v == "default" for v in cfg.provenance.values())


def test_env_overrides_every_knob():
    env = {
        "ZKP2P_MSM_GLV": "1",
        "ZKP2P_MSM_OVERLAP": "0",
        "ZKP2P_MSM_BATCH_AFFINE": "0",
        "ZKP2P_MSM_MULTI": "0",
        "ZKP2P_MSM_PRECOMP": "0",
        "ZKP2P_MSM_PRECOMP_DEPTH": "4",
        "ZKP2P_MSM_PRECOMP_MAX_MB": "512",
        "ZKP2P_MSM_PRECOMP_CACHE": "/tmp/precomp_cache",
        "ZKP2P_MSM_PRECOMP_PERSIST_MIN": "1024",
        "ZKP2P_MSM_PRECOMP_FAMILIES": "a,h",
        "ZKP2P_MATVEC_SEG": "0",
        "ZKP2P_NTT_POOL": "0",
        "ZKP2P_MSM_INTERLEAVE": "0",
        "ZKP2P_NTT_RADIX8": "1",
        "ZKP2P_WITNESS_U64": "0",
        "ZKP2P_BATCH_CHUNK": "8",
        "ZKP2P_FIELD_CONV": "limb_major",
        "ZKP2P_FIELD_MUL": "pallas",
        "ZKP2P_CURVE_KERNEL": "xla",
        "ZKP2P_NATIVE_IFMA": "0",
        "ZKP2P_NATIVE_THREADS": "7",
        "ZKP2P_NO_CACHE": "1",
        "ZKP2P_MSM_PROF": "1",
        "ZKP2P_METRICS_PORT": "9464",
        "ZKP2P_METRICS_ADDR": "0.0.0.0",
        "ZKP2P_METRICS_SINK": "/tmp/sink.jsonl",
        "ZKP2P_TRACE_MAX": "1024",
        "ZKP2P_FAULTS": "prove:raise:p=0.5,emit:enospc:once",
        "ZKP2P_DEADLINE_S": "30",
        "ZKP2P_SPOOL_CAP": "256",
        "ZKP2P_PROVE_RETRIES": "5",
        "ZKP2P_RETRY_BACKOFF_S": "0.5",
        "ZKP2P_SLO_P95_S": "12",
        "ZKP2P_SLO_TARGET": "0.99",
        "ZKP2P_SLO_WINDOW_S": "60",
        "ZKP2P_TS_SAMPLE_S": "2.5",
        "ZKP2P_WORKER_ID": "w3",
        "ZKP2P_FLEET_ID": "fleet-abc",
        "ZKP2P_FLEET_DIR": "/tmp/fleetdir",
        "ZKP2P_FLEET_WORKERS": "4",
        "ZKP2P_DRAIN_TIMEOUT_S": "7.5",
        "ZKP2P_RSS_SOFT_MB": "2048",
        "ZKP2P_RSS_HARD_MB": "4096",
        "ZKP2P_BREAKER_K": "3",
        "ZKP2P_BREAKER_WINDOW_S": "45",
        "ZKP2P_RESTART_BACKOFF_S": "0.1",
        "ZKP2P_FLEET_METRICS_PORT": "9470",
        "ZKP2P_FLEET_SCRAPE_S": "1.5",
        "ZKP2P_SLO_FAST_WINDOW_S": "90",
        "ZKP2P_ALERT_BURN_RATE": "4",
        "ZKP2P_ALERT_RESTARTS": "5",
        "ZKP2P_ALERT_FOR_S": "7",
        "ZKP2P_ALERT_CLEAR_S": "20",
        "ZKP2P_ALERT_HB_GAP_S": "8",
        "ZKP2P_SCHED": "adaptive",
        "ZKP2P_SCHED_TARGET_FILL": "0.7",
        "ZKP2P_SCHED_AMORT": "1:0.9,8:3.0",
        "ZKP2P_SCHED_PRIORITY_DEFAULT": "interactive",
        "ZKP2P_WORKERS_MIN": "1",
        "ZKP2P_WORKERS_MAX": "6",
        "ZKP2P_SCALE_UP_S": "12",
        "ZKP2P_SCALE_DOWN_S": "45",
        "ZKP2P_PROFILE": "0",
        "ZKP2P_PROFILE_PATH": "/tmp/prof.json",
        "ZKP2P_TUNE_BUDGET_S": "45",
        "ZKP2P_TUNE_ARMS": "geometry,columns",
        "ZKP2P_TPU_SHARD": "on",
        "ZKP2P_TPU_MESH": "2x4",
        "ZKP2P_WORKER_TIER": "sharded",
    }
    cfg = load_config(environ=env)
    assert cfg.msm_glv is True
    assert cfg.msm_overlap is False
    assert cfg.msm_batch_affine is False
    assert cfg.msm_multi is False
    assert cfg.msm_precomp is False and cfg.precomp_depth == 4
    assert cfg.precomp_max_mb == 512 and cfg.precomp_cache == "/tmp/precomp_cache"
    assert cfg.precomp_persist_min == 1024 and cfg.precomp_families == "a,h"
    assert cfg.matvec_seg is False and cfg.ntt_pool is False
    assert cfg.msm_interleave is False and cfg.ntt_radix8 is True
    assert cfg.witness_u64 is False
    assert cfg.batch_chunk == "8"
    assert cfg.field_conv == "limb_major" and cfg.field_mul == "pallas" and cfg.curve_kernel == "xla"
    assert cfg.native_ifma is False and cfg.native_threads == 7 and cfg.no_cache is True
    assert cfg.metrics_port == 9464 and cfg.metrics_sink == "/tmp/sink.jsonl" and cfg.trace_max == 1024
    assert cfg.metrics_addr == "0.0.0.0"
    assert cfg.faults == "prove:raise:p=0.5,emit:enospc:once"
    assert cfg.deadline_s == 30.0 and cfg.spool_cap == 256
    assert cfg.prove_retries == 5 and cfg.retry_backoff_s == 0.5
    assert cfg.slo_p95_s == 12.0 and cfg.slo_target == 0.99
    assert cfg.slo_window_s == 60.0 and cfg.ts_sample_s == 2.5
    assert cfg.worker_id == "w3" and cfg.fleet_id == "fleet-abc"
    assert cfg.fleet_dir == "/tmp/fleetdir" and cfg.fleet_workers == 4
    assert cfg.drain_timeout_s == 7.5
    assert cfg.rss_soft_mb == 2048 and cfg.rss_hard_mb == 4096
    assert cfg.breaker_k == 3 and cfg.breaker_window_s == 45.0
    assert cfg.restart_backoff_s == 0.1
    assert cfg.fleet_metrics_port == 9470 and cfg.fleet_scrape_s == 1.5
    assert cfg.slo_fast_window_s == 90.0
    assert cfg.alert_burn_rate == 4.0 and cfg.alert_restarts == 5
    assert cfg.alert_for_s == 7.0 and cfg.alert_clear_s == 20.0
    assert cfg.alert_hb_gap_s == 8.0
    assert cfg.sched == "adaptive" and cfg.sched_target_fill == 0.7
    assert cfg.sched_amort == "1:0.9,8:3.0"
    assert cfg.sched_priority_default == "interactive"
    assert cfg.workers_min == 1 and cfg.workers_max == 6
    assert cfg.scale_up_s == 12.0 and cfg.scale_down_s == 45.0
    assert cfg.profile is False and cfg.profile_path == "/tmp/prof.json"
    assert cfg.tune_budget_s == 45.0 and cfg.tune_arms == "geometry,columns"
    assert cfg.tpu_shard == "on" and cfg.tpu_mesh == "2x4"
    assert cfg.worker_tier == "sharded"
    assert all(v == "env" for v in cfg.provenance.values())


def test_reader_matched_parsers():
    """Parsers must reproduce the semantics of the actual readers: the
    C runtime disables IFMA only on a leading '0' ('true' stays ON),
    and an empty thread count is shell-style unset, not 1 thread."""
    cfg = load_config(environ={"ZKP2P_NATIVE_IFMA": "true"})
    assert cfg.native_ifma is True
    assert load_config(environ={"ZKP2P_NATIVE_IFMA": "0"}).native_ifma is False
    assert load_config(environ={"ZKP2P_NATIVE_THREADS": ""}).native_threads is None
    assert load_config(environ={"ZKP2P_NATIVE_THREADS": "junk"}).native_threads == 1
    # metrics port fails CLOSED (no listener) on anything non-portlike;
    # "auto"/"0" mean EPHEMERAL (bind port 0, record the bound port) so
    # N fleet workers on one host never collide on a fixed port
    assert load_config(environ={"ZKP2P_METRICS_PORT": "0"}).metrics_port == 0
    assert load_config(environ={"ZKP2P_METRICS_PORT": "auto"}).metrics_port == 0
    assert load_config(environ={"ZKP2P_METRICS_PORT": "junk"}).metrics_port is None
    assert load_config(environ={"ZKP2P_METRICS_PORT": "9464"}).metrics_port == 9464
    assert load_config(environ={"ZKP2P_METRICS_PORT": "99999"}).metrics_port is None
    # fleet plane port follows the metrics-port grammar exactly:
    # auto/0 = ephemeral, junk fails CLOSED (plane off), range-checked
    assert load_config(environ={"ZKP2P_FLEET_METRICS_PORT": "auto"}).fleet_metrics_port == 0
    assert load_config(environ={"ZKP2P_FLEET_METRICS_PORT": "0"}).fleet_metrics_port == 0
    assert load_config(environ={"ZKP2P_FLEET_METRICS_PORT": "junk"}).fleet_metrics_port is None
    assert load_config(environ={"ZKP2P_FLEET_METRICS_PORT": "9470"}).fleet_metrics_port == 9470
    assert load_config(environ={}).fleet_metrics_port is None  # default: plane off
    # alert thresholds: malformed keeps the committed default, negative
    # seconds clamp to 0 (fire/clear immediately, never a time machine)
    assert load_config(environ={"ZKP2P_ALERT_BURN_RATE": "junk"}).alert_burn_rate == 2.0
    assert load_config(environ={"ZKP2P_ALERT_RESTARTS": "0"}).alert_restarts == 1
    assert load_config(environ={"ZKP2P_ALERT_FOR_S": "-3"}).alert_for_s == 0.0
    assert load_config(environ={"ZKP2P_FLEET_SCRAPE_S": "junk"}).fleet_scrape_s == 2.0
    # host-profile gate follows the C runtime's not-zero rule (off only
    # on a leading '0'); the tune budget is a seconds knob (0 =
    # unbudgeted, malformed keeps the committed default)
    assert load_config(environ={"ZKP2P_PROFILE": "0"}).profile is False
    assert load_config(environ={"ZKP2P_PROFILE": "true"}).profile is True
    assert load_config(environ={}).profile is True  # default: profiles load
    assert load_config(environ={"ZKP2P_TUNE_BUDGET_S": "0"}).tune_budget_s == 0.0
    assert load_config(environ={"ZKP2P_TUNE_BUDGET_S": "junk"}).tune_budget_s == 120.0
    assert load_config(environ={"ZKP2P_TUNE_BUDGET_S": "-5"}).tune_budget_s == 0.0
    # fleet knobs: breaker/backoff clamp like their service siblings
    assert load_config(environ={"ZKP2P_FLEET_WORKERS": "0"}).fleet_workers == 1
    assert load_config(environ={"ZKP2P_FLEET_WORKERS": "junk"}).fleet_workers == 2
    assert load_config(environ={"ZKP2P_DRAIN_TIMEOUT_S": "-1"}).drain_timeout_s == 0.0
    assert load_config(environ={"ZKP2P_RSS_SOFT_MB": "junk"}).rss_soft_mb == 0
    assert load_config(environ={"ZKP2P_BREAKER_K": "0"}).breaker_k == 1
    assert load_config(environ={"ZKP2P_RESTART_BACKOFF_S": "junk"}).restart_backoff_s == 0.5
    # trace ring bound keeps the committed default on malformed input
    assert load_config(environ={"ZKP2P_TRACE_MAX": "junk"}).trace_max == 65536
    # fault-tolerance seconds/count knobs: 0 is meaningful (disabled /
    # unlimited / no retries), negatives clamp, malformed keeps defaults
    assert load_config(environ={"ZKP2P_DEADLINE_S": "0"}).deadline_s == 0.0
    assert load_config(environ={"ZKP2P_DEADLINE_S": "-3"}).deadline_s == 0.0
    assert load_config(environ={"ZKP2P_DEADLINE_S": "junk"}).deadline_s == 0.0
    assert load_config(environ={"ZKP2P_SPOOL_CAP": "junk"}).spool_cap == 0
    assert load_config(environ={"ZKP2P_PROVE_RETRIES": "0"}).prove_retries == 0
    assert load_config(environ={"ZKP2P_PROVE_RETRIES": "junk"}).prove_retries == 2
    assert load_config(environ={"ZKP2P_RETRY_BACKOFF_S": "junk"}).retry_backoff_s == 0.25
    # SLO knobs: objective 0 = disabled; the target fraction must land
    # strictly inside (0,1) — out-of-range or malformed keeps 0.95 (a
    # target of 1.0 would divide the burn rate by zero error budget)
    assert load_config(environ={"ZKP2P_SLO_P95_S": "0"}).slo_p95_s == 0.0
    assert load_config(environ={"ZKP2P_SLO_P95_S": "junk"}).slo_p95_s == 0.0
    assert load_config(environ={"ZKP2P_SLO_TARGET": "1.0"}).slo_target == 0.95
    assert load_config(environ={"ZKP2P_SLO_TARGET": "0"}).slo_target == 0.95
    assert load_config(environ={"ZKP2P_SLO_TARGET": "junk"}).slo_target == 0.95
    assert load_config(environ={"ZKP2P_SLO_TARGET": "0.9"}).slo_target == 0.9
    assert load_config(environ={"ZKP2P_TS_SAMPLE_S": "0"}).ts_sample_s == 0.0
    assert load_config(environ={"ZKP2P_TS_SAMPLE_S": "junk"}).ts_sample_s == 10.0
    # scheduler knobs: the gate stays a raw string (sched_mode fails
    # CLOSED to "off" on anything but "adaptive"); the headroom
    # fraction follows the SLO-target grammar (strictly inside (0,1),
    # malformed keeps 0.8); autoscale bounds are nonneg ints (0 = off)
    # and the hysteresis windows clamp like their alert siblings
    assert load_config(environ={}).sched == "off"
    assert load_config(environ={"ZKP2P_SCHED": "adaptive"}).sched == "adaptive"
    assert load_config(environ={"ZKP2P_SCHED_TARGET_FILL": "junk"}).sched_target_fill == 0.8
    assert load_config(environ={"ZKP2P_SCHED_TARGET_FILL": "1.5"}).sched_target_fill == 0.8
    assert load_config(environ={"ZKP2P_SCHED_TARGET_FILL": "0.5"}).sched_target_fill == 0.5
    assert load_config(environ={"ZKP2P_WORKERS_MAX": "junk"}).workers_max == 0
    assert load_config(environ={"ZKP2P_WORKERS_MIN": "-2"}).workers_min == 0
    assert load_config(environ={"ZKP2P_SCALE_UP_S": "-1"}).scale_up_s == 0.0
    assert load_config(environ={"ZKP2P_SCALE_DOWN_S": "junk"}).scale_down_s == 30.0
    assert load_config(environ={}).sched_priority_default == "bulk"
    # PR-20 floor knobs: interleave and witness-u64 follow the C
    # runtime's not-zero rule (committed ON, off only on a leading
    # '0'); radix-8 follows the C gate's leading-'1' rule — committed
    # OFF (0.95x on narrow hosts), ON only on an explicit '1'
    assert load_config(environ={}).msm_interleave is True
    assert load_config(environ={"ZKP2P_MSM_INTERLEAVE": "0"}).msm_interleave is False
    assert load_config(environ={"ZKP2P_MSM_INTERLEAVE": "true"}).msm_interleave is True
    assert load_config(environ={}).witness_u64 is True
    assert load_config(environ={"ZKP2P_WITNESS_U64": "0"}).witness_u64 is False
    assert load_config(environ={"ZKP2P_WITNESS_U64": "yes"}).witness_u64 is True
    assert load_config(environ={}).ntt_radix8 is False
    assert load_config(environ={"ZKP2P_NTT_RADIX8": "1"}).ntt_radix8 is True
    assert load_config(environ={"ZKP2P_NTT_RADIX8": "0"}).ntt_radix8 is False
    assert load_config(environ={"ZKP2P_NTT_RADIX8": "true"}).ntt_radix8 is False
    assert load_config(environ={"ZKP2P_NTT_RADIX8": ""}).ntt_radix8 is False


def test_env_is_the_only_layer_above_defaults():
    """default -> env, nothing between: no side file can flip a knob
    (the hardware-session armed_flags.json layer is gone), and the
    provenance map only ever says "default" or "env"."""
    import inspect

    assert list(inspect.signature(load_config).parameters) == ["environ"]
    cfg = load_config(environ={"ZKP2P_BATCH_CHUNK": "2"})
    assert cfg.batch_chunk == "2" and cfg.provenance["batch_chunk"] == "env"
    assert cfg.tpu_shard == "off" and cfg.provenance["tpu_shard"] == "default"
    assert set(cfg.provenance.values()) == {"default", "env"}


def test_compile_cache_is_not_a_knob():
    """The compile cache is placed by the standard
    JAX_COMPILATION_CACHE_DIR (utils.jaxcfg), not by a ZKP2P_* knob."""
    assert "jax_cache_dir" not in KNOBS
    assert not any(var == "ZKP2P_JAX_CACHE_DIR" for var, _p, _d in KNOBS.values())
    assert load_config(environ={"ZKP2P_JAX_CACHE_DIR": "/tmp/x"}) == load_config(environ={})


def test_apply_env_roundtrip():
    cfg = load_config(environ={"ZKP2P_BATCH_CHUNK": "2", "ZKP2P_NATIVE_THREADS": "3"})
    env: dict = {}
    cfg.apply_env(env)
    assert env["ZKP2P_BATCH_CHUNK"] == "2"
    assert env["ZKP2P_MSM_OVERLAP"] == "1"
    assert env["ZKP2P_NATIVE_THREADS"] == "3"
    # a second load from the exported env reproduces the config
    cfg2 = load_config(environ=env)
    assert cfg2 == cfg


def test_every_zkp2p_env_read_is_registered():
    """Scan the tree for ZKP2P_* reads: each must be a registered knob
    (or an explicitly test-scoped variable), so no code path can grow a
    config knob outside the typed config again."""
    registered = {var for var, _p, _d in KNOBS.values()}
    # ONE allowlist, shared with the zkp2p-lint knob checker (which runs
    # this same scan as a tier-1 static pass) — two diverging lists
    # would let a token pass one gate and fail the other
    import sys

    sys.path.insert(0, REPO)
    from tools.lint.knobs import ALLOWED_EXTRA as allowed_extra
    found = set()
    scan_roots = ["zkp2p_tpu", "csrc", "bench.py", "__graft_entry__.py", "tools"]
    for root in scan_roots:
        path = os.path.join(REPO, root)
        files = []
        if os.path.isfile(path):
            files = [path]
        else:
            for dirpath, _dirs, names in os.walk(path):
                files += [os.path.join(dirpath, n) for n in names if n.endswith((".py", ".cpp", ".sh"))]
        for f in files:
            if f.endswith("config.py"):
                continue
            with open(f, errors="ignore") as fh:
                # digits included: ZKP2P_SLO_P95_S was the first knob
                # with one, and an [A-Z_]-only scan truncated it to an
                # unregistered-looking "ZKP2P_SLO_P"
                found |= set(re.findall(r"ZKP2P_[A-Z0-9_]*", fh.read()))
    unregistered = found - registered - allowed_extra
    assert not unregistered, f"env reads outside the typed config: {sorted(unregistered)}"
    # and the armable whitelist refers to real knobs
    assert set(ARMABLE) <= set(KNOBS)
