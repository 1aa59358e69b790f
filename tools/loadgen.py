"""QPS-under-SLO load generator + capacity model for the proving service.

BENCH records "proofs/s min-of-reps" — the number a *benchmark* buys.
A deployment buys a different number: the max arrival rate this host
sustains while holding a latency objective (ROADMAP item 2).  This tool
measures it: an **open-loop Poisson** arrival process (arrivals do NOT
wait for completions — the honest model of independent users; a closed
loop self-throttles and hides saturation) writes spool requests at a
target rate, ramps the rate stepwise, and scores each step against the
p95 objective with the same SLO math the service exposes on /status
(utils.slo).  Output: a capacity JSON naming max sustainable QPS for
this host shape.

    python tools/loadgen.py --spool /tmp/lg --rates 0.5,1,2 --step-s 20 \
        --objective-s 30 --circuit toy --out capacity.json

  --circuit toy    hermetic 2-constraint circuit (the chaos-harness
                   world) — a stub-speed prover for smokes; --prove-s
                   adds artificial per-request service time (scaled by
                   batch fill, in-process and --fleet alike) so
                   saturation is reachable in a 2-second test.
  --circuit venmo  the bench-shape 499k-constraint flagship: one
                   synthetic signed email's witness is built once and
                   replayed per request (witnessing is not what this
                   tool measures), every request is a REAL native
                   prove.  Uses the .bench_cache key like bench.py.

By default the tool runs the service in-process (a worker thread
sweeping the spool with the multi-column native batch prover, preflight
armed, metrics/status endpoint on when ZKP2P_METRICS_PORT is set, the
time-series sampler ticking).  --no-service drives an externally
running worker instead: this tool only writes requests and scores the
terminal artifacts.

Request latency is measured from artifact mtimes (req-file mtime →
terminal-file mtime) — the same spool arrival clock the service's
deadlines and queue_wait_s use, so loadgen numbers and service records
agree.  A request still unterminal when the drain window closes counts
as a MISS with latency = cutoff (an unfinished request is not evidence
the SLO held).

The capacity JSON is also wired into bench.py as the `service` arm
(BENCH_SERVICE_S), so trajectory records gain `service_qps_under_slo`.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import threading
import time
import traceback
from typing import Callable, Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TERMINAL_SUFFIXES = (".proof.json", ".error.json")


# ------------------------------------------------------------ worlds


def _toy_world():
    """The deterministic 2-constraint chaos-harness circuit — ONE
    source of truth (tools/chaos.py `_build_world`); proves in
    milliseconds, so a smoke can reach saturation with --prove-s
    instead of minutes of real MSM."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "zkp2p_chaos", os.path.join(os.path.dirname(os.path.abspath(__file__)), "chaos.py"))
    chaos = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chaos)
    cs, dpk, vk, witness_fn = chaos._build_world()

    def payload_fn(rng):
        return {"x": rng.randrange(2, 50), "y": rng.randrange(2, 50)}

    return cs, dpk, vk, witness_fn, (lambda w: [w[1]]), payload_fn, "toy"


def _venmo_world():
    """Bench-shape venmo (499k constraints) with the .bench_cache key:
    ONE synthetic signed email's witness, replayed per request — every
    prove is real; the capacity number measures the PROVING service,
    not the email parser."""
    import bench  # repo-root module; shares the key cache with bench runs

    cs, lay, make_input = bench._build_venmo()
    dpk, vk = bench.build_keys(cs)
    inputs = make_input(0)
    w = cs.witness(inputs.public_signals, inputs.seed)

    def witness_fn(_payload):
        return w

    def public_fn(wit):
        return list(wit[1 : cs.num_public + 1])

    def payload_fn(rng):
        return {"i": rng.randrange(1 << 30)}

    return cs, dpk, vk, witness_fn, public_fn, payload_fn, "venmo"


# ------------------------------------------------------------ capacity


def _write_request(spool: str, rid: str, payload: Dict) -> str:
    """Atomic request drop (tmp + rename): the service's torn-file grace
    window is for sloppy uploaders; the loadgen should not need it."""
    path = os.path.join(spool, rid + ".req.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)
    return path


def parse_trace(spec: str) -> List[Tuple[float, float]]:
    """Parse a piecewise arrival trace "rate x duration" segment list:
    "0.2x30,4x20,0.2x30" = 0.2 QPS for 30 s, a 4 QPS spike for 20 s,
    0.2 QPS for 30 s.  The low->spike->drain shape is THE scheduler
    A/B instrument (docs/SCHEDULING.md): a flat ramp never shows the
    batch-size controller moving.  Malformed specs raise ValueError
    BEFORE the multi-minute run."""
    segments: List[Tuple[float, float]] = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        try:
            rate_s, dur_s = part.lower().split("x")
            rate, dur = float(rate_s), float(dur_s)
        except ValueError:
            raise ValueError(f"bad --trace segment {part!r} (want 'RATExSECONDS,...')") from None
        if rate <= 0 or dur <= 0:
            raise ValueError(f"bad --trace segment {part!r}: rate and duration must be > 0")
        segments.append((rate, dur))
    if not segments:
        raise ValueError(f"--trace {spec!r} has no segments")
    return segments


def run_capacity(
    svc,
    spool: str,
    rates: List[float],
    step_s: float,
    objective_s: float,
    target: float = 0.95,
    payload_fn: Optional[Callable] = None,
    seed: int = 7,
    drain_s: Optional[float] = None,
    poll_s: float = 0.05,
    run_service: bool = True,
    circuit: str = "?",
    prove_sleep_s: float = 0.0,
    batch_overhead_s: float = 0.0,
    fleet_workers: int = 0,
    segments: Optional[List[Tuple[float, float]]] = None,
    log: Callable[[str], None] = lambda m: print(m, file=sys.stderr, flush=True),
) -> Dict:
    """Drive the ramp and score it; returns the capacity report dict.

    svc: a ProvingService (swept in-process when run_service) — pass
    None with run_service=False to only generate load for an external
    worker.  prove_sleep_s / batch_overhead_s: artificial service time
    added around the prover (per request scaled by batch fill + a
    per-batch fixed cost) — the same model the --fleet toy workers
    apply, so in-process and fleet capacity numbers share one
    service-time definition (smoke-scale saturation).  segments:
    explicit (rate, duration) pairs (--trace); None = one segment of
    step_s per entry in rates."""
    from zkp2p_tpu.pipeline.sched import normalize_sched as _normalize_sched
    from zkp2p_tpu.pipeline.service import TimeseriesSampler
    from zkp2p_tpu.utils.audit import execution_digest
    from zkp2p_tpu.utils.config import load_config
    from zkp2p_tpu.utils.metrics import REGISTRY, host_facts, run_id
    from zkp2p_tpu.utils.slo import SloTracker

    os.makedirs(spool, exist_ok=True)
    # Per-run rid prefix: a reused spool still holds prior runs'
    # terminal artifacts, and a colliding rid would score the OLD proof
    # as an instant completion (attainment 1.0 at every rate — a
    # fabricated capacity number).  Unique rids make stale artifacts
    # inert; scoring below looks up this run's rids only.
    run_tok = f"{os.getpid() & 0xFFFF:04x}{int(time.time() * 1000) & 0xFFFF:04x}"
    stale = [f for f in os.listdir(spool) if f.endswith(".req.json")]
    if stale:
        log(f"[loadgen] note: spool holds {len(stale)} pre-existing request(s); "
            f"this run's rids carry prefix lg{run_tok} and are scored alone")
    # The scoring objective IS this run's SLO: write it through to the
    # typed config so the in-process service's tracker, the
    # zkp2p_slo_* gauges behind /status, and the service_slo digest
    # arm all agree with the capacity math (runs at different
    # objectives stay digest-distinguishable).  Restored (and re-armed)
    # on the way out so a host process (bench's service arm) does not
    # inherit a tool-injected "env" objective in its knob manifest.
    # Scoring-only mode (run_service=False) drives an external process
    # — nothing here to reconcile.
    if not 0.0 < target < 1.0:
        raise ValueError(f"SLO target must be in (0,1), got {target}")
    from zkp2p_tpu.utils import slo as slo_mod

    saved_env: Dict[str, Optional[str]] = {}
    if run_service:
        for k, v in (("ZKP2P_SLO_P95_S", f"{objective_s:g}"),
                     ("ZKP2P_SLO_TARGET", f"{target:g}")):
            saved_env[k] = os.environ.get(k)
            os.environ[k] = v
        slo_mod._reset()
        slo_mod.slo_arm()
    try:
        rng = random.Random(seed)
        if payload_fn is None:
            payload_fn = lambda r: {"x": r.randrange(2, 50), "y": r.randrange(2, 50)}  # noqa: E731

        if (prove_sleep_s > 0 or batch_overhead_s > 0) and svc is not None and svc.prover_fn is not None:
            # fleet.slowed_prover is THE shared artificial-service-time
            # model (per request scaled by fill + per-batch overhead) —
            # the chaos/fleet toy workers wrap with the same helper, so
            # the in-process and --fleet capacity numbers stay
            # comparable by construction
            from zkp2p_tpu.pipeline.fleet import slowed_prover

            svc.prover_fn = slowed_prover(svc.prover_fn, prove_sleep_s, batch_overhead_s)

        stop = threading.Event()
        worker_errors: List[str] = []

        def worker():
            cfg = load_config()
            sampler = TimeseriesSampler(cfg.ts_sample_s, svc.stale_claim_s)
            svc._sampler = sampler
            while not stop.is_set():
                try:
                    svc.process_dir(spool)
                    sampler.maybe_sample(spool, svc._sink(spool))
                except Exception:  # noqa: BLE001 — the ramp must finish and report
                    worker_errors.append(traceback.format_exc())
                stop.wait(poll_s)

        th = None
        if run_service:
            th = threading.Thread(target=worker, daemon=True, name="loadgen-service")
            th.start()

        # ---- ramp: open-loop Poisson arrivals per segment (a --trace
        # spec, or one step_s segment per --rates entry)
        if segments is None:
            segments = [(r, step_s) for r in rates]
        steps_reqs: List[List[str]] = []
        t_ramp0 = time.time()
        for si, (rate, seg_s) in enumerate(segments):
            reqs: List[str] = []
            t_end = time.time() + seg_s
            t_next = time.time()
            while t_next < t_end:
                delay = t_next - time.time()
                if delay > 0:
                    time.sleep(delay)
                rid = f"lg{run_tok}s{si:02d}r{len(reqs):05d}"
                _write_request(spool, rid, payload_fn(rng))
                reqs.append(rid)
                t_next += rng.expovariate(rate)
            steps_reqs.append(reqs)
            log(f"[loadgen] step {si}: target {rate:g} QPS -> {len(reqs)} requests in {seg_s:g}s")

        # ---- drain: give in-flight work a bounded window to terminal
        if drain_s is None:
            drain_s = max(2 * max(s for _r, s in segments), 10.0)
        t_cutoff = time.time() + drain_s
        while time.time() < t_cutoff:
            open_reqs = [
                rid for reqs in steps_reqs for rid in reqs
                if not any(os.path.exists(os.path.join(spool, rid + s)) for s in TERMINAL_SUFFIXES)
            ]
            if not open_reqs:
                break
            time.sleep(min(0.2, poll_s * 4))
        if run_service:
            stop.set()
            th.join(timeout=30.0)

        # ---- score each step with the /status SLO math (window unbounded:
        # a ramp step is its own window)
        now = time.time()
        steps_out: List[Dict] = []
        for si, ((rate, seg_s), reqs) in enumerate(zip(segments, steps_reqs)):
            tracker = SloTracker(objective_s=objective_s, target=target, window_s=0.0)
            done = errors = unfinished = 0
            for rid in reqs:
                base = os.path.join(spool, rid)
                try:
                    t_sub = os.path.getmtime(base + ".req.json")
                except OSError:
                    t_sub = now
                if os.path.exists(base + ".proof.json"):
                    done += 1
                    tracker.observe(os.path.getmtime(base + ".proof.json") - t_sub, ok=True)
                elif os.path.exists(base + ".error.json"):
                    errors += 1
                    tracker.observe(os.path.getmtime(base + ".error.json") - t_sub, ok=False)
                else:
                    # never finished: a miss at the cutoff, not a free pass
                    unfinished += 1
                    tracker.observe(max(0.0, now - t_sub), ok=False)
            snap = tracker.snapshot()
            ok = bool(reqs) and snap["attainment"] >= target
            steps_out.append({
                "qps_target": rate,
                "offered": len(reqs),
                "done": done,
                "errors": errors,
                "unfinished": unfinished,
                # served-under-SLO: done AND inside the objective — THE
                # scheduler-A/B comparison count (a late `done` is not
                # a served request to an SLO)
                "served_under_slo": snap["good"],
                "duration_s": round(seg_s, 3),
                "completed_qps": round(done / seg_s, 4) if seg_s > 0 else 0.0,
                "p50_s": snap["p50_s"],
                "p95_s": snap["p95_s"],
                "max_s": snap["max_s"],
                "attainment": snap["attainment"],
                "burn_rate": snap["burn_rate"],
                "ok": ok,
            })
            log(
                f"[loadgen] step {si}: {rate:g} QPS offered={len(reqs)} done={done} "
                f"under_slo={snap['good']} p95={snap['p95_s']:.2f}s "
                f"attainment={snap['attainment']:.3f} {'OK' if ok else 'MISS'}"
            )

        passing = [s["qps_target"] for s in steps_out if s["ok"]]
        report = {
            "type": "capacity",
            "ts": round(t_ramp0, 3),
            "run_id": run_id(),
            "pid": os.getpid(),
            "host": host_facts(),
            "execution_digest": execution_digest(),
            "circuit": circuit,
            "arrivals": "open-loop poisson",
            "seed": seed,
            "objective_p95_s": objective_s,
            "target": target,
            "step_s": step_s,
            "trace": ",".join(f"{r:g}x{s:g}" for r, s in segments),
            # the scheduler arm that served this run (capacity numbers
            # at different arms are not comparable without it; ONE
            # normalization rule, owned by pipeline.sched)
            "sched": _normalize_sched(load_config().sched),
            "drain_s": round(drain_s, 3),
            "steps": steps_out,
            # THE number: the highest offered rate whose step held the
            # objective.  0.0 = no step held it (rates all above capacity —
            # re-run lower), reported honestly rather than extrapolated.
            "max_sustainable_qps": max(passing) if passing else 0.0,
            # whole-run served-under-SLO count: the scheduler A/B's
            # scalar (per-segment splits live in `steps`)
            "served_under_slo": sum(s["served_under_slo"] for s in steps_out),
        }
        if fleet_workers:
            # the serving side was an N-worker fleet (external processes
            # under the `zkp2p-tpu fleet` supervisor), not the
            # in-process service — capacity numbers at different N are
            # not comparable without this field
            report["fleet_workers"] = fleet_workers
        if worker_errors:
            report["worker_errors"] = worker_errors[:3]
        # service-observability counters snapshot for the record
        fills = [
            m for m in REGISTRY.snapshot()
            if m["name"] == "zkp2p_service_batch_fill" and m["kind"] == "histogram"
        ]
        if fills and fills[0]["count"]:
            report["mean_batch_fill"] = round(fills[0]["sum"] / fills[0]["count"], 3)
        return report
    finally:
        if run_service:
            for k, v in saved_env.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            slo_mod._reset()
            slo_mod.slo_arm()


# ------------------------------------------------------------ CLI


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--spool", required=True, help="spool directory (created if absent)")
    ap.add_argument("--rates", default="0.5,1,2",
                    help="comma-separated target QPS per ramp step")
    ap.add_argument("--step-s", type=float, default=20.0, help="seconds per ramp step")
    ap.add_argument("--trace", default="",
                    help="piecewise arrival trace 'RATExSECONDS,...' (e.g. "
                         "'0.2x30,4x20,0.2x30' = low->spike->drain; overrides "
                         "--rates/--step-s; scored per segment)")
    ap.add_argument("--objective-s", type=float, default=None,
                    help="p95 latency objective in s (default: ZKP2P_SLO_P95_S, else 30)")
    ap.add_argument("--target", type=float, default=None,
                    help="attainment target fraction (default: ZKP2P_SLO_TARGET)")
    ap.add_argument("--circuit", choices=["toy", "venmo"], default="toy")
    ap.add_argument("--batch", type=int, default=4, help="service batch size")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--prove-s", type=float, default=0.0,
                    help="artificial PER-REQUEST prove time, scaled by batch fill "
                         "(smoke-scale saturation; same model in-process and --fleet)")
    ap.add_argument("--batch-overhead-s", type=float, default=0.0,
                    help="artificial PER-BATCH fixed prove cost (models the "
                         "amortization curve's setup term; same model in-process "
                         "and --fleet)")
    ap.add_argument("--sched", choices=["off", "adaptive"], default=None,
                    help="scheduler arm for the serving side (writes ZKP2P_SCHED; "
                         "default: inherit the environment)")
    ap.add_argument("--fleet-min", type=int, default=None,
                    help="with --fleet: autoscale floor (--workers-min)")
    ap.add_argument("--fleet-max", type=int, default=None,
                    help="with --fleet: autoscale ceiling (--workers-max; the "
                         "autoscale demo arm)")
    ap.add_argument("--drain-s", type=float, default=None,
                    help="max wait for in-flight work after the ramp (default 2*step)")
    ap.add_argument("--no-service", action="store_true",
                    help="only generate load; an external worker sweeps the spool")
    ap.add_argument("--fleet", type=int, default=0,
                    help="serve the ramp with N toy workers under the `zkp2p-tpu fleet` "
                         "supervisor (subprocesses) instead of the in-process service — "
                         "the fleet-scaling arm of the capacity model (toy circuit only)")
    ap.add_argument("--out", default="", help="also write the capacity JSON to this path")
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from zkp2p_tpu.pipeline.service import ProvingService
    from zkp2p_tpu.prover.native_prove import prove_native_batch
    from zkp2p_tpu.utils.audit import preflight
    from zkp2p_tpu.utils.config import load_config
    from zkp2p_tpu.utils.metrics import maybe_start_metrics_server

    # the scheduler arm rides the env (fresh-read per sweep): the flag
    # covers the in-process service AND the --fleet workers (inherited)
    if args.sched is not None:
        os.environ["ZKP2P_SCHED"] = args.sched

    cfg = load_config()
    objective_s = args.objective_s if args.objective_s is not None else (cfg.slo_p95_s or 30.0)
    target = args.target if args.target is not None else cfg.slo_target
    segments = None
    if args.trace:
        try:
            segments = parse_trace(args.trace)
        except ValueError as e:
            print(f"[loadgen] {e}", file=sys.stderr)
            return 2
        rates = [r for r, _s in segments]
    else:
        rates = [float(r) for r in args.rates.split(",") if r.strip()]
    if not rates or any(r <= 0 for r in rates):
        print(f"[loadgen] bad --rates {args.rates!r}: need positive QPS values", file=sys.stderr)
        return 2
    # fail BEFORE the multi-minute ramp, not at scoring time
    if not 0.0 < target < 1.0:
        print(f"[loadgen] bad --target {target!r}: need a fraction in (0,1)", file=sys.stderr)
        return 2

    if args.fleet and args.circuit != "toy":
        print("[loadgen] --fleet serves the toy circuit only (each worker is a "
              "fresh process; venmo workers would each rebuild the 499k key)", file=sys.stderr)
        return 2

    svc = None
    payload_fn = None
    circuit = args.circuit
    fleet_proc = None
    if args.fleet:
        # N subprocess workers under the fleet supervisor sweep the
        # spool; this process only generates + scores (the external-
        # worker mode of run_capacity).  Workers linger past spool-
        # terminal — the ramp writes continuously — and drain on the
        # supervisor's SIGTERM at the end.
        import signal as _signal
        import subprocess

        os.makedirs(args.spool, exist_ok=True)
        # per-RUN fleet dir: a reused spool's previous .fleet would
        # satisfy the readiness gate below with STALE heartbeats before
        # the supervisor even starts, billing N cold starts as queue
        # latency — the exact artifact the gate exists to prevent
        fleet_dir = os.path.join(args.spool, f".fleet-{os.getpid():x}{int(time.time()) & 0xFFFF:04x}")
        worker_argv = [
            sys.executable,
            os.path.join(os.path.dirname(os.path.abspath(__file__)), "chaos.py"),
            "--worker", "--linger",
            "--spool", args.spool,
            "--batch", str(args.batch),
            "--prove-s", str(args.prove_s),
            "--batch-overhead-s", str(args.batch_overhead_s),
            "--max-seconds", "100000",
            "--poll-s", "0.05",
        ]
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        # the fleet observability plane rides the run (auto port, bound
        # port in status.json): it IS the readiness gate below, and its
        # merged SLO + fired alerts land in the capacity JSON.  Parse-
        # checked, not setdefault: an explicitly EMPTY (or junk) value
        # in the caller's environment also means plane-off, and a
        # plane-less fleet can never pass the /status gate.
        from zkp2p_tpu.utils.config import _opt_port

        if _opt_port(env.get("ZKP2P_FLEET_METRICS_PORT") or "") is None:
            env["ZKP2P_FLEET_METRICS_PORT"] = "auto"
        # the scoring objective is the WORKERS' objective too — the
        # merged fleet window recorded at teardown must judge "good"
        # by the same bound the capacity math scores against (the
        # in-process arm writes the same env through run_capacity)
        env["ZKP2P_SLO_P95_S"] = f"{objective_s:g}"
        env["ZKP2P_SLO_TARGET"] = f"{target:g}"
        fleet_argv = [
            sys.executable, "-m", "zkp2p_tpu", "fleet",
            "--spool", args.spool,
            "--workers", str(args.fleet),
            "--fleet-dir", fleet_dir,
            "--worker-cmd", json.dumps(worker_argv),
        ]
        if args.fleet_min is not None:
            fleet_argv += ["--workers-min", str(args.fleet_min)]
        if args.fleet_max is not None:
            # the autoscale demo arm: workers grow on the spike, drain
            # back down after it (pipeline.sched.AutoscalePolicy)
            fleet_argv += ["--workers-max", str(args.fleet_max)]
        fleet_proc = subprocess.Popen(fleet_argv, env=env, cwd=REPO)
        # readiness gate: score only once the FLEET /status answers 200
        # — i.e. every live worker is up, scrapable, AND has armed its
        # gates (preflight).  Stronger than the old N-heartbeat-files
        # check: a stale .hb can't fake readiness, an unarmed worker
        # can't hide, and step 0 never pays N cold python/jax imports
        # billed as queue latency.
        from zkp2p_tpu.pipeline.fleet_obs import discover_fleet_port, http_status_json

        deadline = time.time() + 120.0
        fleet_status_url = None
        last_reason = "status.json has no metrics_port yet"
        while time.time() < deadline:
            if fleet_proc.poll() is not None:
                print("[loadgen] fleet supervisor died before the ramp", file=sys.stderr)
                return 2
            if fleet_status_url is None:
                port = discover_fleet_port(fleet_dir)
                if port:
                    fleet_status_url = f"http://127.0.0.1:{port}/status"
            if fleet_status_url is not None:
                st = http_status_json(fleet_status_url)
                if st and st.get("ok"):
                    break
                if st:
                    last_reason = st.get("reason", "not ready")
            time.sleep(0.1)
        else:
            fleet_proc.kill()
            print(f"[loadgen] fleet never became ready ({last_reason})", file=sys.stderr)
            return 2
        print(
            f"[loadgen] fleet ready: /status 200 ({args.fleet} armed workers)",
            file=sys.stderr,
        )
    elif not args.no_service:
        world = _toy_world() if args.circuit == "toy" else _venmo_world()
        cs, dpk, vk, witness_fn, public_fn, payload_fn, circuit = world
        svc = ProvingService(
            cs, dpk, vk, witness_fn, public_fn=public_fn,
            batch_size=args.batch, prover_fn=prove_native_batch,
        )
        # arm the gates (also opens /status — it fails closed until a
        # preflight has run) and the exposition endpoint when configured
        preflight(workload=False,
                  log=lambda m: print(f"[loadgen] {m}", file=sys.stderr, flush=True))
        maybe_start_metrics_server()

    try:
        report = run_capacity(
            svc, args.spool, rates, args.step_s, objective_s, target=target,
            payload_fn=payload_fn, seed=args.seed, drain_s=args.drain_s,
            run_service=not args.no_service and not args.fleet, circuit=circuit,
            prove_sleep_s=args.prove_s, batch_overhead_s=args.batch_overhead_s,
            fleet_workers=args.fleet, segments=segments,
        )
        if args.fleet and fleet_status_url:
            # the serving fleet's own read of the run, BEFORE teardown:
            # merged SLO (sample count = sum of worker windows) and
            # every alert that fired — a capacity number whose run
            # tripped restart_storm or slo_burn is not a capacity number
            fs = http_status_json(fleet_status_url, timeout=5)
            if fs:
                report["fleet_slo"] = fs.get("slo")
                # autoscale record: band, live count, every scale event
                # this run took (the demo's acceptance surface)
                report["fleet_sched"] = fs.get("sched")
                report["fleet_alerts"] = {
                    "active": fs.get("alerts", []),
                    "fired": {
                        rule: st.get("fired_count", 0)
                        for rule, st in (fs.get("alerts_state") or {}).items()
                        if st.get("fired_count")
                    },
                }
    finally:
        if fleet_proc is not None and fleet_proc.poll() is None:
            # graceful fleet teardown: SIGTERM fans drain out to the
            # workers; the supervisor escalates stragglers itself
            fleet_proc.send_signal(_signal.SIGTERM)
            try:
                fleet_proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                fleet_proc.kill()
    print(json.dumps(report, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    print(
        f"[loadgen] max sustainable QPS at p95<={objective_s:g}s "
        f"(target {target:g}): {report['max_sustainable_qps']:g}",
        file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
