"""One run of one cell: the served path, from the client's side, on the chip.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip.  Set-up builds the circuit, loads or builds the
device key, proves the pinned warm-up batch (which is also the oracle
batch) and starts `ProvingService.run` on a spool in a thread; the window
drives requests into the spool as the cell's traffic file says and reads
terminal artifacts back; then the service drains what it has claimed, every
proof is checked against the pairing and against its own request, the
pinned batch against the C++ prover, and the last line of stdout is the
result.  See benchmarks/README.md.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from typing import Dict, List, Optional, Sequence  # noqa: E402

GUARANTEE_COUNTERS = ("zkp2p_service_shed_total", "zkp2p_service_degraded_total", "zkp2p_service_retries_total")
DRAIN_TIMEOUT_S = 150.0
WARM_SERVED_TIMEOUT_S = 600.0  # a checkout's first run has 1200 s
SETUP_INDEX = 10**6  # set-up's requests are numbered from here, the window's from 0


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class Monitor:
    """jax.monitoring events, each stamped with the phase it fell in."""

    def __init__(self):
        self.phase = "setup"
        self.events: List[Dict] = []

    def install(self) -> None:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(
            lambda name, secs, **_kw: self.events.append({"name": name, "secs": secs, "phase": self.phase}))
        monitoring.register_event_listener(
            lambda name, **_kw: self.events.append({"name": name, "secs": None, "phase": self.phase}))

    def count(self, suffix: str, phase: Optional[str] = None) -> int:
        return sum(1 for e in self.events if e["name"].endswith(suffix) and phase in (None, e["phase"]))

    def cache_misses(self) -> int:
        return self.count("/compile_requests_use_cache") - self.count("/cache_hits")


class Phases:
    """The harness's own set-up clock: {name: seconds}."""

    def __init__(self):
        self.seconds: Dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[name] = self.seconds.get(name, 0.0) + time.perf_counter() - t0
            say(f"set-up: {name} {self.seconds[name]:.1f}s")


def device_key(world, config: Dict, root: str):
    """Built once per checkout, kept as .npz under .bench_cache/ (never under
    an output directory: it is 460 MB at venmo 256/192), pinned to the
    circuit's digest."""
    from zkp2p_tpu.prover.keycache import KeyCacheSchemaError, circuit_digest, load_dpk, save_dpk
    from zkp2p_tpu.prover.setup_device import setup_device

    path = os.path.join(root, ".bench_cache", "benchmarks", config["name"] + ".npz")
    digest = circuit_digest(world.cs)
    if os.path.exists(path):
        try:
            dpk, vk = load_dpk(path, digest=digest)
            say(f"device key loaded from {os.path.relpath(path, root)}")
            return dpk, vk
        except KeyCacheSchemaError as e:
            say(f"stale key cache: {e}")
    dpk, vk = setup_device(world.cs, seed=config["key_seed"])
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp.npz"
    save_dpk(tmp, dpk, vk, digest=digest)
    os.replace(tmp, path)
    say(f"device key built (setup_device) and kept at {os.path.relpath(path, root)}")
    return dpk, vk


def counters_by_name(snapshot: List[Dict]) -> Dict[str, Dict]:
    """REGISTRY.snapshot() folded over labels."""
    out: Dict[str, Dict] = {}
    for m in snapshot:
        c = out.setdefault(m["name"], {"kind": m["kind"], "value": 0.0, "count": 0, "sum": 0.0})
        c["value"] += m.get("value", 0.0) or 0.0
        c["count"] += m.get("count", 0) or 0
        c["sum"] += m.get("sum", 0.0) or 0.0
    return out


def read_sink(path: str) -> List[Dict]:
    """The records of the service's JSONL sink (each run has a spool, so a sink, of its own)."""
    try:
        with open(path) as f:
            return [json.loads(line) for line in f if line.strip()]
    except OSError:
        return []


class TraceSlice:
    """The traced run's profiler slice, driven from the generator's tick:
    it starts `start_s` after the first submission and stops
    `after_boundary_s` after the first batch boundary (a terminal artifact
    appearing) it sees, or after `max_s` — so it holds the end of one batch
    and the gap to the next batch's first device operation.  The profiler
    is stopped on a thread of its own: collecting a slice takes seconds,
    and an open loop's generator has requests due meanwhile."""

    def __init__(self, spec: Dict, out_dir: str):
        self.spec, self.out_dir = spec, out_dir
        self.t_start = self.t_stop = self.t_boundary = self.stopping = None
        self.done_at_start = 0
        self.wall_start_ns = self.wall_stop_ns = None

    def tick(self, now: float, completed: int, t_first: Optional[float]) -> None:
        if t_first is None or self.t_stop is not None:
            return
        if self.t_start is None:
            if now - t_first >= self.spec["start_s"]:
                import jax

                from benchmarks.harness.trace_reduce import ANCHOR

                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0  # the host code is Python big-int arithmetic: millions of frames
                opts.host_tracer_level = 2
                jax.profiler.start_trace(self.out_dir, profiler_options=opts)
                self.wall_start_ns = time.time_ns()
                with jax.profiler.TraceAnnotation(ANCHOR, wall_ns=str(self.wall_start_ns)):
                    pass
                self.t_start, self.done_at_start = time.time(), completed
            return
        if self.t_boundary is None and completed > self.done_at_start:
            self.t_boundary = now
        if (self.t_boundary is not None and now - self.t_boundary >= self.spec["after_boundary_s"]) \
                or now - self.t_start >= self.spec["max_s"]:
            self.stop(wait=False)

    def stop(self, wait: bool = True) -> None:
        """The slice ends now; `wait` until the profiler has written it."""
        if self.t_start is not None and self.t_stop is None:
            import jax

            self.wall_stop_ns, self.t_stop = time.time_ns(), time.time()
            self.stopping = threading.Thread(target=jax.profiler.stop_trace, name="bench-trace-stop")
            self.stopping.start()
        if wait and self.stopping is not None:
            self.stopping.join()

    def xplane(self) -> Optional[str]:
        found = sorted(glob.glob(os.path.join(self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


NOT_HOST_INTERVALS = ("device_idle", "stage", "upload")  # accounts of a gap laid end to end, and the device's own clock


def host_spans(records: List[Dict], stage_spans: Sequence[Dict] = ()) -> List[Dict]:
    """What the host was doing, as gap labels (wall clock t0 + ms): the
    service's per-request lifecycle spans, one per batch, and the stage
    spans of the sink (`service/sweep`, `/poll`, `/handover`, `/starved`,
    `tpu/prove_batch/prep`, `/finish`, ...).  The proving thread's and the
    run loop's first (rank 0), then the witness producers'.  A span of a
    replica's loop carries its `replica`."""
    seen, out = set(), []

    def add(label: str, t0: float, ms: float, replica) -> None:
        if (label, t0) not in seen:
            seen.add((label, t0))
            out.append({"label": label, "t0_wall_s": t0, "ms": ms, "replica": replica,
                        "rank": 1 if label.rsplit("/", 1)[-1].startswith(("witness", "inputs")) else 0})

    for rec in records:
        for sp in rec.get("spans") or []:
            add("service/" + sp["name"], sp["t0"], sp["ms"], rec.get("replica"))
    for sp in stage_spans:
        path = sp["stage"]
        at = max(path.rfind("service/"), path.rfind("tpu/"))
        if at >= 0 and not any(part in NOT_HOST_INTERVALS for part in path.split("/")):
            # a path is the nesting of its thread ("service/prove/tpu/prove_batch/finish"): its last layer names it
            add(path[at:], sp["t0"], sp["ms"], sp.get("replica"))
    return out


class Bench:
    """Set-up once (device, caches, native library, circuit, key), then
    `warm_up` and `measure` a seed: the command measures one; the seed sweep
    under benchmarks/tests measures several in one process after one set-up."""

    def __init__(self, cell, chip, root: str):
        from benchmarks.harness import worlds

        self.cell, self.chip, self.root = cell, chip, root
        self.config, self.traffic = cell.config, cell.traffic
        # the program's own knobs, set before the program is imported
        for k, v in self.config.get("env", {}).items():
            os.environ[k] = str(v)
        self.device = chip.require(cell.chips)
        say(f"cell {cell.name}: config {cell.config_name}, traffic {cell.traffic_name}; device {self.device}")

        from zkp2p_tpu.utils.jaxcfg import cache_dir, enable_cache

        enable_cache(min_compile_s=0.0)  # every executable round-trips: later runs here compile nothing
        import jax

        # A machine may cap the cache's size (JAX_COMPILATION_CACHE_MAX_SIZE; the chip tool's
        # 192 MiB): venmo's executables are more, so each run evicted what the next would read
        # and none ever hit.  A checkout's second run has to find every program: no cap.
        jax.config.update("jax_compilation_cache_max_size", -1)
        self.monitor = Monitor()
        self.monitor.install()
        say(f"compile cache: {cache_dir()}")
        self.phases = Phases()
        with self.phases("native"):
            chip.native_library()
        with self.phases("circuit"):
            self.world = worlds.build_world(self.config)
        cs = self.world.cs
        say(f"circuit: {cs.num_constraints} constraints, {cs.num_wires} wires, {cs.num_public} public signals")
        if cs.num_public and self.world.public_tie is None:
            raise SystemExit(f"benchmarks: {cell.config_name} has public signals and names no `public_tie` to hold them to")
        with self.phases("key"):
            self.dpk, self.vk = device_key(self.world, self.config, root)
        self.batch_size = int(self.traffic["batch_size"] or self.config["batch_size"])  # the mix's, else the configuration's

    def service(self):
        kw = {"batch_size": self.batch_size}
        if self.traffic["max_wait_s"] is not None:
            kw["max_wait_s"] = float(self.traffic["max_wait_s"])
        return self.world.make_service(self.dpk, self.vk, **kw)

    def warm_up(self, seed: int) -> Dict:
        """The warm-up batch, which IS the oracle batch: one batch of the cell's
        own shape through `prove_tpu_batch` with (r, s) pinned from the seed;
        it is checked after the window, outside set-up.  Called through
        `in_one_chunk`, or its lowering takes one to four times as long
        depending on the frames this harness happens to put under it."""
        from benchmarks.harness import worlds
        from benchmarks.harness.pystack import in_one_chunk
        from zkp2p_tpu.field.bn254 import R
        from zkp2p_tpu.prover import groth16_tpu

        rng = random.Random(f"pinned-{seed}")
        warm = {"rs": [rng.randrange(1, R) for _ in range(self.batch_size)],
                "ss": [rng.randrange(1, R) for _ in range(self.batch_size)], "svc": self.service(),
                "payloads": [worlds.payload_for(self.world, seed, SETUP_INDEX + i) for i in range(self.batch_size)]}
        with self.phases("warmup_witness"):
            warm["wits"] = worlds.witnesses(warm["svc"], warm["payloads"])
        missed = self.monitor.cache_misses()
        with self.phases("warmup_batch"):
            warm["pinned"] = in_one_chunk(
                groth16_tpu.prove_tpu_batch, self.dpk, warm["wits"], rs=warm["rs"], ss=warm["ss"])
        warm["compiled"] = self.monitor.cache_misses() - missed
        return warm

    def measure(self, seed: int, seconds: float, trace: int, t_process: float, warm: Dict) -> Dict:
        """One window after `warm_up(seed)`, the drain and the checks."""
        from benchmarks.harness import check, loadgen, pystack, readers, score, trace_reduce, worlds
        from zkp2p_tpu.formats.proof_json import proof_to_json, public_to_json
        from zkp2p_tpu.prover.native_prove import prove_native
        from zkp2p_tpu.utils import trace as program_trace
        from zkp2p_tpu.utils.audit import gate_arms, last_preflight
        from zkp2p_tpu.utils.metrics import REGISTRY

        cell, config, traffic, phases, monitor = self.cell, self.config, self.traffic, self.phases, self.monitor
        say(f"seed {seed}, {seconds:g}s, trace {trace}")
        run_dir = os.path.join(self.root, ".bench_runs", f"{cell.name}-s{seed}-t{trace}")
        shutil.rmtree(run_dir, ignore_errors=True)  # a repeated seed must not score the last run's artifacts
        spool = os.path.join(run_dir, "spool")
        os.makedirs(spool)
        sink = spool + ".metrics.jsonl"
        svc, warm_wits, rs, ss, pinned = warm["svc"], warm["wits"], warm["rs"], warm["ss"], warm["pinned"]

        # the service, in a thread of this process: ProvingService.run is the served entry
        svc_error: List[BaseException] = []

        def serve():
            try:
                pystack.in_one_chunk(svc.run, spool, poll_s=float(traffic["poll_s"]))
            except BaseException as e:  # noqa: BLE001 — reported on the main thread below
                svc_error.append(e)

        with phases("service_start"):
            t_started = time.time()
            th = threading.Thread(target=serve, name="bench-service", daemon=True)
            th.start()
            while (last_preflight() or {}).get("ts", 0) < t_started and th.is_alive():
                time.sleep(0.05)
            time.sleep(2 * float(traffic["poll_s"]))
        if svc_error or not th.is_alive():
            raise SystemExit(f"benchmarks.run: the service did not start: {svc_error}")
        if warm["compiled"]:
            # On the chip, a service whose first batch followed a warm-up that had COMPILED
            # (a checkout's first run) was found lowering every program again, in its own
            # thread, and finished nothing in the window (PERF.md, PR 23; not explained).
            # So after such a warm-up one batch is served through the spool before the
            # window opens: whatever the service's thread still lacks, it makes in set-up.
            say(f"the warm-up batch compiled {warm['compiled']} programs: one batch is served before the window")
            lowered = monitor.count("/jaxpr_to_mlir_module_duration")
            with phases("warmup_served"):
                served = loadgen.serve_once(
                    spool, [worlds.payload_for(self.world, seed, 2 * SETUP_INDEX + i) for i in range(self.batch_size)],
                    f"warm{seed}", WARM_SERVED_TIMEOUT_S, lambda: th.is_alive() and not svc_error)
                time.sleep(2 * float(traffic["poll_s"]))  # the sweep that served it ends and writes its records
            if served != ["done"] * self.batch_size:
                raise SystemExit(f"benchmarks.run: the batch served before the window ended {served}: {svc_error}")
            say(f"the service lowered {monitor.count('/jaxpr_to_mlir_module_duration') - lowered} programs serving it")

        # ------------------------------------------------------------ window
        program_trace.reset()  # the warm-up's spans are set-up, not the window's
        sink_before = len(read_sink(sink))  # a batch served in set-up left its records there
        counters_before = counters_by_name(REGISTRY.snapshot())
        slicer = TraceSlice(config["trace"], os.path.join(run_dir, "trace")) if trace else None
        state = {"t_first": None}

        def on_tick(now: float, completed: int) -> None:
            if slicer is not None:
                slicer.tick(now, completed, state["t_first"])

        def make_payload(i: int) -> Dict:
            if state["t_first"] is None:
                state["t_first"] = time.time()
                monitor.phase = "window"
            return worlds.payload_for(self.world, seed, i)

        setup_s = time.time() - t_process  # process start to first submission
        say(f"window opens; set-up took {setup_s:.1f}s; persistent cache "
            f"{monitor.count('/cache_hits')} hits of {monitor.count('/compile_requests_use_cache')} requests")
        win = loadgen.run_window(spool, traffic, make_payload, seed, seconds, on_tick)
        if slicer is not None:
            slicer.stop()

        # stop submitting and let what the service has claimed finish: it is scored
        # with the window's, and the process never exits with device work under way
        t_drain = time.time()
        svc.request_drain()
        th.join(timeout=DRAIN_TIMEOUT_S)
        if th.is_alive() or svc_error:
            raise SystemExit(
                f"benchmarks.run: the service did not drain in {DRAIN_TIMEOUT_S:.0f}s: {svc_error}; since the window "
                f"opened it traced {monitor.count('/jaxpr_trace_duration', 'window')} functions, lowered "
                f"{monitor.count('/jaxpr_to_mlir_module_duration', 'window')} and compiled "
                f"{monitor.count('/backend_compile_duration', 'window')}")
        say(f"service drained in {time.time() - t_drain:.1f}s")
        monitor.phase = "setup"
        loadgen.collect_late_terminals(spool, win["requests"])
        memory = self.chip.memory_stats()
        arms = gate_arms()
        counters_after = counters_by_name(REGISTRY.snapshot())

        # ------------------------------------------------------------ checks
        t_check = time.time()
        workers = max(1, min(12, (os.cpu_count() or 2) - 1))
        vk_ints = check.vk_to_ints(self.vk)
        untied = check.check_window(vk_ints, spool, win["requests"], workers, self.world.public_tie)
        natives = [prove_native(self.dpk, w, r, s_) for w, r, s_ in zip(warm_wits, rs, ss)]
        numbers = check.check_pinned(
            vk_ints, [proof_to_json(p) for p in pinned], [public_to_json(svc.public_fn(w)) for w in warm_wits],
            [proof_to_json(p) for p in natives], workers,
            [self.world.public_tie(p) for p in warm["payloads"]] if self.world.public_tie else ())
        sc = score.score_window(win["requests"], win["t_first"])
        numbers["requests_not_done_or_pairing_invalid_or_passed_over"] = sc["failed"] - untied
        numbers["proofs_with_signals_not_their_requests"] = untied
        counted: Dict[str, int] = {}  # printed beside the numbers compared, with no limit
        if traffic["deadline_s"]:
            # a refusal once the request's own deadline has passed is a miss (it stays in `failed`), not a wrong answer
            numbers["requests_not_done_or_pairing_invalid_or_passed_over"] -= (
                sc["refused_at_deadline"] + sc["refused_before_deadline"])
            numbers["requests_refused_before_their_deadline"] = sc["refused_before_deadline"]
            counted["requests_refused_at_their_deadline"] = sc["refused_at_deadline"]
        numbers["requests_shed_degraded_or_retried"] = int(sum(
            counters_after[n]["value"] - counters_before.get(n, {"value": 0.0})["value"]
            for n in GUARANTEE_COUNTERS if n in counters_after))
        arm_faults = self.chip.arm_faults(arms, config.get("arms", {}))
        numbers["gate_arm_faults"] = len(arm_faults)
        for name, value in numbers.items():
            say(f"check: {name} = {value} (limit 0)")
        for name, value in counted.items():
            say(f"check: {name} = {value} (no limit: counted in failed)")
        if arm_faults:
            say(f"gate arms not as configured: {arm_faults}")
        say(f"gates: {json.dumps(arms, sort_keys=True)}")
        correct = all(v == 0 for v in numbers.values()) and sc["attempted"] > 0 and sc["proofs_per_s"] is not None
        say(f"checked in {time.time() - t_check:.1f}s: of {sc['submitted']} requests the service took on "
            f"{sc['attempted']}, {sc['failed']} failed, {sc['unclaimed_at_end']} it had not claimed when the "
            f"window closed; {sc['latency_samples']} latency samples")
        late = score.lateness(win["lateness_due"], win["lateness_sent"]) if win["lateness_due"] else None
        if late:
            say(f"generator lateness: {json.dumps(late)}")

        # ----------------------------------------------------------- metrics
        values: Dict[str, Optional[float]] = {"setup_s": setup_s}
        for key in ("proofs_per_s", "latency_p50_s", "latency_p90_s"):
            values[key] = sc.get(key)
        metrics: Dict[str, Dict] = {}
        result_device = dict(self.device)
        result_device["memory_peak_bytes"] = max((m.get("peak_bytes_in_use", 0) for m in memory), default=0)
        result: Dict = {"correct": bool(correct), "attempted": sc["attempted"], "failed": sc["failed"]}
        if not trace:
            for m in cell.end_to_end:
                if values.get(m["name"]) is not None:
                    metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        else:
            records = read_sink(sink)[sink_before:]
            run_data = {
                "stage_spans": [r for r in records if r.get("type") == "stage"],
                "request_records": [r for r in records if r.get("type") == "request"],
                "counters": {n: {"before": counters_before.get(n), "after": counters_after.get(n)}
                             for n in counters_after},
                "monitoring": monitor.events, "memory": memory, "phases": phases.seconds,
                "batch_size": self.batch_size, "score": sc, "lateness": late,
            }
            for m in cell.per_layer:
                v = readers.read_metric(m, run_data)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            xplane = slicer.xplane()
            reduced = None
            if xplane:
                say(f"trace: {os.path.getsize(xplane)} bytes, slice {slicer.t_stop - slicer.t_start:.1f}s")
                events = trace_reduce.load_events(xplane)
                spans = host_spans(run_data["request_records"], run_data["stage_spans"])
                with open(os.path.join(run_dir, "trace_meta.json"), "w") as f:  # what cut_trace_fixture.py reads
                    json.dump({"xplane": xplane, "host_spans": spans, "wall_start_ns": slicer.wall_start_ns,
                               "wall_stop_ns": slicer.wall_stop_ns}, f)
                reduced = trace_reduce.reduce_events(events, spans, slicer.wall_start_ns, slicer.wall_stop_ns)
                lines = {p: {n: len(ev) for n, ev in ls.items()} for p, ls in events["device"].items()}
                say(f"trace planes and lines: {json.dumps(lines)}")
            if reduced is None:
                say("trace: no device operation found; busy_s not reported")
            else:
                result_device["busy_s"], result_device["window_s"] = reduced["busy_s"], reduced["window_s"]
                result["breakdown"] = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
        result["metrics"] = metrics
        result["device"] = result_device
        # each number compared beside its limit, last in the line
        result["checks"] = dict({name: {"value": value, "limit": 0} for name, value in numbers.items()},
                                **{name: {"value": value, "limit": None} for name, value in counted.items()})
        with open(os.path.join(run_dir, "result.json"), "w") as f:
            json.dump({"result": result, "score": sc, "numbers": numbers, "phases": phases.seconds,
                       "arms": arms}, f, indent=1)
        return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, chip=None, root: Optional[str] = None) -> int:
    """The command as a function (the tests call it with the chip stubbed)."""
    from benchmarks.harness.cell import load_cell
    from benchmarks.harness.device import Chip

    args = parse_args(argv)
    root = os.path.abspath(root or os.getcwd())
    bench = Bench(load_cell(root, args.workload), chip or Chip(), root)
    warm = bench.warm_up(args.seed)
    result = bench.measure(args.seed, args.seconds, args.trace, T_PROCESS, warm)
    for name, check in result["checks"].items():  # and as the last lines of standard error
        print(f"check: {name} = {check['value']} (limit {check['limit']})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.getcwd())  # `python3 benchmarks/run.py ...` from the root of a checkout
    sys.exit(main())
