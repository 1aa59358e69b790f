#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the served path once, end to end, on a TPU: requests into a spool,
`ProvingService` with the device prover (`prove_tpu_batch`, the armed Pallas
field and curve kernels), swept to terminal, every proof checked against the
pairing, and a pinned-(r, s) batch compared byte for byte with the independent
C++ prover.  Needs a TPU and fails at once, by name, without one.

One process per chip.  This parent never imports JAX; it runs two children,
one after the other:

  serve   the compiled-kernel differential (interpret OFF), key + requests
          from seeds, two waves of requests through the service (cold, then
          warm), the pinned batch against `prove_native`, the gate and
          device-memory assertions, the smoke observations;
  again   one more batch in a FRESH process on the same compile-cache
          directory: its fresh-compile seconds must be a small fraction of
          the cold figure — the check that the cache can be placed from
          outside (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache).

With one device the circuit is venmo at the CLI's default widths (256/192 —
a width cut of the reference's P2POnrampVerify(1024, 6400, 121, 17)); with
four it is the mesh mode: ZKP2P_TPU_SHARD=on, a 1x4 mesh, the sha2b circuit.
Everything it needs is built here from committed source and seeds, into
chiprun_out/chip_smoke/; it reads nothing untracked but the compile cache.
The last line of stdout is one JSON object, printed only if every step held.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")
DEADLINE_S = 1140.0  # the contract allows 1200 s, compilation included
SEED = 21
BATCH = 4  # one service batch == one ZKP2P_BATCH_CHUNK: every batch shares executables
WARM_FRACTION = 0.2  # what "a small fraction" means for the second process's cache misses and compile seconds


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------- the chip
# Everything that only the chip's machine can satisfy lives on this class, so
# the tier-1 test can run the steps' control flow on the CPU with it stubbed.


class Chip:
    def require(self) -> dict:
        """The device as JAX reports it; exits non-zero, naming the
        platform found, when it is not a TPU."""
        import jax
        import jaxlib

        dev = jax.devices()[0]
        if dev.platform != "tpu":
            raise SystemExit(
                f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r} "
                f"({dev.device_kind} x{len(jax.devices())}) — nothing proved"
            )
        try:
            import libtpu

            libtpu_v = getattr(libtpu, "__version__", "unknown")
        except ImportError:
            libtpu_v = "not installed"
        info = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
        say(f"device: {info}  jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu {libtpu_v}")
        return info

    def kernel_differential(self) -> None:
        """Compiled for the chip: interpret is OFF, explicitly."""
        from tools.pallas_hw_diff import kernel_differential

        kernel_differential(interpret=False, log=say)

    def rebuild_native(self) -> None:
        """The native library, rebuilt HERE from committed source: a .so
        that rode along was built -march=native on another CPU."""
        subprocess.run(["make", "-B", "-C", os.path.join(HERE, "csrc")], check=True, capture_output=True)

    def assert_arms(self, arms: dict, mesh: bool) -> None:
        want = {"on_tpu": "tpu", "field_mul": "pallas", "curve_kernel": "pallas",
                "batch_chunk": str(BATCH), "tpu_shard": "1x4" if mesh else "off"}
        got = {k: arms.get(k) for k in want}
        assert got == want, f"gate arms {got} != {want}"
        # host_profile / window_source read "fallback" on any machine that
        # has no tuned host profile — a fresh one never has; that arm names
        # the C++ prover's constants, not a path round the device
        fell_back = {g: a for g, a in arms.items()
                     if a == "fallback" and g not in ("host_profile", "window_source")}
        assert not fell_back, f"gates reading fallback: {fell_back}"

    def assert_device_held(self, key_bytes: int, mesh: bool) -> int:
        """The device(s) held the work: peak HBM above the key's size
        (one device), memory in use on EVERY device (mesh)."""
        import jax

        stats = [d.memory_stats() for d in jax.devices()]
        peak = max(s["peak_bytes_in_use"] for s in stats)
        if mesh:
            idle = [i for i, s in enumerate(stats) if not s["peak_bytes_in_use"]]
            assert not idle, f"devices {idle} report no memory in use"
        else:
            assert peak > key_bytes, f"peak HBM {peak} B is not above the device key's {key_bytes} B"
        return peak


# --------------------------------------------------------------- the worlds
# A world is one circuit with its seeded requests: (name, reduced, cs,
# payload(i) -> dict, make_service(dpk, vk) -> ProvingService).


def venmo_world():
    from zkp2p_tpu.models.venmo import VenmoParams, build_venmo_circuit
    from zkp2p_tpu.pipeline.service import ProvingService

    params = VenmoParams(max_header_bytes=256, max_body_bytes=192)
    cs, lay = build_venmo_circuit(params)

    def payload(i: int) -> dict:  # the synthetic-demo request shape
        return {"raw_id": f"{1234567891234567 + SEED + i}891"[:19], "amount": str(30 + i),
                "order_id": i + 1, "claim_id": i}

    def make_service(dpk, vk):
        return ProvingService.for_venmo(cs, lay, params, dpk, vk, batch_size=BATCH, prover_fn=None)

    return {
        "name": "venmo 256/192", "cs": cs, "payload": payload, "make_service": make_service,
        "reduced": "width cut of P2POnrampVerify(1024, 6400, 121, 17): max_header 1024->256, max_body 6400->192",
    }


def sha2b_world():
    from zkp2p_tpu.models.registry import build_sha2b
    from zkp2p_tpu.pipeline.service import ProvingService

    cs, _out = build_sha2b()
    wires = sorted(cs.input_wires)

    def payload(i: int) -> dict:
        return {"msg": [(SEED + 31 * i + 7 * j) % 256 for j in range(len(wires))]}

    def make_service(dpk, vk):
        return ProvingService(
            cs, dpk, vk,
            witness_fn=lambda p: cs.witness([], dict(zip(wires, p["msg"]))),
            public_fn=lambda w: list(w[1 : cs.num_public + 1]),
            batch_size=BATCH, prover_fn=None,
        )

    return {
        "name": "sha2b", "cs": cs, "payload": payload, "make_service": make_service,
        "reduced": "mesh mode smokes the road on the 54,608-constraint sha2b circuit: four chips are charged "
                   "four times over; venmo 256/192 runs on the same road in the benchmark's cell "
                   "venmo-256-192-mesh4.bulk, and the uncut circuit (2^23, each proof's h stage shared by the "
                   "chips) in venmo-full-mesh4.single",
    }


# ----------------------------------------------------------------- the steps


def _compile_seconds() -> float:
    from zkp2p_tpu.utils.audit import compile_totals

    return compile_totals()[1]


# beside the backend compile seconds the audit listener counts: seconds JAX
# spent lowering to MLIR/Mosaic (Python, not cacheable) and how the
# persistent cache answered
_JAX = {"lower_s": 0.0, "cache_requests": 0, "cache_hits": 0}


def _watch_jax() -> None:
    from jax import monitoring

    def on_duration(name: str, secs: float, **_kw) -> None:
        if name.endswith("/jaxpr_to_mlir_module_duration"):
            _JAX["lower_s"] += secs

    def on_event(name: str, **_kw) -> None:
        if name.endswith("/compile_requests_use_cache"):
            _JAX["cache_requests"] += 1
        elif name.endswith("/cache_hits"):
            _JAX["cache_hits"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


def _stage_spans(sink: str) -> dict:
    """{stage: [ms, ...]} of the utils.trace spans the service flushed
    to its JSONL sink, in order (first entry of a stage = the cold wave)."""
    spans: dict = {}
    with open(sink) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("type") == "stage":
                spans.setdefault(rec["stage"], []).append(round(rec["ms"], 1))
    return spans


def serve_wave(world, svc, vk, spool: str, first: int) -> float:
    """BATCH seeded requests into `spool`, swept to terminal by the
    service; every request must end `done` (none error-*, none shed,
    none degraded) and every proof must satisfy the pairing.  Terminal
    states are counted HERE, from the spool: the worker's exit code says
    nothing about them.  Returns the wave's wall seconds."""
    from zkp2p_tpu.formats.proof_json import load, proof_from_json
    from zkp2p_tpu.snark.groth16 import verify
    from zkp2p_tpu.utils.metrics import REGISTRY

    def guarantee_counters() -> dict:
        # summed over labels; differenced around the wave, because the
        # registry is the process's, not this wave's
        out = dict.fromkeys(("zkp2p_service_shed_total", "zkp2p_service_degraded_total",
                             "zkp2p_service_retries_total"), 0.0)
        for m in REGISTRY.snapshot():
            if m["name"] in out:
                out[m["name"]] += m.get("value") or 0.0
        return out

    os.makedirs(spool, exist_ok=True)
    before = guarantee_counters()
    rids = [f"req{first + i:03d}" for i in range(BATCH)]
    for i, rid in enumerate(rids):
        tmp = os.path.join(spool, rid + ".tmp")
        with open(tmp, "w") as f:
            json.dump(world["payload"](first + i), f)
        os.replace(tmp, os.path.join(spool, rid + ".req.json"))
    t0 = time.perf_counter()
    why = svc.run(spool, poll_s=0.2, exit_when_spool_terminal=True)
    wall = time.perf_counter() - t0
    assert why == "terminal", f"service loop ended {why!r}, spool not terminal"
    for rid in rids:
        base = os.path.join(spool, rid)
        if os.path.exists(base + ".error.json"):
            raise AssertionError(f"{rid} ended {load(base + '.error.json')}")
        proof = proof_from_json(load(base + ".proof.json"))
        pub = [int(x) for x in load(base + ".public.json")]
        assert verify(vk, proof, pub), f"{rid}: proof fails the pairing check"
    bad = {name: v - before[name] for name, v in guarantee_counters().items() if v != before[name]}
    assert not bad, f"requests shed / degraded / retried: {bad}"
    say(f"wave {first // BATCH}: {BATCH} requests done, {BATCH} proofs pairing-verified, {wall:.1f}s wall")
    return wall


def _start(chip: Chip):
    """Common child start-up: device, cache, listeners, mesh mode."""
    from zkp2p_tpu.utils.audit import install_compile_listener
    from zkp2p_tpu.utils.jaxcfg import cache_dir, enable_cache

    device = chip.require()
    mesh = device["count"] == 4
    if mesh:
        os.environ["ZKP2P_TPU_SHARD"] = "on"
        os.environ["ZKP2P_TPU_MESH"] = "1x4"
    # the C++ prover is only the oracle here: a handful of proofs do not
    # repay building (and persisting under .bench_cache/) its fixed-base tables
    os.environ["ZKP2P_MSM_PRECOMP"] = "0"
    # zero floor: every executable of this run round-trips, so the fresh
    # process of step `again` can be held to a small compile figure
    enable_cache(min_compile_s=0.0)
    install_compile_listener()
    _watch_jax()
    say(f"compile cache: {cache_dir()}")
    return device, mesh


def step_serve(out: str, chip: Chip, make_world=None) -> dict:
    device, mesh = _start(chip)
    t0 = time.perf_counter()
    chip.kernel_differential()
    say(f"compiled-kernel differential green, interpret off ({time.perf_counter() - t0:.1f}s)")
    chip.rebuild_native()
    from zkp2p_tpu.native.lib import get_lib

    assert get_lib() is not None, "native library unavailable (it is the oracle and builds the key)"

    import jax

    from zkp2p_tpu.prover.groth16_tpu import prove_tpu_batch
    from zkp2p_tpu.prover.keycache import save_dpk
    from zkp2p_tpu.prover.native_prove import prove_native
    from zkp2p_tpu.prover.setup_device import setup_device
    from zkp2p_tpu.utils.audit import gate_arms

    world = (make_world or (sha2b_world if mesh else venmo_world))()
    cs = world["cs"]
    say(f"circuit: {world['name']}, {cs.num_constraints} constraints, {cs.num_wires} wires")
    say(f"reduced: {world['reduced']}")
    t0 = time.perf_counter()
    dpk, vk = setup_device(cs, seed=f"chip-smoke-{SEED}")
    key_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(dpk))
    say(f"key path: prover/setup_device.py::setup_device, {time.perf_counter() - t0:.1f}s, "
        f"device key {key_bytes} B")
    save_dpk(os.path.join(out, "key.npz"), dpk, vk)

    svc = world["make_service"](dpk, vk)
    c0 = _compile_seconds()
    cold_wall = serve_wave(world, svc, vk, os.path.join(out, "spool"), first=0)
    cold_compile = _compile_seconds() - c0
    warm_wall = serve_wave(world, svc, vk, os.path.join(out, "spool"), first=BATCH)

    # pinned (r, s): the same batch shape as the served one, so it reuses
    # its executables; byte for byte against the independent C++ prover
    wits = [svc.witness_fn(world["payload"](i)) for i in range(BATCH)]
    rs = [1000 + 2 * i for i in range(BATCH)]
    ss = [1001 + 2 * i for i in range(BATCH)]
    proofs = prove_tpu_batch(dpk, wits, rs=rs, ss=ss)
    for i, proof in enumerate(proofs):
        assert proof == prove_native(dpk, wits[i], rs[i], ss[i]), f"pinned proof {i} != prove_native"
    say(f"pinned-(r,s) batch of {BATCH} byte-equal to prove_native")

    arms = gate_arms()
    chip.assert_arms(arms, mesh)
    peak = chip.assert_device_held(key_bytes, mesh)
    say(f"gates: {json.dumps(arms, sort_keys=True)}")
    obs = {
        "device_kind": device["kind"], "device_count": device["count"], "circuit": world["name"],
        "constraints": cs.num_constraints, "batch": BATCH,
        "cold_compile_s": round(cold_compile, 1), "cold_wave_wall_s": round(cold_wall, 1),
        "process_lower_s": round(_JAX["lower_s"], 1), "process_compile_s": round(_compile_seconds(), 1),
        "warm_wave_wall_s": round(warm_wall, 1), "peak_hbm_bytes": peak, "device_key_bytes": key_bytes,
        "spans_ms": _stage_spans(os.path.join(out, "spool.metrics.jsonl")),
    }
    say(f"smoke observation (not a benchmark metric): {json.dumps(obs)}")
    return {"device": device, "compile_s": cold_compile, "cache": dict(_JAX), "obs": obs}


def step_again(out: str, chip: Chip, make_world=None) -> dict:
    """One more batch in a fresh process on the same cache directory."""
    device, mesh = _start(chip)
    from zkp2p_tpu.prover.keycache import load_dpk

    world = (make_world or (sha2b_world if mesh else venmo_world))()
    dpk, vk = load_dpk(os.path.join(out, "key.npz"))
    svc = world["make_service"](dpk, vk)
    wall = serve_wave(world, svc, vk, os.path.join(out, "spool_again"), first=2 * BATCH)
    compile_s = _compile_seconds()
    say(f"smoke observation (not a benchmark metric): second process, {device['kind']}: "
        f"fresh-compile {compile_s:.1f}s, lower {_JAX['lower_s']:.1f}s, wave wall {wall:.1f}s, "
        f"persistent cache {_JAX['cache_hits']} hits of {_JAX['cache_requests']} requests")
    return {"device": device, "compile_s": compile_s, "wall_s": wall, "cache": dict(_JAX)}


STEPS = {"serve": step_serve, "again": step_again}


# ---------------------------------------------------------------- the parent


def _run_child(step: str, t_end: float) -> dict:
    """Run one step as a child that owns the chip; stop it if it
    outlives the deadline (SIGINT first, so JAX can let go of the chip)."""
    res = os.path.join(OUT, step + ".json")
    proc = subprocess.Popen([sys.executable, os.path.abspath(__file__), "--step", step])
    try:
        rc = proc.wait(timeout=max(1.0, t_end - time.time()))
    except subprocess.TimeoutExpired:
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=45)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
        raise SystemExit(f"chip_smoke: step {step!r} outlived the {DEADLINE_S:.0f}s deadline") from None
    if rc != 0:
        raise SystemExit(f"chip_smoke: step {step!r} failed (exit {rc})")
    with open(res) as f:
        return json.load(f)


def cache_fault(cold: dict, warm: dict) -> str | None:
    """Why the second process shows the compile cache did NOT work, or
    None: its cache misses, and its fresh-compile seconds against the
    first process's, must each be a small fraction."""
    requests, hits = warm["cache"]["cache_requests"], warm["cache"]["cache_hits"]
    say(f"second process: {requests - hits} of {requests} compile requests missed the cache; compile "
        f"seconds {warm['compile_s']:.1f} against {cold['compile_s']:.1f} in the first (limit {WARM_FRACTION})")
    if requests - hits > WARM_FRACTION * requests:
        return "the second process recompiled — the compile cache did not hit"
    # the seconds are only a COLD figure when the first process found the
    # cache empty; on a machine that kept its cache both figures are warm
    first_was_cold = cold["cache"]["cache_hits"] <= WARM_FRACTION * cold["cache"]["cache_requests"]
    if first_was_cold and warm["compile_s"] > WARM_FRACTION * cold["compile_s"]:
        return "second-process compile seconds are not a small fraction of the cold figure"
    return None


def main() -> None:
    if len(sys.argv) == 3 and sys.argv[1] == "--step":
        sys.path.insert(0, HERE)
        result = STEPS[sys.argv[2]](OUT, Chip())
        with open(os.path.join(OUT, sys.argv[2] + ".json"), "w") as f:
            json.dump(result, f)
        return
    import zkp2p_tpu  # noqa: F401 — alone, without the program, this script fails here

    t_end = time.time() + DEADLINE_S
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    try:
        cold = _run_child("serve", t_end)
        warm = _run_child("again", t_end)
    finally:
        # the key is ~460 MB at venmo 256/192: it is handed from the first
        # child to the second and is not part of what the run leaves behind
        if os.path.exists(os.path.join(OUT, "key.npz")):
            os.remove(os.path.join(OUT, "key.npz"))
    fault = cache_fault(cold, warm)
    if fault:
        raise SystemExit(f"chip_smoke: {fault}")
    print(json.dumps({"ok": True, "device": cold["device"]}), flush=True)


if __name__ == "__main__":
    main()
