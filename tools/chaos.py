"""Chaos harness for the proving service (docs/ROBUSTNESS.md §chaos).

Spawns N worker subprocesses sweeping ONE spool of requests, SIGKILLs
some of them provably MID-PROVE (the victim is chosen by reading the
pid out of a live `.claim` file — a worker that demonstrably owns
in-flight work), injects faults via ZKP2P_FAULTS across the service's
sites, waits for the survivors to drain the spool, then asserts the
global invariant the service claims to provide:

  1. every request reached EXACTLY ONE terminal state
     (.proof.json xor .error.json — never both, never neither);
  2. every emitted proof pairing-verifies against its public signals,
     and the public signals match the request payload;
  3. no request_id has duplicate terminal records in the metrics sink.

Exit 0 = invariant holds; 1 = violated (details in the JSON report on
stdout).  The circuit is the 2-constraint toy from the service tests —
chaos exercises the SERVING layer's failure machinery, not the prover's
arithmetic (the byte-parity suites own that).

    python tools/chaos.py --workers 2 --kills 1 --requests 6 \
        --faults "seed=7,witness:hang=0.2,prove:raise:p=0.2,emit:enospc:once,claim:raise:p=0.05"

A worker process is this same file with --worker (it builds the
deterministic toy world, then sweeps until the spool is fully terminal
or --max-seconds expires).  `make chaos-smoke` runs the tier-1 shape.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TERMINAL_SUFFIXES = (".proof.json", ".error.json")


# ----------------------------------------------------------- toy world


def _build_world():
    """The deterministic 2-constraint circuit (out = (x*y)^2) every
    worker and the checker rebuild identically (setup seed pins the
    keys, so a proof emitted by any worker verifies under the checker's
    vk)."""
    from zkp2p_tpu.field.bn254 import R
    from zkp2p_tpu.prover.groth16_tpu import device_pk
    from zkp2p_tpu.snark.groth16 import setup
    from zkp2p_tpu.snark.r1cs import LC, ConstraintSystem

    cs = ConstraintSystem("chaos")
    out = cs.new_public("out")
    x = cs.new_wire("x")
    y = cs.new_wire("y")
    z = cs.new_wire("z")
    cs.enforce(LC.of(x), LC.of(y), LC.of(z), "mul")
    cs.enforce(LC.of(z), LC.of(z), LC.of(out), "sq")
    cs.compute(z, lambda a, b: a * b % R, [x, y])
    pk, vk = setup(cs, seed="chaos")
    dpk = device_pk(pk, cs)

    def witness_fn(payload):
        xv, yv = int(payload["x"]), int(payload["y"])
        return cs.witness([pow(xv * yv, 2, R)], {x: xv, y: yv})

    return cs, dpk, vk, witness_fn


# -------------------------------------------------------------- worker


def worker_main(args) -> int:
    from zkp2p_tpu.pipeline.fleet import install_drain_handlers, slowed_prover
    from zkp2p_tpu.pipeline.service import ProvingService
    from zkp2p_tpu.prover.native_prove import prove_native_batch

    cs, dpk, vk, witness_fn = _build_world()
    # artificial PER-REQUEST service time (loadgen --fleet smokes: the
    # toy prove is µs — saturation and mid-prove kill windows need
    # batches that HOLD claims for a while); fleet.slowed_prover is THE
    # shared model, so fleet and in-process capacity stay comparable
    prover_fn = slowed_prover(prove_native_batch, args.prove_s, args.batch_overhead_s)
    svc = ProvingService(
        cs, dpk, vk, witness_fn, public_fn=lambda w: [w[1]],
        batch_size=args.batch,
        prover_fn=prover_fn,
        stale_claim_s=args.stale_claim_s,
        retry_backoff_s=0.05,
    )
    # fleet semantics ride the service run loop: SIGTERM/SIGINT drain
    # (stop claiming, finish in-flight, flush, exit 0), heartbeats +
    # governor ctl via ZKP2P_FLEET_DIR when a supervisor spawned us
    install_drain_handlers(svc)
    print(f"[chaos-worker {os.getpid()}] up, sweeping {args.spool}", flush=True)
    why = svc.run(
        args.spool, poll_s=args.poll_s,
        max_seconds=args.max_seconds,
        # --linger: keep sweeping an empty/terminal spool (loadgen fleet
        # workers outlive the ramp); default chaos workers exit once
        # every request is terminal
        exit_when_spool_terminal=not args.linger,
    )
    print(f"[chaos-worker {os.getpid()}] exiting ({why})", flush=True)
    return 0 if why in ("drained", "terminal") else 2


# ----------------------------------------------------------- invariant


def check_invariants(spool: str, vk=None) -> dict:
    """The global invariant (docs/ROBUSTNESS.md): returns a report dict
    with `violations` (empty = invariant holds).  Standalone-callable on
    any spool a chaos (or production) run left behind."""
    from zkp2p_tpu.field.bn254 import R
    from zkp2p_tpu.formats.proof_json import load, proof_from_json
    from zkp2p_tpu.snark.groth16 import verify

    if vk is None:
        _, _, vk, _ = _build_world()
    violations = []
    states = {}
    verified = 0
    rids = []
    for fn in sorted(os.listdir(spool)):
        if not fn.endswith(".req.json"):
            continue
        rid = fn[: -len(".req.json")]
        rids.append(rid)
        base = os.path.join(spool, rid)
        has_proof = os.path.exists(base + ".proof.json")
        has_error = os.path.exists(base + ".error.json")
        if has_proof and has_error:
            violations.append(f"{rid}: BOTH proof and error artifacts")
        elif not has_proof and not has_error:
            violations.append(f"{rid}: NO terminal state")
        states[rid] = "done" if has_proof else ("error" if has_error else "open")
        if has_proof:
            try:
                proof = proof_from_json(load(base + ".proof.json"))
                pub = [int(v) for v in load(base + ".public.json")]
                with open(base + ".req.json") as f:
                    payload = json.load(f)
                want = [pow(int(payload["x"]) * int(payload["y"]), 2, R)]
                if pub != want:
                    violations.append(f"{rid}: public signals {pub} != payload-derived {want}")
                elif not verify(vk, proof, pub):
                    violations.append(f"{rid}: proof FAILED pairing verification")
                else:
                    verified += 1
            except Exception as e:  # noqa: BLE001 — torn artifact = violation
                violations.append(f"{rid}: unreadable proof artifacts ({e})")

    # terminal records: at most one per rid across every worker's sink
    # writes (the sink is shared, O_APPEND, line-atomic).  Missing
    # records are legal (sink faults, SIGKILL between artifact and
    # record) — duplicates are not.
    rec_counts: dict = {}
    sink = spool.rstrip("/") + ".metrics.jsonl"
    for path in [sink] + [f"{sink}.{i}" for i in range(1, 4)]:
        if not os.path.exists(path):
            continue
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    violations.append(f"{os.path.basename(path)}: torn sink line")
                    continue
                if rec.get("type") == "request" and rec.get("state") != "deferred":
                    # TERMINAL records only: deferred attempt lines
                    # (state="deferred", one per retried sweep — the
                    # request-waterfall history) are expected repeats,
                    # not duplicate terminals
                    rec_counts[rec["request_id"]] = rec_counts.get(rec["request_id"], 0) + 1
    for rid, n in sorted(rec_counts.items()):
        if n > 1:
            violations.append(f"{rid}: {n} terminal records (duplicate)")

    counts: dict = {}
    for s in states.values():
        counts[s] = counts.get(s, 0) + 1
    return {
        "requests": len(rids),
        "states": counts,
        "proofs_verified": verified,
        "terminal_records": sum(rec_counts.values()),
        "violations": violations,
    }


# -------------------------------------------------------------- parent


def _live_claim_pids(spool: str) -> list:
    pids = []
    for fn in os.listdir(spool):
        if fn.endswith(".claim"):
            try:
                with open(os.path.join(spool, fn)) as f:
                    pid = json.load(f).get("pid")
                if pid:
                    pids.append(int(pid))
            except (OSError, ValueError):
                continue
    return pids


def run_chaos(args) -> dict:
    import random

    os.makedirs(args.spool, exist_ok=True)
    rng = random.Random(args.seed)
    for i in range(args.requests):
        with open(os.path.join(args.spool, f"q{i:03d}.req.json"), "w") as f:
            json.dump({"x": rng.randrange(2, 50), "y": rng.randrange(2, 50)}, f)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["ZKP2P_FAULTS"] = args.faults
    env.pop("ZKP2P_METRICS_SINK", None)  # per-spool sink = the shared record file
    cmd = [
        sys.executable, os.path.abspath(__file__), "--worker",
        "--spool", args.spool,
        "--batch", str(args.batch),
        "--stale-claim-s", str(args.stale_claim_s),
        "--max-seconds", str(args.max_seconds),
        "--poll-s", str(args.poll_s),
    ]
    workers = [subprocess.Popen(cmd, env=env, cwd=REPO) for _ in range(args.workers)]
    print(f"[chaos] {args.workers} workers up: {[w.pid for w in workers]}", flush=True)

    # Kill phase: a victim must provably be MID-PROVE — we take the pid
    # from a live .claim file.  Never kill the last standing worker (the
    # invariant needs a survivor to drain the spool).
    killed = []
    deadline = time.time() + args.max_seconds
    while len(killed) < args.kills and time.time() < deadline:
        alive = [w for w in workers if w.poll() is None and w.pid not in killed]
        if len(alive) <= 1:
            break
        candidates = [p for p in _live_claim_pids(args.spool)
                      if p in {w.pid for w in alive}]
        if candidates:
            victim = candidates[0]
            os.kill(victim, signal.SIGKILL)
            killed.append(victim)
            print(f"[chaos] SIGKILL {victim} (owned a live claim)", flush=True)
        else:
            time.sleep(0.02)

    # Drain phase: wait for survivors to finish the spool.
    rc = {}
    for w in workers:
        if w.pid in killed:
            w.wait()
            continue
        remaining = max(1.0, deadline + 15.0 - time.time())
        try:
            rc[w.pid] = w.wait(timeout=remaining)
        except subprocess.TimeoutExpired:
            w.kill()
            rc[w.pid] = "timeout"

    report = check_invariants(args.spool)
    report.update({
        "workers": args.workers,
        "kills": len(killed),
        "killed_pids": killed,
        "worker_rc": rc,
        "faults": args.faults,
    })
    if args.kills and not killed:
        report["violations"].append(
            f"harness: no mid-prove SIGKILL landed (wanted {args.kills})"
        )
    return report


# --------------------------------------------------------------- fleet


def _fleet_pids(fleet_dir: str) -> dict:
    """worker id -> pid, from the supervisor's status.json (written per
    tick, so pids are visible the moment workers spawn — heartbeats
    only land once a worker finishes its first sweep) with the
    heartbeat files as fallback."""
    pids = {}
    try:
        with open(os.path.join(fleet_dir, "status.json")) as f:
            status = json.load(f)
        for wid, w in status.get("workers", {}).items():
            if w.get("pid"):
                pids[wid] = int(w["pid"])
    except (OSError, ValueError):
        pass
    try:
        names = os.listdir(fleet_dir)
    except OSError:
        return pids
    for fn in names:
        if not fn.endswith(".hb"):
            continue
        try:
            with open(os.path.join(fleet_dir, fn)) as f:
                hb = json.load(f)
            if hb.get("pid"):
                pids.setdefault(fn[:-3], int(hb["pid"]))
        except (OSError, ValueError):
            continue
    return pids


def _live_claims(spool: str) -> list:
    """[(rid, owner_pid)] for every live .claim file."""
    out = []
    for fn in os.listdir(spool):
        if fn.endswith(".claim"):
            try:
                with open(os.path.join(spool, fn)) as f:
                    pid = json.load(f).get("pid")
                if pid:
                    out.append((fn[: -len(".claim")], int(pid)))
            except (OSError, ValueError):
                continue
    return out


def _http_json(url: str, timeout: float = 3.0):
    # the ONE fleet-status client (fleet_obs): a 503 body is still the
    # status JSON
    from zkp2p_tpu.pipeline.fleet_obs import http_status_json

    return http_status_json(url, timeout=timeout)


def _http_text(url: str, timeout: float = 3.0):
    import urllib.request

    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.read().decode()
    except (OSError, ValueError):
        return None


def _prom_counters(text: str, name: str) -> dict:
    """{label-string: value} for one counter family out of Prometheus
    exposition text (the fleet /metrics side of the parity check)."""
    out = {}
    for line in (text or "").splitlines():
        if not line.startswith(name):
            continue
        rest = line[len(name):]
        if rest.startswith("{"):
            labels, _, val = rest[1:].partition("} ")
        elif rest.startswith(" "):
            labels, val = "", rest[1:]
        else:
            continue
        try:
            out[labels] = out.get(labels, 0.0) + float(val)
        except ValueError:
            pass
    return out


def check_plane(args, env) -> dict:
    """The fleet-observability-plane assertions (ISSUE-12 satellite),
    run as two self-contained mini-fleets after the main chaos phases:

      A. FEDERATION PARITY under fault: a lingering 2-worker fleet
         serves a small spool to terminal (faults still armed); once
         quiesced, the fleet /metrics `zkp2p_service_requests_total`
         counters must EQUAL the sum of the live workers' /snapshot
         counters — the merge invents nothing and loses nothing.
      B. RESTART STORM: a crash-looping worker under breaker_k=2 must
         get PARKED, and the plane's restart_storm alert must FIRE
         (status.json alert state + zkp2p_fleet_alerts_total).
    """
    report = {"violations": []}
    env = dict(env)
    env["ZKP2P_FLEET_SCRAPE_S"] = "0.5"
    env["ZKP2P_FLEET_METRICS_PORT"] = "auto"

    # ---- A: counter federation parity
    spool = args.spool.rstrip("/") + "_plane"
    os.makedirs(spool, exist_ok=True)
    for i in range(6):
        with open(os.path.join(spool, f"p{i:03d}.req.json"), "w") as f:
            json.dump({"x": 3 + i, "y": 5 + i}, f)
    fleet_dir = os.path.join(spool, ".fleet")
    worker_argv = [
        sys.executable, os.path.abspath(__file__), "--worker", "--linger",
        "--spool", spool, "--batch", "2", "--poll-s", "0.05",
        "--max-seconds", "90", "--prove-s", "0.1",
    ]
    sup = subprocess.Popen(
        [sys.executable, "-m", "zkp2p_tpu", "fleet",
         "--spool", spool, "--workers", "2", "--fleet-dir", fleet_dir,
         "--fleet-metrics-port", "auto", "--restart-backoff-s", "0.2",
         "--max-seconds", "90", "--worker-cmd", json.dumps(worker_argv)],
        env=env, cwd=REPO,
    )
    try:
        from zkp2p_tpu.pipeline.fleet_obs import discover_fleet_port

        deadline = time.time() + 60
        port = None
        while time.time() < deadline and port is None:
            port = discover_fleet_port(fleet_dir)
            time.sleep(0.1)
        status = None
        while time.time() < deadline:
            status = _http_json(f"http://127.0.0.1:{port}/status") if port else None
            if status and status.get("ok"):
                break
            time.sleep(0.2)
        if not (status and status.get("ok")):
            report["violations"].append("plane: fleet /status never reached 200")
            return report
        # serve to terminal, then let the scrape loop catch up.  A
        # quiesce TIMEOUT is its own violation and ends the check: a
        # counter comparison against a still-moving fleet would report
        # a misleading federation-parity failure for what is really a
        # slow-host harness problem.
        from zkp2p_tpu.pipeline.service import spool_terminal

        while time.time() < deadline and not spool_terminal(spool):
            time.sleep(0.2)
        if not spool_terminal(spool):
            report["violations"].append(
                "plane: harness spool never quiesced inside the deadline "
                "(parity not comparable; not a federation failure)"
            )
            return report
        time.sleep(2.0)  # >= 2 scrape intervals: counters quiesced AND federated
        status = _http_json(f"http://127.0.0.1:{port}/status")
        fleet_text = _http_text(f"http://127.0.0.1:{port}/metrics")
        fleet_counts = _prom_counters(fleet_text, "zkp2p_service_requests_total")
        worker_sum: dict = {}
        scraped = 0
        for wid, w in (status.get("workers") or {}).items():
            if w.get("state") not in ("up", "starting", "draining") or not w.get("port"):
                continue
            snap = _http_json(f"http://127.0.0.1:{w['port']}/snapshot")
            if snap is None:
                report["violations"].append(f"plane: worker {wid} /snapshot unreachable")
                continue
            scraped += 1
            for m in snap.get("metrics") or []:
                if m["name"] == "zkp2p_service_requests_total" and m["kind"] == "counter":
                    key = ",".join(f'{k}="{v}"' for k, v in sorted(m["labels"].items()))
                    worker_sum[key] = worker_sum.get(key, 0.0) + m["value"]
        report["parity"] = {
            "fleet": fleet_counts, "worker_sum": worker_sum, "workers_scraped": scraped,
        }
        if scraped < 2:
            report["violations"].append(f"plane: only {scraped} worker snapshots scraped")
        if fleet_counts != worker_sum:
            report["violations"].append(
                f"plane: fleet /metrics request counters {fleet_counts} != "
                f"per-worker sums {worker_sum}"
            )
        n_done = sum(v for k, v in worker_sum.items() if 'state="done"' in k)
        n_proofs = len([f for f in os.listdir(spool) if f.endswith(".proof.json")])
        if n_done != n_proofs:
            report["violations"].append(
                f"plane: summed done counter {n_done} != {n_proofs} proof artifacts"
            )
    finally:
        if sup.poll() is None:
            sup.send_signal(signal.SIGTERM)
            try:
                sup.wait(timeout=60)
            except subprocess.TimeoutExpired:
                sup.kill()

    # ---- B: breaker park -> restart_storm alert
    spool_b = args.spool.rstrip("/") + "_storm"
    os.makedirs(spool_b, exist_ok=True)
    fleet_dir_b = os.path.join(spool_b, ".fleet")
    sup_b = subprocess.run(
        [sys.executable, "-m", "zkp2p_tpu", "fleet",
         "--spool", spool_b, "--workers", "1", "--fleet-dir", fleet_dir_b,
         "--fleet-metrics-port", "auto", "--breaker-k", "2",
         "--breaker-window-s", "60", "--restart-backoff-s", "0.05",
         "--max-seconds", "45",
         "--worker-cmd", json.dumps([sys.executable, "-c", "import sys; sys.exit(1)"])],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    storm = {"supervisor_rc": sup_b.returncode}
    try:
        with open(os.path.join(fleet_dir_b, "status.json")) as f:
            st = json.load(f)
        storm["alerts_state"] = st.get("alerts_state")
        fired = ((st.get("alerts_state") or {}).get("restart_storm") or {}).get("fired_count", 0)
        if sup_b.returncode != 4:
            report["violations"].append(
                f"plane: storm fleet exited rc={sup_b.returncode} (want 4 = all parked)"
            )
        if not fired:
            report["violations"].append(
                "plane: breaker parked the worker but restart_storm never fired"
            )
    except (OSError, ValueError) as e:
        report["violations"].append(f"plane: storm status.json unreadable ({e})")
    report["restart_storm"] = storm
    return report


def run_fleet_chaos(args) -> dict:
    """Fleet-scale chaos (the ISSUE-10 acceptance shape): a SUPERVISED
    fleet of N workers on one spool, faults armed in every worker, then

      1. SIGKILL a worker that provably owns in-flight work (the
         supervisor must restart it with backoff, not flap);
      2. SIGTERM-drain another claim-owning worker (its in-flight
         requests must terminal `done` — drain finishes what it owns —
         and the supervisor must count the clean exit, not restart it);
      3. SIGKILL the supervisor itself mid-run, then start a
         replacement on the same spool (the supervisor holds no request
         state: orphaned workers keep sweeping, the new supervisor's
         workers join them, claims arbitrate).

    Then the PR-7 global invariant is asserted over the spool, plus the
    drain contract: every request the drained worker held at SIGTERM
    time has a .proof.json (terminal `done`, not deferred/stolen)."""
    import random

    os.makedirs(args.spool, exist_ok=True)
    rng = random.Random(args.seed)
    for i in range(args.requests):
        with open(os.path.join(args.spool, f"q{i:03d}.req.json"), "w") as f:
            json.dump({"x": rng.randrange(2, 50), "y": rng.randrange(2, 50)}, f)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["ZKP2P_FAULTS"] = args.faults
    env.pop("ZKP2P_METRICS_SINK", None)  # per-spool sink = the shared record file
    env.setdefault("ZKP2P_METRICS_PORT", "auto")  # N workers: ephemeral ports
    # the observability plane rides the chaos run: the supervisor
    # federates /metrics + /status while workers are being killed —
    # the plane must tolerate exactly this.  Parse-checked: an empty
    # inherited ZKP2P_FLEET_METRICS_PORT means plane-off and would
    # silently skip every plane assertion.
    from zkp2p_tpu.utils.config import _opt_port

    if _opt_port(env.get("ZKP2P_FLEET_METRICS_PORT") or "") is None:
        env["ZKP2P_FLEET_METRICS_PORT"] = "auto"
    env.setdefault("ZKP2P_FLEET_SCRAPE_S", "0.5")
    worker_argv = [
        sys.executable, os.path.abspath(__file__), "--worker",
        "--spool", args.spool,
        "--batch", str(args.batch),
        "--stale-claim-s", str(args.stale_claim_s),
        "--max-seconds", str(args.max_seconds),
        "--poll-s", str(args.poll_s),
        "--prove-s", str(args.prove_s),
        "--batch-overhead-s", str(args.batch_overhead_s),
    ]

    def sup_cmd(fleet_dir: str) -> list:
        return [
            sys.executable, "-m", "zkp2p_tpu", "fleet",
            "--spool", args.spool,
            "--workers", str(args.fleet),
            "--fleet-dir", fleet_dir,
            "--drain-timeout-s", str(max(4 * args.prove_s, 15.0)),
            "--restart-backoff-s", "0.2",
            "--liveness-s", "60",
            "--max-seconds", str(args.max_seconds + 30.0),
            "--worker-cmd", json.dumps(worker_argv),
        ]

    fleet_dir = os.path.join(args.spool, ".fleet1")
    sup = subprocess.Popen(sup_cmd(fleet_dir), env=env, cwd=REPO)
    print(f"[chaos] fleet supervisor up (pid {sup.pid}, {args.fleet} workers)", flush=True)
    deadline = time.time() + args.max_seconds

    def kill_claim_owner(sig, exclude: set) -> tuple:
        """Deliver `sig` to a fleet worker that owns >=1 live claim;
        returns (pid, [the rids it held]).  The pid comes from
        status.json/claim files, which can lag reality (a worker that
        crashed on an injected fault leaves claims behind, and the
        supervisor keeps its last pid visible through the backoff
        window) — a pid that is gone by the time the signal lands is
        excluded and the hunt continues, never a harness crash."""
        excl = set(exclude)
        while time.time() < deadline:
            pids = set(_fleet_pids(fleet_dir).values()) - excl
            claims = _live_claims(args.spool)
            for rid, pid in claims:
                if pid in pids:
                    held = sorted(r for r, p in claims if p == pid)
                    try:
                        os.kill(pid, sig)
                    except (ProcessLookupError, PermissionError):
                        excl.add(pid)  # died between discovery and signal
                        continue
                    return pid, held
            time.sleep(0.02)
        return None, []

    # phase 1: SIGKILL a worker that provably owns in-flight work
    killed_pid, _ = kill_claim_owner(signal.SIGKILL, set())
    if killed_pid is not None:
        print(f"[chaos] SIGKILL worker {killed_pid} (owned a live claim)", flush=True)

    # phase 2: SIGTERM-drain a DIFFERENT claim-owning worker; remember
    # exactly what it held — the drain contract is judged on those rids
    drained_pid, drained_claims = kill_claim_owner(
        signal.SIGTERM, {killed_pid} if killed_pid else set()
    )
    if drained_pid is not None:
        print(
            f"[chaos] SIGTERM worker {drained_pid} (drains {len(drained_claims)} "
            f"held claim(s): {drained_claims})", flush=True,
        )

    # phase 3: kill the supervisor mid-run, start a replacement
    supervisor_rcs = []
    if args.supervisor_kill and sup.poll() is None:
        sup.send_signal(signal.SIGKILL)
        supervisor_rcs.append(sup.wait())
        print("[chaos] SIGKILL supervisor; starting replacement", flush=True)
        fleet_dir2 = os.path.join(args.spool, ".fleet2")
        sup = subprocess.Popen(sup_cmd(fleet_dir2), env=env, cwd=REPO)

    try:
        supervisor_rcs.append(sup.wait(timeout=args.max_seconds + 60.0))
    except subprocess.TimeoutExpired:
        sup.kill()
        supervisor_rcs.append("timeout")

    report = check_invariants(args.spool)
    report.update({
        "fleet": args.fleet,
        "killed_worker": killed_pid,
        "drained_worker": drained_pid,
        "drained_claims": drained_claims,
        "supervisor_rcs": supervisor_rcs,
        "faults": args.faults,
    })
    if killed_pid is None:
        report["violations"].append("harness: no mid-prove worker SIGKILL landed")
    if drained_pid is None:
        report["violations"].append("harness: no claim-owning worker was SIGTERM-drained")
    # the drain contract: everything the drained worker held at SIGTERM
    # time finished as `done` — not error, not stolen-and-deferred
    for rid in drained_claims:
        if not os.path.exists(os.path.join(args.spool, rid + ".proof.json")):
            report["violations"].append(
                f"{rid}: held by the drained worker but did not terminal done"
            )
    if supervisor_rcs and supervisor_rcs[-1] != 0:
        report["violations"].append(
            f"harness: final supervisor exited rc={supervisor_rcs[-1]} (want 0 = clean)"
        )
    # fleet-plane assertions (federation parity + restart-storm alert)
    # as their own mini-fleets — the main run's workers exit the moment
    # the spool goes terminal, too racy a target for a counter-equality
    # check that needs a quiesced, still-scrapable fleet
    plane = check_plane(args, env)
    report["plane"] = {k: v for k, v in plane.items() if k != "violations"}
    report["violations"].extend(plane["violations"])
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--spool", default="/tmp/zkp2p_chaos_spool")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--kills", type=int, default=1)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--stale-claim-s", type=float, default=3.0,
                    help="claim staleness for takeover; heartbeats keep live claims fresh")
    ap.add_argument("--max-seconds", type=float, default=90.0)
    ap.add_argument("--poll-s", type=float, default=0.05)
    ap.add_argument("--prove-s", type=float, default=0.0,
                    help="artificial PER-REQUEST prove time, scaled by batch fill "
                         "(fleet kill windows / loadgen saturation)")
    ap.add_argument("--batch-overhead-s", type=float, default=0.0,
                    help="artificial PER-BATCH fixed prove cost (the amortization "
                         "curve's setup term; scheduler A/Bs need a curve to sit on)")
    ap.add_argument("--linger", action="store_true",
                    help="worker: keep sweeping after the spool goes terminal (loadgen fleet workers)")
    ap.add_argument("--fleet", type=int, default=0,
                    help="fleet-scale chaos: run N workers under the `zkp2p-tpu fleet` "
                         "supervisor (SIGKILL a worker, SIGTERM-drain a worker, kill + "
                         "restart the supervisor) instead of bare Popen workers")
    ap.add_argument("--supervisor-kill", action="store_true", default=None,
                    help="fleet mode: SIGKILL the supervisor mid-run and start a "
                         "replacement (default on in fleet mode; --no-supervisor-kill disables)")
    ap.add_argument("--no-supervisor-kill", dest="supervisor_kill", action="store_false")
    ap.add_argument(
        "--faults",
        default="seed=7,witness:hang=0.2,prove:raise:p=0.2,emit:enospc:once,claim:raise:p=0.05",
        help="ZKP2P_FAULTS spec exported to every worker (>=3 sites for the acceptance shape)",
    )
    ap.add_argument("--report", default="",
                    help="also write the JSON report to this path (stdout is shared "
                         "with the workers' logs, so machine consumers read the file)")
    args = ap.parse_args(argv)
    if args.worker:
        return worker_main(args)
    if args.supervisor_kill is None:
        args.supervisor_kill = bool(args.fleet)
    report = run_fleet_chaos(args) if args.fleet else run_chaos(args)
    print(json.dumps(report, indent=1, default=str))
    if args.report:
        with open(args.report, "w") as f:
            json.dump(report, f, indent=1, default=str)
    if report["violations"]:
        print(f"[chaos] INVARIANT VIOLATED: {report['violations']}", file=sys.stderr)
        return 1
    kills = report.get("kills", 1 if report.get("killed_worker") else 0)
    print(f"[chaos] invariant holds: {report['requests']} requests, "
          f"{report['proofs_verified']} proofs verified, {kills} kills", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
