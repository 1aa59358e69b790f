"""The proving pipeline CLI — `prover=tpu` beside snarkjs/rapidsnark.

Command-for-command parity with the reference's L2 scripts
(`dizkus-scripts/1..6_*.sh`, `circuit/scripts/*`, SURVEY.md §2.3):

  setup    ~ 1_compile.sh + 3_gen_both_zkeys.sh + 4_gen_vkey.sh +
             generate_contract.sh: build the circuit, run the dev setup,
             write circuit_final.zkey (snarkjs format, optionally b..k
             chunked) + verification_key.json + verifier.sol
  prove    ~ 2_gen_wtns.sh + 5/6_gen_proof: email/eml (or input.json) in,
             proof.json + public.json out, TPU prover
  verify   ~ verify_proof_groth16.sh: pairing check against the vkey
  batch    ~ the batching service of BASELINE.json: a directory of inputs
             proved as ONE vmapped batch

Config is flags + env (CIRCUIT_NAME/BUILD_DIR convention of
`dizkus-scripts/circuit.env.example`), centralised here instead of
scattered shell env files (SURVEY.md §5 config).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time


def _log(*a):
    print("[zkp2p-tpu]", *a, file=sys.stderr, flush=True)


def _build_circuit(name: str, header: int, body: int, message_bytes: int = 64):
    if name == "venmo":
        from ..models.venmo import VenmoParams, build_venmo_circuit

        params = VenmoParams(max_header_bytes=header, max_body_bytes=body)
        cs, lay = build_venmo_circuit(params)
        return cs, (params, lay)
    if name == "email_verify":
        from ..models.email_verify import EmailVerifyParams, build_email_verify

        params = EmailVerifyParams(max_header_bytes=header, max_body_bytes=body)
        cs, lay = build_email_verify(params)
        return cs, (params, lay)
    if name == "sha256":
        from ..gadgets import core, sha256
        from ..snark.r1cs import ConstraintSystem

        cs = ConstraintSystem("sha256")
        msg = cs.new_wires(header, "msg")
        cs.mark_input(msg)
        bits = core.assert_bytes(cs, msg)
        sha256.sha256_blocks(cs, bits, None)
        return cs, (None, msg)
    if name == "sha256_preimage":
        # the digest public and the padding wired in: `sha256` above takes
        # pre-padded bytes and has no public signal
        from ..models.registry import build_sha256_preimage

        cs, msg = build_sha256_preimage(message_bytes)
        return cs, (None, msg)
    if name == "toy":
        # smoke-test circuit: public out = (x*y)^2 over two byte inputs
        from ..field.bn254 import R
        from ..snark.r1cs import LC, ConstraintSystem

        cs = ConstraintSystem("toy")
        out = cs.new_public("out")
        x = cs.new_wire("x")
        y = cs.new_wire("y")
        z = cs.new_wire("z")
        cs.mark_input([x, y])
        cs.enforce(LC.of(x), LC.of(y), LC.of(z), "mul")
        cs.enforce(LC.of(z), LC.of(z), LC.of(out), "sq")
        cs.compute(z, lambda a, b: a * b % R, [x, y])
        return cs, (None, [x, y, out])
    raise SystemExit(f"unknown circuit {name!r} (have: venmo, email_verify, sha256, sha256_preimage, toy)")


def cmd_setup(args):
    from ..formats.proof_json import dump, vkey_to_json
    from ..formats.solidity import export_verifier
    from ..formats.zkey import split_zkey, write_zkey
    from ..snark.groth16 import qap_rows, setup

    os.makedirs(args.build_dir, exist_ok=True)
    t0 = time.perf_counter()
    _log(f"building circuit {args.circuit} ...")
    cs, meta = _build_circuit(args.circuit, args.max_header, args.max_body, args.message_bytes)
    _log(f"constraints={cs.num_constraints} wires={cs.num_wires} ({time.perf_counter()-t0:.0f}s)")
    if not args.skip_audit:
        # the registry admission gate (docs/STATIC_ANALYSIS.md §circuit
        # audit): no key material is cut for a circuit with unwaived
        # soundness findings.  Registered names carry their declared
        # on-chain public layout into the public-layout rule.
        from ..models.registry import SPECS
        from ..snark.analysis import audit_circuit, require_clean

        # the preimage circuit's layout is one at every --message-bytes
        spec = SPECS.get("sha256-64" if args.circuit == "sha256_preimage" else args.circuit)
        rep = require_clean(audit_circuit(
            cs,
            name=f"{args.circuit}_{args.max_header}_{args.max_body}",
            declared_n_public=spec.n_public if spec else None,
        ))
        _log(
            f"soundness audit clean: 0 unwaived / {rep['waived']} waived "
            f"findings in {rep['audit_s']}s ({rep['source']}, digest {rep['digest']})"
        )
    _log("running development setup (production: import a ceremony zkey instead)")
    pk, vk = setup(cs, seed=args.seed)
    zkey_path = os.path.join(args.build_dir, "circuit_final.zkey")
    write_zkey(zkey_path, pk, vk, qap_rows(cs))
    if args.chunks:
        split_zkey(zkey_path, args.chunks)
        _log(f"wrote {args.chunks} zkey chunks (b..) beside {zkey_path}")
    if args.publish:
        # The S3 layer (upload_chunked_keys_to_s3.sh semantics): gzip
        # chunks + manifest + integrity hash into the artifact store.
        from ..formats.artifact_store import DirBackend, upload_chunked

        with open(zkey_path, "rb") as f:
            blob = f.read()
        man = upload_chunked(DirBackend(args.publish), "circuit.zkey", blob)
        _log(f"published {len(man.chunks)} gzip chunks -> {args.publish} (sha256 {man.sha256[:16]}…)")
    dump(vkey_to_json(vk), os.path.join(args.build_dir, "verification_key.json"))
    with open(os.path.join(args.build_dir, "verifier.sol"), "w") as f:
        f.write(export_verifier(vk))
    _log(f"setup done in {time.perf_counter()-t0:.0f}s -> {args.build_dir}/")


def _infer_widths(args) -> bool:
    """zkey width inference is on unless --no-infer-widths was passed
    (one knob, consumed by every subcommand that imports a zkey)."""
    return not getattr(args, "no_infer_widths", False)


def _load_zkey(args):
    """The key material always travels as a snarkjs-format .zkey (never
    pickle): --zkey overrides (monolithic path or glob of b..k chunks),
    --zkey-store pulls through the chunked artifact store (the browser's
    S3-download + IndexedDB-cache path, `zkp.ts:24-68`), default is the
    build dir's circuit_final.zkey."""
    from ..formats.zkey import read_zkey

    if getattr(args, "zkey_store", None):
        from ..formats.artifact_store import DirBackend, download_chunked

        blob = download_chunked(
            DirBackend(args.zkey_store),
            "circuit.zkey",
            cache_dir=os.path.join(args.build_dir, "zkey_cache"),
        )
        return read_zkey(blob)
    if getattr(args, "zkey", None):
        paths = sorted(glob.glob(args.zkey)) if any(c in args.zkey for c in "*?[") else args.zkey
        if isinstance(paths, list) and not paths:
            raise SystemExit(f"no zkey matches {args.zkey}")
        return read_zkey(paths)
    return read_zkey(os.path.join(args.build_dir, "circuit_final.zkey"))


def _check_zkey_matches(zk, cs):
    """Fail fast on a key/circuit mismatch instead of deep in jitted code."""
    from ..snark.groth16 import domain_size_for

    if zk.n_vars != cs.num_wires or zk.domain_size != domain_size_for(cs):
        raise SystemExit(
            f"zkey does not match circuit: zkey has {zk.n_vars} wires / domain "
            f"{zk.domain_size}, circuit has {cs.num_wires} / {domain_size_for(cs)} "
            "(check --circuit/--max-header/--max-body against the setup)"
        )


def _witness_for(args, cs, meta, source=None):
    """Build (witness, public_signals) for one input.  `source` is an
    input file path (.eml or .json) — None falls back to --eml/--message
    flags or the synthetic demo email."""
    params, lay = meta
    if args.circuit == "venmo":
        from ..inputs.email import email_from_eml, generate_inputs, make_test_key, make_venmo_email

        src = source or getattr(args, "eml", None)
        if src:
            with open(src, "rb") as f:
                email = email_from_eml(f.read())  # unknown keys raise in _verified_eml
            modulus = email.modulus
        else:
            key = make_test_key(1)
            email = make_venmo_email(key)
            modulus = key.n
        inputs = generate_inputs(email, modulus, args.order_id, args.claim_id, params, lay)
        return cs.witness(inputs.public_signals, inputs.seed), inputs.public_signals
    elif args.circuit == "email_verify":
        from ..inputs.email import (
            email_verify_from_eml,
            generate_email_verify_inputs,
            make_test_key,
            make_twitter_email,
        )

        src = source or getattr(args, "eml", None)
        if src:
            with open(src, "rb") as f:
                email, modulus = email_verify_from_eml(f.read())  # unknown keys raise
        else:
            key = make_test_key(1)
            email, modulus = make_twitter_email(key), key.n
        inputs = generate_email_verify_inputs(email, modulus, params, lay)
        return cs.witness(inputs.public_signals, inputs.seed), inputs.public_signals
    elif args.circuit == "sha256_preimage":
        from ..models.registry import sha256_preimage_inputs

        msg = args.message
        if source:
            with open(source) as f:
                msg = json.load(f)["message"]
        data = (msg or "zkp2p").encode()
        if len(data) > len(lay):
            raise SystemExit(f"the message has {len(data)} bytes, --message-bytes is {len(lay)}")
        # a shorter message is zero-filled: the circuit hashes exactly --message-bytes bytes
        pub, seed = sha256_preimage_inputs(lay, {"msg": list(data.ljust(len(lay), b"\x00"))})
        return cs.witness(pub, seed), pub
    elif args.circuit == "toy":
        from ..field.bn254 import R

        msg = args.message
        if source:
            with open(source) as f:
                msg = json.load(f)["message"]
        data = (msg or "35").encode().ljust(2, b"\x00")[:2]
        x_v, y_v = data[0], data[1]
        out_v = pow(x_v * y_v, 2, R)
        x, y, _ = lay
        return cs.witness([out_v], {x: x_v, y: y_v}), [out_v]
    else:
        from ..inputs.sha_host import sha256_pad

        msg = args.message
        if source:
            with open(source) as f:
                msg = json.load(f)["message"]
        data = (msg or "zkp2p").encode()
        padded, _ = sha256_pad(data, len(lay))
        return cs.witness([], {w: b for w, b in zip(lay, padded)}), []


def _prover_fn(args):
    """--prover tpu (default, XLA device path) | native (C++ Pippenger
    runtime, prover.native_prove) — the snarkjs-vs-rapidsnark split of
    the reference's scripts (5_gen_proof.sh / 6_gen_proof_rapidsnark.sh),
    selected by flag over the same zkey + witness."""
    if getattr(args, "prover", "tpu") == "native":
        from ..prover.native_prove import prove_native

        return prove_native
    from ..prover.groth16_tpu import prove_tpu

    return prove_tpu


def cmd_prove(args):
    from ..formats.proof_json import dump, proof_to_json, public_to_json
    from ..prover.groth16_tpu import device_pk_from_zkey

    prove_fn = _prover_fn(args)
    if getattr(args, "wtns", None):
        # Drop-in rapidsnark/snarkjs parity (`6_gen_proof_rapidsnark.sh:24-31`):
        # externally generated witness.wtns + zkey in, proof out — no
        # circuit rebuild needed, everything comes from the files.
        from ..formats.circom_bin import read_wtns

        zk = _load_zkey(args)
        w = read_wtns(args.wtns)
        if len(w) != zk.n_vars:
            raise SystemExit(f"witness has {len(w)} wires, zkey expects {zk.n_vars}")
        dpk = device_pk_from_zkey(zk, infer_widths=_infer_widths(args))
        pub = w[1 : zk.n_public + 1]
        t0 = time.perf_counter()
        proof = prove_fn(dpk, w)
        _log(f"proved in {time.perf_counter()-t0:.1f}s (incl. first-call compile)")
        dump(proof_to_json(proof), args.proof)
        dump(public_to_json(pub), args.public)
        _log(f"wrote {args.proof} {args.public}")
        return

    cs, meta = _build_circuit(args.circuit, args.max_header, args.max_body, args.message_bytes)
    zk = _load_zkey(args)
    _check_zkey_matches(zk, cs)
    dpk = device_pk_from_zkey(zk, infer_widths=_infer_widths(args))
    w, pub = _witness_for(args, cs, meta)
    t0 = time.perf_counter()
    proof = prove_fn(dpk, w)
    _log(f"proved in {time.perf_counter()-t0:.1f}s (incl. first-call compile)")
    dump(proof_to_json(proof), args.proof)
    dump(public_to_json(pub or w[1 : cs.num_public + 1]), args.public)
    _log(f"wrote {args.proof} {args.public}")


def cmd_verify(args):
    from ..formats.proof_json import load, proof_from_json, vkey_from_json
    from ..snark.groth16 import verify

    vk = vkey_from_json(load(os.path.join(args.build_dir, "verification_key.json")))
    proof = proof_from_json(load(args.proof))
    pub = [int(x) for x in load(args.public)]
    ok = verify(vk, proof, pub)
    print("OK" if ok else "INVALID")
    sys.exit(0 if ok else 1)


def cmd_ceremony(args):
    """Phase-2 MPC ops over zkeys (`dizkus-scripts/3_gen_both_zkeys.sh`)."""
    from ..snark import ceremony

    if args.op == "contribute":
        ceremony.contribute(args.zkey_in, args.zkey_out, args.entropy.encode(), name=args.name)
        print(f"contributed -> {args.zkey_out}")
    elif args.op == "beacon":
        if not args.beacon_hash:
            print("beacon requires --beacon-hash", file=sys.stderr)
            sys.exit(2)
        ceremony.beacon(args.zkey_in, args.zkey_out, bytes.fromhex(args.beacon_hash),
                        iter_exp=args.iter_exp, name=args.name or "final beacon")
        print(f"beacon applied -> {args.zkey_out}")
    else:
        ok, log = ceremony.verify_chain(args.zkey_in, args.zkey_out)
        for line in log:
            print(line)
        print("ZKEY OK" if ok else "ZKEY INVALID")
        sys.exit(0 if ok else 1)


def cmd_batch(args):
    """Prove every input in a directory as one vmapped batch —
    circuit-generic: .eml files for the email circuits, .json
    ({"message": ...}) for sha256/toy, all through the same per-circuit
    witness builder as `prove`."""
    from ..formats.proof_json import dump, proof_to_json, public_to_json
    from ..prover.groth16_tpu import device_pk_from_zkey, prove_tpu_batch

    if getattr(args, "prover", "tpu") == "native":
        # multi-column CPU batch tier: ONE base sweep per G1 MSM family
        # across the whole batch (ZKP2P_MSM_MULTI=0 falls back to
        # sequential per-proof proves inside)
        from ..prover.native_prove import prove_native_batch as prove_tpu_batch  # noqa: F811

    cs, meta = _build_circuit(args.circuit, args.max_header, args.max_body, args.message_bytes)
    zk = _load_zkey(args)
    _check_zkey_matches(zk, cs)
    dpk = device_pk_from_zkey(zk, infer_widths=_infer_widths(args))
    # Per-circuit input type: email circuits consume .eml, the rest .json
    # ({"message": ...}) — one glob per circuit so a stray file of the
    # other type can't crash the batch or collide on output basenames.
    ext = "*.eml" if args.circuit in ("venmo", "email_verify") else "*.json"
    files = sorted(glob.glob(os.path.join(args.indir, ext)))
    if not files:
        raise SystemExit(f"no {ext} inputs in {args.indir}")
    wits, pubs = [], []
    for fp in files:
        w, pub = _witness_for(args, cs, meta, source=fp)
        wits.append(w)
        pubs.append(pub)
    t0 = time.perf_counter()
    proofs = prove_tpu_batch(dpk, wits)
    dt = time.perf_counter() - t0
    _log(f"batch of {len(wits)} proved in {dt:.1f}s -> {len(wits)/dt:.2f} proofs/s")
    os.makedirs(args.outdir, exist_ok=True)
    for fp, proof, pub in zip(files, proofs, pubs):
        base = os.path.basename(fp).rsplit(".", 1)[0]
        dump(proof_to_json(proof), os.path.join(args.outdir, base + ".proof.json"))
        dump(public_to_json(pub), os.path.join(args.outdir, base + ".public.json"))
    _log(f"wrote {len(proofs)} proofs to {args.outdir}")


def cmd_service(args):
    """Run the batched proving service daemon over a spool directory
    (queue -> witness||prove -> verify sample -> emit;
    pipeline.service.ProvingService)."""
    from ..pipeline.service import ProvingService
    from ..prover.groth16_tpu import device_pk_from_zkey

    if args.circuit not in ("venmo", "email_verify", "sha256_preimage"):
        raise SystemExit("service supports the email circuits (venmo, email_verify) and sha256_preimage")
    from ..formats.proof_json import load, vkey_from_json

    cs, meta = _build_circuit(args.circuit, args.max_header, args.max_body, args.message_bytes)
    zk = _load_zkey(args)
    _check_zkey_matches(zk, cs)
    dpk = device_pk_from_zkey(zk, infer_widths=_infer_widths(args))
    vk = vkey_from_json(load(os.path.join(args.build_dir, "verification_key.json")))
    params, lay = meta
    prover_fn = None
    if getattr(args, "prover", "tpu") == "native":
        # the service fast path: whole claimed batches feed the native
        # multi-column prover (one base sweep, S scalar columns per G1
        # MSM family) instead of a per-request prove loop
        from ..prover.native_prove import prove_native_batch as prover_fn  # noqa: F811

    # SLO observability (docs/OBSERVABILITY.md §SLO): the flags ride the
    # env knobs (the tracker and sampler read the typed config), written
    # BEFORE run() so preflight arms the gates with the operator's values
    if getattr(args, "slo_p95_s", None) is not None:
        os.environ["ZKP2P_SLO_P95_S"] = str(args.slo_p95_s)
    if getattr(args, "ts_sample_s", None) is not None:
        os.environ["ZKP2P_TS_SAMPLE_S"] = str(args.ts_sample_s)
    # adaptive scheduler arm (pipeline.sched; fresh-read per sweep, so
    # the env write is the whole wiring)
    if getattr(args, "sched_flag", None) is not None:
        os.environ["ZKP2P_SCHED"] = args.sched_flag
    # fault-tolerance policy (docs/ROBUSTNESS.md): flags override the
    # ZKP2P_DEADLINE_S / ZKP2P_SPOOL_CAP config defaults; None defers
    svc_kw = dict(
        batch_size=args.batch, prover_fn=prover_fn, prefetch=args.prefetch,
        stale_claim_s=args.stale_claim_s, deadline_s=args.deadline_s,
        spool_cap=args.spool_cap,
    )
    if args.circuit == "sha256_preimage":
        def make(cs, msg_wires, _params, key, vk, **kw):
            return ProvingService.for_sha256_preimage(cs, msg_wires, key, vk, **kw)
    else:
        make = ProvingService.for_venmo if args.circuit == "venmo" else ProvingService.for_email_verify
    replicas = getattr(args, "replicas", "1")
    if replicas != "1":
        # one replica a local device, in this process, on the one spool
        # (pipeline.replicas): the four-chip host that is not a mesh
        from ..pipeline.replicas import ReplicaSet

        svc = ReplicaSet(lambda key: make(cs, lay, params, key, vk, **svc_kw), dpk,
                         n=None if replicas == "auto" else int(replicas))
    else:
        svc = make(cs, lay, params, dpk, vk, **svc_kw)
    os.makedirs(args.spool, exist_ok=True)
    # graceful drain (docs/ROBUSTNESS.md §fleet): SIGTERM/SIGINT stop
    # claiming, finish in-flight batches, flush sinks, exit 0 — so a
    # fleet restart (or a plain ^C) loses zero requests
    from ..pipeline.fleet import install_drain_handlers

    install_drain_handlers(svc)
    _log(f"service sweeping {args.spool} (batch={args.batch})")
    why = svc.run(
        args.spool, poll_s=args.poll, max_sweeps=args.max_sweeps,
        max_seconds=args.max_seconds,
        exit_when_spool_terminal=args.exit_when_terminal,
    )
    # exit-code contract (the supervisor and init systems key off it):
    # 0 = clean (drained / spool terminal / sweeps done), 2 = timeout
    sys.exit(0 if why in ("drained", "terminal", "sweeps") else 2)


def cmd_fleet(args):
    """Supervise N `service` workers on one spool (pipeline.fleet):
    restart with exponential backoff + crash-loop circuit breaker,
    graceful drain on SIGTERM/SIGINT with bounded SIGKILL escalation,
    per-worker RSS governor, heartbeat watchdog, and a status.json +
    per-worker auto metrics ports for scrape discovery.  Exit codes:
    0 clean, 3 drain escalated, 4 every worker parked."""
    import json as _json

    from ..pipeline.fleet import FleetSupervisor

    os.makedirs(args.spool, exist_ok=True)
    if args.worker_cmd:
        # advanced/chaos arm: the operator supplies the worker argv
        # (JSON list; '{wid}'/'{spool}' substitute per worker)
        template = _json.loads(args.worker_cmd)
        if not isinstance(template, list) or not template:
            raise SystemExit("--worker-cmd must be a non-empty JSON argv list")

        def worker_cmd(wid):
            return [str(t).replace("{wid}", wid).replace("{spool}", args.spool) for t in template]
    else:
        # default: this same CLI's `service` subcommand, one process per
        # worker, sharing the spool (the claim files arbitrate) and the
        # build dir's key material (the flock'd precomp/plan sidecars
        # serialize the cold builds to ONE across the fleet)
        base = [
            sys.executable, "-m", "zkp2p_tpu",
            "--build-dir", args.build_dir,
            "--circuit", args.circuit,
            "--max-header", str(args.max_header),
            "--max-body", str(args.max_body),
            "--message-bytes", str(args.message_bytes),
            "service",
            "--spool", args.spool,
            "--batch", str(args.batch),
            "--poll", str(args.poll),
            "--prover", args.prover,
            "--prefetch", str(args.prefetch),
            "--stale-claim-s", str(args.stale_claim_s),
        ]
        if args.zkey:
            base += ["--zkey", args.zkey]
        if args.no_infer_widths:
            base += ["--no-infer-widths"]
        for flag, v in (
            ("--deadline-s", args.deadline_s), ("--spool-cap", args.spool_cap),
            ("--slo-p95-s", args.slo_p95_s), ("--ts-sample-s", args.ts_sample_s),
            ("--sched", args.sched_flag),
        ):
            if v is not None:
                base += [flag, str(v)]

        def worker_cmd(_wid):
            return list(base)

    # fleet observability plane port (stable aggregated /metrics +
    # /status): flag wins, else ZKP2P_FLEET_METRICS_PORT; same
    # "auto"/"0" = ephemeral semantics as the worker metrics port
    fleet_metrics_port = None
    if args.fleet_metrics_port is not None:
        from ..utils.config import _opt_port

        fleet_metrics_port = _opt_port(str(args.fleet_metrics_port))
        if fleet_metrics_port is None:
            raise SystemExit(
                f"--fleet-metrics-port {args.fleet_metrics_port!r}: want a port, 'auto', or 0"
            )

    # a --sched flag on the supervisor reaches workers through the env
    # (the child env inherits; the knob is fresh-read per sweep)
    if args.sched_flag is not None:
        os.environ["ZKP2P_SCHED"] = args.sched_flag
    sup = FleetSupervisor(
        args.spool, worker_cmd,
        workers=args.workers,
        fleet_dir=args.fleet_dir,
        drain_timeout_s=args.drain_timeout_s,
        breaker_k=args.breaker_k,
        breaker_window_s=args.breaker_window_s,
        restart_backoff_s=args.restart_backoff_s,
        rss_soft_mb=args.rss_soft_mb,
        rss_hard_mb=args.rss_hard_mb,
        liveness_s=args.liveness_s,
        fleet_metrics_port=fleet_metrics_port,
        workers_min=args.workers_min,
        workers_max=args.workers_max,
        scale_up_s=args.scale_up_s,
        scale_down_s=args.scale_down_s,
        log=lambda m: _log(f"fleet: {m}"),
    )
    # the supervisor's own exposition (fleet gauges/counters) — workers
    # get auto ports regardless (FleetSupervisor rewrites the env)
    from ..utils.metrics import maybe_start_metrics_server

    maybe_start_metrics_server()
    _log(
        f"fleet {sup.fleet_id}: {sup.n} worker(s) on {args.spool} "
        f"(fleet dir {sup.fleet_dir}, drain timeout {sup.drain_timeout_s:g}s)"
    )
    sys.exit(sup.run(max_seconds=args.max_seconds))


def cmd_top(args):
    """Live fleet terminal view: poll the fleet plane's /status and
    render worker table + merged SLO + active alerts (pipeline.fleet_obs
    renders; this loop only fetches).  The endpoint is found from
    --url, --port, or a --fleet-dir's status.json (`metrics_port` —
    the supervisor records its bound port there, so `zkp2p-tpu top
    --fleet-dir <spool>/.fleet` needs no port bookkeeping)."""
    import time as _time

    from ..pipeline.fleet_obs import discover_fleet_port, http_status_json, render_top

    def resolve_url() -> str:
        if args.url:
            return args.url.rstrip("/") + ("" if args.url.rstrip("/").endswith("/status") else "/status")
        port = args.port
        if port is None and args.fleet_dir:
            port = discover_fleet_port(args.fleet_dir)
            if port is None:
                raise SystemExit(
                    f"{args.fleet_dir}/status.json has no metrics_port — is the "
                    "fleet running with --fleet-metrics-port (or ZKP2P_FLEET_METRICS_PORT)?"
                )
        if port is None:
            raise SystemExit("top needs --url, --port, or --fleet-dir")
        return f"http://127.0.0.1:{port}/status"

    url = resolve_url()
    try:
        while True:
            # a 503 body still renders (the reason line is the point);
            # transport failure degrades to an unreachable frame, not a die
            body = http_status_json(url, timeout=5) or {"ok": False, "reason": f"unreachable: {url}"}
            frame = render_top(body)
            if args.once:
                print(frame)
                return
            # clear + home, then the frame (plain ANSI; no curses dep)
            sys.stdout.write("\x1b[2J\x1b[H" + frame + "\n")
            sys.stdout.flush()
            _time.sleep(args.interval)
    except KeyboardInterrupt:
        # Ctrl-C is the live view's ONLY interactive exit — leave the
        # last frame on screen, not a stack trace over it
        print()


def cmd_serve(args):
    """Serve the client order-book UI (client/web.py) with the in-process
    escrow; --with-prover loads the build dir's zkey so /api/onramp can
    prove receipts on the TPU."""
    import time as _time

    from ..client.web import OnrampApp, ProverBundle, serve
    from ..contracts.deploy import VENMO_RSA_KEY_LIMBS
    from ..contracts.ramp import FakeUSDC, Ramp
    from ..formats.proof_json import load, vkey_from_json

    vk = vkey_from_json(load(os.path.join(args.build_dir, "verification_key.json")))
    usdc = FakeUSDC()
    # --demo deploys the escrow with the synthetic test key's modulus limbs:
    # the UI's synthetic /api/onramp path proves against make_test_key(1), so
    # a Ramp holding the production Venmo limbs would reject every demo proof
    # with 'RSA modulus not matched' (r3 advisor).  Without --demo the served
    # form only offers the server-side .eml path.
    if args.demo:
        from ..gadgets.bigint import int_to_limbs_host
        from ..inputs.email import make_test_key

        key_limbs = int_to_limbs_host(make_test_key(1).n, 121, 17)
    else:
        key_limbs = VENMO_RSA_KEY_LIMBS
    ramp = Ramp(key_limbs, usdc, max_amount=args.max_amount, vk=vk)
    prover = None
    if args.with_prover:
        from ..prover.groth16_tpu import device_pk_from_zkey

        if args.circuit != "venmo":
            raise SystemExit("/api/onramp proves venmo receipts; pass --circuit venmo")
        cs, meta = _build_circuit(args.circuit, args.max_header, args.max_body, args.message_bytes)
        zk = _load_zkey(args)
        _check_zkey_matches(zk, cs)
        prover = ProverBundle(cs=cs, dpk=device_pk_from_zkey(zk, infer_widths=_infer_widths(args)), params=meta[0], layout=meta[1])
        _log("prover bundle loaded")
    app = OnrampApp(
        ramp, usdc, prover, eml_spool=args.eml_spool,
        zkey_store=getattr(args, "zkey_store", None),
        zkey_cache=os.path.join(args.build_dir, "zkey_cache"),
    )
    srv = serve(app, port=args.port)
    _log(f"serving on http://127.0.0.1:{srv.server_address[1]} (ctrl-c to stop)")
    try:
        while True:
            _time.sleep(3600)
    except KeyboardInterrupt:
        srv.shutdown()


def cmd_lint(args):
    """Run the zkp2p-lint suite (tools/lint) over this checkout.  The
    linter lives beside the tools it polices rather than inside the
    package, so it can parse a tree whose imports are broken — exactly
    the tree that needs linting most."""
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from tools.lint import main as lint_main

    argv = []
    if args.rules:
        argv += ["--rules", args.rules]
    if args.json:
        argv.append("--json")
    if args.circuits is not None:
        argv += ["--circuits", args.circuits] if args.circuits != "all" else ["--circuits"]
        if args.flagship:
            argv.append("--flagship")
        if args.no_cache:
            argv.append("--no-cache")
    raise SystemExit(lint_main(argv))


def cmd_doctor(args):
    """Execution-path preflight: initialise the backend, arm EVERY gate
    through its real resolver, and report which arm each one took — a
    fast-path gate that silently resolved to its fallback is one run
    away from being seen, not a wasted chip session.  Takes the chip on
    a TPU host (it initialises the backend), so run it alone.

    Machine output (`--json`) is one JSON object on stdout: backend,
    gate→arm map, knobs+provenance, warnings, device memory (when the
    backend exposes it) and the execution digest — the comparison key
    two runs must share before their numbers are comparable."""
    import json as _json

    from ..utils.audit import preflight

    # log=None: the text mode below prints rep["warnings"] itself —
    # letting preflight log them too would show every mis-arm twice
    rep = preflight(workload=not args.no_workload)
    if args.json:
        print(_json.dumps(rep))
    else:
        _log(f"backend: {rep['backend']}")
        prov = rep["provenance"]
        gate_knob = {  # gate -> the knob that steers it, for the listing
            "field_mul": "field_mul", "curve_kernel": "curve_kernel", "batch_chunk": "batch_chunk",
            "native_msm_glv": "msm_glv", "native_batch_affine": "msm_batch_affine",
            "native_tier": "native_ifma",
        }
        _log("gates:")
        for gate, arm in sorted(rep["gates"].items()):
            src = f"  [{gate_knob[gate]}:{prov.get(gate_knob[gate])}]" if gate in gate_knob else ""
            _log(f"  {gate:<22} = {arm}{src}")
        if rep.get("workload_s") is not None:
            _log(f"workload: tiny jit ran in {rep['workload_s']}s")
        mem = rep.get("device_memory")
        if mem:
            _log(
                f"device memory: {mem['bytes_in_use']/2**30:.2f} GiB in use, "
                f"peak {mem['peak_bytes_in_use']/2**30:.2f} GiB"
                + (f" of {mem['bytes_limit']/2**30:.2f} GiB" if mem.get("bytes_limit") else "")
            )
        _log(f"execution digest: {rep['execution_digest']}")
        for w in rep["warnings"]:
            _log(f"WARNING: {w}")
        if not rep["warnings"]:
            _log("no mis-armed gates detected")
    if args.strict and rep["warnings"]:
        sys.exit(1)


def cmd_tune(args):
    """Budgeted host micro-sweep → fingerprint-keyed profile beside
    .bench_cache (pipeline.tune).  Every resolver that today falls back
    to a hand-picked constant (fixed-tier MSM geometry, native thread
    default, the scheduler's amortization curve) loads the profile at
    startup; `--out` writes elsewhere (set ZKP2P_PROFILE_PATH to load
    it), `--arms` filters the sweep, `--budget-s` caps wall clock."""
    from .tune import run_tune

    prof = run_tune(
        n=args.n,
        reps=args.reps,
        budget_s=args.budget_s,
        out_path=args.out or None,
        arms_spec=args.arms,
        log=_log,
    )
    if prof is None:
        _log("tune: nothing tuned (native library unavailable)")
        sys.exit(1)


def cmd_warm_cache(args):
    """Pre-populate the persistent XLA compile cache with the batch
    prover's executables, so the first REAL batch of a service/fleet
    session dispatches warm instead of paying the multi-minute
    shard_map compile inline (docs/TPU.md §warm-start).

    The executables XLA caches are keyed by SHAPES (circuit wires +
    domain, batch width, mesh geometry, window), not key material — so
    a dev in-memory setup over the same circuit warms exactly the
    entries a production zkey will hit, and no zkey file is needed.
    Run it with the same --circuit/--batch and ZKP2P_TPU_SHARD/
    ZKP2P_TPU_MESH (or --shard) the service will use."""
    # knob wiring BEFORE any compile — flags ride the env knobs like
    # cmd_service's --sched (the prover's shard gate fresh-reads)
    if args.shard:
        os.environ["ZKP2P_TPU_SHARD"] = "on"
        if args.shard != "on":
            os.environ["ZKP2P_TPU_MESH"] = args.shard
    # re-assert the cache with a ZERO compile-time floor: main() enabled
    # it with the 1.0 s default, which would skip sub-second executables
    # (the toy-circuit smoke depends on those round-tripping)
    from ..utils.audit import compile_totals as _compile_totals, install_compile_listener
    from ..utils.jaxcfg import cache_dir as _resolved_cache_dir, enable_cache

    enable_cache(min_compile_s=0.0)
    install_compile_listener()
    cdir = _resolved_cache_dir()

    def _cache_entries():
        files = total = 0
        for root, _dirs, fns in os.walk(cdir):
            for fn in fns:
                files += 1
                try:
                    total += os.path.getsize(os.path.join(root, fn))
                except OSError:
                    pass
        return files, total

    f0, b0 = _cache_entries()
    ev0, s0 = _compile_totals()

    cs, meta = _build_circuit(args.circuit, args.max_header, args.max_body, args.message_bytes)
    from ..prover import device_pk
    from ..prover.groth16_tpu import prove_tpu_batch
    from ..snark.groth16 import setup

    pk, _vk = setup(cs)
    dpk = device_pk(pk, cs)
    w, _pub = _witness_for(args, cs, meta)
    wits = [w] * max(1, args.batch)
    _log(f"warm-cache: compiling batch={len(wits)} of {args.circuit!r} into {cdir}")
    t0 = time.perf_counter()
    prove_tpu_batch(dpk, wits)
    dt = time.perf_counter() - t0
    f1, b1 = _cache_entries()
    ev1, s1 = _compile_totals()
    from ..utils.audit import gate_arms

    _log(
        f"warm-cache: {dt:.1f}s wall, {ev1 - ev0:.0f} compiles "
        f"({s1 - s0:.1f}s compile time), cache {'+' if f1 >= f0 else ''}{f1 - f0} "
        f"entries ({(b1 - b0) / 2**20:.1f} MiB) -> {f1} total"
    )
    _log(f"warm-cache: tpu_shard arm = {gate_arms().get('tpu_shard', 'off')}")
    # warm runs still fire backend_compile EVENTS (the cache hit and its
    # deserialization happen inside the span) — zero NEW entries is the
    # round-trip proof
    if f1 - f0 == 0:
        _log("warm-cache: zero new cache entries — every executable loaded warm")


def main(argv=None):
    ap = argparse.ArgumentParser("zkp2p-tpu", description=__doc__)
    ap.add_argument("--build-dir", default=os.environ.get("BUILD_DIR", "build"))
    ap.add_argument("--circuit", default=os.environ.get("CIRCUIT_NAME", "sha256"))
    ap.add_argument("--max-header", type=int, default=256)
    ap.add_argument("--max-body", type=int, default=192)
    ap.add_argument("--message-bytes", type=int, default=64,
                    help="sha256_preimage: the fixed preimage length (4096: the benchmark's sha256-4k)")
    sub = ap.add_subparsers(dest="cmd", required=True)

    s = sub.add_parser("setup", help="build circuit + dev zkey + vkey + verifier.sol")
    s.add_argument("--skip-audit", action="store_true",
                   help="bypass the circuit soundness audit (admission gate)")
    s.add_argument("--seed", default="zkp2p-tpu-dev")
    s.add_argument("--chunks", type=int, default=0, help="also split the zkey into N chunks (b..)")
    s.add_argument("--publish", help="artifact-store dir: upload gzip zkey chunks + manifest")
    s.set_defaults(fn=cmd_setup)

    s = sub.add_parser("prove", help="prove one input on TPU")
    s.add_argument("--eml", help="email file (venmo / email_verify circuits)")
    s.add_argument("--demo", action="store_true", help="use the synthetic signed email")
    s.add_argument("--message", help="message (sha256 circuit)")
    s.add_argument("--zkey", help="zkey path or chunk glob (default: BUILD_DIR/circuit_final.zkey)")
    s.add_argument("--no-infer-widths", action="store_true", help="disable the zkey bit-constraint width inference (use when the circuit contains x*(x-1)=y rows)")
    s.add_argument("--zkey-store", help="artifact-store dir to pull the chunked zkey from")
    s.add_argument("--wtns", help="externally generated witness.wtns (drop-in prover parity)")
    s.add_argument("--prover", choices=["tpu", "native"], default="tpu",
                   help="tpu: XLA device path; native: C++ Pippenger runtime")
    s.add_argument("--order-id", type=int, default=1)
    s.add_argument("--claim-id", type=int, default=0)
    s.add_argument("--proof", default="proof.json")
    s.add_argument("--public", default="public.json")
    s.set_defaults(fn=cmd_prove)

    s = sub.add_parser("verify", help="verify proof.json against the vkey")
    s.add_argument("--proof", default="proof.json")
    s.add_argument("--public", default="public.json")
    s.set_defaults(fn=cmd_verify)

    s = sub.add_parser("service", help="run the batched proving service over a spool dir")
    s.add_argument("--spool", required=True)
    s.add_argument("--batch", type=int, default=4)
    s.add_argument("--poll", type=float, default=1.0)
    s.add_argument("--max-sweeps", type=int, default=None)
    s.add_argument("--zkey", help="zkey path or chunk glob")
    s.add_argument("--no-infer-widths", action="store_true", help="disable the zkey bit-constraint width inference")
    s.add_argument("--prover", choices=["tpu", "native"], default="tpu",
                   help="tpu: vmapped XLA batch; native: C++ runtime, sequential")
    s.add_argument("--prefetch", type=int, default=1, help="ready-batch queue depth")
    s.add_argument("--replicas", default="1", metavar="N|auto",
                   help="one-chip replicas of this process on the spool, one a local device, each "
                        "with the key pinned to its device (auto = every local device; 1 = a solo "
                        "service; a mesh over the same chips is ZKP2P_TPU_SHARD=on instead)")
    s.add_argument("--stale-claim-s", type=float, default=300.0,
                   help="claim age after which a dead worker's request is taken over")
    s.add_argument("--deadline-s", type=float, default=None,
                   help="default per-request deadline in s (payload deadline_s overrides; "
                        "default: ZKP2P_DEADLINE_S; 0 = none)")
    s.add_argument("--spool-cap", type=int, default=None,
                   help="max pending requests admitted per sweep — the excess is shed as "
                        "error-shed (default: ZKP2P_SPOOL_CAP; 0 = unlimited)")
    s.add_argument("--slo-p95-s", type=float, default=None,
                   help="p95 latency objective in s for the SLO tracker + /status "
                        "(default: ZKP2P_SLO_P95_S; 0 = none)")
    s.add_argument("--ts-sample-s", type=float, default=None,
                   help="time-series sampler interval in s "
                        "(default: ZKP2P_TS_SAMPLE_S; 0 = off)")
    s.add_argument("--sched", dest="sched_flag", choices=["off", "adaptive"], default=None,
                   help="batching/admission scheduler: off = static batch_size + "
                        "newest-first shed; adaptive = SLO-driven sizing, deadline-"
                        "aware shed, priority lanes (default: ZKP2P_SCHED)")
    s.add_argument("--max-seconds", type=float, default=None,
                   help="exit (rc 2) after this many seconds (tests/fleet smokes)")
    s.add_argument("--exit-when-terminal", action="store_true",
                   help="exit 0 once every spool request has a terminal artifact")
    s.set_defaults(fn=cmd_service)

    s = sub.add_parser(
        "fleet",
        help="supervise N service workers on one spool (restart/backoff/"
             "circuit-breaker, graceful drain, RSS governor)",
    )
    s.add_argument("--spool", required=True)
    s.add_argument("--workers", type=int, default=None,
                   help="worker count (default: ZKP2P_FLEET_WORKERS)")
    s.add_argument("--batch", type=int, default=4)
    s.add_argument("--poll", type=float, default=1.0)
    s.add_argument("--zkey", help="zkey path or chunk glob")
    s.add_argument("--no-infer-widths", action="store_true",
                   help="disable the zkey bit-constraint width inference")
    s.add_argument("--prover", choices=["tpu", "native"], default="native",
                   help="worker prover arm (native = multi-column C batch path)")
    s.add_argument("--prefetch", type=int, default=1)
    s.add_argument("--stale-claim-s", type=float, default=300.0)
    s.add_argument("--deadline-s", type=float, default=None)
    s.add_argument("--spool-cap", type=int, default=None)
    s.add_argument("--slo-p95-s", type=float, default=None)
    s.add_argument("--ts-sample-s", type=float, default=None)
    s.add_argument("--fleet-dir", default=None,
                   help="heartbeat/ctl/status dir (default: <spool>/.fleet)")
    s.add_argument("--drain-timeout-s", type=float, default=None,
                   help="bounded wait between SIGTERM and SIGKILL escalation "
                        "(default: ZKP2P_DRAIN_TIMEOUT_S)")
    s.add_argument("--liveness-s", type=float, default=60.0,
                   help="heartbeat age past which a live worker counts as hung")
    s.add_argument("--rss-soft-mb", type=int, default=None,
                   help="per-worker RSS soft budget: degrade ctl (default: ZKP2P_RSS_SOFT_MB; 0 = off)")
    s.add_argument("--rss-hard-mb", type=int, default=None,
                   help="per-worker RSS hard budget: drain + restart (default: ZKP2P_RSS_HARD_MB; 0 = off)")
    s.add_argument("--breaker-k", type=int, default=None,
                   help="failures inside the window that park a worker (default: ZKP2P_BREAKER_K)")
    s.add_argument("--breaker-window-s", type=float, default=None,
                   help="circuit-breaker window (default: ZKP2P_BREAKER_WINDOW_S)")
    s.add_argument("--restart-backoff-s", type=float, default=None,
                   help="exponential restart-backoff base (default: ZKP2P_RESTART_BACKOFF_S)")
    s.add_argument("--max-seconds", type=float, default=None,
                   help="drain + exit after this long (tests/chaos)")
    s.add_argument("--worker-cmd", default=None,
                   help="JSON argv for each worker (advanced/chaos; '{wid}' and "
                        "'{spool}' substitute) — default spawns 'zkp2p-tpu service' workers")
    s.add_argument("--fleet-metrics-port", default=None,
                   help="fleet observability plane port: aggregated /metrics + /status "
                        "+ /healthz ('auto'/0 = ephemeral, recorded in status.json; "
                        "default: ZKP2P_FLEET_METRICS_PORT; unset = plane off)")
    s.add_argument("--sched", dest="sched_flag", choices=["off", "adaptive"], default=None,
                   help="worker batching/admission scheduler arm (default: ZKP2P_SCHED)")
    s.add_argument("--workers-min", type=int, default=None,
                   help="autoscale floor (default: ZKP2P_WORKERS_MIN)")
    s.add_argument("--workers-max", type=int, default=None,
                   help="autoscale ceiling; 0 = autoscale off "
                        "(default: ZKP2P_WORKERS_MAX)")
    s.add_argument("--scale-up-s", type=float, default=None,
                   help="how long backlog growth / slo burn must hold before +1 worker "
                        "(default: ZKP2P_SCALE_UP_S)")
    s.add_argument("--scale-down-s", type=float, default=None,
                   help="how long an idle backlog must hold before -1 worker "
                        "(default: ZKP2P_SCALE_DOWN_S)")
    s.set_defaults(fn=cmd_fleet)

    s = sub.add_parser("top", help="live fleet view: poll the fleet /status and render it")
    s.add_argument("--url", help="full fleet status URL (overrides --port/--fleet-dir)")
    s.add_argument("--port", type=int, default=None, help="fleet plane port on 127.0.0.1")
    s.add_argument("--fleet-dir", help="read the port from <fleet-dir>/status.json")
    s.add_argument("--interval", type=float, default=2.0, help="poll interval in s")
    s.add_argument("--once", action="store_true",
                   help="print one frame and exit (scripts/tests)")
    s.set_defaults(fn=cmd_top)

    s = sub.add_parser("serve", help="serve the client order-book UI")
    s.add_argument("--port", type=int, default=8080)
    s.add_argument("--max-amount", type=int, default=10_000_000)
    s.add_argument("--with-prover", action="store_true", help="load the zkey so /api/onramp proves")
    s.add_argument("--zkey", help="zkey path or chunk glob")
    s.add_argument("--no-infer-widths", action="store_true", help="disable the zkey bit-constraint width inference")
    s.add_argument("--demo", action="store_true", help="deploy the escrow with the synthetic test-key limbs")
    s.add_argument("--eml-spool", help="directory server-side .eml paths are restricted to")
    s.set_defaults(fn=cmd_serve)

    s = sub.add_parser("ceremony", help="phase-2 zkey MPC: contribute / beacon / verify")
    s.add_argument("op", choices=["contribute", "beacon", "verify"])
    s.add_argument("zkey_in", help="input zkey (for verify: the trusted initial zkey)")
    s.add_argument("zkey_out", help="output zkey (for verify: the final zkey to check)")
    s.add_argument("--entropy", default="", help="contributor entropy string (contribute)")
    s.add_argument("--name", default="", help="contributor name recorded in the transcript")
    s.add_argument("--beacon-hash", default="", help="public beacon value, hex (beacon)")
    s.add_argument("--iter-exp", type=int, default=10, help="beacon hash iterations = 2^n (beacon)")
    s.set_defaults(fn=cmd_ceremony)

    s = sub.add_parser(
        "tune",
        help="budgeted host micro-sweep -> fingerprint-keyed profile (geometry/threads/amortization)",
    )
    s.add_argument("--n", type=int, default=1 << 15,
                   help="MSM shape per micro-arm (default 32768; bigger = more faithful, slower)")
    s.add_argument("--reps", type=int, default=3, help="min-of-reps per measurement")
    s.add_argument("--budget-s", type=float, default=None,
                   help="wall-clock budget (default: ZKP2P_TUNE_BUDGET_S)")
    s.add_argument("--out", default=None,
                   help="profile path (default: .bench_cache/host_profile_<fp>.json)")
    s.add_argument("--arms", default=None,
                   help="comma list of arms (threads,window,geometry,columns,ladder); default: ZKP2P_TUNE_ARMS or all")
    s.set_defaults(fn=cmd_tune)

    s = sub.add_parser(
        "warm-cache",
        help="pre-compile the batch prover into the persistent XLA cache "
             "(sharded arm included when ZKP2P_TPU_SHARD/--shard asks)",
    )
    s.add_argument("--batch", type=int, default=8,
                   help="batch width to compile for (must match the service's; "
                        "sharded: a multiple of the mesh batch dim)")
    s.add_argument("--shard", nargs="?", const="on", default=None, metavar="BxS",
                   help="arm the sharded batch prover (sets ZKP2P_TPU_SHARD=on; "
                        "an explicit BxS value also sets ZKP2P_TPU_MESH)")
    s.add_argument("--message", help=argparse.SUPPRESS)
    s.add_argument("--eml", help=argparse.SUPPRESS)
    s.set_defaults(fn=cmd_warm_cache)

    s = sub.add_parser("doctor", help="execution-path preflight: arm every gate, report arms + digest")
    s.add_argument("--json", action="store_true", help="machine-readable report on stdout")
    s.add_argument("--no-workload", action="store_true", help="skip the tiny jitted workload")
    s.add_argument("--strict", action="store_true", help="exit 1 when any gate is mis-armed")
    s.set_defaults(fn=cmd_doctor)

    s = sub.add_parser(
        "lint",
        help="static invariant checks: knob/gate discipline, stats-ABI drift, "
        "metric naming, durability, clocks, pyflakes tier — docs/STATIC_ANALYSIS.md",
    )
    s.add_argument("--rules", default="", help="comma-separated rule filter")
    s.add_argument("--json", action="store_true", help="machine-readable findings")
    s.add_argument(
        "--circuits", nargs="?", const="all", default=None, metavar="IDS",
        help="run the R1CS soundness audit on registered circuits "
        "(the registry admission gate) instead of the source rules",
    )
    s.add_argument("--flagship", action="store_true",
                   help="with --circuits: include the 4.9M-wire flagship")
    s.add_argument("--no-cache", action="store_true",
                   help="with --circuits: ignore cached audit reports")
    # no_jax: lint is the pre-commit path — it must answer in seconds
    # without importing jax or touching the compilation cache (the
    # circuit tier builds real circuits but still needs only numpy)
    s.set_defaults(fn=cmd_lint, no_jax=True)

    s = sub.add_parser("batch", help="prove a directory of inputs as one batch")
    s.add_argument("--indir", required=True)
    s.add_argument("--outdir", required=True)
    s.add_argument("--prover", choices=["tpu", "native"], default="tpu",
                   help="tpu: vmapped XLA batch; native: C++ runtime, sequential")
    s.add_argument("--zkey", help="zkey path or chunk glob")
    s.add_argument("--no-infer-widths", action="store_true", help="disable the zkey bit-constraint width inference")
    s.add_argument("--message", help=argparse.SUPPRESS)
    s.add_argument("--order-id", type=int, default=1)
    s.add_argument("--claim-id", type=int, default=0)
    s.set_defaults(fn=cmd_batch)

    args = ap.parse_args(argv)
    if getattr(args, "no_jax", False):
        args.fn(args)
        return
    from ..utils.jaxcfg import enable_cache

    if getattr(args, "prover", None) == "native":
        # one process per chip: `--prover native` proves in the C++
        # runtime, so its JAX (key arrays, witness limbs) stays on the
        # host platform and the chip is left to a `--prover tpu` process
        import jax

        jax.config.update("jax_platforms", "cpu")
    enable_cache()
    args.fn(args)


if __name__ == "__main__":
    main()
