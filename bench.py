#!/usr/bin/env python
"""Device-tier bench: batched Groth16 proving of the venmo circuit on the TPU.

Runs in THIS process on the chip or exits non-zero: no probe, no child
process, no CPU tier.  Without a TPU it proves nothing and prints no
record.  (`chip_smoke.py` is the quickest check that the served path
still starts on the chip; ROADMAP S1 replaces this file with the cell
table.)

Prints ONE JSON line with the device it ran on, the circuit shape, the
batch wall times and the execution digest.  Stage breakdown is printed
to stderr via utils.trace.  Keys cache under .bench_cache/ as data-only
.npz device arrays (prover.keycache) — no pickle anywhere.
"""

from __future__ import annotations

import json
import os
import sys
import time

CACHE = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".bench_cache")
BATCH = int(os.environ.get("BENCH_BATCH", "16"))
HEADER = int(os.environ.get("BENCH_HEADER", "256"))
BODY = int(os.environ.get("BENCH_BODY", "192"))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def build_keys(cs):
    """Device key from the .npz cache, else array-path setup (native)."""
    from zkp2p_tpu.prover.keycache import (
        KeyCacheSchemaError,
        circuit_digest,
        load_dpk,
        save_dpk,
    )
    from zkp2p_tpu.utils.trace import trace

    from zkp2p_tpu.snark.groth16 import domain_size_for

    os.makedirs(CACHE, exist_ok=True)
    path = os.path.join(CACHE, f"venmo_{HEADER}_{BODY}.npz")
    digest = circuit_digest(cs)
    if os.path.exists(path):
        log("loading cached device key")
        try:
            with trace("load_key"):
                dpk, vk = load_dpk(path, digest=digest)
            # A gadget change alters wire count/domain -> a stale cache must
            # re-setup, not crash deep inside jit with a shape mismatch
            # (the digest above also catches same-count REORDERS).
            if dpk.n_wires == cs.num_wires and (1 << dpk.log_m) == domain_size_for(cs):
                return dpk, vk
            log("cached key does not match the rebuilt circuit; re-running setup")
        except KeyCacheSchemaError as exc:
            log(f"stale key cache: {exc}")
    log("array-path setup (native fixed-base batches; cached for future runs) ...")
    t0 = time.perf_counter()
    with trace("setup"):
        from zkp2p_tpu.prover.setup_device import setup_device

        dpk, vk = setup_device(cs, seed="bench")
    log(f"setup took {time.perf_counter() - t0:.0f}s")
    save_dpk(path, dpk, vk, digest=digest)
    return dpk, vk


def _build_venmo(index: int = 0):
    """One venmo bench instance at the BENCH_HEADER/BENCH_BODY shape:
    (cs, layout, make_input).  Shared with tools/loadgen.py so both
    measure the SAME circuit + witness."""
    from zkp2p_tpu.inputs.email import generate_inputs, make_test_key, make_venmo_email
    from zkp2p_tpu.models.venmo import VenmoParams, build_venmo_circuit
    from zkp2p_tpu.utils.trace import trace

    params = VenmoParams(max_header_bytes=HEADER, max_body_bytes=BODY)
    log(f"building venmo circuit ({HEADER}/{BODY}) ...")
    with trace("build_circuit"):
        cs, lay = build_venmo_circuit(params)
    log(f"constraints={cs.num_constraints} wires={cs.num_wires}")

    def make_input(i: int):
        key = make_test_key(1)
        email = make_venmo_email(
            key, raw_id=f"{1234567891234567 + i}891"[:19], amount=str(30 + i), body_filler=40
        )
        return generate_inputs(email, key.n, order_id=i + 1, claim_id=i, params=params, layout=lay)

    return cs, lay, make_input


def main():
    from zkp2p_tpu.utils.audit import execution_digest, gate_arms, install_compile_listener, preflight
    from zkp2p_tpu.utils.jaxcfg import enable_cache
    from zkp2p_tpu.utils.metrics import maybe_start_metrics_server, run_id

    maybe_start_metrics_server()
    enable_cache()
    # flight recorder: register the jit compile-event listener before
    # the first compile, so a cold prover compile is attributed to its
    # stage, not inferred from wall-clock gaps
    install_compile_listener()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py needs a TPU; JAX found platform {dev.platform!r} ({dev.device_kind})")
    log("devices:", jax.devices())
    rep = preflight(workload=False, log=log)
    if rep["warnings"]:
        raise SystemExit(f"mis-armed gates: {rep['warnings']}")

    from zkp2p_tpu.prover.groth16_tpu import prove_tpu_batch
    from zkp2p_tpu.snark.groth16 import verify
    from zkp2p_tpu.utils.trace import dump_trace, trace

    cs, lay, make_input = _build_venmo()
    dpk, vk = build_keys(cs)

    wits, pubs = [], []
    with trace("witness_gen", batch=BATCH):
        for i in range(BATCH):
            inputs = make_input(i)
            wits.append(cs.witness(inputs.public_signals, inputs.seed))
            pubs.append(inputs.public_signals)

    log("warmup (compile) ...")
    t0 = time.perf_counter()
    with trace("first_batch_incl_compile", batch=BATCH):
        proofs = prove_tpu_batch(dpk, wits)
    first = time.perf_counter() - t0
    log(f"first batch (incl compile): {first:.1f}s")
    for i, (proof, pub) in enumerate(zip(proofs, pubs)):
        if not verify(vk, proof, pub):
            raise SystemExit(f"proof {i} failed the pairing check")
    log(f"{len(proofs)} proofs verified against the pairing equation")

    log("timed runs ...")
    times = []
    for run in range(int(os.environ.get("BENCH_TIMED_RUNS", "3"))):
        t0 = time.perf_counter()
        with trace("prove_batch", run=run, batch=BATCH):
            prove_tpu_batch(dpk, wits)
        times.append(time.perf_counter() - t0)
    log("--- stage trace ---")
    dump_trace()
    print(
        json.dumps(
            {
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": len(jax.devices()),
                },
                "circuit": f"venmo {HEADER}/{BODY}",
                "constraints": cs.num_constraints,
                "batch": BATCH,
                "first_batch_incl_compile_s": round(first, 3),
                "batch_wall_s": [round(t, 3) for t in times],
                "gates": gate_arms(),
                "run_id": run_id(),
                "execution_digest": execution_digest(),
            }
        )
    )


if __name__ == "__main__":
    main()
