#!/usr/bin/env python
"""Prove the FULL-SIZE flagship (P2POnrampVerify 1024/6400: 4.94 M
constraints, domain 2^23) ON THE REAL TPU CHIP.

Loads the device key + witness that tools/prove_fullsize_native.py
cached under .bench_cache/ (run it first on CPU; ~15 min setup), pushes
the key to HBM, jits `prove_tpu` at batch=1, and writes a per-stage
trace to docs/fullsize_proof/timing_tpu.json with the proof pairing-
verified against the same vkey the native run used.

HBM budget note (v5e, 15.75 G usable): the key is ~4-5 GB resident
(a/b1/b2/c/h bases + QAP coeff rows), NTT scratch at 2^23 is ~0.5 GB per
live array.  The XLA field-mul path would materialise an (nnz, 16, 16)
partial-product tensor (~11 GB at full-size nnz) in the matvec — this
tool therefore requires the fused Pallas field path (utils.jaxcfg.on_tpu
routing), which keeps the Montgomery chain in VMEM.  Run only after
tools/pallas_hw_diff.py is green on this chip; FULLSIZE_ALLOW_XLA=1
overrides the guard for A/B forensics.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CACHE = os.path.join(ROOT, ".bench_cache")
OUT = os.path.join(ROOT, "docs", "fullsize_proof")

T0 = time.time()


def log(msg):
    print(f"[fullsize-tpu +{time.time() - T0:7.1f}s] {msg}", flush=True)


def main():
    from zkp2p_tpu.utils.jaxcfg import enable_cache, on_tpu

    enable_cache()
    import jax

    devs = jax.devices()
    log(f"devices: {devs}")
    if not on_tpu():
        log("not on a TPU — this tool measures the chip; aborting")
        return 2
    from zkp2p_tpu.field.jfield import field_mul_impl

    if field_mul_impl() != "pallas" and not os.environ.get("FULLSIZE_ALLOW_XLA"):
        log(
            "pallas field path not engaged (would OOM the XLA matvec at "
            "full-size nnz); set FULLSIZE_ALLOW_XLA=1 to force"
        )
        return 2

    import numpy as np

    from zkp2p_tpu.prover.keycache import load_dpk
    from zkp2p_tpu.prover.groth16_tpu import prove_tpu
    from zkp2p_tpu.snark.groth16 import verify
    from zkp2p_tpu.utils.trace import dump_trace, trace

    key_path = os.path.join(CACHE, "venmo_1024_6400.npz")
    wit_path = os.path.join(CACHE, "venmo_witness_1024_6400.npz")
    for p in (key_path, wit_path):
        if not os.path.exists(p):
            log(f"missing {p} — run tools/prove_fullsize_native.py (CPU) first")
            return 2

    timing = {}
    t = time.perf_counter()
    log("loading device key (npz -> host arrays)")
    dpk, vk = load_dpk(key_path)
    timing["load_key_s"] = round(time.perf_counter() - t, 1)

    t = time.perf_counter()
    z = np.load(wit_path)
    # (n, 4) u64 standard-form limbs — witness_to_device's vectorized
    # fast path consumes this directly (no Python bigint loop).
    w = z["witness"].astype(np.uint64)
    pubs = [
        sum(int(limb) << (64 * i) for i, limb in enumerate(row)) for row in z["pubs"]
    ]
    timing["load_witness_s"] = round(time.perf_counter() - t, 1)
    log(f"witness loaded ({w.shape[0]} wires) in {timing['load_witness_s']}s")

    # Deterministic (r, s) so the proof is byte-comparable to the native
    # run's committed artifact (same contract as prove_native there).
    t = time.perf_counter()
    log("prove_tpu (first call: key transfer + compile + prove) ...")
    with trace("fullsize_tpu_first"):
        proof = prove_tpu(dpk, w, r=123456789, s=987654321)
    timing["first_prove_incl_compile_s"] = round(time.perf_counter() - t, 1)
    log(f"first prove (incl compile/transfer): {timing['first_prove_incl_compile_s']}s")

    t = time.perf_counter()
    assert verify(vk, proof, pubs), "full-size TPU proof failed pairing verification"
    timing["verify_s"] = round(time.perf_counter() - t, 1)
    log("pairing verified")

    t = time.perf_counter()
    with trace("fullsize_tpu_steady"):
        proof2 = prove_tpu(dpk, w, r=123456789, s=987654321)
    timing["steady_prove_s"] = round(time.perf_counter() - t, 1)
    assert proof2 == proof, "determinism: same (witness, r, s) must re-emit the same proof"
    log(f"steady-state prove: {timing['steady_prove_s']}s")

    timing["constraints"] = 4939112
    timing["device"] = str(devs[0])
    timing["field_mul"] = field_mul_impl()
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "timing_tpu.json"), "w") as f:
        json.dump(timing, f, indent=1)
    dump_trace()
    log(f"done: {json.dumps(timing)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
