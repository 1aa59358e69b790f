#!/usr/bin/env python
"""Prove the FULL-SIZE flagship circuit P2POnrampVerify(1024, 6400, 121, 17)
with the native C++ runtime, end to end, on one CPU core.

The analog of the reference's one real full-scale proof (its rapidsnark
run: 6.62M constraints in 9.2 s on 48 cores, zkp-mooc-hackathon-
submission.md:89-101; its pinned proof vector: test/ramp.test.js:193).
Artifacts land in docs/fullsize_proof/ (proof.json, public.json,
timing.json) and the witness + device key are cached under .bench_cache/
so reruns skip the expensive builds.

Run:  JAX_PLATFORMS=cpu python tools/prove_fullsize_native.py
"""

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CACHE = os.path.join(ROOT, ".bench_cache")
OUT = os.path.join(ROOT, "docs", "fullsize_proof")

import jax  # noqa: E402
import numpy as np  # noqa: E402

# The native prover never proves on the device: keep this process's JAX
# (key arrays) on the host platform so it does not take the chip.
jax.config.update("jax_platforms", "cpu")


def log(msg):
    print(f"[fullsize +{time.time() - T0:7.1f}s] {msg}", flush=True)


T0 = time.time()


def main():
    from zkp2p_tpu.field.bn254 import R
    from zkp2p_tpu.formats.proof_json import proof_to_json, public_to_json
    from zkp2p_tpu.inputs.email import generate_inputs, make_test_key, make_venmo_email
    from zkp2p_tpu.models.venmo import VenmoParams, build_venmo_circuit
    from zkp2p_tpu.prover.keycache import (
        KeyCacheSchemaError,
        circuit_digest,
        load_dpk,
        save_dpk,
    )
    from zkp2p_tpu.prover.native_prove import prove_native
    from zkp2p_tpu.snark.groth16 import domain_size_for, verify

    os.makedirs(OUT, exist_ok=True)
    timing = {}

    params = VenmoParams()  # full size: 1024 header / 6400 body
    wit_path = os.path.join(CACHE, "venmo_witness_1024_6400.npz")
    key_path = os.path.join(CACHE, "venmo_1024_6400.npz")

    t = time.perf_counter()
    log("building full-size circuit (expect ~7 min) ...")
    cs, lay = build_venmo_circuit(params)
    timing["build_circuit_s"] = round(time.perf_counter() - t, 1)
    log(f"constraints={cs.num_constraints} wires={cs.num_wires} domain={domain_size_for(cs)}")

    wit_digest = circuit_digest(cs)
    if os.path.exists(wit_path):
        log("loading cached witness")
        z = np.load(wit_path)
        cached_digest = bytes(z["digest"]).decode() if "digest" in z else "<none>"
        if int(z["n_wires"][0]) == cs.num_wires and cached_digest == wit_digest:
            # hoist the arrays OUT of the npz handle: indexing an NpzFile
            # decompresses the whole member per access
            wit_arr, pubs_arr = z["witness"], z["pubs"]
            wbuf = wit_arr.tobytes()
            w = [int.from_bytes(wbuf[i * 32 : (i + 1) * 32], "little") for i in range(cs.num_wires)]
            pbuf = pubs_arr.tobytes()
            pubs = [int.from_bytes(pbuf[i * 32 : (i + 1) * 32], "little") for i in range(pubs_arr.shape[0])]
        else:
            log("cached witness is for a different circuit; regenerating")
            w = None
    else:
        w = None
    if w is None:
        t = time.perf_counter()
        key = make_test_key(1)
        email = make_venmo_email(key, raw_id="1234567891234567891", amount="42", body_filler=40)
        inputs = generate_inputs(email, key.n, order_id=1, claim_id=1, params=params, layout=lay)
        w = cs.witness(inputs.public_signals, inputs.seed)
        pubs = inputs.public_signals
        timing["witness_s"] = round(time.perf_counter() - t, 1)
        log(f"witness generated in {timing['witness_s']}s; checking")
        t = time.perf_counter()
        cs.check_witness(w)
        timing["check_witness_s"] = round(time.perf_counter() - t, 1)
        from zkp2p_tpu.native.lib import _scalars_to_u64

        np.savez(
            wit_path,
            witness=_scalars_to_u64([x % R for x in w]),
            pubs=_scalars_to_u64([x % R for x in pubs]),
            n_wires=np.array([cs.num_wires], dtype=np.int64),
            digest=np.frombuffer(wit_digest.encode(), dtype=np.uint8),
        )
        log("witness cached")

    digest = wit_digest  # same circuit, one digest pass
    n_wires_expect, domain_expect = cs.num_wires, domain_size_for(cs)
    n_constraints = cs.num_constraints
    dpk = vk = None
    if os.path.exists(key_path):
        try:
            t = time.perf_counter()
            dpk, vk = load_dpk(key_path, digest=digest)
            timing["load_key_s"] = round(time.perf_counter() - t, 1)
            if dpk.n_wires != n_wires_expect or (1 << dpk.log_m) != domain_expect:
                log("cached key does not match the rebuilt circuit; re-running setup")
                dpk = vk = None
        except KeyCacheSchemaError as exc:
            log(f"stale key cache: {exc}")
    if dpk is not None:
        # Release the ~8 GB circuit object (wire labels, hook closures)
        # before the prove: holding it costs ~25% prove throughput in
        # cache/memory pressure on this host.
        import gc

        cs = lay = None
        gc.collect()
    if dpk is None:
        t = time.perf_counter()
        log("full-size device setup (native fixed-base batches; expect ~15 min) ...")
        from zkp2p_tpu.prover.setup_device import setup_device

        dpk, vk = setup_device(cs, seed="bench")
        timing["setup_s"] = round(time.perf_counter() - t, 1)
        log(f"setup took {timing['setup_s']}s; caching")
        save_dpk(key_path, dpk, vk, digest=digest)

    t = time.perf_counter()
    log("native prove ...")
    proof = prove_native(dpk, w, r=123456789, s=987654321)
    timing["prove_native_s"] = round(time.perf_counter() - t, 1)
    log(f"native prove took {timing['prove_native_s']}s; verifying")

    t = time.perf_counter()
    assert verify(vk, proof, pubs), "full-size proof failed pairing verification"
    timing["verify_s"] = round(time.perf_counter() - t, 1)
    timing["constraints"] = n_constraints
    timing["wires"] = n_wires_expect
    timing["reference_rapidsnark_s_48core"] = 9.2
    timing["host"] = "1 CPU core"

    with open(os.path.join(OUT, "proof.json"), "w") as f:
        json.dump(proof_to_json(proof), f, indent=1)
    with open(os.path.join(OUT, "public.json"), "w") as f:
        json.dump(public_to_json(pubs), f, indent=1)
    with open(os.path.join(OUT, "timing.json"), "w") as f:
        json.dump(timing, f, indent=1)
    log(f"DONE: verified full-size proof written to {OUT}")
    log(json.dumps(timing))


if __name__ == "__main__":
    main()
