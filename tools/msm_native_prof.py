#!/usr/bin/env python
"""Phase profile of the native G1 Pippenger tier on a real prove.

Runs prove_native on the cached bench-shape key/witness with
ZKP2P_MSM_PROF=1 and prints the csrc counters after each stage:
fill (incl. apply), the batched 8-wide apply alone, and the serial
suffix reduction — the measurement behind any suffix-vectorization
decision (no perf(1) on the driver box; see zkp2p_msm_prof_dump).

Run: JAX_PLATFORMS=cpu python tools/msm_native_prof.py
"""

import ctypes
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["ZKP2P_MSM_PROF"] = "1"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
import numpy as np  # noqa: E402


def main():
    from zkp2p_tpu.inputs.email import generate_inputs, make_test_key, make_venmo_email
    from zkp2p_tpu.models.venmo import VenmoParams, build_venmo_circuit
    from zkp2p_tpu.native.lib import get_lib
    from zkp2p_tpu.prover.keycache import load_dpk
    from zkp2p_tpu.prover.native_prove import prove_native
    from zkp2p_tpu.snark.groth16 import verify

    lib = get_lib()
    assert lib is not None, "native library unavailable"
    from zkp2p_tpu.utils.config import load_config

    cfg = load_config()
    print(
        f"native msm mode: glv={'on' if cfg.msm_glv else 'off'} "
        f"batch_affine={'on' if cfg.msm_batch_affine else 'off'}",
        flush=True,
    )
    nthreads = cfg.native_threads
    if nthreads and nthreads > 1:
        print(
            f"WARNING: ZKP2P_NATIVE_THREADS={nthreads} — fill counters sum "
            "across workers; phase ratios are only valid single-threaded",
            flush=True,
        )
    dump = lib.zkp2p_msm_prof_dump
    dump.argtypes = [ctypes.POINTER(ctypes.c_longlong)]

    def read_prof(tag):
        buf = (ctypes.c_longlong * 4)()
        dump(buf)
        fill, apply_, suffix, bailfill = (x / 1e6 for x in buf)
        sched = fill - apply_
        print(
            f"[{tag}] fill={fill:8.1f} ms (apply={apply_:8.1f}, sched={sched:8.1f})"
            f"  bailfill={bailfill:8.1f}  suffix={suffix:8.1f} ms",
            flush=True,
        )
        return fill, apply_, suffix

    params = VenmoParams(max_header_bytes=256, max_body_bytes=192)
    print("building bench-shape circuit ...", flush=True)
    cs, lay = build_venmo_circuit(params)
    key = make_test_key(1)
    email = make_venmo_email(key, raw_id="1234567891234567891"[:19], amount="30", body_filler=40)
    inputs = generate_inputs(email, key.n, order_id=1, claim_id=0, params=params, layout=lay)
    w = cs.witness(inputs.public_signals, inputs.seed)

    path = os.path.join(ROOT, ".bench_cache", "venmo_256_192.npz")
    dpk, vk = load_dpk(path)
    print("warm prove ...", flush=True)
    prove_native(dpk, w)
    read_prof("warm (discard)")
    t0 = time.perf_counter()
    proof = prove_native(dpk, w)
    total = time.perf_counter() - t0
    fill, apply_, suffix = read_prof("steady")
    assert verify(vk, proof, inputs.public_signals)
    print(f"prove total {total:.2f}s; G1 phases sum {(fill + suffix) / 1e3:.2f}s", flush=True)


if __name__ == "__main__":
    main()
