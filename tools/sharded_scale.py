"""Sharded prove at representative scale.

The green driver dryrun proves sharded-dataflow bit-exactness on a
319-constraint demo; this closes the scale gap: `prove_tpu_sharded` on
the 8-virtual-device CPU mesh over a >=27k-constraint circuit (two
SHA-256 blocks — the venmo circuit's dominant gadget family), diffed
byte-for-byte against the native prover (itself oracle-pinned to
`prove_host`) and pairing-verified.

Run: JAX_PLATFORMS=cpu python tools/sharded_scale.py  (the script
re-asserts the platform itself; ~10-20 min compile-dominated COLD —
warm runs load every executable from the persistent compile cache
(JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache) in seconds, and the log
carries a per-stage cache HIT/MISS line so the split is auditable).
"""

import hashlib
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

T0 = time.time()


def stage(msg: str) -> None:
    print(f"[sharded-scale +{time.time() - T0:7.1f}s] {msg}", flush=True)


def main() -> None:
    from zkp2p_tpu.utils.jaxcfg import cache_dir, enable_cache

    # persistent cache with a zero compile-time floor: every executable
    # of this run round-trips, so the NEXT session's run is warm (the
    # per-session 10-20 min compile stall was the whole wall clock) —
    # `make warm-cache` shares the same directory
    enable_cache(min_compile_s=0.0)
    import jax
    import numpy as np

    from zkp2p_tpu.utils.audit import compile_totals, install_compile_listener

    install_compile_listener()
    cdir = cache_dir()

    def _cache_entries() -> int:
        n = 0
        for _root, _dirs, fns in os.walk(cdir):
            n += sum(1 for fn in fns if fn.endswith("-cache"))
        return n

    def _compiles() -> float:
        return compile_totals()[0]

    _cache_state = {"entries": _cache_entries(), "compiles": _compiles()}
    stage(f"persistent cache at {cdir}: {_cache_state['entries']} entries")

    def cache_report(label: str) -> None:
        # per-stage hit/miss accounting: a fresh XLA compile that left a
        # new cache entry = MISS (now warmed); a compile-free stage with
        # executables dispatched = HIT (loaded from cache)
        entries, compiles = _cache_entries(), _compiles()
        de = entries - _cache_state["entries"]
        dc = compiles - _cache_state["compiles"]
        _cache_state.update(entries=entries, compiles=compiles)
        verdict = "MISS (cold compile, cached for next run)" if dc else "HIT (warm)"
        stage(f"cache[{label}]: {verdict} — {dc:.0f} compiles, {de:+d} entries")

    from jax.sharding import Mesh

    from zkp2p_tpu.prover.groth16_tpu import device_pk, prove_tpu_sharded
    from zkp2p_tpu.prover.native_prove import prove_native
    from zkp2p_tpu.snark.groth16 import setup, verify

    devs = jax.devices()
    assert len(devs) >= 8 and devs[0].platform == "cpu", devs
    mesh = Mesh(np.array(devs[:8]).reshape(8), ("shard",))
    stage(f"8-device virtual mesh up ({devs[0].platform})")

    # two-block fixed SHA-256 over 128 padded bytes: the flagship's
    # dominant gadget at a domain (2^16) 128x the dryrun's
    msg = b"zkp2p sharded-scale witness " + bytes(range(64))

    def sha_pad(m: bytes, max_len: int) -> bytes:
        # MD padding to max_len bytes (shaHash.ts sha256Pad semantics)
        length = len(m) * 8
        padded = bytearray(m) + b"\x80"
        while (len(padded) + 8) % 64:
            padded.append(0)
        padded += length.to_bytes(8, "big")
        assert len(padded) <= max_len and max_len % 64 == 0
        return bytes(padded) + b"\x00" * (max_len - len(padded))

    padded = sha_pad(msg, 128)
    # the registry's sha2b shape (ONE definition; its audit gate covers
    # this run's circuit too — zkp2p-tpu lint --circuits)
    from zkp2p_tpu.models.registry import build_sha2b

    cs, out = build_sha2b()
    wires = sorted(cs.input_wires)
    seed = {wr: padded[i] for i, wr in enumerate(wires)}
    stage(f"circuit: {cs.num_constraints} constraints, {cs.num_wires} wires")
    assert cs.num_constraints >= 27_000, "scale target not met"

    w = cs.witness([], seed)
    cs.check_witness(w)
    digest_bits = [w[b] for b in out]
    # circuit emits 8 words x 32 LSB-first bits of the big-endian words
    want_bits = []
    digest = hashlib.sha256(msg).digest()
    for wi in range(8):
        word = int.from_bytes(digest[4 * wi : 4 * wi + 4], "big")
        want_bits.extend((word >> i) & 1 for i in range(32))
    assert digest_bits == want_bits, "SHA circuit output mismatch vs hashlib"
    stage("witness checked; circuit digest == hashlib")

    pk, vk = setup(cs, seed="sharded-scale")
    dpk = device_pk(pk, cs)
    stage("setup + device key")

    r, s = 123456789, 987654321
    oracle = prove_native(dpk, w, r=r, s=s)  # byte-pinned to prove_host
    stage("native oracle proof done")

    def traced_stage(msg: str) -> None:
        # compile deltas attribute to the stage that just FINISHED (the
        # one the progress message names)
        cache_report(msg.split()[0])
        stage(msg)

    t0 = time.perf_counter()
    proof = prove_tpu_sharded(dpk, w, mesh, r=r, s=s, unified=True, progress=traced_stage)
    stage(f"prove_tpu_sharded done in {time.perf_counter() - t0:.1f}s (incl. compile)")
    cache_report("assemble")
    assert proof == oracle, "sharded proof != native/host oracle proof"
    assert verify(vk, proof, [])
    # Observability flush, wired the way bench.py's native tier is: the
    # per-stage records (sharded/h_evals, sharded/msm_*) go to the
    # configured JSONL sink (stderr when unset) with run_id/pid and the
    # knob/gate manifest, so MULTICHIP runs are aggregatable and
    # `trace_report --diff RID_A RID_B` works across dryrun rounds.
    from zkp2p_tpu.utils.config import load_config
    from zkp2p_tpu.utils.metrics import run_id
    from zkp2p_tpu.utils.trace import dump_trace

    sink = load_config().metrics_sink
    dump_trace(sink or None)
    if sink:
        stage(f"stage trace appended to {sink} (run_id {run_id()})")
    stage(
        f"SHARDED == ORACLE and pairing-verified at {cs.num_constraints} constraints "
        f"on the 8-device mesh — scale evidence recorded (run_id {run_id()})"
    )


if __name__ == "__main__":
    sys.exit(main())
