"""Several seeds of one cell in ONE process on the chip, after one set-up:

    python3 -m benchmarks.tests.chip_seeds --workload <cell> --seconds <s> \\
        --sound 11,12,13 --control 21,22,23

A sound seed must read `correct: true`.  A control seed runs the same
window with the timed path broken underneath — the device prover's answer
altered where it is produced: one limb of the second proof of every served
batch flipped (not the first: the service's own sample verify checks that
one; not the last: under an open loop a batch short of its size proves
with its last witness repeated, and what the padding proved is dropped) —
and must read `correct: false`.  The arithmetic is exact, so there is
no lower precision to fall into: the control breaks the configuration's
guarantee "every proof verifies under the key's vk".  The benchmark's own
runs never run this.  Needs a TPU, like the command.
"""

import argparse
import dataclasses
import json
import os
import sys
import time


def main(argv=None) -> int:
    t_process = time.time()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sound", default="")
    ap.add_argument("--control", default="")
    args = ap.parse_args(argv)
    root = os.getcwd()

    from benchmarks.harness.cell import load_cell
    from benchmarks.harness.device import Chip
    from benchmarks.run import Bench

    bench = Bench(load_cell(root, args.workload), Chip(), root)
    from zkp2p_tpu.prover import groth16_tpu

    real = groth16_tpu.prove_tpu_batch
    state = {"tamper": False}

    def prove(dpk, witnesses, rs=None, ss=None):
        proofs = real(dpk, witnesses, rs=rs, ss=ss)
        if state["tamper"] and rs is None and len(proofs) > 1:
            proofs[1] = dataclasses.replace(proofs[1], c=(proofs[1].c[0] ^ (1 << 64), proofs[1].c[1]))
        return proofs

    groth16_tpu.prove_tpu_batch = prove
    faults = []
    for kind, seeds in (("sound", args.sound), ("control", args.control)):
        for seed in [int(s) for s in seeds.split(",") if s]:
            state["tamper"] = False
            warm = bench.warm_up(seed)
            state["tamper"] = kind == "control"
            res = bench.measure(seed, args.seconds, 0, t_process, warm)  # only the first seed's setup_s is a set-up
            t_process = time.time()
            print(json.dumps({"kind": kind, "seed": seed, "correct": res["correct"], "attempted": res["attempted"],
                              "failed": res["failed"], "metrics": res["metrics"]}), flush=True)
            if res["correct"] != (kind == "sound"):
                faults.append((kind, seed))
    print(json.dumps({"seeds_fault": faults}), flush=True)
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
