"""Open arrivals at several fixed rates on the chip, one window each, in ONE
process after one set-up: how a cell's knee is found, once, when an open
cell is defined or its knee has moved:

    python3 -m benchmarks.tests.knee_sweep --workload sha2b.bulk --seconds 50 \\
        --rates 1.6,2.0,2.3,2.5,2.7,3.1@burst8 [--repeat 1] [--deadline-s 10] [--seed 4000000001]

The cell gives the configuration and the service's knobs (`batch_size`,
`poll_s`, `max_wait_s`); its loop is replaced by an open one at each rate
in turn (`rate`, `rate@uniform` or `rate@burst<size>`; Poisson unless
said), `--repeat` windows a rate, each on a seed of its own.  A line a
window: what was offered, what came out, the latencies of the window's two
halves (by when a request was due) and what the service had not claimed
when the window closed; the run's sink is kept beside it, because the
sweeps' sizes say more than the halves (PERF.md, PR 40: what is unclaimed
at the close is what arrived during the last sweep, not a backlog).  The
benchmark's own runs never run this: a cell's rate is fixed in its traffic
file.  Needs a TPU, like the command.
"""

import argparse
import json
import os
import re
import shutil
import sys
import time


def halves(requests, t_first: float, seconds: float):
    """(p50, p90, n) of the good requests due in each half of the window."""
    from benchmarks.harness.score import percentile

    out = []
    for lo, hi in ((float("-inf"), seconds / 2), (seconds / 2, float("inf"))):  # the first is due a moment before it is sent
        lat = [r["t_terminal"] - r["t_ref"] for r in requests
               if r.get("state") == "done" and r.get("valid") and lo <= r["t_ref"] - t_first < hi]
        out.append({"n": len(lat), "p50_s": percentile(lat, 0.5) if lat else None,
                    "p90_s": percentile(lat, 0.9) if lat else None})
    return out


def main(argv=None) -> int:
    t_process = time.time()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--repeat", type=int, default=1)
    ap.add_argument("--deadline-s", type=float, default=None)
    ap.add_argument("--seed", type=int, default=4000000001)
    args = ap.parse_args(argv)
    root = os.getcwd()

    from benchmarks.harness import score
    from benchmarks.harness.cell import load_cell
    from benchmarks.harness.device import Chip
    from benchmarks.run import Bench

    bench = Bench(load_cell(root, args.workload), Chip(), root)
    seen = {}
    real = score.score_window

    def keep(requests, t_first):  # what the window's score was taken from, for the halves
        seen.update(requests=requests, t_first=t_first, score=real(requests, t_first))
        return seen["score"]

    score.score_window = keep
    out_dir = os.path.join(root, "chiprun_out", "knee")
    rows = []
    specs = [re.fullmatch(r"([0-9.]+)(?:@(poisson|uniform|burst)([0-9]*))?", tok).groups()
             for tok in args.rates.split(",") if tok for _ in range(args.repeat)]
    for k, (rate, arrival, burst) in enumerate(specs):
        bench.traffic = dict(bench.traffic, loop="open", arrival=arrival or "poisson", burst_size=int(burst or 1),
                             rate_per_s=float(rate), deadline_s=args.deadline_s)
        seed = args.seed + k
        res = bench.measure(seed, args.seconds, 0, t_process, bench.warm_up(seed))
        t_process = time.time()
        sc = seen["score"]
        first, second = halves(seen["requests"], seen["t_first"], args.seconds)
        row = dict({"rate_per_s": float(rate), "arrival": (arrival or "poisson") + (burst or ""), "seed": seed, "correct": res["correct"]},
                   **{key: sc.get(key) for key in ("submitted", "attempted", "failed", "refused_at_deadline", "unclaimed_at_end",
                                                   "passed_over", "proofs_per_s", "latency_p50_s", "latency_p90_s")})
        row.update(latency_max_s=max((r["t_terminal"] - r["t_ref"] for r in seen["requests"] if r.get("valid")), default=None),
                   first_half=first, second_half=second)
        rows.append(row)
        print(json.dumps({"knee_sweep": row}), flush=True)
        kept = os.path.join(out_dir, f"s{seed}")  # the spans say where a latency went
        os.makedirs(kept, exist_ok=True)
        for name in ("spool.metrics.jsonl", "result.json"):
            shutil.copy(os.path.join(root, ".bench_runs", f"{bench.cell.name}-s{seed}-t0", name), kept)
    with open(os.path.join(out_dir, f"{bench.cell.name}-s{args.seed}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
