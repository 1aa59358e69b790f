"""Cut a small fixture out of a traced run's profile:

    python3 -m benchmarks.tests.cut_trace_fixture .bench_runs/<run> out.json [pad_ms]

It keeps the device events around the LONGEST idle gap of the first device
(pad_ms on either side), the host spans that touch that stretch, and the
anchor, so that trace_reduce's arithmetic can be checked against real
operation names and a real batch boundary without committing a whole trace.
"""

import json
import os
import sys

from benchmarks.harness import trace_reduce


def main(run_dir: str, out: str, pad_ms: float = 15.0) -> None:
    with open(os.path.join(run_dir, "trace_meta.json")) as f:
        meta = json.load(f)
    events = trace_reduce.load_events(meta["xplane"])
    ops = next(o for o in ([ev for name in trace_reduce.op_lines(lines) for ev in lines[name]]
                           for _, lines in sorted(events["device"].items())) if o)  # the first device that ran anything
    busy = trace_reduce.merge([(ev[1], ev[1] + ev[2]) for ev in ops])
    gaps = [(busy[i + 1][0] - busy[i][1], busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
    _, g0, g1 = max(gaps)
    lo, hi = g0 - pad_ms * 1e6, g1 + pad_ms * 1e6
    cut = trace_reduce.slice_events(events, lo, hi)
    offset = cut["anchor"]["wall_ns"] - cut["anchor"]["start_ns"]
    spans = [sp for sp in meta["host_spans"]
             if sp["t0_wall_s"] * 1e9 - offset < hi and sp["t0_wall_s"] * 1e9 + sp["ms"] * 1e6 - offset > lo]
    fixture = {"events": cut, "host_spans": spans, "wall_start_ns": int(lo + offset), "wall_stop_ns": int(hi + offset)}
    fixture["expected"] = trace_reduce.reduce_events(cut, spans, fixture["wall_start_ns"], fixture["wall_stop_ns"])
    with open(out, "w") as f:
        json.dump(fixture, f)
    print(json.dumps(fixture["expected"]))  # look at it: the test holds the reduction to what is written here


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], float(sys.argv[3]) if len(sys.argv) > 3 else 15.0)
