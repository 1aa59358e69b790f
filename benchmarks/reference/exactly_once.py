"""The plain reference for a DEPLOYMENT's guarantee, as `groth16_verify.py`
is for a proof's: a spool served by several replicas, checked from its
files alone.  Imports nothing of the program.

    python3 -m benchmarks.reference.exactly_once <spool> [<sink.jsonl>]

After the service has drained:
  - a request file has at most one terminal artifact (`.proof.json` or
    `.error.json`, never both), a proof has its public signals beside it,
    and no claim file is left behind;
  - every terminal artifact has exactly one terminal record in the sink
    and every terminal record its artifact (a request the service never
    claimed has neither, and counts nowhere);
  - no request id appears in two replicas' records;
  - the replicas' counts sum to the total.
Every number it returns has the limit 0; `served` says who served what.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, List, Optional

TERMINAL_PREFIXES = ("done", "error-")  # `deferred` is a claim given back, not an end


def _records(sink: str) -> List[Dict]:
    out = []
    try:
        with open(sink) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # a torn line is not a record
                if rec.get("type") == "request" and str(rec.get("state", "")).startswith(TERMINAL_PREFIXES):
                    out.append(rec)
    except OSError:
        pass
    return out


def check(spool: str, sink: Optional[str] = None) -> Dict:
    sink = sink or spool.rstrip("/") + ".metrics.jsonl"
    names = os.listdir(spool)
    rids = sorted(n[: -len(".req.json")] for n in names if n.endswith(".req.json"))
    have = set(names)
    both = no_public = 0
    ended = set()
    for rid in rids:
        proof, error = rid + ".proof.json" in have, rid + ".error.json" in have
        both += proof and error
        no_public += proof and rid + ".public.json" not in have
        if proof or error:
            ended.add(rid)
    by_rid: Dict[str, List[Dict]] = {}
    for rec in _records(sink):
        by_rid.setdefault(rec["request_id"], []).append(rec)
    served: Dict[str, int] = {}
    for recs in by_rid.values():
        for who in {str(r.get("replica", "solo")) for r in recs}:
            served[who] = served.get(who, 0) + 1
    numbers = {
        "requests_with_two_terminal_artifacts": both,
        "proofs_without_public_signals": no_public,
        "claims_left_behind": sum(1 for n in names if n.endswith(".claim")),
        "terminal_artifacts_without_one_record": sum(1 for rid in ended if len(by_rid.get(rid, ())) != 1),
        "terminal_records_without_artifact": sum(1 for rid in by_rid if rid not in ended),
        "request_ids_in_two_replicas_records": sum(
            1 for recs in by_rid.values() if len({r.get("replica") for r in recs}) > 1),
        "replica_counts_short_of_total": abs(sum(served.values()) - len(by_rid)),
    }
    return {"numbers": numbers, "served": dict(sorted(served.items())), "requests": len(rids), "ended": len(ended),
            "ok": all(v == 0 for v in numbers.values())}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not 1 <= len(argv) <= 2:
        print(__doc__, file=sys.stderr)
        return 2
    res = check(*argv)
    print(json.dumps(res))
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
