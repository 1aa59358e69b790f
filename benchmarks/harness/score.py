"""The arithmetic of the end-to-end metrics, on plain timestamps.

Copied in spirit from tools/loadgen.py (latency from one machine's clock:
terminal artifact mtime minus the instant the request entered the spool)
and utils/slo.py (the percentile rule); both originals stay where they
are and are listed in PERF.md for a later PR.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """utils/slo.py's rule: the sample at round(q * (n - 1)) of the sorted
    values — an observed latency, never an interpolation."""
    if not values:
        raise ValueError("percentile of no samples")
    s = sorted(values)
    return s[max(0, min(len(s) - 1, int(round(q * (len(s) - 1)))))]


def proofs_per_s(t_first_submit: float, done_times: Sequence[float]) -> Optional[float]:
    """Valid proofs of the requests the service took on in the window, over
    the seconds from the first submission to the LAST of their artifacts
    (the drain included: what was claimed when the window closed is
    finished and counted).  Not over the window's length: at one or two
    batches to a window that quotient steps when a change lets one more
    batch finish; and a stall after a completion shows, because the work
    claimed behind it ends late."""
    if not done_times:
        return None
    span = max(done_times) - t_first_submit
    return len(done_times) / span if span > 0 else None


def lateness(due: Sequence[float], sent: Sequence[float]) -> Dict[str, float]:
    """How late the generator ran: sent minus due, per request."""
    late = [max(0.0, s - d) for d, s in zip(due, sent)]
    if not late:
        return {"n": 0, "mean_s": 0.0, "p95_s": 0.0, "max_s": 0.0}
    return {"n": len(late), "mean_s": sum(late) / len(late),
            "p95_s": percentile(late, 0.95), "max_s": max(late)}


DEADLINE_STATE = "error-deadline-exceeded"
CLOCK_SLACK_S = 0.05  # the service's deadline runs from the request file's mtime, ours from the instant after the rename


def refused_at_deadline(r: Dict) -> Optional[bool]:
    """For a request the service refused as past its deadline: True when its
    own `deadline_s` really had passed by then (a miss, counted as failed
    and nothing else), False when it had not (a wrong answer).  None for
    every other request, and for a refusal of a request that carried no
    deadline."""
    deadline_s = (r.get("payload") or {}).get("deadline_s")
    if r.get("state") != DEADLINE_STATE or not deadline_s:
        return None
    return r["t_terminal"] >= r["t_sent"] + float(deadline_s) - CLOCK_SLACK_S


def score_window(requests: List[Dict], t_first: float) -> Dict:
    """requests: one dict per request submitted in the window, in the order
    sent, with `t_ref` (closed loop: the instant it entered the spool; open
    loop: when it was DUE), and, once terminal, `t_terminal`, `state`
    ("done" or an error state) and `valid` (its proof passed the pairing
    and carries its own request's public signals; None until checked).

    Read after the drain.  The service stops claiming when the window
    closes and finishes what it had claimed, so by then a request is
    terminal or was never claimed.  Every terminal request is attempted,
    and fails unless done and valid.  A request left without a terminal
    artifact counts nowhere if it was sent after every request that has
    one (the window closed before the service came to it), and fails if a
    later one was served: the service passed over it.

    A request that carried `deadline_s` and was refused as past it is
    attempted and failed like any other error, and counted apart: at or
    after its deadline (`refused_at_deadline`: the service honestly
    missed), or before it (`refused_before_deadline`).  A proof that comes
    out `done` after its deadline is a late proof: valid, scored, in the
    tail."""
    terminal = [r for r in requests if r.get("t_terminal") is not None]
    good = [r for r in terminal if r["state"] == "done" and r.get("valid")]
    last_served = max((i for i, r in enumerate(requests) if r.get("t_terminal") is not None), default=-1)
    passed_over = [r for i, r in enumerate(requests) if r.get("t_terminal") is None and i < last_served]
    refusals = [refused_at_deadline(r) for r in terminal]
    lat = [r["t_terminal"] - r["t_ref"] for r in good]
    out = {
        "submitted": len(requests), "attempted": len(terminal) + len(passed_over),
        "failed": len(terminal) - len(good) + len(passed_over), "passed_over": len(passed_over),
        "refused_at_deadline": refusals.count(True), "refused_before_deadline": refusals.count(False),
        "unclaimed_at_end": len(requests) - len(terminal) - len(passed_over), "latency_samples": len(lat),
        "proofs_per_s": proofs_per_s(t_first, [r["t_terminal"] for r in good]),
    }
    if lat:
        out["latency_p50_s"] = percentile(lat, 0.50)
        out["latency_p90_s"] = percentile(lat, 0.90)
    return out
