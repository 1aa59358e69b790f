"""The one general load generator: it reads a traffic file's parameters and
drives requests into a spool, from one thread of the benchmark's process.

closed  `clients` callers; each sends its next request `think_s` after the
        terminal artifact of its previous one appears.  All send at window
        start.  Latency runs from the instant the request entered the spool.
open    arrivals on a schedule drawn from the seed (poisson / uniform /
        burst at `rate_per_s`), sent whether or not earlier ones finished.
        Latency runs from when a request was DUE, and how late the
        generator ran is reported.

Every seed gives the same number of requests and the same set of gaps
between them; the seed changes payload contents and, for poisson, the
order of the gaps.
"""

from __future__ import annotations

import json
import os
import random
import time
from typing import Callable, Dict, List, Optional

TERMINAL = (".proof.json", ".error.json")
POLL_S = 0.005  # artifact poll: 8 outstanding requests cost 16 stats a tick


def write_request(spool: str, rid: str, payload: Dict) -> float:
    """Atomic drop (tmp + rename), tools/loadgen.py's `_write_request`;
    returns the instant the request became visible to the service."""
    path = os.path.join(spool, rid + ".req.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)
    return time.time()


def arrival_offsets(traffic: Dict, seed: int, seconds: float) -> List[float]:
    """Open loop: seconds after window start at which each request is due."""
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    if traffic["arrival"] == "uniform":
        return [k / rate for k in range(n)]
    if traffic["arrival"] == "burst":  # `burst_size` together, bursts evenly spaced to keep the mean rate
        size = int(traffic["burst_size"])
        return [(k // size) * size / rate for k in range(n)]
    # poisson: ONE set of exponential gaps whatever the seed, scaled to fill the
    # window; the seed only reorders them, so every seed offers the same load
    gaps = [random.Random(f"gaps-{n}-{k}").expovariate(rate) for k in range(n)]
    scale = seconds / sum(gaps)
    random.Random(f"arrivals-{seed}").shuffle(gaps)
    out, t = [], 0.0
    for g in gaps:
        out.append(t)
        t += g * scale
    return out


def terminal_of(spool: str, rid: str) -> Optional[Dict]:
    """{"state", "t_terminal"} once `rid` has a terminal artifact."""
    base = os.path.join(spool, rid)
    for suffix in TERMINAL:
        try:
            t = os.path.getmtime(base + suffix)
        except OSError:
            continue
        if suffix == ".proof.json":
            return {"state": "done", "t_terminal": t}
        try:
            with open(base + suffix) as f:
                state = json.load(f).get("state", "error")
        except (OSError, ValueError):
            state = "error"
        return {"state": state, "t_terminal": t}
    return None


def run_window(spool: str, traffic: Dict, make_payload: Callable[[int], Dict], seed: int,
               seconds: float, on_tick: Callable[[float, int], None] = lambda now, done: None) -> Dict:
    """Drive the window; returns {"requests": [...], "t_first", "t_end",
    "lateness_due", "lateness_sent"}.  `on_tick(now, completed)` is called
    every poll (the traced run starts and stops its slice from it)."""
    os.makedirs(spool, exist_ok=True)
    deadline_s = traffic["deadline_s"]
    requests: List[Dict] = []

    def submit(t_due: Optional[float]) -> Dict:
        i = len(requests)
        payload = dict(make_payload(i))
        if deadline_s:
            payload["deadline_s"] = deadline_s
        rid = f"s{seed}-{i:06d}"
        t_sent = write_request(spool, rid, payload)
        req = {"rid": rid, "payload": payload, "t_sent": t_sent,
               "t_ref": t_sent if t_due is None else t_due, "t_due": t_due}
        requests.append(req)
        return req

    open_reqs: List[Dict] = []
    completed = 0
    t0 = time.time()
    if traffic["loop"] == "closed":
        ready_at = [t0] * int(traffic["clients"])   # per client: when it may send again
        holding: List[Optional[Dict]] = [None] * len(ready_at)
        due: List[float] = []
    else:
        due = [t0 + off for off in arrival_offsets(traffic, seed, seconds)]
        next_due = 0
    t_first = None
    t_end = t0 + seconds
    while True:
        now = time.time()
        if t_first is not None and now >= t_end:
            break
        if traffic["loop"] == "closed":
            for c, req in enumerate(holding):
                if req is not None and "t_terminal" in req:
                    holding[c] = None
                    ready_at[c] = req["t_terminal"] + float(traffic["think_s"])
                if holding[c] is None and now >= ready_at[c]:
                    holding[c] = submit(None)
                    open_reqs.append(holding[c])
        else:
            while next_due < len(due) and now >= due[next_due]:
                open_reqs.append(submit(due[next_due]))
                next_due += 1
        if t_first is None and requests:
            t_first = requests[0]["t_sent"]
            t_end = t_first + seconds
        still: List[Dict] = []
        for req in open_reqs:
            term = terminal_of(spool, req["rid"])
            if term is None:
                still.append(req)
            else:
                req.update(term)
                completed += 1
        open_reqs = still
        on_tick(now, completed)
        time.sleep(POLL_S)
    return {"requests": requests, "t_first": t_first, "t_end": t_end,
            "lateness_due": [r["t_due"] for r in requests if r["t_due"] is not None],
            "lateness_sent": [r["t_sent"] for r in requests if r["t_due"] is not None]}


def serve_once(spool: str, payloads: List[Dict], tag: str, timeout_s: float,
               alive: Callable[[], bool]) -> List[Optional[str]]:
    """Set-up's one batch through the served path: drop `payloads` together,
    wait until each has a terminal artifact, return their states (None for
    one that had none after `timeout_s`, or when `alive()` turned false)."""
    rids = [f"{tag}-{k:03d}" for k in range(len(payloads))]
    for rid, payload in zip(rids, payloads):
        write_request(spool, rid, payload)
    t_give_up = time.time() + timeout_s
    while True:
        terms = [terminal_of(spool, rid) for rid in rids]
        if all(terms) or time.time() > t_give_up or not alive():
            return [t["state"] if t else None for t in terms]
        time.sleep(0.05)


def collect_late_terminals(spool: str, requests: List[Dict]) -> None:
    """After the service has drained: fill in the requests it had claimed
    when the window closed and finished since."""
    for req in requests:
        if "t_terminal" not in req:
            term = terminal_of(spool, req["rid"])
            if term is not None:
                req.update(term)
