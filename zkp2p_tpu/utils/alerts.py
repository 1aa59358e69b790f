"""Alert engine for the fleet observability plane.

The supervisor aggregates signals nothing per-worker can see — fleet
burn rate over the MERGED SLO windows, spool backlog trend, restart
storms, breaker parks, lingering governor degrades, heartbeat gaps —
and something has to turn those time-series into a bounded set of
actionable facts.  This module is that something: a small rule
evaluator with explicit HYSTERESIS, so a signal flapping across its
threshold raises ONE alert (and later ONE clear), not a stream of
page-worthy transitions every evaluation tick.

State machine per rule (docs/OBSERVABILITY.md §fleet plane):

  ok --cond true--> pending --held for_s--> FIRING --cond false
     <--cond false--          (counter+log)    held clear_s--> ok

  * `for_s`   how long the condition must hold before firing — a
    single noisy evaluation never pages;
  * `clear_s` how long the condition must be CONTINUOUSLY false before
    a firing alert clears — the flap damper; a re-trip inside clear_s
    keeps the ORIGINAL alert firing (same `since`, no new counter inc).
  * a rule whose signal is absent this tick (condition returns None)
    holds its current state — missing data is not evidence either way.

Transitions land in four places at once: the returned transition list
(the caller logs them), `zkp2p_fleet_alerts_total{rule}` (fires only),
the engine's `active()`/`state()` views (fleet status.json + the
`/status` payload), and the caller's log lines.  Evaluation is pure
over (signals, now) — tests drive synthetic time-series with an
injected clock, and the supervisor drives wall-clock.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional


@dataclass
class Rule:
    """One alert rule: `cond(signals)` returns True (condition met),
    False (not met), or None (no data this tick — hold state).
    `detail(signals)` renders the human one-liner stamped on the alert
    at fire time (threshold + observed value)."""

    name: str
    cond: Callable[[Dict], Optional[bool]]
    for_s: float = 0.0
    clear_s: float = 30.0
    detail: Optional[Callable[[Dict], str]] = None


@dataclass
class _RuleState:
    firing: bool = False
    since: float = 0.0           # fire time while firing
    pending_since: Optional[float] = None
    clear_since: Optional[float] = None
    fired_count: int = 0
    last_detail: str = ""


class TrendTracker:
    """Rolling (t, value) history for trend rules (backlog growth):
    `update()` per evaluation, `growing(window_s)` answers "did the
    value rise by >= min_delta across the last window_s, with enough
    history to judge?".  Insufficient history returns None (hold state)
    rather than False — a freshly started supervisor must not CLEAR a
    real backlog alert just because it forgot the past."""

    def __init__(self, keep_s: float = 600.0):
        self.keep_s = keep_s
        self._hist: deque = deque()  # (t, value), oldest first

    def update(self, now: float, value: float) -> None:
        self._hist.append((now, float(value)))
        edge = now - self.keep_s
        while self._hist and self._hist[0][0] < edge:
            self._hist.popleft()

    def growing(self, window_s: float, now: float, min_delta: float = 1.0) -> Optional[bool]:
        if not self._hist:
            return None
        base = None
        for t, v in self._hist:
            if t <= now - window_s:
                base = v
            else:
                break
        if base is None:
            # history does not yet span the window: only a confident
            # False (value at/near zero) is safe to report
            return False if self._hist[-1][1] <= 0 else None
        cur = self._hist[-1][1]
        return cur > 0 and (cur - base) >= min_delta

    def delta(self, window_s: float, now: float) -> Optional[float]:
        """value_now − value_at(now − window_s) for cumulative signals
        (restart counts).  History not yet spanning the window uses the
        oldest sample as the base — an under-estimate, never an
        invented spike.  No history at all returns None."""
        if not self._hist:
            return None
        base = self._hist[0][1]
        for t, v in self._hist:
            if t <= now - window_s:
                base = v
            else:
                break
        return self._hist[-1][1] - base


class AlertEngine:
    def __init__(
        self,
        rules: List[Rule],
        registry=None,
        log: Optional[Callable[[str], None]] = None,
        clock=time.time,
    ):
        self.rules = list(rules)
        self._states: Dict[str, _RuleState] = {r.name: _RuleState() for r in self.rules}
        self._registry = registry
        self._log = log
        self._clock = clock

    def _counter(self, rule: str):
        reg = self._registry
        if reg is None:
            from .metrics import REGISTRY as reg  # noqa: N811 — late default
        return reg.counter("zkp2p_fleet_alerts_total", {"rule": rule})

    def evaluate(self, signals: Dict, now: Optional[float] = None) -> List[Dict]:
        """One evaluation tick; returns the TRANSITIONS (fired/cleared)
        this tick — steady firing/ok states return nothing."""
        t = self._clock() if now is None else now
        transitions: List[Dict] = []
        for rule in self.rules:
            st = self._states[rule.name]
            try:
                cond = rule.cond(signals)
            except Exception:  # noqa: BLE001 — a broken rule must not kill the tick
                cond = None
            if cond is None:
                continue
            if cond:
                st.clear_since = None
                if st.firing:
                    continue
                if st.pending_since is None:
                    st.pending_since = t
                if t - st.pending_since >= rule.for_s:
                    st.firing = True
                    st.since = t
                    st.fired_count += 1
                    st.pending_since = None
                    st.last_detail = rule.detail(signals) if rule.detail else ""
                    self._counter(rule.name).inc()
                    tr = {"rule": rule.name, "event": "fired", "ts": round(t, 3),
                          "detail": st.last_detail}
                    transitions.append(tr)
                    if self._log:
                        self._log(f"ALERT {rule.name}: FIRED ({st.last_detail})")
            else:
                st.pending_since = None
                if not st.firing:
                    continue
                if st.clear_since is None:
                    st.clear_since = t
                if t - st.clear_since >= rule.clear_s:
                    st.firing = False
                    st.clear_since = None
                    tr = {"rule": rule.name, "event": "cleared", "ts": round(t, 3),
                          "after_s": round(t - st.since, 3)}
                    transitions.append(tr)
                    if self._log:
                        self._log(f"ALERT {rule.name}: cleared after {t - st.since:.1f}s")
        return transitions

    def active(self) -> List[Dict]:
        """Currently-firing alerts (the `/status` + status.json view)."""
        return [
            {"rule": name, "since": round(st.since, 3), "detail": st.last_detail}
            for name, st in self._states.items()
            if st.firing
        ]

    def state(self) -> Dict:
        """Full engine state, rule by rule (fired counts survive clears
        — the status.json record of what has EVER paged this run)."""
        return {
            name: {
                "firing": st.firing,
                "since": round(st.since, 3) if st.firing else None,
                "fired_count": st.fired_count,
                "detail": st.last_detail,
            }
            for name, st in self._states.items()
        }


# ---------------------------------------------------------------------------
# The fleet rule set.  Signals schema (built by pipeline.fleet_obs from
# the merged scrape + supervisor state; any key may be absent — rules
# treat missing data as "hold"):
#
#   burn_fast / burn_slow   merged-window burn rates (utils.slo)
#   slo_n                   merged window sample count
#   backlog_growing         bool|None from TrendTracker (spool scan)
#   backlog                 open spool requests now
#   restarts_recent         supervisor restarts inside the trend window
#   parked                  workers parked by the circuit breaker
#   degraded                workers whose heartbeat says degraded=True
#   hb_gap_s                max heartbeat age over live workers (None
#                           when no live worker has beaten yet)


def _num(signals: Dict, key: str):
    v = signals.get(key)
    return v if isinstance(v, (int, float)) else None


def fleet_rules(cfg=None) -> List[Rule]:
    """The built-in fleet rule set, thresholds from the typed config
    (the alert_burn_rate/alert_restarts/alert_for_s/alert_clear_s/
    alert_hb_gap_s knobs).  Returned as plain Rule objects so callers
    can extend/replace the set."""
    if cfg is None:
        from .config import load_config

        cfg = load_config()
    burn_thr = cfg.alert_burn_rate
    restarts_thr = cfg.alert_restarts
    for_s = cfg.alert_for_s
    clear_s = cfg.alert_clear_s
    hb_gap_thr = cfg.alert_hb_gap_s

    def slo_burn(s: Dict) -> Optional[bool]:
        bf, bs = _num(s, "burn_fast"), _num(s, "burn_slow")
        if bf is None or bs is None:
            return None
        if not _num(s, "slo_n"):
            return False  # empty window: no traffic is not an outage
        # the multi-window AND: fast alone is a blip, slow alone is
        # stale history — both over threshold is a real, current burn
        return bf >= burn_thr and bs >= burn_thr

    def backlog_growth(s: Dict) -> Optional[bool]:
        return s.get("backlog_growing")

    def restart_storm(s: Dict) -> Optional[bool]:
        parked, rr = _num(s, "parked"), _num(s, "restarts_recent")
        if parked is None and rr is None:
            return None
        # a breaker park IS the storm's terminal state — fire
        # immediately even when the restarts that led there happened
        # before our trend window
        return bool(parked) or (rr is not None and rr >= restarts_thr)

    def governor_degrade(s: Dict) -> Optional[bool]:
        d = _num(s, "degraded")
        return None if d is None else bool(d)

    def heartbeat_gap(s: Dict) -> Optional[bool]:
        gap = _num(s, "hb_gap_s")
        return None if gap is None else gap >= hb_gap_thr

    return [
        Rule(
            "slo_burn", slo_burn, for_s=for_s, clear_s=clear_s,
            detail=lambda s: (
                f"burn fast={s.get('burn_fast')} slow={s.get('burn_slow')} "
                f">= {burn_thr:g} over n={s.get('slo_n')}"
            ),
        ),
        Rule(
            "backlog_growth", backlog_growth, for_s=for_s, clear_s=clear_s,
            detail=lambda s: f"backlog {s.get('backlog')} and growing",
        ),
        Rule(
            # park fires NOW (for_s=0): by the time the breaker parks a
            # worker the flap already lasted a full breaker window
            "restart_storm", restart_storm, for_s=0.0, clear_s=clear_s,
            detail=lambda s: (
                f"parked={s.get('parked')} restarts_recent={s.get('restarts_recent')}"
                f" (threshold {restarts_thr})"
            ),
        ),
        Rule(
            "governor_degrade", governor_degrade, for_s=for_s, clear_s=clear_s,
            detail=lambda s: f"{s.get('degraded')} worker(s) soft-degraded",
        ),
        Rule(
            "heartbeat_gap", heartbeat_gap, for_s=0.0, clear_s=clear_s,
            detail=lambda s: f"max heartbeat age {s.get('hb_gap_s')}s >= {hb_gap_thr:g}s",
        ),
    ]
