"""History sentry — trajectory changepoints onto the policy bus.

``HistorySentry.scan(store)`` walks every banked (platform, probe,
metric) trajectory plus each row's within-run step series through the
deterministic changepoint kernel and publishes ONE
``history_regression`` verdict per new episode onto the policy bus
(plane/kind/severity/evidence envelope — the PR 17 grammar), so the
pre-verified action vocabulary (arm demotion, route_weight,
quant-block resize) can answer a *trend*, not just a spike.

Scanning is idempotent: the same ledger scanned twice publishes
nothing new (episodes are keyed by platform/probe/metric/onset
run_id/direction).  A changepoint only becomes a verdict when it
points in the metric's *bad* direction — latency/byte/time gauges
regress upward, throughput/quality gauges regress downward; the
improvement direction is still reported (comm_doctor --history) but
never raises policy.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple

from . import changepoint as _cp
from .store import HistoryStore

# suffix/substring cues for gauges where HIGHER is worse (latency,
# wire bytes, recovery time, regression counters); everything else —
# tokens/s, busbw, goodput, SNR, acceptance — regresses DOWN
_HIGHER_IS_BAD = ("_ms", "_s", "_us", "bytes", "time_to", "latency",
                  "regressions", "violations", "stall", "itl", "ttft",
                  "p99", "p50")
# overrides where a cue substring would misclassify
_LOWER_IS_BAD = ("tokens_per_s", "busbw", "goodput", "mfu", "snr",
                 "accept", "speedup", "hit", "recovered_MBps")


def bad_direction(metric: str) -> str:
    m = metric.lower()
    for cue in _LOWER_IS_BAD:
        if cue in m:
            return "down"
    for cue in _HIGHER_IS_BAD:
        if cue in m:
            return "up"
    return "down"


class HistorySentry:
    """Idempotent trajectory judge; one verdict per episode."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._published: set = set()     # episode keys already raised
        self._verdicts: List[Dict[str, Any]] = []
        self._changepoints = 0

    # ---- scanning --------------------------------------------------

    def scan(self, store: HistoryStore,
             platform: Optional[str] = None) -> List[Dict[str, Any]]:
        """Judge every trajectory (and step series) in the store;
        returns the verdicts newly published by THIS scan."""
        fresh: List[Dict[str, Any]] = []
        combos = sorted({(r["platform"], r["probe"], r["metric"])
                         for r in store.rows()
                         if platform is None
                         or r["platform"] == platform})
        for plat, probe, metric in combos:
            traj = store.trajectory(probe, metric, plat)
            if not traj:
                continue
            run_ids = [rid for rid, _ in traj]
            values = [val for _, val in traj]
            for cp in _cp.detect(values):
                v = self._admit(plat, probe, metric,
                                run_ids[cp["index"]], cp,
                                scope="runs", runs=len(values))
                if v:
                    fresh.append(v)
            # within-run drift: the newest run's step series through
            # the same kernel; index maps to a step offset, the
            # changepoint still attributes to (metric, run_id)
            rid = run_ids[-1]
            series = store.series_of(rid, plat, probe, metric)
            for cp in _cp.detect(series):
                v = self._admit(plat, probe, metric, rid, cp,
                                scope="series", runs=len(series),
                                step_index=cp["index"])
                if v:
                    fresh.append(v)
        return fresh

    def _admit(self, platform: str, probe: str, metric: str,
               run_id: int, cp: Dict[str, Any], scope: str,
               runs: int, step_index: Optional[int] = None
               ) -> Optional[Dict[str, Any]]:
        key = (platform, probe, metric, scope, int(run_id),
               cp["direction"],
               step_index if step_index is not None else -1)
        with self._lock:
            if key in self._published:
                return None
            self._published.add(key)
            self._changepoints += 1
        if cp["direction"] != bad_direction(metric):
            return None                  # improvement: count, no raise
        mag_pct = round(100.0 * cp["magnitude"], 2)
        severity = "error" if abs(cp["magnitude"]) >= 0.25 else "warn"
        verdict = {"plane": "history", "kind": "history_regression",
                   "severity": severity, "probe": probe,
                   "metric": metric, "platform": platform,
                   "run_id": int(run_id), "direction": cp["direction"],
                   "magnitude_pct": mag_pct, "scope": scope,
                   "stat": cp["stat"], "runs": int(runs)}
        if step_index is not None:
            verdict["step_index"] = int(step_index)
        with self._lock:
            self._verdicts.append(verdict)
            if len(self._verdicts) > 64:
                del self._verdicts[:len(self._verdicts) - 64]
        from .. import trace
        if trace.enabled:
            trace.instant("history_changepoint", "history", args=verdict)
        from .. import policy
        if policy.enabled:
            policy.publish("history", "history_regression", severity,
                           evidence=verdict)
        return verdict

    # ---- queries ---------------------------------------------------

    def changepoints(self) -> int:
        with self._lock:
            return self._changepoints

    def verdicts(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._verdicts)

    def rearm(self, platform: str, probe: str, metric: str) -> int:
        """Forget published episodes for one gauge — the explicit
        re-arm hook tests use to model 'episode
        over after a recovered run' across repeated scans."""
        with self._lock:
            drop = [k for k in self._published
                    if k[0] == platform and k[1] == probe
                    and k[2] == metric]
            for k in drop:
                self._published.discard(k)
            return len(drop)

    def reset(self) -> None:
        with self._lock:
            self._published.clear()
            self._verdicts.clear()
            self._changepoints = 0
