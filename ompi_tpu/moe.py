"""MoE routing plane — per-expert load observability + live adaptation.

The seventh plane, and the first that closes an observe→act loop
(ROADMAP item 3's "creative part"): hot-expert skew IS a hot-link
verdict.  A token router that collapses onto one expert produces
exactly the traffic signature the hot-link sentry was built for — one
edge of the bipartite exchange carrying disproportionate bytes — so
this plane judges the per-expert token loads with the SAME statistical
discipline (max vs median with a MAD gate, one trip per episode) and
then *acts*: an audited capacity-factor + aux-weight adaptation with
cooldown hysteresis so routing cannot flap.

Three coupled pieces:

* **counters** — ``moe_routed_tokens`` / ``moe_dropped_tokens`` /
  ``moe_hot_expert_trips`` pvars (read-through in ``spc.py`` under the
  Prometheus grammar) plus a cumulative per-expert load ledger for
  ``comm_doctor --moe``.
* **HotExpertSentry** — the hot-link sentry's judge transplanted from
  directed edges to expert ids: trip when the hottest expert's token
  load exceeds ``moe_sentry_ratio`` x median AND clears the MAD gate,
  one trip per skew episode (re-arms when the expert cools or the hot
  spot moves).  A trip emits a ``moe_hot_expert`` trace instant naming
  the guilty expert.
* **adaptation** — a sentry trip (past the ``moe_adapt_cooldown``
  hysteresis window) grows the live capacity-factor scale by
  ``moe_adapt_growth`` (so fewer overflow tokens drop while the router
  re-learns) and boosts the load-balance aux weight by
  ``moe_adapt_aux_boost`` (so the router actually re-learns), emitting
  exactly ONE audited ``moe_adapt`` decision event carrying the verdict
  that caused it.  The verdict rides the policy plane's bus
  (``ompi_tpu/policy``) and the engine's builtin moe rule calls back
  into :func:`apply_adaptation`; ``moe_block_ep`` reads the scales
  live through ``capacity_factor(base)`` / ``aux_weight(base)``.

All entry points are behind ONE ``moe.enabled`` attribute read — the
same disabled-path bar as trace/health/perf/traffic.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence

from .core import var as _var

_var.register("moe", "", "enabled", False, type=bool, level=3,
              help="Master switch for the MoE routing plane (per-expert "
                   "load ledger, hot-expert sentry, live capacity/aux "
                   "adaptation). Off by default; the disabled path is "
                   "one attribute read per routing step.")
_var.register("moe", "sentry", "ratio", 2.0, type=float, level=3,
              help="Hot-expert trip: max per-expert token load above "
                   "this multiple of the median expert (and past the "
                   "MAD gate). Tighter than the traffic sentry's 4.0 — "
                   "a 2x expert skew already doubles the capacity "
                   "needed for zero drops.")
_var.register("moe", "sentry", "z", 3.0, type=float, level=3,
              help="MAD gate: (max - median) must exceed z x MAD of "
                   "the per-expert load distribution before a trip "
                   "(a naturally wide spread never flags its own tail).")
_var.register("moe", "sentry", "min_tokens", 64, type=int, level=3,
              help="The hot expert must hold at least this many tokens "
                   "in the step before the sentry judges (startup / "
                   "tiny-batch noise floor).")
_var.register("moe", "adapt", "growth", 1.25, type=float, level=3,
              help="Capacity-factor scale multiplier applied per "
                   "hot-expert adaptation (compounding across trips, "
                   "capped by moe_adapt_max_cf).")
_var.register("moe", "adapt", "max_cf", 4.0, type=float, level=3,
              help="Ceiling on the ADAPTED effective capacity factor "
                   "(base x scale); growth beyond it is clamped so a "
                   "pathological router cannot inflate capacity "
                   "unboundedly.")
_var.register("moe", "adapt", "aux_boost", 2.0, type=float, level=3,
              help="Load-balance aux-weight multiplier applied per "
                   "adaptation (capped at 16x base) — the 'act' half "
                   "that makes the router re-learn balance instead of "
                   "just paying for the skew with capacity.")
_var.register("moe", "adapt", "cooldown", 4, type=int, level=3,
              help="Minimum routing steps between adaptations "
                   "(hysteresis): a persistent skew episode adapts "
                   "once per window, not once per step, so capacity "
                   "and routing cannot flap.")

enabled: bool = bool(_var.get("moe_enabled", False))

PVARS = ("moe_routed_tokens", "moe_dropped_tokens",
         "moe_hot_expert_trips")

_AUX_SCALE_CAP = 16.0


def enable() -> None:
    global enabled
    enabled = True


def disable() -> None:
    global enabled
    enabled = False


def _on_enabled_var(v: Any) -> None:
    # mid-run OMPI_TPU_MOE_ENABLED / set_cli writes take effect
    global enabled
    enabled = bool(v)


_var.watch("moe_enabled", _on_enabled_var)


def _median(xs: List[float]) -> float:
    s = sorted(xs)
    n = len(s)
    if not n:
        return 0.0
    m = n // 2
    return s[m] if n % 2 else (s[m - 1] + s[m]) / 2.0


class HotExpertSentry:
    """Streaming judge over per-step per-expert token loads — the
    hot-link sentry's statistics applied to the expert axis."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._hot: Dict[int, bool] = {}
        self._verdicts: List[Dict[str, Any]] = []
        self._trips = 0

    def check(self, loads: Sequence[int],
              step: Optional[int] = None) -> Optional[Dict[str, Any]]:
        """One pass over this step's per-expert token loads; returns
        the new hot-expert verdict when this call tripped, else None."""
        vals = [float(v) for v in loads]
        if len(vals) < 2:
            return None
        min_tokens = int(_var.get("moe_sentry_min_tokens", 64))
        ratio = float(_var.get("moe_sentry_ratio", 2.0))
        z_thr = float(_var.get("moe_sentry_z", 3.0))
        med = _median(vals)
        mad = _median([abs(v - med) for v in vals])
        he = max(range(len(vals)), key=lambda i: vals[i])
        hb = vals[he]
        hot = (hb >= min_tokens
               and hb > ratio * max(med, 1.0)
               and (hb - med) > z_thr * mad)
        verdict = None
        with self._lock:
            # re-arm every expert that is no longer the hot one / no
            # longer hot at all — one trip per skew episode
            for k in list(self._hot):
                if k != he or not hot:
                    del self._hot[k]
            if hot and not self._hot.get(he):
                self._hot[he] = True
                self._trips += 1
                verdict = {"kind": "hot_expert", "plane": "moe",
                           "severity": "warn", "expert": he,
                           "tokens": int(hb), "median_tokens": int(med),
                           "ratio": round(hb / max(med, 1.0), 2),
                           "mad_tokens": int(mad),
                           "n_experts": len(vals)}
                if step is not None:
                    verdict["step"] = int(step)
                self._verdicts.append(verdict)
                if len(self._verdicts) > 64:
                    del self._verdicts[:len(self._verdicts) - 64]
        self._emit(verdict)
        return verdict

    @staticmethod
    def _emit(verdict: Optional[Dict[str, Any]]) -> None:
        # trace emission outside the lock (the ring has its own)
        if verdict is None:
            return
        from . import trace
        if trace.enabled:
            trace.instant("moe_hot_expert", "moe", args=verdict)

    def hot(self) -> bool:
        with self._lock:
            return bool(self._hot)

    def trips(self) -> int:
        return self._trips

    def verdicts(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._verdicts)

    def reset(self) -> None:
        with self._lock:
            self._hot.clear()
            self._verdicts.clear()
            self._trips = 0


sentry = HotExpertSentry()

_lock = threading.Lock()
_routed = 0
_dropped = 0
_steps = 0
_expert_load: Dict[int, int] = {}
_cf_scale = 1.0
_aux_scale = 1.0
_last_adapt_step: Optional[int] = None
_adaptations: List[Dict[str, Any]] = []


def note_routing(expert_load: Sequence[int], routed: Optional[int] = None,
                 dropped: int = 0,
                 step: Optional[int] = None) -> Optional[Dict[str, Any]]:
    """Feed one routing step's per-expert dispatched-token loads (global
    across ranks), judge the skew, and adapt if a trip clears the
    cooldown.  Returns this step's hot-expert verdict, if any."""
    global _routed, _dropped, _steps
    if not enabled:
        return None
    loads = [int(v) for v in expert_load]
    r = int(sum(loads) if routed is None else routed)
    with _lock:
        _steps += 1
        this_step = _steps if step is None else int(step)
        _routed += r
        _dropped += int(dropped)
        for e, v in enumerate(loads):
            _expert_load[e] = _expert_load.get(e, 0) + v
    verdict = sentry.check(loads, step=this_step)
    if verdict is not None:
        # the observe->decide->act hop now rides the policy plane: the
        # verdict goes onto the bus and the engine's builtin moe rule
        # routes it back through apply_adaptation with ONE audited
        # decide:moe_adapt event naming this verdict as the cause
        from . import policy
        policy.publish("moe", "hot_expert", "warn", evidence=verdict,
                       step=this_step)
    return verdict


def apply_adaptation(verdict: Dict[str, Any],
                     step: int) -> Optional[Dict[str, Any]]:
    """The act half of the hot-expert loop, called by the policy
    engine's moe rule.  Grows the live capacity/aux scales and banks
    the adaptation event, or returns None inside the cooldown window
    (the hysteresis half of 'can't flap' — the sentry's episode re-arm
    is the other half).  The window lives HERE, against state
    ``reset()`` clears, so the absorbed loop stays exactly PR 14's."""
    global _cf_scale, _aux_scale, _last_adapt_step
    growth = float(_var.get("moe_adapt_growth", 1.25))
    max_cf = float(_var.get("moe_adapt_max_cf", 4.0))
    boost = float(_var.get("moe_adapt_aux_boost", 2.0))
    cooldown = int(_var.get("moe_adapt_cooldown", 4))
    with _lock:
        if (_last_adapt_step is not None
                and step - _last_adapt_step < max(cooldown, 1)):
            return None                 # inside the hysteresis window
        _last_adapt_step = int(step)
        _cf_scale = _cf_scale * max(growth, 1.0)
        _aux_scale = min(_aux_scale * max(boost, 1.0), _AUX_SCALE_CAP)
        event = {"step": int(step), "expert": verdict["expert"],
                 "cf_scale": round(_cf_scale, 4),
                 "aux_scale": round(_aux_scale, 4),
                 "max_cf": max_cf,
                 "reason": (f"sentry:moe_hot_expert:e{verdict['expert']}"
                            f":ratio={verdict['ratio']}")}
        _adaptations.append(event)
        if len(_adaptations) > 64:
            del _adaptations[:len(_adaptations) - 64]
    return event


def capacity_factor(base: float) -> float:
    """The LIVE effective capacity factor: base x adapted scale, capped
    at moe_adapt_max_cf. The identity when the plane is disabled."""
    if not enabled:
        return float(base)
    with _lock:
        return min(float(base) * _cf_scale,
                   float(_var.get("moe_adapt_max_cf", 4.0)))


def aux_weight(base: float) -> float:
    """The LIVE load-balance aux weight: base x adapted scale."""
    if not enabled:
        return float(base)
    with _lock:
        return float(base) * _aux_scale


def adaptations() -> List[Dict[str, Any]]:
    with _lock:
        return list(_adaptations)


def pvar_value(name: str) -> float:
    if name == "moe_routed_tokens":
        return float(_routed)
    if name == "moe_dropped_tokens":
        return float(_dropped)
    if name == "moe_hot_expert_trips":
        return float(sentry.trips())
    raise KeyError(name)


def report() -> Dict[str, Any]:
    """Structured snapshot for comm_doctor --moe."""
    with _lock:
        return {
            "steps": _steps,
            "routed_tokens": _routed,
            "dropped_tokens": _dropped,
            "drop_rate": round(_dropped / max(_routed + _dropped, 1), 6),
            "expert_load": {str(e): v
                            for e, v in sorted(_expert_load.items())},
            "cf_scale": round(_cf_scale, 4),
            "aux_scale": round(_aux_scale, 4),
            "hot_expert_trips": sentry.trips(),
            "hot_now": sentry.hot(),
            "verdicts": sentry.verdicts(),
            "adaptations": list(_adaptations),
        }


def reset() -> None:
    global _routed, _dropped, _steps, _cf_scale, _aux_scale
    global _last_adapt_step
    sentry.reset()
    with _lock:
        _routed = _dropped = _steps = 0
        _expert_load.clear()
        _cf_scale = _aux_scale = 1.0
        _last_adapt_step = None
        _adaptations.clear()
