"""Policy plane — the seventh plane, the one that *acts*.

Six planes observe (trace, doctor, health, perf, traffic, numerics);
this one closes the observe->decide->act loop over ALL of them.  Every
sentry publishes its trip as a :class:`~ompi_tpu.policy.bus.Verdict`
onto one bus; declarative rules (:mod:`~ompi_tpu.policy.engine`) map
verdicts to adaptations drawn from a fixed, statically PRE-VERIFIED
action vocabulary; with a control-plane context the fleet votes
out-of-band so every rank switches arms on the same step.  Each
applied adaptation emits exactly one audited ``decide:<op>`` event
naming its causing verdict, and the full verdict -> vote -> action ->
effect ledger renders through ``comm_doctor --policy``.

Plane conventions (same bar as trace/health/perf/traffic/moe):

* ONE module attribute ``enabled`` gates the bridged sentry publishes
  (the disabled path is one attribute read); the moe plane's absorbed
  loop runs whenever *moe* is enabled, policy plane on or off.
* ``PVARS`` read through ``spc.get``/``snapshot`` -> MPI_T ->
  Prometheus, zero new transport.
* ``report()``/``reset()`` for the doctor.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional

from ..core import var as _var
from .bus import SEVERITIES, Verdict, VerdictBus, severity_rank  # noqa: F401

_var.register("policy", "", "enabled", False, type=bool, level=3,
              help="Master switch for the policy plane's bridged sentry "
                   "verdict publishes (perf/traffic/numerics/health/"
                   "straggler -> bus -> engine). Off by default; the "
                   "disabled path is one attribute read per trip site. "
                   "The moe plane's absorbed adaptation loop rides "
                   "moe_enabled instead, so PR 14 behavior is "
                   "unchanged.")
_var.register("policy", "vote", "lead", 2, type=int, level=3,
              help="Steps between fleet-vote agreement and the "
                   "synchronized arm switch: switch_step = max proposed "
                   "step + lead, a pure function of the gathered votes, "
                   "so every rank flips on the same step.")
_var.register("policy", "vote", "timeout", 5.0, type=float, level=3,
              help="Per-peer control-plane gather timeout (seconds) for "
                   "one policy vote round; a missing peer is recorded, "
                   "never waited on forever.")
_var.register("policy", "", "cooldown", 8, type=int, level=3,
              help="Default per-action cooldown (steps) between applied "
                   "adaptations — the hysteresis half of 'arms cannot "
                   "flap' (the sentries' one-trip-per-episode re-arm is "
                   "the other half).")

enabled: bool = bool(_var.get("policy_enabled", False))

PVARS = ("policy_verdicts", "policy_decisions", "policy_vote_rounds")


def enable() -> None:
    global enabled
    enabled = True


def disable() -> None:
    global enabled
    enabled = False


def _on_enabled_var(v: Any) -> None:
    # mid-run OMPI_TPU_POLICY_ENABLED / set_cli writes take effect
    global enabled
    enabled = bool(v)


_var.watch("policy_enabled", _on_enabled_var)


bus = VerdictBus()

_engine_lock = threading.Lock()
_engine: Optional[Any] = None


def default_engine():
    """The process-wide engine (lazily built over the builtin rules)
    subscribed to the bus.  ``set_engine`` swaps it (e.g. for a
    fleet-voting instance carrying a control-plane ctx)."""
    global _engine
    with _engine_lock:
        if _engine is None:
            from .engine import PolicyEngine, builtin_rules
            _engine = PolicyEngine(builtin_rules())
            bus.subscribe(_engine.consider)
        return _engine


def set_engine(engine) -> None:
    global _engine
    with _engine_lock:
        if _engine is not None:
            bus.unsubscribe(_engine.consider)
        _engine = engine
        if engine is not None:
            bus.subscribe(engine.consider)


def publish(plane: str, kind: str, severity: str,
            evidence: Optional[Dict[str, Any]] = None,
            step: Optional[int] = None) -> Verdict:
    """Publish one sentry trip onto the bus (building the default
    engine on first use so the builtin rules are always listening)."""
    default_engine()
    v = Verdict(plane=plane, kind=kind, severity=severity,
                evidence=dict(evidence or {}),
                step=None if step is None else int(step))
    return bus.publish(v)


def tick(step: int) -> None:
    """Per-step hook: applies fleet-scheduled adaptations whose agreed
    switch step has arrived.  Cheap when nothing is pending."""
    eng = _engine
    if eng is not None:
        eng.tick(step)


def pvar_value(name: str) -> float:
    if name == "policy_verdicts":
        return float(bus.count())
    if name == "policy_decisions":
        eng = _engine
        return float(eng.decisions() if eng is not None else 0)
    if name == "policy_vote_rounds":
        eng = _engine
        return float(eng.vote_rounds() if eng is not None else 0)
    raise KeyError(name)


def report() -> Dict[str, Any]:
    """Structured snapshot for comm_doctor --policy:
    the decision ledger plus the attribution figure (share of applied
    adaptations naming their causing verdict — the acceptance bar is
    100, i.e. zero unattributed decisions)."""
    eng = default_engine()
    ledger = eng.ledger()
    applied = [r for r in ledger if r["outcome"] == "applied"]
    attributed = [r for r in applied if r.get("verdict")]
    return {
        "enabled": enabled,
        "verdicts_published": bus.count(),
        "verdicts": [v.as_dict() for v in bus.verdicts()],
        "rules": [{"rule": r.name, "plane": r.plane, "kind": r.kind,
                   "min_severity": r.min_severity,
                   "action": r.action.name,
                   "audit_op": r.action.audit_op,
                   "arm": r.action.arm,
                   "verified": eng.verified.get(r.action.name, [])}
                  for r in eng.rules],
        "ledger": ledger,
        "decisions_applied": len(applied),
        "vote_rounds": eng.vote_rounds(),
        "pending": eng.pending(),
        "attribution_pct": round(
            100.0 * len(attributed) / len(applied), 2) if applied
        else 100.0,
        "unattributed": len(applied) - len(attributed),
    }


def reset() -> None:
    bus.reset()
    eng = _engine
    if eng is not None:
        eng.reset()
