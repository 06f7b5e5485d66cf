"""ompi_tpu.numerics — the numerics plane (payload observability).

The five planes before this one (trace/doctor/health/perf/traffic)
watch *metadata* — timings, bytes, arms, seq numbers — but never the
payload: a NaN born on one rank, a silently corrupted replica, or a
quant arm whose SNR drifts below the EQuARX baseline sails through
every existing sentry.  This plane watches the numbers themselves,
live, at collective boundaries (docs/observability.md, "Numerics
plane"):

* ``probes``      — cheap on-device fingerprints (l2, absmax, NaN/Inf
  counts per rank row; optional chunked blake2s payload digest),
  sampled every ``numerics_sample_interval``-th collective via the
  coll dispatch wrapper and at the grad-sync boundary.
* ``sentry``      — (a) non-finite origin attribution: pre- vs
  post-collective row stats name the FIRST (rank, step, op) that
  *produced* a NaN/Inf versus ranks that merely received it through
  the reduction; episode semantics, ``numerics_nonfinite`` trace
  instant.  (b) quant-SNR: live dequant-path SNR vs the banked ~40 dB
  EQuARX baseline, perf-sentry trip grammar.
* ``consistency`` — cross-replica divergence auditor: dp replicas
  compared out-of-band over the control plane (bitwise on native
  arms, tolerance-bounded on quant), majority vote naming the first
  divergent (step, bucket, rank).

Disabled path (the default): ONE module attribute read
(``numerics.enabled``) per instrumented call site — the same bar as
trace/health/perf/traffic, asserted in tests/test_numerics.py.

Per-step telemetry (grad norm, loss, non-finite totals) banks to
``NUMERICS_<platform>.json`` (``save_ledger``/``load_ledger``);
loading re-arms the SNR sentry's baseline from the banked window.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Any, Dict, List, Optional

from ..core import var as _var
from . import consistency, probes  # noqa: F401
from .sentry import NonfiniteSentry, SnrSentry

_var.register("numerics", "", "enabled", False, type=bool, level=3,
              help="Master switch for the numerics plane (non-finite "
                   "origin sentry, quant-SNR sentry, divergence "
                   "auditor feeds, step telemetry). Off by default; "
                   "the disabled path is one attribute read per call "
                   "site.")
_var.register("numerics", "", "sample_interval", 1, type=int, level=3,
              help="Fingerprint every Nth dispatched collective (1 = "
                   "all). The skipped dispatches pay one counter "
                   "increment — the knob that keeps the hot path cheap "
                   "on collective-dense programs.")
_var.register("numerics", "", "ledger", "", type=str, level=3,
              help="Path of a NUMERICS JSON to load at enable() time "
                   "(empty: no autoload; load_ledger() is explicit).")

enabled: bool = bool(_var.get("numerics_enabled", False))

nonfinite = NonfiniteSentry()
snr = SnrSentry()

PVARS = ("numerics_nonfinite_trips", "numerics_snr_trips",
         "numerics_snr_db", "numerics_samples",
         "numerics_divergence_trips")


def enable() -> None:
    global enabled
    path = str(_var.get("numerics_ledger", "") or "")
    if path and os.path.exists(path):
        load_ledger(path)
    enabled = True


def disable() -> None:
    global enabled
    enabled = False


def _on_enabled_var(v: Any) -> None:
    # mid-run OMPI_TPU_NUMERICS_ENABLED / set_cli writes take effect;
    # the watcher fires on CHANGE only so enable()/disable() stay in
    # charge
    global enabled
    enabled = bool(v)


_var.watch("numerics_enabled", _on_enabled_var)


# ---- plane state -----------------------------------------------------

_lock = threading.Lock()
_samples = 0                  # fingerprinted collectives
_skip = 0                     # dispatch counter for the interval gate
_cur_step = 0                 # training-step attribution for verdicts
_steps: List[Dict[str, Any]] = []     # per-step telemetry rows
_divergence_trips = 0
_div_verdicts: List[Dict[str, Any]] = []

_tls = threading.local()      # in-flight probe entry (note_arm target)


def begin_step(step: int) -> None:
    """Set the step index verdicts attribute to (training loops
    call this; record_step advances it otherwise)."""
    global _cur_step
    _cur_step = int(step)


def current_step() -> int:
    return _cur_step


# ---- sample source 1: the coll dispatch wrapper ----------------------

def _sampled() -> bool:
    """Interval gate: True every numerics_sample_interval-th call."""
    global _skip
    ival = max(int(_var.get("numerics_sample_interval", 1)), 1)
    with _lock:
        _skip += 1
        return _skip % ival == 0


def probed_coll(fn, comm, name: str, a: tuple, kw: dict):
    """Invoke one collective under pre/post fingerprinting (the coll
    dispatch wrapper's numerics arm).  coll/xla's audit annotates the
    in-flight entry with the executed arm (note_arm) before the probe
    judges; host-path buffers and non-array payloads are skipped."""
    global _samples
    buf = a[0] if a else None
    if buf is None or not hasattr(buf, "dtype") or not _sampled():
        return fn(comm, *a, **kw)
    ent = {"arm": ""}
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    st.append(ent)
    try:
        pre = probes.fingerprint(buf)
        # opt-in flight-recorder payload mode: fold the pre-collective
        # digest into the health signature so the desync sentinel can
        # catch same-seq/same-metadata/different-data divergence
        from .. import health
        if health.enabled and bool(_var.get("health_payload_digest",
                                            False)):
            health.note_payload(probes.payload_digest(buf))
        out = fn(comm, *a, **kw)
    finally:
        st.pop()
    post = probes.fingerprint(out) if hasattr(out, "dtype") else None
    with _lock:
        _samples += 1
    nonfinite.observe(name, _cur_step, pre, post, arm=ent["arm"])
    return out


def note_arm(arm: str) -> None:
    """Called by coll/xla._audit post-decision: annotate the in-flight
    probe entry with the executed arm (the verdict's compare mode and
    context). No entry -> no-op (direct DeviceComm use)."""
    st = getattr(_tls, "stack", None)
    if not st:
        return
    st[-1]["arm"] = str(arm)


# ---- sample source 2: the grad-sync boundary -------------------------

def observe_grad_sync(leaves, mode: str, ndev: int,
                      plan=None, arms=None) -> Optional[Dict[str, Any]]:
    """Fingerprint one synced gradient (flat leaf list) at the
    parallel/overlap boundary: grad-norm telemetry for the step row
    plus non-finite detection with bucket attribution when the bucketed
    plan is available."""
    if not _sampled():
        return None
    global _samples
    tnf = probes.tree_nonfinite(leaves)
    gnorm = probes.grad_norm(leaves)
    with _lock:
        _samples += 1
        _pending_step().update(grad_norm=round(gnorm, 6),
                               grad_nonfinite=tnf["total_nonfinite"])
    bucket = -1
    if tnf["first_leaf"] >= 0 and plan is not None:
        for bi, b in enumerate(plan.buckets):
            if tnf["first_leaf"] in b.indices:
                bucket = bi
                break
    nf = [tnf["total_nonfinite"]]
    pre = {"nonfinite": nf} if tnf["total_nonfinite"] else {"nonfinite": [0]}
    verdict = nonfinite.observe(
        "grad_sync", _cur_step, pre, None,
        arm=(arms[bucket] if arms and 0 <= bucket < len(arms) else mode))
    if verdict is not None and bucket >= 0:
        verdict["bucket"] = bucket
    return verdict


def _pending_step() -> Dict[str, Any]:
    """The telemetry row for the CURRENT step (created on first touch;
    record_step finalizes it). Callers hold _lock."""
    if not _steps or _steps[-1].get("step") != _cur_step \
            or _steps[-1].get("final"):
        _steps.append({"step": _cur_step})
        if len(_steps) > 4096:
            del _steps[:len(_steps) - 4096]
    return _steps[-1]


def record_step(loss: Optional[float] = None, **kw: Any) -> Dict[str, Any]:
    """Finalize the current step's telemetry row (loss + anything the
    caller measured) and advance the step counter."""
    global _cur_step
    with _lock:
        row = _pending_step()
        if loss is not None:
            row["loss"] = float(loss)
        row.update({k: v for k, v in kw.items() if v is not None})
        row["final"] = True
        out = dict(row)
        _cur_step += 1
    return out


# ---- sample source 3: the quant dequant path -------------------------

def observe_quant_snr(coll: str, x, block: int,
                      scale_dtype=None) -> Optional[float]:
    """Sample the live quantization SNR of one quant-arm collective
    (coll/quant entry points call this behind ONE enabled read) and
    judge it with the trip grammar."""
    if not _sampled():
        return None
    db = probes.snr_db(x, block, scale_dtype)
    if db is None:
        return None
    global _samples
    with _lock:
        _samples += 1
    snr.observe(coll, db, block=block)
    return db


# ---- the divergence auditor (consistency.py front door) --------------

def audit_replicas(ctx, step: int, buckets,
                   peers=None) -> Dict[str, Any]:
    """Run one out-of-band cross-replica audit and fold the verdict
    into the plane's ledger + pvar (``numerics_divergence_trips``)."""
    global _divergence_trips
    v = consistency.audit(ctx, step, buckets, peers=peers)
    if v["divergent"]:
        with _lock:
            _divergence_trips += 1
            _div_verdicts.append(v)
            if len(_div_verdicts) > 64:
                del _div_verdicts[:len(_div_verdicts) - 64]
        from .. import trace
        if trace.enabled:
            trace.instant("numerics_divergence", "numerics",
                          args={"step": v["step"], "rank": v["rank"],
                                "first": v["first"]})
    return v


# ---- ledger persistence ----------------------------------------------

def default_ledger_path(platform: str, root: Optional[str] = None) -> str:
    return os.path.join(root or os.getcwd(),
                        f"NUMERICS_{platform}.json")


def save_ledger(path: str, platform: str = "") -> Dict[str, Any]:
    doc = {"version": 1, "platform": platform, "report": report()}
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(doc, fh, indent=1)
    os.replace(tmp, path)
    return doc


def load_ledger(path: str) -> Dict[str, int]:
    """Load a NUMERICS json: the step telemetry banks and the SNR
    sentry re-arms its baseline from the banked sample window."""
    with open(path) as fh:
        doc = json.load(fh)
    rep = doc.get("report", doc)
    with _lock:
        _steps.extend(rep.get("steps") or [])
        if len(_steps) > 4096:
            del _steps[:len(_steps) - 4096]
    keys = snr.load_baseline(rep.get("snr", {}).get("samples") or [])
    return {"steps": len(rep.get("steps") or []), "baseline_keys": keys}


# ---- pvars + report --------------------------------------------------

def pvar_value(name: str) -> float:
    if name == "numerics_nonfinite_trips":
        return float(nonfinite.trips())
    if name == "numerics_snr_trips":
        return float(snr.trips())
    if name == "numerics_snr_db":
        return float(snr.last_db())
    if name == "numerics_samples":
        return float(_samples)
    if name == "numerics_divergence_trips":
        return float(_divergence_trips)
    raise KeyError(name)


def report() -> Dict[str, Any]:
    """Structured snapshot for comm_doctor --numerics."""
    with _lock:
        steps = [dict(r) for r in _steps]
        div = [dict(v) for v in _div_verdicts]
        samples = _samples
    return {
        "samples": samples,
        "nonfinite": {"trips": nonfinite.trips(),
                      "verdicts": nonfinite.verdicts()},
        "snr": {"trips": snr.trips(), "last_db": snr.last_db(),
                "samples": snr.samples(), "verdicts": snr.verdicts()},
        "divergence": {"trips": _divergence_trips, "verdicts": div},
        "steps": steps,
    }


def reset() -> None:
    """Tests: clear sentries, telemetry, counters and the TLS stack."""
    global _samples, _skip, _cur_step, _divergence_trips
    nonfinite.reset()
    snr.reset()
    with _lock:
        _samples = 0
        _skip = 0
        _cur_step = 0
        _steps.clear()
        _divergence_trips = 0
        _div_verdicts.clear()
    if getattr(_tls, "stack", None):
        _tls.stack = []
