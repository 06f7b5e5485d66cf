"""History plane — fleet-lifetime telemetry with deterministic
changepoint detection (the ninth plane; docs/observability.md,
"History plane").

Three coupled pieces:

* ``store``       — append-only, schema-versioned run ledger
  (``BENCH_HISTORY.jsonl`` + per-run downsampled step-series chunks)
  keyed by (run_id, platform, probe, metric).  ``run_id`` is supplied
  by the caller — derived from ledger content
  (``store.next_run_id``); the plane itself never reads a wall clock.
* ``changepoint`` — deterministic Page-Hinkley/CUSUM kernel over
  MAD-normalized residuals with min-run-count and sustain gates;
  identical trajectory in, identical changepoint list out.
* ``sentry``      — ``HistorySentry`` publishing one
  ``history_regression`` verdict per episode onto the policy bus so
  the pre-verified action vocabulary can answer a trend.

Disabled path (the default): ONE module attribute read
(``history.enabled``) per instrumented call site — the same bar as
every other plane, asserted in tests/test_history.py.  ``enable()``
rehydrates the store from the ``history_path`` ledger when it exists
(perf's ledger-autoload contract).
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

from ..core import var as _var
from .changepoint import detect  # noqa: F401
from .sentry import HistorySentry, bad_direction  # noqa: F401
from .store import HistoryStore, append_jsonl, downsample  # noqa: F401

_var.register("history", "", "enabled", False, type=bool, level=3,
              help="Master switch for the history plane (run ledger, "
                   "changepoint sentry). Off by default; the disabled "
                   "path is one attribute read per call site.")
_var.register("history", "", "path", "", type=str, level=3,
              help="Path of the BENCH_HISTORY.jsonl ledger to "
                   "rehydrate at enable() time and to append each "
                   "banked row to (empty: in-memory only).")
_var.register("history", "", "series_cap", 64, type=int, level=4,
              help="Deterministic bucket-mean downsample cap for "
                   "per-run step-series chunks banked with a row.")

enabled: bool = bool(_var.get("history_enabled", False))

store = HistoryStore(series_cap=int(_var.get("history_series_cap", 64)))
sentry = HistorySentry()

PVARS = ("history_runs", "history_samples", "history_changepoints")


def enable() -> None:
    global enabled
    path = str(_var.get("history_path", "") or "")
    if path and os.path.exists(path):
        store.load_jsonl(path)
    enabled = True


def disable() -> None:
    global enabled
    enabled = False


def _on_enabled_var(v: Any) -> None:
    # mid-run OMPI_TPU_HISTORY_ENABLED / set_cli writes take effect;
    # the watcher fires on CHANGE only so enable()/disable() stay in
    # charge
    global enabled
    enabled = bool(v)


_var.watch("history_enabled", _on_enabled_var)


# ---- the write path --------------------------------------------------

def record_run(run_id: int, platform: str, probe: str, metric: str,
               value: float, unit: str = "",
               series: Optional[List[float]] = None,
               extra: Optional[Dict[str, Any]] = None
               ) -> Optional[Dict[str, Any]]:
    """Bank one headline gauge for one run: into the in-memory store
    AND appended to the on-disk ledger when ``history_path`` is set.
    No-op while the plane is disabled (probes call unconditionally
    behind the one-attribute-read gate)."""
    if not enabled:
        return None
    row = store.record(run_id, platform, probe, metric, value,
                       unit=unit, series=series, extra=extra)
    path = str(_var.get("history_path", "") or "")
    if path:
        append_jsonl(path, row)
    return row


def next_run_id(platform: str, probe: str) -> int:
    """The caller-supplied run id: 1 + highest banked for this
    (platform, probe) — ledger content only, never a clock."""
    return store.next_run_id(platform, probe)


def scan(platform: Optional[str] = None) -> List[Dict[str, Any]]:
    """Run the changepoint sentry over every banked trajectory;
    returns verdicts newly published by this scan."""
    return sentry.scan(store, platform)


# ---- the banked artifact schema --------------------------------------

# one entry per banked probe artifact: (artifact stem, dotted paths of
# the extra headline gauges recorded beside the doc's own metric/value
# row).  tools/history_backfill.py reads it to turn a directory of
# ``<STEM>_<platform>.json`` artifacts into ledger rows.
PROBE_GAUGES: Dict[str, Any] = {
    "goodput":   ("GOODPUT", ("mfu_pct", "overlap_efficiency")),
    "traffic":   ("TRAFFIC", ("hot_edge.ratio", "planes.ici")),
    "pod":       ("BENCH_POD", ()),
    "reshard":   ("RESHARD", ("busbw_GBps", "peak_bytes")),
    "elastic":   ("ELASTIC", ("steps_lost", "wire_bytes")),
    "moe":       ("MOE", ("skew.trips",)),
    "numerics":  ("NUMERICS", ("snr_db_last",)),
    "serve":     ("SERVE", ("speculative.acceptance_rate",
                            "fused.tokens_per_s",
                            "quant.quant_wire_bytes")),
    "fleet":     ("FLEET", ("itl_p99_ms_colocated",
                            "itl_p99_ms_disaggregated",
                            "migration.bytes")),
    "slo":       ("REQUESTS", ("report.slo_breaches",
                               "report.completed")),
    "selfdrive": ("POLICY", ("time_to_retune_steps", "recovered_MBps",
                             "report.verdicts_published",
                             "report.decisions_applied")),
}


def _dig(doc: Dict[str, Any], path: str) -> Any:
    cur: Any = doc
    for part in path.split("."):
        if not isinstance(cur, dict):
            return None
        cur = cur.get(part)
    return cur


def headline_rows(probe: str, doc: Dict[str, Any]
                  ) -> List[Any]:
    """The (metric, value, unit) rows one banked probe doc yields:
    the doc's own metric/value pair plus the probe's extra headline
    gauges from ``PROBE_GAUGES`` (non-numeric/missing paths skipped)."""
    rows: List[Any] = []
    metric, value = doc.get("metric"), doc.get("value")
    if metric is not None and isinstance(value, (int, float)):
        rows.append((str(metric), float(value),
                     str(doc.get("unit", ""))))
    _, extras = PROBE_GAUGES.get(probe, ("", ()))
    for path in extras:
        v = _dig(doc, path)
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            continue
        rows.append((path.replace(".", "_"), float(v), ""))
    return rows


# ---- pvars + Prometheus ----------------------------------------------

def pvar_value(name: str) -> float:
    if name == "history_runs":
        return float(store.run_count())
    if name == "history_samples":
        return float(store.sample_count())
    if name == "history_changepoints":
        return float(sentry.changepoints())
    raise KeyError(name)


def prometheus_rows(rank: int = 0, comm: str = "world",
                    prefix: str = "ompi_tpu") -> List[str]:
    """Latest banked value per gauge for the Prometheus exporter:
    ``<prefix>_history_metric{probe,metric}``."""
    pairs = store.metrics()
    if not pairs:
        return []
    name = f"{prefix}_history_metric"
    rows = [f"# HELP {name} Latest banked run value per history-plane "
            "gauge (run trajectory head).",
            f"# TYPE {name} gauge"]
    for probe, metric in pairs:
        got = store.latest(probe, metric)
        if got is None:
            continue
        _, val = got
        rows.append(f'{name}{{rank="{int(rank)}",comm="{comm}",'
                    f'probe="{probe}",metric="{metric}"}} {val:.9g}')
    return rows


# ---- report / reset --------------------------------------------------

def report() -> Dict[str, Any]:
    """Structured snapshot for comm_doctor --history."""
    gauges = []
    for probe, metric in store.metrics():
        traj = store.trajectory(probe, metric)
        values = [v for _, v in traj]
        gauges.append({"probe": probe, "metric": metric,
                       "runs": len(traj),
                       "first_run_id": traj[0][0] if traj else None,
                       "last_run_id": traj[-1][0] if traj else None,
                       "latest": values[-1] if values else None,
                       "values": values})
    return {"runs": store.run_count(),
            "samples": store.sample_count(),
            "changepoints": sentry.changepoints(),
            "gauges": gauges,
            "verdicts": sentry.verdicts()}


def reset() -> None:
    store.clear()
    sentry.reset()
