"""Append-only, schema-versioned run ledger for the history plane.

One row per (run_id, platform, probe, metric): the headline gauge a
probe banked for that run — goodput/MFU, per-plane busbw and
bytes, serve tokens/s + ITL quantiles, spec-decode acceptance, quant
SNR dB, ft time-to-recover, verdict/decision counts.  Rows optionally
carry a deterministically downsampled ``series`` chunk (per-step
values within the run) so within-run drift is judged by the same
changepoint kernel as the run-over-run trajectory.

The on-disk form is JSONL (``BENCH_HISTORY.jsonl``): one JSON object
per line, append-only, tolerant of hand-edited or foreign lines on
load (same contract as ``perf.model.load_json``).  ``run_id`` is
supplied by the caller — the store never reads a wall clock; bench
derives the next id from ledger content (``next_run_id``), so an
identical ledger always yields an identical id sequence.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Any, Dict, List, Optional, Tuple

SCHEMA = 1

Key = Tuple[int, str, str, str]          # (run_id, platform, probe, metric)


def downsample(series: List[float], cap: int) -> List[float]:
    """Deterministic bucket-mean downsample to at most ``cap`` points.

    Equal-width index buckets, mean per bucket — preserves slow drift
    (the thing the changepoint kernel judges) rather than extremes.
    """
    vals = [float(v) for v in series]
    n = len(vals)
    cap = max(int(cap), 2)
    if n <= cap:
        return vals
    out: List[float] = []
    for b in range(cap):
        lo = b * n // cap
        hi = max((b + 1) * n // cap, lo + 1)
        chunk = vals[lo:hi]
        out.append(sum(chunk) / len(chunk))
    return out


class HistoryStore:
    """In-memory mirror of the JSONL ledger; last row per key wins."""

    def __init__(self, series_cap: int = 64) -> None:
        self._lock = threading.Lock()
        self.series_cap = int(series_cap)
        self._rows: Dict[Key, Dict[str, Any]] = {}
        self._order: List[Key] = []      # first-append order per key
        self._appended = 0               # monotonic; survives dedup

    # ---- writes ----------------------------------------------------

    def record(self, run_id: int, platform: str, probe: str, metric: str,
               value: float, unit: str = "",
               series: Optional[List[float]] = None,
               extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        row: Dict[str, Any] = {
            "schema": SCHEMA, "run_id": int(run_id),
            "platform": str(platform), "probe": str(probe),
            "metric": str(metric), "value": float(value),
            "unit": str(unit),
        }
        if series:
            row["series"] = downsample(series, self.series_cap)
        if extra:
            for k, v in extra.items():
                row.setdefault(k, v)
        key: Key = (row["run_id"], row["platform"], row["probe"],
                    row["metric"])
        with self._lock:
            if key not in self._rows:
                self._order.append(key)
            self._rows[key] = row
            self._appended += 1
        return row

    # ---- queries ---------------------------------------------------

    def rows(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(self._rows[k]) for k in self._order]

    def sample_count(self) -> int:
        """Monotonic count of record() calls (dedup never decrements)."""
        with self._lock:
            return self._appended

    def run_count(self) -> int:
        """Distinct (platform, probe, run_id) triples banked."""
        with self._lock:
            return len({(k[1], k[2], k[0]) for k in self._rows})

    def next_run_id(self, platform: str, probe: str) -> int:
        """1 + the highest banked run_id for (platform, probe) — the
        caller-supplied id; pure ledger content, no clock."""
        with self._lock:
            ids = [k[0] for k in self._rows
                   if k[1] == platform and k[2] == probe]
        return (max(ids) + 1) if ids else 1

    def metrics(self, probe: Optional[str] = None
                ) -> List[Tuple[str, str]]:
        """Sorted distinct (probe, metric) pairs."""
        with self._lock:
            got = {(k[2], k[3]) for k in self._rows
                   if probe is None or k[2] == probe}
        return sorted(got)

    def trajectory(self, probe: str, metric: str,
                   platform: Optional[str] = None
                   ) -> List[Tuple[int, float]]:
        """Chronological (run_id, value) for one gauge, sorted by
        run_id (the ledger's only notion of time)."""
        with self._lock:
            rows = [self._rows[k] for k in self._order
                    if k[2] == probe and k[3] == metric
                    and (platform is None or k[1] == platform)]
        return sorted(((r["run_id"], r["value"]) for r in rows),
                      key=lambda rv: rv[0])

    def series_of(self, run_id: int, platform: str, probe: str,
                  metric: str) -> List[float]:
        with self._lock:
            row = self._rows.get((int(run_id), platform, probe, metric))
        return list(row.get("series", [])) if row else []

    def latest(self, probe: str, metric: str,
               platform: Optional[str] = None
               ) -> Optional[Tuple[int, float]]:
        traj = self.trajectory(probe, metric, platform)
        return traj[-1] if traj else None

    def clear(self) -> None:
        with self._lock:
            self._rows.clear()
            self._order.clear()
            self._appended = 0

    # ---- persistence (JSONL) ---------------------------------------

    def load_jsonl(self, path: str) -> int:
        """Merge a JSONL ledger in; returns rows accepted.  Bad or
        foreign lines are skipped, not fatal — the ledger is meant to
        survive hand edits and version skew."""
        n = 0
        try:
            with open(path) as fh:
                lines = fh.readlines()
        except OSError:
            return 0
        for line in lines:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
                self.record(row["run_id"], row["platform"], row["probe"],
                            row["metric"], row["value"],
                            unit=row.get("unit", ""),
                            series=row.get("series"),
                            extra={k: v for k, v in row.items()
                                   if k not in ("schema", "run_id",
                                                "platform", "probe",
                                                "metric", "value", "unit",
                                                "series")})
                n += 1
            except (KeyError, TypeError, ValueError):
                continue
        return n

    def save_jsonl(self, path: str) -> int:
        """Rewrite the full ledger atomically (tmp + os.replace) —
        used by the backfill tool; live writers append via
        append_jsonl."""
        rows = self.rows()
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            for row in rows:
                fh.write(json.dumps(row, sort_keys=True) + "\n")
        os.replace(tmp, path)
        return len(rows)


def append_jsonl(path: str, row: Dict[str, Any]) -> None:
    """Append one row to the on-disk ledger (the live write path)."""
    with open(path, "a") as fh:
        fh.write(json.dumps(row, sort_keys=True) + "\n")
