"""history_backfill — seed BENCH_HISTORY.jsonl from banked artifacts.

One-shot: walks a directory of already-banked probe artifacts
(``GOODPUT_<platform>.json``, ``SERVE_<platform>.json``, ...) and
appends one history-plane run per (platform, probe) artifact, so the
trajectory is non-empty from day one.  The probe -> headline-gauge map
is ``ompi_tpu.history.PROBE_GAUGES``.

Idempotent against an existing ledger: an artifact whose gauges
already match the newest banked run for its (platform, probe) is
skipped; anything else banks as the next run_id (derived from ledger
content — no wall clock).

    python -m ompi_tpu.tools.history_backfill [--root DIR] \
        [--out BENCH_HISTORY.jsonl] [--dry-run]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional

from .. import history
from ..history import HistoryStore, append_jsonl


def _load(path: str) -> Optional[Dict[str, Any]]:
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return None


def backfill(root: str, out: str,
             dry_run: bool = False) -> List[Dict[str, Any]]:
    """Returns one summary row per artifact considered."""
    store = HistoryStore()
    store.load_jsonl(out)
    summary: List[Dict[str, Any]] = []
    for probe in sorted(history.PROBE_GAUGES):
        stem, _ = history.PROBE_GAUGES[probe]
        for path in sorted(glob.glob(os.path.join(
                root, f"{stem}_*.json"))):
            doc = _load(path)
            if not isinstance(doc, dict):
                summary.append({"artifact": os.path.basename(path),
                                "probe": probe, "status": "unreadable"})
                continue
            platform = str(doc.get("platform", "") or "")
            rows = history.headline_rows(probe, doc)
            if not platform or not rows:
                summary.append({"artifact": os.path.basename(path),
                                "probe": probe, "status": "no_gauges"})
                continue
            newest = {m: store.latest(probe, m, platform)
                      for m, _v, _u in rows}
            if all(newest[m] is not None and newest[m][1] == v
                   for m, v, _u in rows):
                summary.append({"artifact": os.path.basename(path),
                                "probe": probe, "platform": platform,
                                "status": "already_banked",
                                "run_id": newest[rows[0][0]][0]})
                continue
            rid = store.next_run_id(platform, probe)
            for metric, value, unit in rows:
                row = store.record(rid, platform, probe, metric, value,
                                   unit=unit)
                if not dry_run:
                    append_jsonl(out, row)
            summary.append({"artifact": os.path.basename(path),
                            "probe": probe, "platform": platform,
                            "status": "dry_run" if dry_run else "banked",
                            "run_id": rid, "rows": len(rows)})
    return summary


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="history_backfill",
        description="Seed the history plane's BENCH_HISTORY.jsonl from "
                    "already-banked probe artifacts (one run per "
                    "artifact; idempotent).")
    ap.add_argument("--root", default=".",
                    help="directory holding the banked *_<platform>"
                         ".json artifacts (default: cwd)")
    ap.add_argument("--out", default=None,
                    help="ledger to append to (default: "
                         "<root>/BENCH_HISTORY.jsonl)")
    ap.add_argument("--dry-run", action="store_true",
                    help="report what would bank without writing")
    ns = ap.parse_args(argv)
    out = ns.out or os.path.join(ns.root, "BENCH_HISTORY.jsonl")
    summary = backfill(ns.root, out, dry_run=ns.dry_run)
    banked = [s for s in summary if s["status"] in ("banked", "dry_run")]
    print(json.dumps({"ledger": out, "artifacts": len(summary),
                      "banked": len(banked), "rows": summary}, indent=1))
    return 0 if banked or summary else 1


if __name__ == "__main__":
    sys.exit(main())
