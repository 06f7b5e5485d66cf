"""comm_doctor — fleet communication health from merged traces.

Post-mortem mode (the default): point it at N per-rank Chrome dumps
written by ``trace.save_chrome`` (or one multi-rank dump), optionally
with a saved mpisync offsets table, and it merges them into one
offset-aligned timeline, runs the analyzer (trace/analyze.py) and
renders a human report — flagged stragglers, per-collective entry-skew
distributions, worst (span, arm) latencies, pipeline bubble fraction,
and arm-vs-DEVICE_RULES disagreements.  ``--json`` emits the full
structured report for CI; ``--merged-out`` additionally writes the one
global Chrome trace (pid = rank) for perfetto.

Live mode (``--live`` under tpurun): every rank gathers its ring over
comm_world with an in-band clock sync; rank 0 analyzes and reports.

    python -m ompi_tpu.tools.comm_doctor TRACE.0.json TRACE.1.json \\
        --rules DEVICE_RULES.txt --z 2.5 --json
    tpurun -np 8 -m ompi_tpu.tools.comm_doctor --live
"""

from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Any, Dict, List, Optional, Tuple

from ..trace import analyze as _an
from ..trace import merge as _merge

# bumped whenever any --json report mode changes shape; every mode
# (default merge, --health-dump, --perf, --traffic, --numerics,
# --reshard, --analyze, --live) emits it so downstream tooling can
# detect drift (ISSUE 7 satellite; 4 = the numerics plane section,
# ISSUE 9; 5 = the reshard plan-cache/last-plan section, ISSUE 10;
# 6 = the static-verifier section, ISSUE 11;
# 7 = the ft/elastic recovery section, ISSUE 13;
# 8 = the MoE routing-plane section, ISSUE 14;
# 9 = the serving-plane section, ISSUE 15;
# 10 = the decode fast path: speculative accept/reject ledger +
#      fused-vs-eager dispatch counts in --serve, ISSUE 16;
# 11 = the policy-plane section: verdict->vote->action->effect
#      ledger with attribution, ISSUE 17;
# 12 = the serving-fleet section: per-replica rows, migration
#      ledger, router decision table, ISSUE 18;
# 13 = the request-plane section: per-request stage waterfall,
#      tail-attribution rollup, SLO judge counters, ISSUE 19;
# 14 = the history-plane section: run-trajectory sparklines +
#      changepoint verdicts, ISSUE 20)
SCHEMA_VERSION = 14


def build_report(tl: "_merge.FleetTimeline", rules: Optional[str] = None,
                 z_thresh: float = 2.5) -> Tuple[str, Dict[str, Any]]:
    """(human text, structured dict) for one merged timeline."""
    data = _an.analyze(tl, rules=rules, z_thresh=z_thresh)
    lines: List[str] = []
    w = lines.append
    w(f"comm_doctor: {len(tl.ranks)} rank(s), {len(tl.events)} events")
    conf = data["alignment"]["confidence_us"]
    if conf:
        worst = max(conf.values())
        w(f"  clock alignment: ±{worst:.1f} us worst-rank confidence "
          "(mpisync best-RTT/2)")

    health = data["ring_health"]
    if not health["skew_trustworthy"]:
        w("  !! RING OVERFLOW on rank(s) "
          f"{health['overflowed_ranks']} "
          f"(dropped {health['dropped_by_rank']}) — oldest events were "
          "overwritten mid-capture; skew numbers below are UNTRUSTWORTHY")

    skew = data["entry_skew"]
    if skew["flagged"]:
        w(f"  STRAGGLER(S): rank {skew['flagged']} "
          f"(z >= {skew['z_thresh']}, above clock-sync confidence)")
    elif skew["per_coll"]:
        w(f"  no stragglers flagged (z threshold {skew['z_thresh']})")
    if skew["per_coll"]:
        w("  entry skew per collective (max-min arrival, us):")
        w(f"    {'coll':24s} {'n':>5s} {'p50':>10s} {'p99':>10s} "
          f"{'max':>10s}  last-in")
        for op, row in sorted(skew["per_coll"].items()):
            w(f"    {op:24s} {row['count']:5d} {row['p50']:10.1f} "
              f"{row['p99']:10.1f} {row['max']:10.1f}  "
              f"rank {row['worst_rank']} "
              f"({row['worst_rank_last_count']}x)")
        late = skew["rank_lateness_us"]
        if late:
            w("  mean lateness vs fleet (us): " + ", ".join(
                f"r{r}={v:+.1f}" for r, v in late.items()))

    lat = data["latency"]
    if lat:
        w("  worst links — span latency p99 (us), slowest first:")
        worst = sorted(lat.items(), key=lambda kv: -kv[1]["p99"])[:8]
        for key, row in worst:
            bw = row.get("busbw_GBps")
            w(f"    {key:40s} n={row['count']:<5d} p50={row['p50']:>9.1f} "
              f"p99={row['p99']:>9.1f}"
              + (f"  busbw p50={bw['p50']} GB/s" if bw else ""))

    pipe = data["pipeline"]
    if pipe.get("runs"):
        w(f"  pipeline bubble fraction: {pipe['bubble_fraction_mean']} "
          f"over {len(pipe['runs'])} run(s) "
          + ", ".join(f"[P={r['stages']} M={r['microbatches']} "
                      f"-> {r['bubble_fraction']}]"
                      for r in pipe["runs"][:4]))

    drift = data.get("decision_drift")
    if drift is not None:
        if drift["drift_count"]:
            w(f"  ARM DRIFT: {drift['drift_count']} decision(s) disagree "
              f"with the rules file (checked {drift['checked']}):")
            for d in drift["drift"][:8]:
                w(f"    {d['op']} rank {d['rank']} {d['nbytes']}B: "
                  f"rules say {d['expected']}, executed {d['actual']} "
                  f"({d['reason']})")
        else:
            w(f"  arm-vs-rules: {drift['checked']} decision(s) checked, "
              "no drift")
    return "\n".join(lines), data


def load_health_dump(dump_dir: str) -> List[Dict[str, Any]]:
    """The per-rank ``rank<r>.health.json`` reports a watchdog trip wrote
    into ``health_dump_dir``, sorted by rank."""
    reports = []
    for path in sorted(glob.glob(os.path.join(dump_dir,
                                              "rank*.health.json"))):
        with open(path) as fh:
            reports.append(json.load(fh))
    return reports


def build_health_report(
        reports: List[Dict[str, Any]]) -> Tuple[str, Dict[str, Any]]:
    """(human text, structured dict) for a health_dump_dir's reports:
    per-rank watchdog state, the in-flight op table at trip time, and
    the desync-sentinel verdicts (which rank is behind / desynced)."""
    lines: List[str] = []
    w = lines.append
    w(f"health dump: {len(reports)} rank report(s)")
    behind: Dict[int, int] = {}
    desync: Dict[int, int] = {}
    for rep in reports:
        r = rep.get("rank")
        wd = rep.get("watchdog", {})
        w(f"  rank {r}: action={rep.get('action')} "
          f"timeout={rep.get('timeout_s')}s trips={wd.get('trips')} "
          f"ft_failed={rep.get('ft_failed')}")
        flight = rep.get("inflight") or rep.get("tripped") or []
        if flight:
            w(f"    {'cid':>4s} {'seq':>5s} {'op':20s} {'age_s':>8s} "
              f"{'signature':12s} tripped")
            for e in flight:
                w(f"    {e['cid']:4d} {e['seq']:5d} {e['op']:20s} "
                  f"{e['age_us'] / 1e6:8.3f} {e['signature']:12s} "
                  f"{'*' if e.get('tripped') else ''}")
        v = rep.get("verdict")
        if v:
            from ..health import sentinel
            for ln in sentinel.format_verdict(v).splitlines():
                w("    " + ln)
            for row in v.get("behind", ()):
                behind[row["rank"]] = behind.get(row["rank"], 0) + 1
            for row in v.get("desync", ()):
                desync[row["rank"]] = desync.get(row["rank"], 0) + 1
    if desync:
        worst = max(desync, key=lambda k: desync[k])
        w(f"  VERDICT: rank {worst} called a DIFFERENT collective than "
          f"{desync[worst]} peer(s) at the same sequence point — desync "
          "bug, not a straggler")
    elif behind:
        worst = max(behind, key=lambda k: behind[k])
        w(f"  VERDICT: rank {worst} is BEHIND {behind[worst]} peer(s) — "
          "straggler or hang on that rank")
    elif reports:
        w("  VERDICT: no cross-rank attribution in the dumps "
          "(uniform stall, or sentinel heads unavailable)")
    return "\n".join(lines), {
        "reports": reports,
        "behind_votes": behind,
        "desync_votes": desync,
    }


def build_perf_report(
        ledger_path: Optional[str] = None) -> Tuple[str, Dict[str, Any]]:
    """(human text, structured dict) for the continuous performance
    plane: the cost-model table (per coll/arm/size-bucket busbw + sample
    counts), the current goodput/MFU snapshot, and any active
    perf_regression verdicts. ``ledger_path`` loads a banked
    PERF_LEDGER first (the CLI usually runs in a fresh process, where
    the ledger file IS the state); live in-process state composes on
    top when present."""
    from .. import perf

    if ledger_path:
        perf.load_ledger(ledger_path)
    rep = perf.report()
    lines: List[str] = []
    w = lines.append
    src = f" (ledger: {ledger_path})" if ledger_path else ""
    w(f"perf plane: {len(rep['model'])} modeled cell(s), "
      f"{rep['baseline_keys']} sentry baseline(s){src}")
    if rep["model"]:
        w(f"  {'coll':22s} {'arm':7s} {'bucket':>10s} {'n':>5s} "
          f"{'busbw p50':>10s} {'p95':>8s} {'ewma':>8s} {'lat p50':>9s}")
        for row in rep["model"]:
            w(f"  {row['coll']:22s} {row['arm']:7s} "
              f"{row['bucket_bytes']:>9d}B {row['count']:5d} "
              f"{row['busbw_GBps_p50']:>10.3f} {row['busbw_GBps_p95']:>8.3f} "
              f"{row['busbw_GBps_ewma']:>8.3f} {row['lat_us_p50']:>8.1f}u")
    gp = rep["goodput"]
    if gp["steps"]:
        w(f"  goodput: {gp['goodput_pct']}% of wall is compute "
          f"(MFU {gp['mfu_pct']}%, overlap eff "
          f"{gp['overlap_efficiency']}) over {gp['steps']} step(s)")
    else:
        w("  goodput: no steps recorded")
    if rep["verdicts"]:
        w(f"  PERF REGRESSION: {rep['regressions']} sentry trip(s):")
        for v in rep["verdicts"][-8:]:
            what = (f"{v['coll']} {v['arm']} @{v['bucket_bytes']}B "
                    f"busbw {v['busbw_GBps']} GB/s"
                    if "coll" in v else
                    f"goodput {v.get('goodput_pct')}%")
            w(f"    {what} vs baseline p50 {v['baseline_p50']} "
              f"(z={v['z']}, {v['sustained']} consecutive)")
    elif rep["baseline_keys"]:
        w("  no perf regressions vs the loaded baseline")
    return "\n".join(lines), rep


# byte-intensity ramp for the edge heatmap (space = no traffic)
_HEAT = " .:-=+*#%@"


def build_traffic_report(
        path: Optional[str] = None) -> Tuple[str, Dict[str, Any]]:
    """(human text, structured dict) for the topology traffic plane: the
    per-edge byte matrix as an ASCII heatmap (meshes up to 16 devices),
    the hottest edges, the ICI/DCN/host per-plane rollup, and the
    hot-link sentry verdicts. ``path`` loads a TRAFFIC json (the
    plane's report, bare or under ``"traffic"``); default reads the
    live in-process plane."""
    if path:
        with open(path) as fh:
            rep = json.load(fh)
        rep = rep.get("traffic", rep)
    else:
        from .. import traffic
        rep = traffic.report()
    lines: List[str] = []
    w = lines.append
    edges = rep.get("edges") or []
    planes = rep.get("planes") or {}
    src = f" (from {path})" if path else ""
    w(f"traffic plane: {len(edges)} directed edge(s), "
      f"{int(rep.get('attributed_bytes', 0))} B attributed, "
      f"{int(rep.get('unattributed_bytes', 0))} B unattributed{src}")
    if rep.get("unattributed_bytes"):
        w("  !! CONSERVATION BREACH: bytes placed on no edge — "
          "attribution bug (see traffic_unattributed_bytes)")
    if edges:
        nodes = sorted({e["src"] for e in edges}
                       | {e["dst"] for e in edges})
        if max(nodes) < 16:
            n = max(nodes) + 1
            peak = max(e["bytes"] for e in edges)
            grid = [[0] * n for _ in range(n)]
            for e in edges:
                grid[e["src"]][e["dst"]] = e["bytes"]
            w(f"  edge heatmap (row=src, col=dst; peak {peak} B = "
              f"'{_HEAT[-1]}'):")
            w("       " + " ".join(f"{j:>2d}" for j in range(n)))
            for i in range(n):
                cells = []
                for j in range(n):
                    b = grid[i][j]
                    g = (_HEAT[max(1, round(b / peak
                                            * (len(_HEAT) - 1)))]
                         if b else _HEAT[0])
                    cells.append(f" {g} ")
                w(f"    {i:>2d} " + "".join(cells).rstrip())
        w("  hottest edges:")
        for e in edges[:8]:
            w(f"    {e['src']:3d} -> {e['dst']:3d} "
              f"{e['bytes']:>14d} B  [{e['plane']}]")
    if planes:
        tot = sum(planes.values()) or 1
        w("  per-plane rollup:")
        for p, b in sorted(planes.items()):
            w(f"    {p:5s} {int(b):>14d} B  {100.0 * b / tot:5.1f}%")
    pc = rep.get("per_coll") or {}
    if pc:
        w("  per-collective attribution: " + ", ".join(
            f"{k}={v}B" for k, v in
            sorted(pc.items(), key=lambda kv: -kv[1])[:8]))
    hier = rep.get("hier")
    if hier and hier.get("count"):
        ni = int(hier.get("n_inner") or 0)
        inner_b = int(hier["inner_bytes"])
        outer_b = int(hier["outer_bytes"])
        expect = int(hier["expected_outer_bytes"])
        w(f"  hierarchical split: {int(hier['count'])} collective(s), "
          f"inner (ICI) {inner_b} B vs outer (DCN) {outer_b} B "
          f"(expected <= {expect} B at 1/{ni or '?'} of the buffer)")
        if outer_b > expect:
            w("  !! HIER SPLIT BREACH: outer-plane bytes exceed the "
              f"expected 1/{ni or '?'} fraction — the slow-plane cut "
              "the hier arm exists for is NOT happening (quantized "
              "outer inflated by block padding, or a stage charged to "
              "the wrong plane)")
        else:
            w("  hier outer plane within the expected 1/n_inner "
              "fraction")
    verd = rep.get("verdicts") or []
    if verd:
        w(f"  HOT LINK: {int(rep.get('hotlink_trips', 0))} sentry "
          "trip(s):")
        for v in verd[-8:]:
            if v.get("kind") == "hotlink":
                w(f"    edge {v['src']} -> {v['dst']} carries "
                  f"{v['bytes']} B ({v['ratio']}x the median "
                  f"{v['median_bytes']} B) [{v['plane']}]")
            else:
                w(f"    plane imbalance: {v['hot_plane']} mean/edge is "
                  f"{v['ratio']}x the other plane "
                  f"({v['mean_bytes']})")
    elif edges:
        w("  no hot-link verdicts")
    return "\n".join(lines), rep


def build_numerics_report(
        path: Optional[str] = None) -> Tuple[str, Dict[str, Any]]:
    """(human text, structured dict) for the numerics plane: sample
    counts, non-finite origin verdicts (the first rank/step/op that
    produced each NaN/Inf episode), quant-SNR state vs the banked
    baseline, divergence-auditor verdicts and the per-step grad-norm /
    loss telemetry tail. ``path`` loads a NUMERICS json (the plane's
    report, bare or under ``"report"``); default reads the live
    in-process plane."""
    if path:
        with open(path) as fh:
            rep = json.load(fh)
        rep = rep.get("report", rep)
    else:
        from .. import numerics
        rep = numerics.report()
    lines: List[str] = []
    w = lines.append
    nf = rep.get("nonfinite") or {}
    snr = rep.get("snr") or {}
    div = rep.get("divergence") or {}
    src = f" (from {path})" if path else ""
    w(f"numerics plane: {int(rep.get('samples', 0))} payload "
      f"fingerprint(s){src}")
    if nf.get("verdicts"):
        w(f"  NON-FINITE: {int(nf.get('trips', 0))} episode(s):")
        for v in nf["verdicts"][-8:]:
            who = (f"rank {v['rank']} (input already non-finite)"
                   if v.get("origin") == "input"
                   else "the reduction itself (every input was clean)")
            w(f"    step {v['step']} {v['op']}"
              + (f" [{v['arm']}]" if v.get("arm") else "")
              + f": produced by {who}; "
              f"received by rank(s) {v.get('received_ranks')}")
    else:
        w("  no non-finite episodes")
    if snr.get("samples"):
        w(f"  quant SNR: last {snr.get('last_db')} dB over "
          f"{len(snr['samples'])} sample(s)")
    if snr.get("verdicts"):
        w(f"  SNR REGRESSION: {int(snr.get('trips', 0))} trip(s):")
        for v in snr["verdicts"][-8:]:
            w(f"    {v['coll']} block {v['block']}: {v['snr_db']} dB vs "
              f"baseline p50 {v['baseline_p50']} dB "
              f"(z={v['z']}, {v['sustained']} consecutive)")
    if div.get("verdicts"):
        from ..numerics import consistency
        w(f"  DIVERGENCE: {int(div.get('trips', 0))} audit(s) found "
          "replicas disagreeing:")
        for v in div["verdicts"][-4:]:
            for ln in consistency.format_verdict(v).splitlines():
                w("    " + ln)
    elif div is not None:
        w("  no cross-replica divergence")
    steps = rep.get("steps") or []
    if steps:
        w("  step telemetry (tail):")
        for row in steps[-6:]:
            w(f"    step {row.get('step')}: "
              f"loss={row.get('loss')} grad_norm={row.get('grad_norm')} "
              f"grad_nonfinite={row.get('grad_nonfinite', 0)}")
    return "\n".join(lines), rep


def build_reshard_report(
        path: Optional[str] = None) -> Tuple[str, Dict[str, Any]]:
    """(human text, structured dict) for the redistribution engine:
    plan/step/byte counters, the compiled-plan cache (op sequence, wire
    bytes, peak-vs-bound accounting, device_put fallback reasons) and
    the last executed plan's per-step decision audit. ``path`` loads a
    RESHARD json (the engine's report, bare or under ``"report"``);
    default reads the live in-process engine."""
    if path:
        with open(path) as fh:
            rep = json.load(fh)
        rep = rep.get("report", rep)
    else:
        from ..parallel.reshard import report as _rs_report
        rep = _rs_report()
    lines: List[str] = []
    w = lines.append
    c = rep.get("counters") or {}
    src = f" (from {path})" if path else ""
    w(f"reshard engine: {int(c.get('reshard_plans', 0))} plan(s) "
      f"compiled, {int(c.get('reshard_steps', 0))} step(s) executed, "
      f"{int(c.get('reshard_bytes', 0))} modeled wire byte(s){src}")
    plans = rep.get("plans") or []
    if plans:
        w("  plan cache:")
        for p in plans[-12:]:
            steps = p.get("steps") or []
            w(f"    {p.get('plan')}: "
              + (" -> ".join(steps) if steps else "(identity)"))
            w(f"      wire {int(p.get('wire_bytes', 0))} B, peak "
              f"{int(p.get('peak_bytes', 0))} B within bound "
              f"{int(p.get('bound_bytes', 0))} B"
              + (f"  [fallback: {p['fallback_reason']}]"
                 if p.get("fallback_reason") else ""))
    else:
        w("  plan cache empty (no reshard compiled yet)")
    last = rep.get("last")
    if last:
        w(f"  last plan: {last.get('plan')} — "
          f"{len(last.get('steps') or [])} step(s), "
          f"{int(last.get('wire_bytes', 0))} B wire, peak "
          f"{int(last.get('peak_bytes', 0))}/"
          f"{int(last.get('bound_bytes', 0))} B")
        for s in (last.get("steps") or [])[:12]:
            dur = s.get("dur_us")
            w(f"    step {s.get('step')}: {s.get('op')} -> "
              f"{s.get('arm')} ({s.get('reason')}), "
              f"{int(s.get('wire_bytes', 0))} B"
              + (f", {dur} us" if dur is not None else ""))
    return "\n".join(lines), rep


def build_analyze_report(
        path: Optional[str] = None) -> Tuple[str, Dict[str, Any]]:
    """(human text, structured dict) for the static communication
    verifier: per-program static-vs-runtime wire rows and SPMD check
    issues from an ANALYZE json (``value`` the verdict, ``programs``
    the per-program rows).  The verifier has no live in-process state
    (it runs whole programs), so the default picks the newest
    ``ANALYZE_*.json`` in the working directory."""
    if not path:
        hits = sorted(glob.glob("ANALYZE_*.json"))
        if not hits:
            return ("static verifier: no ANALYZE_*.json in the "
                    "working directory (pass --analyze PATH)"), {}
        path = hits[-1]
    with open(path) as fh:
        doc = json.load(fh)
    lines: List[str] = []
    w = lines.append
    ok = bool(doc.get("value"))
    w(f"static verifier: {'byte-for-byte OK' if ok else 'DISAGREEMENT'}"
      f" on {doc.get('ndev')} device(s) (from {path})")
    for key in ("train_step", "reshard_plan"):
        rep = doc.get(key) or {}
        if not rep:
            continue
        w(f"  {rep.get('source')}: {int(rep.get('n_records', 0))} "
          f"collective record(s), "
          f"{'OK' if rep.get('ok') else 'FAIL'}")
        for r in rep.get("rows") or []:
            w(f"    {r.get('coll')} [{r.get('model')}]: static "
              f"{int(r.get('static', 0))} B vs runtime "
              f"{int(r.get('runtime', 0))} B "
              f"{'==' if r.get('ok') else '!='}")
        for i in rep.get("issues") or []:
            w(f"    [{i.get('severity')}] {i.get('kind')}: "
              f"{i.get('msg')}")
        for h in rep.get("host_transfers") or []:
            w(f"    host transfer: {h}")
    return "\n".join(lines), doc


def build_ft_report(
        path: Optional[str] = None) -> Tuple[str, Dict[str, Any]]:
    """(human text, structured dict) for the elastic-recovery plane:
    recovery/steps-lost/shadow-refresh counters and, per recovery, the
    full choreography timeline (trip verdict -> shrink epoch -> reshard
    plan -> resume step) with wall-clock milestones.  ``path`` loads an
    ELASTIC json (the plane's report, bare or under ``"report"``);
    default reads the live in-process plane."""
    if path:
        with open(path) as fh:
            rep = json.load(fh)
        rep = rep.get("report", rep)
    else:
        from ..ft.elastic import report as _ft_report
        rep = _ft_report()
    lines: List[str] = []
    w = lines.append
    c = rep.get("counters") or {}
    src = f" (from {path})" if path else ""
    w(f"elastic recovery: {int(c.get('ft_recoveries', 0))} recovery(ies), "
      f"{int(c.get('ft_steps_lost', 0))} step(s) lost, "
      f"{int(c.get('ft_shadow_refreshes', 0))} shadow refresh(es){src}")
    recs = rep.get("recoveries") or []
    if not recs:
        w("  no recoveries recorded (no rank death survived yet)")
    for r in recs[-6:]:
        w(f"  recovery: rank {r.get('dead_rank')} died ({r.get('kind')}) "
          f"at step {r.get('trip_step')}, mesh "
          f"{r.get('mesh_before')} -> {r.get('mesh_after')} device(s)")
        w(f"    trip    +{float(r.get('t_trip_ms', 0.0)):.1f} ms  "
          f"verdict={r.get('kind')} dead={r.get('dead')}")
        shrink = r.get("shrink") or {}
        w(f"    shrink  +{float(r.get('t_shrink_ms', 0.0)):.1f} ms  "
          + (f"cid {shrink.get('old_cid')} -> {shrink.get('cid')} "
             f"({shrink.get('name')})" if shrink
             else "single-controller (no comm)"))
        w(f"    reshard +{float(r.get('t_reshard_ms', 0.0)):.1f} ms  "
          f"{int(r.get('leaves', 0))} leaf/leaves, "
          f"{int(r.get('wire_bytes', 0))} B wire, "
          f"{int(r.get('ckpt_reads', 0))} checkpoint read(s)")
        w(f"    resume  +{float(r.get('t_resume_ms', 0.0)):.1f} ms  "
          f"step {r.get('resume_step')} "
          f"({r.get('steps_lost')} step(s) lost, budget "
          f"{r.get('budget_steps')})")
    return "\n".join(lines), rep


def build_moe_report(
        path: Optional[str] = None) -> Tuple[str, Dict[str, Any]]:
    """(human text, structured dict) for the MoE routing plane: routed/
    dropped token counters, per-expert load table, live capacity/aux
    scaling, hot-expert verdicts and the adaptation timeline.  ``path``
    loads a MOE json (the plane's report, bare or under ``"report"``);
    default reads the live in-process plane."""
    if path:
        with open(path) as fh:
            rep = json.load(fh)
        rep = rep.get("report", rep)
    else:
        from .. import moe as _moe
        rep = _moe.report()
    lines: List[str] = []
    w = lines.append
    src = f" (from {path})" if path else ""
    routed = int(rep.get("routed_tokens", 0))
    dropped = int(rep.get("dropped_tokens", 0))
    w(f"moe routing: {int(rep.get('steps', 0))} step(s), "
      f"{routed} token(s) routed, {dropped} dropped "
      f"({100.0 * float(rep.get('drop_rate', 0.0)):.2f}%){src}")
    loads = rep.get("expert_load") or {}
    if loads:
        total = max(sum(int(v) for v in loads.values()), 1)
        w("  per-expert load (share of routed tokens):")
        for e in sorted(loads, key=lambda k: int(k)):
            v = int(loads[e])
            bar = "#" * max(1, round(40 * v / total)) if v else ""
            w(f"    e{int(e):<3d} {v:>10d}  {bar}")
    w(f"  live scaling: capacity x{float(rep.get('cf_scale', 1.0)):g}, "
      f"aux weight x{float(rep.get('aux_scale', 1.0)):g}")
    trips = int(rep.get("hot_expert_trips", 0))
    hot = rep.get("hot_now") or []
    w(f"  hot-expert sentry: {trips} trip(s)"
      + (f", currently hot: {hot}" if hot else ""))
    for v in (rep.get("verdicts") or [])[-6:]:
        w(f"    step {v.get('step')}: expert {v.get('expert')} carried "
          f"{v.get('tokens')} token(s) vs median {v.get('median_tokens')} "
          f"({float(v.get('ratio', 0.0)):.1f}x)")
    adapts = rep.get("adaptations") or []
    if not adapts:
        w("  no capacity adaptations (skew never cleared the cooldown)")
    for a in adapts[-6:]:
        w(f"  adaptation @ step {a.get('step')}: "
          f"cf_scale -> x{float(a.get('cf_scale', 1.0)):g}, "
          f"aux -> x{float(a.get('aux_scale', 1.0)):g}  "
          f"[{a.get('reason')}]")
    return "\n".join(lines), rep


def build_serve_report(
        path: Optional[str] = None) -> Tuple[str, Dict[str, Any]]:
    """(human text, structured dict) for the serving plane: continuous-
    batching occupancy, the prefill/decode/host goodput split, inter-
    token latency percentiles, the per-request lifecycle table and the
    decode collective arm audit.  ``path`` loads a SERVE json (the
    plane's report, bare or under ``"report"`` beside ``"decisions"``);
    default reads the live in-process plane."""
    decisions: Dict[str, Any] = {}
    if path:
        with open(path) as fh:
            doc = json.load(fh)
        rep = doc.get("report", doc)
        decisions = doc.get("decisions", {})
    else:
        from .. import serving as _serving
        from .. import trace as _trace
        rep = _serving.report()
        for c in ("decode_ag", "decode_rs", "decode_collmm"):
            last = _trace.explain_last(c)
            if last is not None:
                decisions[c] = last
    lines: List[str] = []
    w = lines.append
    src = f" (from {path})" if path else ""
    g = rep.get("goodput") or {}
    w(f"serving: {int(rep.get('prefills', 0))} prefill(s), "
      f"{int(rep.get('decode_steps', 0))} decode step(s), "
      f"{int(rep.get('tokens', 0))} token(s), "
      f"{int(rep.get('evictions', 0))} eviction(s){src}")
    w(f"  batch occupancy: "
      f"{100.0 * float(rep.get('batch_occupancy', 0.0)):.1f}% "
      f"(active now: {int(rep.get('active_seqs', 0))}, KV pages held: "
      f"{int(rep.get('kv_pages_used', 0))})")
    if g:
        w("  goodput split: "
          f"prefill {float(g.get('prefill_pct', 0.0)):.1f}% / "
          f"decode {float(g.get('decode_pct', 0.0)):.1f}% / "
          f"host {float(g.get('host_pct', 0.0)):.1f}%  "
          f"({float(g.get('decode_tokens_per_s', 0.0)):.1f} decode "
          "tok/s)")
    itl = rep.get("itl") or {}
    if int(itl.get("count", 0)):
        w(f"  inter-token latency: p50 {float(itl.get('p50_ms', 0)):.2f} "
          f"ms, p99 {float(itl.get('p99_ms', 0)):.2f} ms "
          f"(n={int(itl['count'])})")
    spec = rep.get("speculative") or {}
    if int(spec.get("windows", 0)):
        drafted = int(spec.get("drafted", 0))
        accepted = int(spec.get("accepted", 0))
        w(f"  speculative: {int(spec['windows'])} verify window(s), "
          f"{accepted}/{drafted} draft(s) accepted "
          f"({100.0 * float(spec.get('acceptance_rate', 0.0)):.1f}% "
          f"measured), {drafted - accepted} rejected")
    disp = rep.get("dispatches") or {}
    if any(int(v) for v in disp.values()):
        w(f"  decode dispatches: eager {int(disp.get('eager', 0))} "
          f"(decode_ag/decode_rs between jitted pieces), fused "
          f"{int(disp.get('fused', 0))} (decode_collmm rings inside "
          "the one-program path)")
    decisions = {c: d for c, d in (decisions or {}).items() if d}
    if decisions:
        w("  decode collective arms:")
        for c in sorted(decisions):
            d = decisions[c]
            w(f"    {c}: arm={d.get('arm')} "
              f"wire={int(d.get('wire_bytes', 0))}B/call  "
              f"[{d.get('reason')}]")
    rows = rep.get("requests") or []
    if rows:
        w("  requests (most recent):")
        w("    rid   state    prompt  gen/max  queue_ms  reason")
        for r in rows[-12:]:
            w(f"    {r.get('rid')!s:<5} {r.get('state', '?'):<8} "
              f"{int(r.get('prompt_len', 0)):>6}  "
              f"{int(r.get('generated', 0)):>3}/"
              f"{int(r.get('max_new', 0)):<3}  "
              f"{1e3 * float(r.get('queue_wait_s', 0.0)):>8.2f}  "
              f"{r.get('evict_reason') or '-'}")
    rep = dict(rep)
    if decisions:
        rep["decisions"] = decisions
    return "\n".join(lines), rep


def build_policy_report(
        path: Optional[str] = None) -> Tuple[str, Dict[str, Any]]:
    """(human text, structured dict) for the policy plane: published
    verdicts, the registered (statically pre-verified) rule table, and
    the verdict->vote->action->effect ledger with its attribution
    percentage.  ``path`` loads a POLICY json (the plane's report, bare
    or under ``"report"``); default reads the live in-process plane."""
    if path:
        with open(path) as fh:
            rep = json.load(fh)
        rep = rep.get("report", rep)
    else:
        from .. import policy as _policy
        rep = _policy.report()
    lines: List[str] = []
    w = lines.append
    src = f" (from {path})" if path else ""
    w(f"policy: {'enabled' if rep.get('enabled') else 'disabled'}, "
      f"{int(rep.get('verdicts_published', 0))} verdict(s) published, "
      f"{int(rep.get('decisions_applied', 0))} adaptation(s) applied, "
      f"{int(rep.get('vote_rounds', 0))} vote round(s){src}")
    w(f"  attribution: {float(rep.get('attribution_pct', 100.0)):.1f}% "
      "of applied actions name their causing verdict"
      + (f" ({int(rep.get('unattributed', 0))} unattributed)"
         if int(rep.get("unattributed", 0)) else ""))
    rules = rep.get("rules") or []
    if rules:
        w(f"  rule table ({len(rules)} rule(s), every reachable arm "
          "statically pre-verified at registration):")
        for r in sorted(rules, key=lambda r: str(r.get("rule"))):
            scope = f"{r.get('plane') or '*'}/{r.get('kind') or '*'}"
            reports = r.get("verified") or []
            pred = ""
            if reports:
                v0 = reports[0]
                pred = (f"  wire {int(v0.get('predicted_wire_bytes', 0))}B"
                        f"/{int(v0.get('native_wire_bytes', 0))}B native")
            w(f"    {r.get('rule'):<24} on {scope:<24} "
              f"-> {r.get('action')}{pred}")
    for v in (rep.get("verdicts") or [])[-8:]:
        w(f"  verdict step {v.get('step')}: [{v.get('severity')}] "
          f"{v.get('plane')}/{v.get('kind')}")
    ledger = rep.get("ledger") or []
    if not ledger:
        w("  ledger empty (no verdict has matched an enabled rule)")
    for row in ledger[-10:]:
        vd = row.get("verdict") or {}
        vote = row.get("vote") or {}
        eff = row.get("effect") or {}
        cause = f"{vd.get('plane')}/{vd.get('kind')}"
        votestr = ""
        if vote:
            votestr = (f"  vote r{vote.get('round')} "
                       f"{int(vote.get('yes', 0))}y "
                       f"-> step {vote.get('switch_step')}")
        effstr = ""
        if eff:
            effstr = f"  {eff.get('cvar') or eff.get('arm') or ''}"
            if "prev" in eff:
                effstr += f" {eff.get('prev')}->{eff.get('arm')}"
        w(f"  step {row.get('step')}: {cause} => "
          f"{row.get('rule')} [{row.get('outcome')}]{votestr}{effstr}")
    return "\n".join(lines), rep


def build_fleet_report(
        path: Optional[str] = None) -> Tuple[str, Dict[str, Any]]:
    """(human text, structured dict) for the serving fleet: per-replica
    occupancy/goodput/ITL rows, the KV-page migration ledger (wire
    bytes + standing under the reshard peak contract) and the router
    decision table.  ``path`` loads a FLEET json (the fleet report,
    bare or under ``"report"``); default reads the live in-process
    fleet ledger."""
    if path:
        with open(path) as fh:
            rep = json.load(fh)
        rep = rep.get("report", rep)
    else:
        from .. import serving as _serving
        rep = _serving.fleet_report()
    lines: List[str] = []
    w = lines.append
    src = f" (from {path})" if path else ""
    w(f"fleet: {int(rep.get('replicas', 0))} replica(s), "
      f"{int(rep.get('migrations', 0))} KV-page migration(s), "
      f"{int(rep.get('migrated_bytes', 0))} byte(s) migrated, "
      f"{int(rep.get('rebalances', 0))} route rebalance(s){src}")
    rows = rep.get("replica_rows") or []
    if rows:
        w("  replicas:")
        w("    id  role     reqs  tokens  tok/s     occ%   "
          "itl p50/p99 ms  bias")
        for r in rows:
            if r.get("role") == "prefill":
                w(f"    {int(r.get('replica', 0)):<3d} prefill  "
                  f"{int(r.get('prefills', 0)):>4}  "
                  f"(prefill lane: "
                  f"{float(r.get('prefill_s', 0.0)):.3f}s busy of "
                  f"{float(r.get('clock_s', 0.0)):.3f}s)")
                continue
            w(f"    {int(r.get('replica', 0)):<3d} "
              f"{str(r.get('role', '?')):<8} "
              f"{int(r.get('requests', 0)):>4}  "
              f"{int(r.get('tokens', 0)):>6}  "
              f"{float(r.get('tokens_per_s', 0.0)):>7.1f}  "
              f"{100.0 * float(r.get('occupancy', 0.0)):>5.1f}  "
              f"{float(r.get('itl_p50_ms', 0.0)):>7.2f}/"
              f"{float(r.get('itl_p99_ms', 0.0)):<7.2f}  "
              f"{float(r.get('route_bias', 1.0)):g}")
    migs = rep.get("migration_log") or []
    if migs:
        over = [m for m in migs if not m.get("within_bound", True)]
        w(f"  migration ledger ({len(migs)} most recent"
          + (f"; {len(over)} OVER the peak bound" if over else
             "; all within the reshard peak bound") + "):")
        for m in migs[-8:]:
            w(f"    rid {m.get('rid')!s:<5} r{int(m.get('src', 0))}->"
              f"r{int(m.get('dst', 0))}  {int(m.get('pages', 0)):>3} "
              f"page(s)  {int(m.get('bytes', 0)):>9}B  peak "
              f"{int(m.get('peak_bytes', 0))}/"
              f"{int(m.get('bound_bytes', 0))}B  "
              f"{float(m.get('dur_ms', 0.0)):.2f} ms")
    routes = rep.get("routes") or []
    if routes:
        w(f"  router decisions ({len(routes)} most recent):")
        for r in routes[-8:]:
            ws = "/".join(f"{float(x):g}" for x in
                          (r.get("weights") or []))
            w(f"    rid {r.get('rid')!s:<5} -> replica "
              f"{int(r.get('replica', 0))}  [weights {ws}]")
    return "\n".join(lines), rep


def build_requests_report(
        path: Optional[str] = None) -> Tuple[str, Dict[str, Any]]:
    """(human text, structured dict) for the request plane: headline
    counters, the SLO judge targets, per-stage latency quantiles, the
    tail-attribution rollup and an ASCII waterfall of the slowest kept
    exemplar.  ``path`` loads a REQUESTS json (the plane's report, bare
    or under ``"report"``); default reads the live in-process request
    ledger."""
    if path:
        with open(path) as fh:
            rep = json.load(fh)
        rep = rep.get("report", rep)
    else:
        from ..serving import requests as _requests
        rep = _requests.report()
    lines: List[str] = []
    w = lines.append
    src = f" (from {path})" if path else ""
    w(f"requests: {int(rep.get('completed', 0))} completed, "
      f"{int(rep.get('active', 0))} active, "
      f"{int(rep.get('slo_breaches', 0))} SLO breach(es) in "
      f"{int(rep.get('episodes', 0))} episode(s), "
      f"{int(rep.get('exemplars_kept', 0))} exemplar(s) kept{src}")
    slo = rep.get("slo") or {}
    targets = [f"{k}<={float(v):g}ms" for k, v in sorted(slo.items())
               if float(v or 0.0) > 0.0]
    w("  SLO: " + (" ".join(targets) if targets
                   else "no targets set (judge disarmed)"))
    e2e = rep.get("e2e") or {}
    if e2e.get("count"):
        w(f"  e2e: p50 {float(e2e.get('p50_ms', 0.0)):.2f} ms  "
          f"p99 {float(e2e.get('p99_ms', 0.0)):.2f} ms  "
          f"over {int(e2e['count'])} request(s)")
    stages = rep.get("stages") or {}
    if stages:
        w("  stage           count    p50 ms    p99 ms")
        for name, row in stages.items():
            w(f"    {name:<12} {int(row.get('count', 0)):>6}  "
              f"{float(row.get('p50_ms', 0.0)):>8.2f}  "
              f"{float(row.get('p99_ms', 0.0)):>8.2f}")
    rollup = rep.get("tail_attribution") or {}
    if rollup:
        total = sum(rollup.values()) or 1
        parts = [f"{k}={v} ({100.0 * v / total:.0f}%)" for k, v in
                 sorted(rollup.items(), key=lambda kv: -kv[1])]
        w("  tail attribution (kept exemplars): " + "  ".join(parts))
    brollup = rep.get("breach_attribution") or {}
    if brollup:
        parts = [f"{k}={v}" for k, v in
                 sorted(brollup.items(), key=lambda kv: -kv[1])]
        w("  breach attribution: " + "  ".join(parts))
    exemplars = rep.get("exemplars") or []
    if exemplars:
        worst = max(exemplars,
                    key=lambda e: float(e.get("e2e_ms", 0.0)))
        span = max(float(worst.get("e2e_ms", 0.0)), 1e-9)
        arrival = float(worst.get("arrival", 0.0))
        w(f"  slowest exemplar rid {worst.get('rid')!s} "
          f"(replica {int(worst.get('replica', 0))}, "
          f"{float(worst.get('e2e_ms', 0.0)):.2f} ms e2e, "
          f"attributed {worst.get('attributed_stage')}"
          + (", BREACH" if worst.get("breach") else "") + "):")
        width = 40
        for s in worst.get("spans") or []:
            off = 1e3 * (float(s.get("t0", arrival)) - arrival)
            dur = 1e3 * (float(s.get("t1", 0.0)) - float(s.get("t0", 0.0)))
            lo = int(round(width * max(off, 0.0) / span))
            n = max(1, int(round(width * max(dur, 0.0) / span)))
            bar = " " * min(lo, width - 1) + "#" * min(n, width - lo)
            w(f"    {str(s.get('stage', '?')):<8} r{int(s.get('rank', 0))} "
              f"|{bar:<{width}}| {dur:8.2f} ms")
        cons = worst.get("conservation") or {}
        if cons:
            w(f"    stage sum {float(cons.get('stage_sum_ms', 0.0)):.2f} ms"
              f" vs e2e {float(cons.get('e2e_ms', 0.0)):.2f} ms"
              f" (resid {float(cons.get('resid_ms', 0.0)):.4f} ms)")
    return "\n".join(lines), rep


_SPARK = "▁▂▃▄▅▆▇█"


def _sparkline(values: List[float], width: int = 24) -> str:
    """Deterministic unicode sparkline of a trajectory (downsampled to
    ``width`` by the history store's bucket-mean rule)."""
    from ..history import downsample
    vals = downsample([float(v) for v in values], width)
    lo, hi = min(vals), max(vals)
    span = hi - lo
    if span <= 0.0:
        return _SPARK[3] * len(vals)
    idx = [int((v - lo) / span * (len(_SPARK) - 1)) for v in vals]
    return "".join(_SPARK[i] for i in idx)


def build_history_report(
        path: Optional[str] = None) -> Tuple[str, Dict[str, Any]]:
    """(human text, structured dict) for the history plane: one
    sparkline + trend row per banked (probe, metric) trajectory and
    the changepoint verdicts the sentry attributed.  ``path`` loads a
    HISTORY json (the plane's report, bare or under ``"report"``);
    default reads the live in-process run ledger."""
    if path:
        with open(path) as fh:
            rep = json.load(fh)
        rep = rep.get("report", rep)
    else:
        from .. import history as _history
        rep = _history.report()
    lines: List[str] = []
    w = lines.append
    src = f" (from {path})" if path else ""
    w(f"history: {int(rep.get('runs', 0))} run(s), "
      f"{int(rep.get('samples', 0))} sample(s), "
      f"{int(rep.get('changepoints', 0))} changepoint(s){src}")
    gauges = rep.get("gauges") or []
    if gauges:
        w("  probe      metric                        runs  "
          "trend                     latest")
        for g in gauges:
            vals = [float(v) for v in g.get("values") or []]
            if not vals:
                continue
            spark = _sparkline(vals)
            first, last = vals[0], vals[-1]
            pct = 100.0 * (last - first) / abs(first) if first else 0.0
            w(f"    {str(g.get('probe', '?')):<9}"
              f"{str(g.get('metric', '?')):<30}"
              f"{int(g.get('runs', len(vals))):>4}  "
              f"{spark:<24}  {last:>10.3f} ({pct:+.1f}%)")
    verdicts = rep.get("verdicts") or []
    if verdicts:
        w("  changepoints (one verdict per episode):")
        for v in verdicts:
            where = (f"step {int(v['step_index'])} of run "
                     f"{int(v.get('run_id', 0))}"
                     if v.get("scope") == "series"
                     and v.get("step_index") is not None
                     else f"run {int(v.get('run_id', 0))}")
            w(f"    [{str(v.get('severity', '?')):<5}] "
              f"{str(v.get('probe', '?'))}/"
              f"{str(v.get('metric', '?'))} "
              f"{str(v.get('direction', '?'))} "
              f"{float(v.get('magnitude_pct', 0.0)):+.1f}% at {where} "
              f"(stat {float(v.get('stat', 0.0)):.1f})")
    else:
        w("  no changepoints attributed (trajectory clean or below "
          "the min-run gate)")
    return "\n".join(lines), rep


def _default_ledger() -> Optional[str]:
    hits = sorted(glob.glob("PERF_LEDGER_*.json"))
    return hits[0] if hits else None


def _parse_args(argv: Optional[List[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        prog="comm_doctor",
        description="Merge per-rank traces and diagnose fleet "
                    "communication health.")
    ap.add_argument("dumps", nargs="*",
                    help="per-rank Chrome trace JSON files "
                         "(trace.save_chrome output)")
    ap.add_argument("--offsets", default=None,
                    help="JSON {rank: offset_seconds} clock-offset table "
                         "(mpisync) applied before merging")
    ap.add_argument("--rules", default=None,
                    help="DEVICE_RULES file for the decision-drift check")
    ap.add_argument("--z", type=float, default=2.5,
                    help="straggler z-score flag threshold (default 2.5)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the structured report (CI mode)")
    ap.add_argument("--merged-out", default=None,
                    help="also write the merged global Chrome trace here")
    ap.add_argument("--health-dump", default=None, metavar="DIR",
                    help="load a health_dump_dir written by the watchdog "
                         "(rank*.health.json + rank*.trace.json): renders "
                         "the in-flight table and desync verdict, and "
                         "merges the trace halves through the normal "
                         "pipeline")
    ap.add_argument("--perf", action="store_true",
                    help="render the continuous-performance-plane "
                         "section: cost-model table, goodput/MFU, "
                         "active perf_regression verdicts (loads "
                         "--ledger, or the first PERF_LEDGER_*.json "
                         "in the working directory)")
    ap.add_argument("--ledger", default=None, metavar="PERF_LEDGER.json",
                    help="PERF_LEDGER file for --perf (default: "
                         "autodetect PERF_LEDGER_*.json)")
    ap.add_argument("--traffic", nargs="?", const="", default=None,
                    metavar="TRAFFIC.json",
                    help="render the topology-traffic-plane section: "
                         "per-edge ASCII heatmap, ICI/DCN rollup, "
                         "hot-link verdicts. With a path, loads a "
                         "TRAFFIC json (the plane's report); "
                         "bare flag reads the live in-process plane")
    ap.add_argument("--numerics", nargs="?", const="", default=None,
                    metavar="NUMERICS.json",
                    help="render the numerics-plane section: non-finite "
                         "origin verdicts (rank/step/op), quant-SNR "
                         "sentry state, divergence-auditor verdicts, "
                         "step telemetry. With a path, loads a banked "
                         "NUMERICS json (the plane's report); bare "
                         "flag reads the live in-process plane")
    ap.add_argument("--reshard", nargs="?", const="", default=None,
                    metavar="RESHARD.json",
                    help="render the redistribution-engine section: "
                         "plan cache (op sequences, wire/peak "
                         "accounting), last-plan per-step decision "
                         "audit. With a path, loads a RESHARD json "
                         "(the engine's report); bare flag reads "
                         "the live in-process engine")
    ap.add_argument("--analyze", nargs="?", const="", default=None,
                    metavar="ANALYZE.json",
                    help="render the static-verifier section: "
                         "per-program static-vs-runtime wire rows and "
                         "SPMD check issues from an ANALYZE json; "
                         "bare flag picks "
                         "the newest ANALYZE_*.json")
    ap.add_argument("--ft", nargs="?", const="", default=None,
                    metavar="ELASTIC.json",
                    help="render the elastic-recovery section: the "
                         "trip -> shrink -> reshard -> resume timeline "
                         "per survived rank death, counters, shadow "
                         "refreshes. With a path, loads a banked "
                         "ELASTIC json (the plane's report); bare "
                         "flag reads the live in-process plane")
    ap.add_argument("--moe", nargs="?", const="", default=None,
                    metavar="MOE.json",
                    help="render the MoE routing-plane section: routing "
                         "table, per-expert load, hot-expert verdicts, "
                         "capacity/aux adaptation timeline. With a "
                         "path, loads a MOE json (the plane's "
                         "report); bare flag reads the live in-process "
                         "plane")
    ap.add_argument("--serve", nargs="?", const="", default=None,
                    metavar="SERVE.json",
                    help="render the serving-plane section: continuous-"
                         "batching occupancy, goodput split, inter-"
                         "token latency p50/p99, per-request lifecycle "
                         "table and the decode_ag/decode_rs arm audit. "
                         "With a path, loads a SERVE json (the "
                         "plane's report); bare flag reads the live "
                         "in-process plane")
    ap.add_argument("--policy", nargs="?", const="", default=None,
                    metavar="POLICY.json",
                    help="render the policy-plane section: published "
                         "verdicts, the pre-verified rule table and "
                         "the verdict->vote->action->effect ledger "
                         "with attribution. With a path, loads a "
                         "POLICY json (the plane's report); "
                         "bare flag reads the live in-process plane")
    ap.add_argument("--fleet", nargs="?", const="", default=None,
                    metavar="FLEET.json",
                    help="render the serving-fleet section: per-replica "
                         "occupancy/goodput/ITL rows, the KV-page "
                         "migration ledger and the router decision "
                         "table. With a path, loads a FLEET json "
                         "(the fleet report); bare flag reads "
                         "the live in-process fleet ledger")
    ap.add_argument("--requests", nargs="?", const="", default=None,
                    metavar="REQUESTS.json",
                    help="render the request-plane section: per-request "
                         "stage waterfall, tail-attribution rollup and "
                         "the SLO judge counters. With a path, loads a "
                         "REQUESTS json (the plane's report); bare "
                         "flag reads the live in-process request ledger")
    ap.add_argument("--history", nargs="?", const="", default=None,
                    metavar="HISTORY.json",
                    help="render the history-plane section: one "
                         "sparkline/trend row per banked run "
                         "trajectory plus the changepoint verdicts. "
                         "With a path, loads a HISTORY json (the "
                         "plane's report); bare flag reads the "
                         "live in-process run ledger")
    ap.add_argument("--live", action="store_true",
                    help="gather over comm_world instead of reading "
                         "dumps (run under tpurun)")
    ap.add_argument("--rounds", type=int, default=8,
                    help="clock-sync ping-pong rounds in --live mode")
    return ap.parse_args(argv)


def main(argv: Optional[List[str]] = None) -> int:
    ns = _parse_args(argv)
    if ns.live:
        from .. import runtime

        ctx = runtime.init()
        tl = _merge.gather(ctx.comm_world, rounds=ns.rounds)
        try:
            if tl is None:            # non-root ranks
                return 0
            return _report(tl, ns)
        finally:
            runtime.finalize()
    if ns.health_dump:
        reports = load_health_dump(ns.health_dump)
        if not reports:
            print(f"comm_doctor: no rank*.health.json under "
                  f"{ns.health_dump}")
            return 2
        htext, hdata = build_health_report(reports)
        # the dump's trace halves go through the normal merge pipeline so
        # the stall shows up in context (skew, latency, decisions)
        traces = ns.dumps or sorted(glob.glob(
            os.path.join(ns.health_dump, "rank*.trace.json")))
        tl = _merge.merge(_merge.load_chrome(traces)) if traces else None
        return _report(tl, ns, health=(htext, hdata))
    if not ns.dumps:
        if (ns.perf or ns.traffic is not None or ns.numerics is not None
                or ns.reshard is not None or ns.analyze is not None
                or ns.ft is not None or ns.moe is not None
                or ns.serve is not None or ns.policy is not None
                or ns.fleet is not None or ns.requests is not None
                or ns.history is not None):
            # plane sections render standalone (no merged timeline)
            return _report(None, ns)
        print("comm_doctor: no trace dumps given (and not --live); "
              "nothing to diagnose")
        return 2
    offsets, best_rtt = (_merge.load_offsets_ex(ns.offsets)
                         if ns.offsets else (None, None))
    per_rank = _merge.load_chrome(ns.dumps)
    tl = _merge.merge(per_rank, offsets=offsets, best_rtt=best_rtt)
    return _report(tl, ns)


def _report(tl: Optional["_merge.FleetTimeline"], ns: argparse.Namespace,
            health: Optional[Tuple[str, Dict[str, Any]]] = None) -> int:
    if tl is not None and ns.merged_out:
        tl.save_chrome(ns.merged_out)
    text, data = (build_report(tl, rules=ns.rules, z_thresh=ns.z)
                  if tl is not None else ("", {}))
    if health is not None:
        text = (health[0] + "\n" + text) if text else health[0]
        data["health"] = health[1]
    if getattr(ns, "perf", False):
        ptext, pdata = build_perf_report(ns.ledger or _default_ledger())
        text = (text + "\n" + ptext) if text else ptext
        data["perf"] = pdata
    if getattr(ns, "traffic", None) is not None:
        ttext, tdata = build_traffic_report(ns.traffic or None)
        text = (text + "\n" + ttext) if text else ttext
        data["traffic"] = tdata
    if getattr(ns, "numerics", None) is not None:
        ntext, ndata = build_numerics_report(ns.numerics or None)
        text = (text + "\n" + ntext) if text else ntext
        data["numerics"] = ndata
    if getattr(ns, "reshard", None) is not None:
        rtext, rdata = build_reshard_report(ns.reshard or None)
        text = (text + "\n" + rtext) if text else rtext
        data["reshard"] = rdata
    if getattr(ns, "analyze", None) is not None:
        atext, adata = build_analyze_report(ns.analyze or None)
        text = (text + "\n" + atext) if text else atext
        data["analyze"] = adata
    if getattr(ns, "ft", None) is not None:
        ftext, fdata = build_ft_report(ns.ft or None)
        text = (text + "\n" + ftext) if text else ftext
        data["ft"] = fdata
    if getattr(ns, "moe", None) is not None:
        mtext, mdata = build_moe_report(ns.moe or None)
        text = (text + "\n" + mtext) if text else mtext
        data["moe"] = mdata
    if getattr(ns, "serve", None) is not None:
        stext, sdata = build_serve_report(ns.serve or None)
        text = (text + "\n" + stext) if text else stext
        data["serve"] = sdata
    if getattr(ns, "policy", None) is not None:
        ptext, pdata = build_policy_report(ns.policy or None)
        text = (text + "\n" + ptext) if text else ptext
        data["policy"] = pdata
    if getattr(ns, "fleet", None) is not None:
        fltext, fldata = build_fleet_report(ns.fleet or None)
        text = (text + "\n" + fltext) if text else fltext
        data["fleet"] = fldata
    if getattr(ns, "requests", None) is not None:
        rqtext, rqdata = build_requests_report(ns.requests or None)
        text = (text + "\n" + rqtext) if text else rqtext
        data["requests"] = rqdata
    if getattr(ns, "history", None) is not None:
        hitext, hidata = build_history_report(ns.history or None)
        text = (text + "\n" + hitext) if text else hitext
        data["history"] = hidata
    data["schema_version"] = SCHEMA_VERSION
    if ns.as_json:
        if ns.merged_out:
            data["merged_chrome_trace"] = ns.merged_out
        print(json.dumps(data, indent=1))
    else:
        print(text)
        if ns.merged_out:
            print(f"  merged Chrome trace: {ns.merged_out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
