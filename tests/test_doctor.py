"""Fleet flight recorder: cross-rank merge, straggler doctor, mpisync,
Prometheus exposition (trace/merge.py, trace/analyze.py,
tools/comm_doctor.py, tools/mpisync.py, spc.export_prometheus)."""

import json
import re
import time

import numpy as np
import pytest

from ompi_tpu import mpit, runtime, spc, trace
from ompi_tpu.core import var
from ompi_tpu.tools import comm_doctor, mpisync
from ompi_tpu.trace import analyze, merge


@pytest.fixture(autouse=True)
def _tracing():
    trace.clear()
    trace.enable(capacity=65536)
    yield
    trace.disable()
    trace.clear()


# ---------------------------------------------------------------------------
# mpisync: size-1 short-circuit, offsets + best_rtt, bcast agreement
# ---------------------------------------------------------------------------

def test_mpisync_size1_no_pingpong():
    """A size-1 comm has no peer clock: both tables are zero and NO
    traffic is generated (the early return never touches send/recv)."""
    def fn(ctx):
        c = ctx.comm_world
        before = ctx.spc.get("sends") + ctx.spc.get("isends")
        off, rtt = mpisync.clock_sync_ex(c)
        off_only = mpisync.clock_sync(c)
        after = ctx.spc.get("sends") + ctx.spc.get("isends")
        return off, rtt, off_only, after - before

    off, rtt, off_only, traffic = runtime.run_ranks(1, fn)[0]
    assert off.shape == (1,) and off[0] == 0.0
    assert rtt.shape == (1,) and rtt[0] == 0.0
    assert off_only.shape == (1,) and off_only[0] == 0.0
    assert traffic == 0


def test_mpisync_offsets_and_best_rtt():
    def fn(ctx):
        return mpisync.clock_sync_ex(ctx.comm_world, rounds=6)

    res = runtime.run_ranks(2, fn, timeout=60)
    for off, rtt in res:
        assert off.shape == (2,) and rtt.shape == (2,)
        assert off[0] == 0.0 and rtt[0] == 0.0        # rank 0 is the origin
        assert rtt[1] > 0.0 and np.isfinite(off[1])
        # threaded ranks share one monotonic clock: the measured offset is
        # pure scheduling residual, bounded by the confidence the RTT sets
        assert abs(off[1]) <= max(rtt[1], 0.1)
    # the table is bcast: every rank sees the same numbers
    np.testing.assert_array_equal(res[0][0], res[1][0])
    np.testing.assert_array_equal(res[0][1], res[1][1])


# ---------------------------------------------------------------------------
# satellite: the enabled gate follows the vars without losing the
# one-attribute-read disabled path
# ---------------------------------------------------------------------------

def test_trace_var_write_toggles_enabled():
    trace.disable()
    var.registry.set_cli("trace_enabled", "1")
    var.registry.reset_cache()
    try:
        assert trace.enabled is True          # CLI write reached the gate
        # notify fires on CHANGE only: with the var still resolving to 1,
        # a reset_cache pass does NOT clobber a direct disable()
        trace.disable()
        var.registry.reset_cache()
        assert trace.enabled is False
        trace.enable()
    finally:
        var.registry.clear_cli("trace_enabled")
    assert trace.enabled is False             # 1 → default False IS a change
    # cvar_write (MPI_T path) flows through the same watcher
    mpit.cvar_write("trace_enabled", True)
    assert trace.enabled is True
    mpit.cvar_write("trace_enabled", False)
    assert trace.enabled is False
    # and enable() survives a no-change reset_cache pass
    trace.enable()
    var.registry.reset_cache()
    assert trace.enabled is True


def test_trace_enable_rereads_capacity_var():
    var.registry.set_cli("trace_buffer_events", "16")
    var.registry.reset_cache()
    try:
        trace.enable()                        # no arg → re-read the var
        for i in range(40):
            trace.instant(f"e{i}", "event")
        assert len(trace.events()) == 16
        assert trace.dropped_events() == 24
    finally:
        var.registry.clear_cli("trace_buffer_events")


def test_trace_disabled_path_is_one_attribute_read():
    """The cost contract: ``trace.enabled`` is a plain module attribute
    (no property, no module __getattr__, no function call) holding a
    plain bool — one LOAD_ATTR on the disabled path."""
    trace.disable()
    assert "enabled" in vars(trace)           # real attribute, not derived
    assert type(trace.enabled) is bool
    assert not hasattr(trace, "__getattr__")  # no module-level lazy hook
    assert not isinstance(vars(trace)["enabled"], property)


# ---------------------------------------------------------------------------
# satellite: per-rank dropped-event accounting
# ---------------------------------------------------------------------------

def test_dropped_events_per_rank():
    trace.enable(capacity=4)
    for r, n in ((0, 4), (1, 7), (2, 12)):
        for i in range(n):
            trace.instant(f"r{r}e{i}", "event", rank=r)
    assert trace.dropped_events(0) == 0
    assert trace.dropped_events(1) == 3
    assert trace.dropped_events(2) == 8
    assert trace.dropped_events(99) == 0      # no ring, nothing dropped
    assert trace.dropped_by_rank() == {0: 0, 1: 3, 2: 8}
    assert trace.dropped_events() == 11       # process-wide pvar total
    st = trace.stats()
    assert st["dropped_by_rank"] == {0: 0, 1: 3, 2: 8}
    assert st["dropped_events"] == 11
    assert "dropped by rank" in trace.format_stats()
    # per-rank view through stats(rank=...)
    assert trace.stats(1)["dropped_by_rank"] == {1: 3}


# ---------------------------------------------------------------------------
# tentpole: merge + straggler attribution on synthetic arrivals
# ---------------------------------------------------------------------------

def _synthetic_fleet(n_ranks=4, straggler=3, delay=8e-4, instances=12):
    """Every rank enters each allreduce instance; one rank enters late."""
    for k in range(instances):
        base = k * 1e-3
        for r in range(n_ranks):
            late = delay if r == straggler else 0.0
            trace.instant("enter:allreduce", "coll-enter", rank=r,
                          args={"op": "allreduce"},
                          t=base + late + r * 1e-6)


def test_doctor_flags_injected_straggler_exactly():
    _synthetic_fleet(straggler=3, delay=8e-4)
    tl = merge.merge({r: trace.events(r) for r in range(4)})
    sk = analyze.entry_skew(tl, z_thresh=2.0)
    assert sk["flagged"] == [3]               # exactly the injected rank
    row = sk["per_coll"]["allreduce"]
    assert row["count"] == 12
    assert 750 <= row["p99"] <= 850           # ~800 us injected skew
    assert row["worst_rank"] == 3 and row["worst_rank_last_count"] == 12
    assert sk["z_scores"][3] >= 2.0
    assert sk["rank_lateness_us"][3] > 0


def test_straggler_gated_by_clock_confidence():
    """Lateness inside the mpisync ±rtt/2 bound is never flagged — it
    may be alignment error, not a straggler."""
    _synthetic_fleet(straggler=3, delay=8e-4)
    tl = merge.merge({r: trace.events(r) for r in range(4)},
                     best_rtt={3: 0.01})      # ±5000 us >> 600 us lateness
    sk = analyze.entry_skew(tl, z_thresh=2.0)
    assert sk["flagged"] == []
    assert sk["z_scores"][3] >= 2.0           # the z still reports it


# ---------------------------------------------------------------------------
# tentpole: decision drift vs DEVICE_RULES
# ---------------------------------------------------------------------------

def test_decision_drift_vetoes_and_last_row_wins():
    rules = [("allreduce", 1, 0, "staged"),
             ("allreduce", 1, 1 << 20, "native")]
    kw = dict(ndev=4)
    # below the 1 MiB row: expected staged
    trace.decision("allreduce", "native", "default:platform cpu", 4096, **kw)
    trace.decision("allreduce", "staged", "rule:allreduce 1 0 staged",
                   4096, **kw)
    trace.decision("allreduce", "quant",
                   "force:coll_xla_allreduce_mode=quant", 4096, **kw)
    # above it: LAST matching row wins → expected native, so this is clean
    trace.decision("allreduce", "native", "default:platform cpu",
                   2 << 20, **kw)
    # a veto prefix sanctions disagreement even against the last row
    trace.decision("allreduce", "staged",
                   "ineligible:dtype", 2 << 20, **kw)
    # unmatched op: not checked at all
    trace.decision("alltoall", "staged", "default:small", 4096, **kw)
    tl = merge.merge({0: trace.events(0)})
    rep = analyze.decision_drift(tl, rules)
    assert rep["checked"] == 5
    assert rep["drift_count"] == 1
    d = rep["drift"][0]
    assert d["op"] == "allreduce" and d["nbytes"] == 4096
    assert d["expected"] == "staged" and d["actual"] == "native"
    assert d["reason"].startswith("default:")


def test_bubble_fraction_from_pipeline_span():
    trace.record_span("pipeline:run", "pipeline", 0.0, 0.1,
                      args={"stages": 4, "microbatches": 4, "ticks": 7})
    trace.record_span("grad_sync:run", "overlap", 0.2, 0.25,
                      args={"mode": "bucketed", "ndev": 8})
    tl = merge.merge({0: trace.events(0)})
    pipe = analyze.bubble_fraction(tl)
    assert pipe["runs"][0]["bubble_fraction"] == round(3 / 7, 4)
    assert pipe["bubble_fraction_mean"] == round(3 / 7, 4)
    assert pipe["grad_sync_run_us"] == [pytest.approx(50000.0, abs=1)]


# ---------------------------------------------------------------------------
# tentpole: per-rank dumps → load → merge → one global Chrome trace
# ---------------------------------------------------------------------------

def test_merged_chrome_monotonic_and_nonoverlapping(tmp_path):
    # adjacent spans per rank — the worst case for µs floor-rounding —
    # plus an arrival instant, on three ranks with skewed clocks
    for r in range(3):
        t = 0.0
        for i in range(5):
            trace.record_span(f"work:{i}", "span", t, t + 1e-4, rank=r)
            t += 1e-4
        trace.instant("enter:allreduce", "coll-enter", rank=r,
                      args={"op": "allreduce"}, t=t)
    paths = []
    for r in range(3):
        p = str(tmp_path / f"trace.{r}.json")
        assert trace.save_chrome(p, rank=r) == p
        paths.append(p)

    per_rank = merge.load_chrome(paths)
    assert sorted(per_rank) == [0, 1, 2]
    assert all(len(v) == 6 for v in per_rank.values())
    offsets = {0: 0.0, 1: -2e-3, 2: 3e-3}     # rank clocks disagree
    tl = merge.merge(per_rank, offsets=offsets,
                     best_rtt={r: 1e-5 for r in range(3)})
    ts = [e["t"] for e in tl.events]
    assert ts == sorted(ts)                   # globally monotonic after align
    assert tl.ranks == [0, 1, 2]

    out = str(tmp_path / "merged.json")
    tl.save_chrome(out)
    with open(out) as fh:
        doc = json.load(fh)
    rows = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    assert rows and all(e["ts"] >= 0 for e in rows)
    assert [e["ts"] for e in rows] == sorted(e["ts"] for e in rows)
    assert {e["pid"] for e in rows} == {0, 1, 2}          # pid = rank kept
    lanes = {}
    for e in rows:
        if e["ph"] == "X":
            lanes.setdefault((e["pid"], e["tid"]), []).append(e)
    assert lanes
    for spans in lanes.values():
        spans.sort(key=lambda e: e["ts"])
        for a, b in zip(spans, spans[1:]):
            assert a["ts"] + a["dur"] <= b["ts"], (a, b)
    meta = doc["otherData"]
    assert meta["merged_ranks"] == [0, 1, 2]
    assert meta["clock_offsets_s"]["2"] == 3e-3
    assert meta["best_rtt_s"]["1"] == 1e-5


def test_load_offsets_flat_list_and_combined_forms(tmp_path):
    flat = tmp_path / "flat.json"
    flat.write_text(json.dumps({"0": 0.0, "1": -2e-3}))
    as_list = tmp_path / "list.json"
    as_list.write_text(json.dumps([0.0, -2e-3, 3e-3]))
    combined = tmp_path / "combined.json"
    combined.write_text(json.dumps({"offsets": {"0": 0.0, "1": 4e-3},
                                    "best_rtt": {"0": 0.0, "1": 1e-4}}))

    assert merge.load_offsets(str(flat)) == {0: 0.0, 1: -2e-3}
    assert merge.load_offsets(str(as_list)) == {0: 0.0, 1: -2e-3, 2: 3e-3}
    offs, rtt = merge.load_offsets_ex(str(combined))
    assert offs == {0: 0.0, 1: 4e-3} and rtt == {0: 0.0, 1: 1e-4}
    # flat forms carry no RTT half — the analyzer then has no
    # clock-confidence bound to gate stragglers on
    assert merge.load_offsets_ex(str(flat))[1] == {}


# ---------------------------------------------------------------------------
# tentpole: in-band gather over the comm (the --live path)
# ---------------------------------------------------------------------------

def test_gather_over_comm_attributes_live_straggler():
    """4 threaded ranks run host allreduces; rank 2 carries an injected
    delay.  gather() clock-syncs, ships every ring to rank 0 and the
    analyzer attributes exactly that rank."""
    def fn(ctx):
        c = ctx.comm_world
        for _ in range(6):
            if ctx.rank == 2:
                time.sleep(0.006)
            c.coll.allreduce(c, np.ones(8, np.float32))
        return merge.gather(c, rounds=5)

    res = runtime.run_ranks(4, fn, timeout=120)
    tl = res[0]
    assert all(r is None for r in res[1:])    # root-only result
    assert isinstance(tl, merge.FleetTimeline)
    assert tl.ranks == [0, 1, 2, 3]
    assert set(tl.dropped) == {0, 1, 2, 3}
    assert all(v == 0 for v in tl.dropped.values())
    arr = tl.arrivals("allreduce")
    assert {e["rank"] for e in arr} == {0, 1, 2, 3}
    sk = analyze.entry_skew(tl, z_thresh=2.0)
    assert sk["flagged"] == [2], sk
    assert sk["per_coll"]["allreduce"]["p99"] >= 3000   # ~6 ms injected


# ---------------------------------------------------------------------------
# tentpole: the doctor CLI
# ---------------------------------------------------------------------------

def test_comm_doctor_cli_json_and_text(tmp_path, capsys):
    _synthetic_fleet(straggler=1, delay=1e-3)
    trace.decision("allreduce", "native", "default:platform cpu",
                   4096, ndev=4)
    trace.record_span("pipeline:run", "pipeline", 0.05, 0.15,
                      args={"stages": 4, "microbatches": 4, "ticks": 7})
    paths = []
    for r in range(4):
        p = str(tmp_path / f"t.{r}.json")
        trace.save_chrome(p, rank=r)
        paths.append(p)
    rules = tmp_path / "rules.txt"
    rules.write_text("allreduce 1 0 staged\n")
    merged = str(tmp_path / "merged.json")

    rc = comm_doctor.main(paths + ["--rules", str(rules), "--z", "2.0",
                                   "--json", "--merged-out", merged])
    assert rc == 0
    data = json.loads(capsys.readouterr().out)
    assert data["entry_skew"]["flagged"] == [1]
    assert data["entry_skew"]["per_coll"]["allreduce"]["p99"] > 0
    assert data["decision_drift"]["drift_count"] == 1
    assert data["pipeline"]["runs"][0]["bubble_fraction"] == round(3 / 7, 4)
    assert data["ring_health"]["skew_trustworthy"]
    assert data["merged_chrome_trace"] == merged
    assert json.load(open(merged))["traceEvents"]

    rc = comm_doctor.main(paths + ["--z", "2.0"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "STRAGGLER(S): rank [1]" in out
    assert "entry skew per collective" in out
    assert "pipeline bubble fraction" in out

    assert comm_doctor.main([]) == 2          # nothing to diagnose


def test_comm_doctor_warns_on_ring_overflow(tmp_path, capsys):
    trace.enable(capacity=4)
    for i in range(10):
        trace.instant(f"e{i}", "event", rank=0)
    tl = merge.merge({0: trace.events(0)},
                     dropped=dict(trace.dropped_by_rank()))
    text, data = comm_doctor.build_report(tl)
    assert "RING OVERFLOW" in text and "UNTRUSTWORTHY" in text
    assert data["ring_health"]["overflowed_ranks"] == [0]
    assert data["ring_health"]["dropped_by_rank"] == {0: 6}


# ---------------------------------------------------------------------------
# tentpole: Prometheus text exposition over pvars + monitoring matrices
# ---------------------------------------------------------------------------

_PROM_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_PROM_LABEL = r'[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\.)*"'
_PROM_SAMPLE = re.compile(
    rf"^{_PROM_NAME}(?:\{{{_PROM_LABEL}(?:,{_PROM_LABEL})*\}})?"
    r" [-+]?(?:[0-9]*\.?[0-9]+(?:[eE][-+]?[0-9]+)?|NaN|Inf)$")
_PROM_HELP = re.compile(rf"^# HELP {_PROM_NAME} \S.*$")
_PROM_TYPE = re.compile(
    rf"^# TYPE ({_PROM_NAME}) (counter|gauge|histogram|summary|untyped)$")


def _assert_prometheus_grammar(text):
    """Every line must be a HELP, TYPE or sample line of the Prometheus
    text exposition format; samples must follow their TYPE."""
    assert text.endswith("\n")
    typed = set()
    samples = 0
    for line in text.rstrip("\n").split("\n"):
        m = _PROM_TYPE.match(line)
        if m:
            typed.add(m.group(1))
            continue
        if _PROM_HELP.match(line):
            continue
        assert _PROM_SAMPLE.match(line), f"bad exposition line: {line!r}"
        samples += 1
        assert line.split("{")[0] in typed, f"sample before TYPE: {line!r}"
    assert samples > 0
    return samples


def test_export_prometheus_parses_and_carries_labels():
    from ompi_tpu import monitoring

    def fn(ctx):
        monitoring.install(ctx)
        c = ctx.comm_world
        if ctx.rank == 0:
            c.send(np.ones(4), 1, tag=5)
        else:
            c.recv(np.zeros(4), 0, tag=5)
        c.coll.allreduce(c, np.ones(4, np.float32))
        c.barrier()
        return spc.export_prometheus(ctx) if ctx.rank == 0 else None

    text = runtime.run_ranks(2, fn, timeout=60)[0]
    n = _assert_prometheus_grammar(text)
    assert n >= len(spc.COUNTERS)
    assert 'ompi_tpu_isends{rank="0",comm="world"}' in text
    assert "ompi_tpu_trace_dropped_events" in text       # pvar read-through
    # monitoring matrices rode along with class/peer labels
    assert 'ompi_tpu_monitoring_bytes{rank="0",comm="world",' in text
    assert 'ompi_tpu_monitoring_coll_ops_total{' in text
    assert 'coll="allreduce"' in text


def test_export_prometheus_bare_counters():
    """No monitoring installed: the plain Counters surface alone still
    parses, with custom comm/prefix labels."""
    c = spc.Counters()
    c.inc("isends", 3)
    text = spc.export_prometheus(c, comm="sub0", prefix="tpu")
    _assert_prometheus_grammar(text)
    assert 'tpu_isends{rank="0",comm="sub0"} 3' in text


# ---------------------------------------------------------------------------
# partial clock-offset tables: the merge must degrade LOUDLY (ISSUE 17)
# ---------------------------------------------------------------------------

def _capture_output_stream():
    """output._stream is bound at import (pytest's capture object), so
    capsys/capfd never see it — swap in a StringIO for the assertion."""
    import contextlib
    import io
    from ompi_tpu.core.output import output

    @contextlib.contextmanager
    def cm():
        buf = io.StringIO()
        prev = output._stream
        output._stream = buf
        try:
            yield buf
        finally:
            output._stream = prev
    return cm()


def test_merge_partial_offsets_degrades_loudly():
    """Ranks missing from a non-empty offsets table stay on their local
    clocks, are recorded in unaligned_ranks, and an error is printed —
    silently merging half-aligned clocks manufactures stragglers out of
    alignment error."""
    _synthetic_fleet(straggler=3, delay=8e-4)
    per_rank = {r: trace.events(r) for r in range(4)}
    t_orig = {r: [e["t"] for e in evs] for r, evs in per_rank.items()}
    with _capture_output_stream() as buf:
        tl = merge.merge(per_rank, offsets={0: 0.0, 1: -2e-3, 2: 1e-3})
    assert tl.unaligned_ranks == [3]
    err = buf.getvalue()
    assert "offsets table covers rank(s) [0, 1, 2] but not [3]" in err
    assert "local clocks" in err
    # covered ranks shifted by their offset; the uncovered rank untouched
    assert [e["t"] for e in tl.by_rank(1)] == pytest.approx(
        [t + 2e-3 for t in sorted(t_orig[1])])
    assert [e["t"] for e in tl.by_rank(3)] == pytest.approx(
        sorted(t_orig[3]))


def test_merge_empty_offsets_stays_quiet():
    """An empty/absent table means 'no alignment attempted' (single-clock
    runs) — no unaligned ranks, no error."""
    _synthetic_fleet()
    with _capture_output_stream() as buf:
        tl = merge.merge({r: trace.events(r) for r in range(4)})
        assert tl.unaligned_ranks == []
        tl = merge.merge({r: trace.events(r) for r in range(4)}, offsets={})
        assert tl.unaligned_ranks == []
    assert "unaligned" not in buf.getvalue()


def test_entry_skew_never_flags_unaligned_rank():
    """A rank the merge could not align is never attributed as a
    straggler — its 'lateness' is its unshifted clock."""
    _synthetic_fleet(straggler=3, delay=8e-4)
    tl = merge.merge({r: trace.events(r) for r in range(4)},
                     offsets={0: 0.0, 1: 0.0, 2: 0.0})
    sk = analyze.entry_skew(tl, z_thresh=2.0)
    assert sk["flagged"] == []
    assert sk["z_scores"][3] >= 2.0           # the z still reports it


def test_load_chrome_partial_offsets_roundtrip(tmp_path):
    """load_chrome dumps + a partial offsets table: unaligned_ranks
    survives into analyze()'s alignment section and the merged Chrome
    export's otherData."""
    _synthetic_fleet(n_ranks=2, straggler=1, delay=8e-4)
    paths = []
    for r in range(2):
        p = str(tmp_path / f"t.{r}.json")
        trace.save_chrome(p, rank=r)
        paths.append(p)
    per = merge.load_chrome(paths)
    assert set(per) == {0, 1}
    with _capture_output_stream() as buf:
        tl = merge.merge(per, offsets={0: 0.0})   # table misses rank 1
    assert tl.unaligned_ranks == [1]
    assert "not [1]" in buf.getvalue()
    rep = analyze.analyze(tl, z_thresh=2.0)
    assert rep["alignment"]["unaligned_ranks"] == [1]
    assert rep["entry_skew"]["flagged"] == []
    merged = str(tmp_path / "merged.json")
    tl.save_chrome(merged)
    assert json.load(open(merged))["otherData"]["unaligned_ranks"] == [1]


# ---------------------------------------------------------------------------
# comm_doctor --policy (schema v11, ISSUE 17)
# ---------------------------------------------------------------------------

def test_comm_doctor_policy_banked_json_golden(tmp_path, capsys):
    """--policy with a banked POLICY json (a report under "report")
    renders standalone and round-trips the report verbatim into the
    structured output, under the v11 schema pin."""
    report = {
        "enabled": True, "verdicts_published": 2, "decisions_applied": 2,
        "vote_rounds": 2, "pending": 0, "attribution_pct": 100.0,
        "unattributed": 0,
        "rules": [{"rule": "perf_demote_quant", "plane": "perf",
                   "kind": "perf_regression", "min_severity": "warn",
                   "action": "demote_arm_quant", "audit_op": "policy",
                   "arm": "quant",
                   "verified": [{"coll": "allreduce", "arm": "quant",
                                 "predicted_wire_bytes": 465920,
                                 "native_wire_bytes": 1835008}]}],
        "verdicts": [{"plane": "perf", "kind": "perf_regression",
                      "severity": "warn", "step": 9,
                      "evidence": {"coll": "allreduce"}}],
        "ledger": [{"step": 9, "rule": "perf_demote_quant",
                    "action": "demote_arm_quant", "audit_op": "policy",
                    "outcome": "applied",
                    "verdict": {"plane": "perf",
                                "kind": "perf_regression",
                                "severity": "warn", "step": 9},
                    "vote": {"round": 1, "mode": "local", "yes": 1,
                             "missing": [], "passed": True,
                             "switch_step": 9},
                    "effect": {"arm": "quant", "coll": "allreduce",
                               "cvar": "coll_xla_allreduce_mode",
                               "prev": "", "step": 9}}],
    }
    banked = tmp_path / "POLICY_cpu.json"
    banked.write_text(json.dumps(
        {"metric": "policy_selfdrive", "value": 4, "report": report}))

    rc = comm_doctor.main(["--policy", str(banked), "--json"])
    assert rc == 0
    data = json.loads(capsys.readouterr().out)
    assert data["schema_version"] == 14       # the v13 -> v14 pin
    assert data["policy"] == report           # banked report, verbatim

    rc = comm_doctor.main(["--policy", str(banked)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "policy: enabled, 2 verdict(s) published" in out
    assert "attribution: 100.0%" in out
    assert "statically pre-verified at registration" in out
    assert "perf_demote_quant" in out
    assert "wire 465920B/1835008B native" in out
    assert "perf/perf_regression => perf_demote_quant [applied]" in out


def test_comm_doctor_policy_live_section(capsys):
    """Bare --policy reads the live in-process plane: one published
    verdict drives the builtin engine and the rendered ledger."""
    from ompi_tpu import policy
    from ompi_tpu.coll import xla  # noqa: F401  (registers the mode cvars)
    policy.reset()
    policy.enable()
    try:
        policy.publish("perf", "perf_regression", "warn",
                       evidence={"coll": "allreduce"}, step=5)
        rc = comm_doctor.main(["--policy", "--json"])
        assert rc == 0
        data = json.loads(capsys.readouterr().out)
        assert data["schema_version"] == 14
        pol = data["policy"]
        assert pol["verdicts_published"] == 1
        assert pol["decisions_applied"] == 1
        assert pol["attribution_pct"] == 100.0
        applied = [r for r in pol["ledger"] if r["outcome"] == "applied"]
        assert applied[0]["verdict"]["kind"] == "perf_regression"
        assert var.get("coll_xla_allreduce_mode") == "quant"
    finally:
        var.registry.set_override("coll_xla_allreduce_mode", "")
        var.registry.reset_cache()
        policy.disable()
        policy.reset()
