"""History plane: fleet-lifetime telemetry with deterministic
changepoint detection (PR 20).

Covers the append-only run ledger (dedup, run_id derivation from
ledger content, JSONL round-trip tolerant of foreign lines, the
deterministic bucket-mean series downsample), the Page-Hinkley/CUSUM
kernel (step + drift attribution pinned on two noise seeds, min-run
and sustain gates, episode re-arm, clean-trajectory zero false
positives), the HistorySentry (idempotent scans, CL007 verdict
envelope, bad-direction filtering, within-run series drift, policy-bus
integration driving exactly one audited decide:policy), the pvar
read-through under the Prometheus grammar, comm_doctor --history
(live + banked golden under the v14 schema), and the backfill tool's
idempotency.
"""

import json
import os
import re

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from ompi_tpu import history, policy, spc, trace  # noqa: E402
from ompi_tpu.core import var  # noqa: E402
from ompi_tpu.history import (HistoryStore, append_jsonl, bad_direction,  # noqa: E402
                              detect, downsample)
from ompi_tpu.history.sentry import HistorySentry  # noqa: E402
from ompi_tpu.tools import comm_doctor, history_backfill  # noqa: E402

pytestmark = pytest.mark.history

_VARS = ("history_enabled", "history_path", "history_series_cap",
         "history_cp_min_runs", "history_cp_lambda", "history_cp_delta",
         "history_cp_sustain", "history_cp_rel_floor", "policy_enabled")


@pytest.fixture(autouse=True)
def _clean_state():
    """Every test leaves the planes and CLI vars as it found them."""
    yield
    for name in _VARS:
        var.registry.clear_cli(name)
    try:
        var.registry.set_override("coll_xla_allreduce_mode", "")
    except KeyError:
        pass                            # coll.xla cvars not registered
    var.registry.reset_cache()
    history.disable()
    history.reset()
    policy.disable()
    policy.reset()
    trace.disable()
    trace.clear()


@pytest.fixture
def plane():
    def set_vars(**kw):
        for k, v in kw.items():
            var.registry.set_cli(k, str(v))
        var.registry.reset_cache()
    return set_vars


def _hist_lcg(seed):
    """A deterministic noise source (a 31-bit LCG)."""
    s = (int(seed) * 2654435761) & 0x7FFFFFFF
    while True:
        s = (1103515245 * s + 12345) & 0x7FFFFFFF
        yield (s / 0x7FFFFFFF) * 2.0 - 1.0


# ---------------------------------------------------------------------------
# store: ledger semantics
# ---------------------------------------------------------------------------

def test_store_record_dedup_and_counts():
    st = HistoryStore()
    st.record(1, "cpu", "serve", "decode_tokens_per_s", 220.0,
              unit="tokens/s")
    st.record(1, "cpu", "serve", "decode_tokens_per_s", 221.0)
    st.record(2, "cpu", "serve", "decode_tokens_per_s", 219.0)
    st.record(1, "cpu", "goodput", "mfu_pct", 38.0)
    # last row per key wins; sample_count is monotonic
    assert len(st.rows()) == 3
    assert st.sample_count() == 4
    assert st.run_count() == 3          # (cpu,serve,1) (cpu,serve,2) (cpu,goodput,1)
    assert st.latest("serve", "decode_tokens_per_s") == (2, 219.0)
    assert st.trajectory("serve", "decode_tokens_per_s") == \
        [(1, 221.0), (2, 219.0)]
    assert st.metrics() == [("goodput", "mfu_pct"),
                            ("serve", "decode_tokens_per_s")]
    assert st.metrics(probe="serve") == [("serve", "decode_tokens_per_s")]


def test_store_next_run_id_is_ledger_content():
    st = HistoryStore()
    assert st.next_run_id("cpu", "serve") == 1
    st.record(7, "cpu", "serve", "decode_tokens_per_s", 1.0)
    assert st.next_run_id("cpu", "serve") == 8
    assert st.next_run_id("cpu", "goodput") == 1
    assert st.next_run_id("tpu", "serve") == 1


def test_downsample_deterministic_bucket_mean():
    assert downsample([1.0, 2.0, 3.0], 8) == [1.0, 2.0, 3.0]
    got = downsample([float(i) for i in range(100)], 4)
    assert len(got) == 4
    # equal-width index buckets, mean per bucket
    assert got == [12.0, 37.0, 62.0, 87.0]
    # deterministic: identical input, identical output
    assert downsample([float(i) for i in range(100)], 4) == got


def test_store_series_downsampled_on_record():
    st = HistoryStore(series_cap=8)
    st.record(1, "cpu", "serve", "tok", 1.0,
              series=[float(i) for i in range(64)])
    ser = st.series_of(1, "cpu", "serve", "tok")
    assert len(ser) == 8
    assert st.series_of(2, "cpu", "serve", "tok") == []


def test_store_jsonl_round_trip_tolerant(tmp_path):
    path = str(tmp_path / "BENCH_HISTORY.jsonl")
    st = HistoryStore()
    st.record(1, "cpu", "serve", "tok", 220.0, unit="tokens/s",
              series=[1.0, 2.0, 3.0], extra={"note": "x"})
    st.record(2, "cpu", "serve", "tok", 200.0)
    assert st.save_jsonl(path) == 2
    # foreign/broken lines are skipped, not fatal
    with open(path, "a") as fh:
        fh.write("not json at all\n")
        fh.write(json.dumps({"foreign": "row"}) + "\n")
        fh.write("\n")
    st2 = HistoryStore()
    assert st2.load_jsonl(path) == 2
    assert st2.trajectory("serve", "tok") == [(1, 220.0), (2, 200.0)]
    assert st2.series_of(1, "cpu", "serve", "tok") == [1.0, 2.0, 3.0]
    assert st2.rows()[0]["note"] == "x"
    # append_jsonl is the live write path
    append_jsonl(path, st.record(3, "cpu", "serve", "tok", 210.0))
    st3 = HistoryStore()
    st3.load_jsonl(path)
    assert st3.latest("serve", "tok") == (3, 210.0)
    assert HistoryStore().load_jsonl(str(tmp_path / "missing.jsonl")) == 0


# ---------------------------------------------------------------------------
# changepoint kernel: pinned attribution, gates, episodes
# ---------------------------------------------------------------------------

def test_kernel_step_attribution_exact():
    vals = [100.0] * 7 + [80.0] * 5
    cps = detect(vals)
    assert len(cps) == 1
    cp = cps[0]
    assert cp["index"] == 7             # the injection point, exactly
    assert cp["direction"] == "down"
    assert cp["confirm_index"] == 8     # sustain=2: second bad point
    assert cp["magnitude"] == pytest.approx(-0.2, abs=1e-6)


def test_kernel_drift_onset_mid_ramp():
    # busbw -2%/run, noise-free: the probe's pinned drift trajectory
    vals = [1.8 * (1.0 - 0.02 * i) for i in range(12)]
    cps = detect(vals)
    assert [c["direction"] for c in cps] == ["down"]
    # half-max onset rule lands mid-ramp at index 6 (run_id 7 in a
    # 1-based ledger) — pinned
    assert cps[0]["index"] == 6
    assert cps[0]["magnitude"] < 0.0


def test_kernel_clean_trajectory_zero_false_positives():
    for seed in (20, 21):
        noise = _hist_lcg(seed)
        vals = [81.0 * (1.0 + 0.005 * next(noise)) for _ in range(12)]
        assert detect(vals) == []
    assert detect([5.0] * 12) == []     # constant: no div-by-zero trip
    assert detect([0.0] * 12) == []     # all-zero baseline


def test_kernel_deterministic_across_seeds():
    # identical trajectory in, identical changepoint list out — and
    # the step onset survives any 0.5% noise seed (half-max rule)
    for seed in (20, 21):
        noise = _hist_lcg(seed)
        vals = [220.0 * (0.8 if i >= 7 else 1.0)
                * (1.0 + 0.005 * next(noise)) for i in range(12)]
        first = detect(vals)
        assert detect(vals) == first
        assert [c["index"] for c in first if c["direction"] == "down"] \
            == [7]


def test_kernel_min_run_gate():
    assert detect([100.0, 80.0, 80.0]) == []
    assert detect([100.0] * 5 + [80.0]) == []       # n < min_runs+sustain
    assert detect([100.0] * 7 + [80.0] * 5, min_runs=11) == []


def test_kernel_sustain_gate():
    vals = [100.0] * 7 + [80.0] * 5
    cps = detect(vals, sustain=3)
    assert len(cps) == 1
    assert cps[0]["index"] == 7
    assert cps[0]["confirm_index"] == 9
    # a single outlier never trips
    spike = [100.0] * 7 + [80.0] + [100.0] * 4
    assert detect(spike) == []


def test_kernel_up_direction():
    cps = detect([10.0] * 7 + [20.0] * 5)
    assert [c["direction"] for c in cps] == ["up"]
    assert cps[0]["index"] == 7
    assert cps[0]["magnitude"] == pytest.approx(1.0, abs=1e-6)


def test_kernel_recovered_point_rearms_episode():
    vals = [100.0] * 7 + [60.0] * 3 + [100.0] + [80.0] * 4
    downs = [c for c in detect(vals) if c["direction"] == "down"]
    assert [c["index"] for c in downs] == [7, 11]   # two episodes


# ---------------------------------------------------------------------------
# sentry: episode grammar onto the bus
# ---------------------------------------------------------------------------

def _step_store(metric="decode_tokens_per_s", probe="serve"):
    st = HistoryStore()
    for i in range(12):
        st.record(i + 1, "cpu", probe, metric,
                  220.0 * (0.8 if i >= 7 else 1.0))
    return st


def test_bad_direction_cues():
    assert bad_direction("decode_tokens_per_s") == "down"
    assert bad_direction("busbw_GBps") == "down"
    assert bad_direction("goodput_pct") == "down"
    assert bad_direction("snr_db_last") == "down"
    assert bad_direction("itl_p99_ms_colocated") == "up"
    assert bad_direction("wire_bytes") == "up"
    assert bad_direction("time_to_retune_steps") == "up"
    assert bad_direction("report_slo_breaches") == "up"
    # override beats the "_s" suffix cue
    assert bad_direction("fused.tokens_per_s") == "down"
    assert bad_direction("recovered_MBps") == "down"


def test_sentry_scan_idempotent_and_envelope():
    sen = HistorySentry()
    st = _step_store()
    fresh = sen.scan(st)
    assert len(fresh) == 1
    v = fresh[0]
    # CL007: plane + kind + severity ride ON the verdict
    assert v["plane"] == "history"
    assert v["kind"] == "history_regression"
    assert v["severity"] == "warn"      # |magnitude| 20% < 25% error bar
    assert (v["probe"], v["metric"], v["platform"]) == \
        ("serve", "decode_tokens_per_s", "cpu")
    assert v["run_id"] == 8
    assert v["direction"] == "down"
    assert v["scope"] == "runs"
    assert v["magnitude_pct"] == pytest.approx(-20.0, abs=0.01)
    # idempotent: the same ledger scanned twice publishes nothing new
    assert sen.scan(st) == []
    assert sen.changepoints() == 1
    assert len(sen.verdicts()) == 1


def test_sentry_severity_error_at_25pct():
    sen = HistorySentry()
    st = HistoryStore()
    for i in range(12):
        st.record(i + 1, "cpu", "serve", "decode_tokens_per_s",
                  220.0 * (0.6 if i >= 7 else 1.0))
    assert [v["severity"] for v in sen.scan(st)] == ["error"]


def test_sentry_improvement_counted_never_published():
    sen = HistorySentry()
    st = HistoryStore()
    for i in range(12):
        st.record(i + 1, "cpu", "serve", "decode_tokens_per_s",
                  220.0 * (1.5 if i >= 7 else 1.0))
    assert sen.scan(st) == []           # up-shift on a down-bad gauge
    assert sen.changepoints() == 1      # still counted for the doctor


def test_sentry_series_scope_attributes_step_index():
    sen = HistorySentry()
    st = HistoryStore()
    st.record(1, "cpu", "serve", "decode_tokens_per_s", 200.0,
              series=[200.0] * 10 + [100.0] * 10)
    fresh = sen.scan(st)
    assert len(fresh) == 1
    v = fresh[0]
    assert v["scope"] == "series"
    assert v["run_id"] == 1
    assert v["step_index"] == 10
    assert sen.scan(st) == []


def test_sentry_rearm_reopens_episodes():
    sen = HistorySentry()
    st = _step_store()
    assert len(sen.scan(st)) == 1
    assert sen.rearm("cpu", "serve", "decode_tokens_per_s") == 1
    assert len(sen.scan(st)) == 1       # same episode republishable
    assert sen.rearm("cpu", "serve", "other_metric") == 0


def test_sentry_new_episode_after_recovered_run():
    sen = HistorySentry()
    st = _step_store()
    assert [v["run_id"] for v in sen.scan(st)] == [8]
    st.record(13, "cpu", "serve", "decode_tokens_per_s", 220.0)
    st.record(14, "cpu", "serve", "decode_tokens_per_s", 176.0)
    st.record(15, "cpu", "serve", "decode_tokens_per_s", 176.0)
    again = [v for v in sen.scan(st) if v["scope"] == "runs"]
    assert [v["run_id"] for v in again] == [14]


# ---------------------------------------------------------------------------
# policy-bus integration: trend -> one audited adaptation
# ---------------------------------------------------------------------------

def test_history_verdict_drives_one_audited_decision(plane):
    from ompi_tpu.coll import xla  # noqa: F401  (registers the mode cvars)
    plane(history_enabled="true", policy_enabled="true")
    history.enable()
    policy.enable()
    trace.enable()
    trace.clear()
    for i in range(12):
        history.record_run(i + 1, "cpu", "serve", "decode_tokens_per_s",
                           220.0 * (0.8 if i >= 7 else 1.0))
    fresh = history.scan("cpu")
    assert [v["run_id"] for v in fresh] == [8]
    rep = policy.report()
    bus = [v for v in rep["verdicts"] if v["plane"] == "history"]
    assert bus and bus[0]["kind"] == "history_regression"
    # the builtin history_demote_quant rule answered the trend
    assert var.get("coll_xla_allreduce_mode") == "quant"
    decide = [e for e in trace.events()
              if e.get("name") == "decide:policy"
              and (e.get("args", {}).get("verdict") or
                   {}).get("plane") == "history"]
    assert len(decide) == 1
    # ... and the trace carries the changepoint instant
    assert [e for e in trace.events()
            if e.get("name") == "history_changepoint"]


def test_history_demote_quant_rule_registered():
    from ompi_tpu.policy import engine
    rules = {r.name: r for r in engine.builtin_rules()}
    r = rules["history_demote_quant"]
    assert r.plane == "history"
    assert r.kind == "history_regression"
    assert r.action.name == "demote_arm_quant"


# ---------------------------------------------------------------------------
# plane surface: enable/disable, autoload, disabled path
# ---------------------------------------------------------------------------

def test_disabled_path_is_noop():
    assert history.enabled is False
    assert history.record_run(1, "cpu", "serve", "tok", 1.0) is None
    assert history.store.sample_count() == 0
    assert history.scan() == []
    rep = history.report()
    assert rep["runs"] == 0 and rep["verdicts"] == []


def test_enable_via_var_watcher(plane):
    plane(history_enabled="true")
    assert history.enabled is True
    var.registry.clear_cli("history_enabled")
    var.registry.reset_cache()
    assert history.enabled is False


def test_enable_rehydrates_ledger(tmp_path, plane):
    path = str(tmp_path / "BENCH_HISTORY.jsonl")
    seed = HistoryStore()
    for i in range(3):
        append_jsonl(path, seed.record(i + 1, "cpu", "serve", "tok",
                                       200.0 + i))
    plane(history_enabled="true", history_path=path)
    history.enable()
    assert history.store.trajectory("serve", "tok") == \
        [(1, 200.0), (2, 201.0), (3, 202.0)]
    assert history.next_run_id("cpu", "serve") == 4
    # record_run appends to the on-disk ledger too
    history.record_run(4, "cpu", "serve", "tok", 203.0)
    st = HistoryStore()
    st.load_jsonl(path)
    assert st.latest("serve", "tok") == (4, 203.0)


# ---------------------------------------------------------------------------
# headline rows: the probe -> gauge map backfill reads
# ---------------------------------------------------------------------------

def test_headline_rows_doc_metric_plus_extras():
    doc = {"metric": "goodput_pct", "value": 81.5, "unit": "%",
           "mfu_pct": 38.0, "overlap_efficiency": 0.9,
           "nested": {"skip": True}}
    rows = history.headline_rows("goodput", doc)
    assert rows[0] == ("goodput_pct", 81.5, "%")
    assert ("mfu_pct", 38.0, "") in rows
    assert ("overlap_efficiency", 0.9, "") in rows


def test_headline_rows_dotted_paths_and_bools():
    doc = {"metric": "serve_tokens_per_s_best", "value": 100.0,
           "speculative": {"acceptance_rate": 0.7},
           "fused": {"tokens_per_s": True},   # bool: skipped
           "quant": {}}                        # missing: skipped
    rows = history.headline_rows("serve", doc)
    assert ("speculative_acceptance_rate", 0.7, "") in rows
    assert all(m != "fused_tokens_per_s" for m, _, _ in rows)
    # every wired probe has an artifact stem
    for probe, (stem, extras) in history.PROBE_GAUGES.items():
        assert stem and isinstance(extras, tuple)


# ---------------------------------------------------------------------------
# pvars through spc + Prometheus grammar
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


def test_pvars_in_spc_counters():
    names = {n for n, _ in spc.COUNTERS}
    for name in history.PVARS:
        assert name in names            # CL003: every pvar is exported


def test_pvars_read_through_spc(plane):
    plane(history_enabled="true")
    history.enable()
    for i in range(12):
        history.record_run(i + 1, "cpu", "serve", "decode_tokens_per_s",
                           220.0 * (0.8 if i >= 7 else 1.0))
    history.scan("cpu")
    c = spc.Counters()
    assert c.get("history_runs") == 12.0
    assert c.get("history_samples") == 12.0
    assert c.get("history_changepoints") == 1.0
    snap = c.snapshot()
    for name in history.PVARS:
        assert name in snap
    assert snap["history_runs"] == 12.0


def test_prometheus_gauge_family_and_grammar(plane):
    assert history.prometheus_rows() == []      # empty store: no family
    plane(history_enabled="true")
    history.enable()
    history.record_run(1, "cpu", "serve", "decode_tokens_per_s", 220.0)
    history.record_run(1, "cpu", "goodput", "mfu_pct", 38.0)
    text = spc.export_prometheus(spc.Counters())
    _assert_prometheus_grammar(text)
    assert ('ompi_tpu_history_metric{rank="0",comm="world",'
            'probe="serve",metric="decode_tokens_per_s"} 220') in text
    assert "# TYPE ompi_tpu_history_metric gauge" in text


# ---------------------------------------------------------------------------
# comm_doctor --history: live + banked golden under the v14 schema
# ---------------------------------------------------------------------------

def _doctor_json(capsys, args):
    rc = comm_doctor.main(args)
    return rc, json.loads(capsys.readouterr().out)


def test_doctor_history_banked_golden(plane, capsys, tmp_path):
    plane(history_enabled="true")
    history.enable()
    for i in range(12):
        history.record_run(i + 1, "cpu", "serve", "decode_tokens_per_s",
                           220.0 * (0.8 if i >= 7 else 1.0))
    history.scan("cpu")
    report = history.report()
    banked = tmp_path / "HISTORY_cpu.json"
    banked.write_text(json.dumps(
        {"metric": "history_changepoints", "value": 1.0,
         "report": report}))

    rc, data = _doctor_json(capsys, ["--history", str(banked), "--json"])
    assert rc == 0
    assert data["schema_version"] == 14       # the v13 -> v14 pin
    assert data["history"] == report          # banked report, verbatim

    rc = comm_doctor.main(["--history", str(banked)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "history: 12 run(s), 12 sample(s), 1 changepoint(s)" in out
    assert "decode_tokens_per_s" in out
    assert "serve/decode_tokens_per_s down -20.0% at run 8" in out


def test_doctor_history_live_section(plane, capsys):
    plane(history_enabled="true")
    history.enable()
    history.record_run(1, "cpu", "goodput", "goodput_pct", 81.0)
    rc, data = _doctor_json(capsys, ["--history", "--json"])
    assert rc == 0
    assert data["schema_version"] == 14
    assert data["history"]["runs"] == 1
    assert data["history"]["gauges"][0]["metric"] == "goodput_pct"


# ---------------------------------------------------------------------------
# backfill tool: seed the ledger from banked artifacts, idempotently
# ---------------------------------------------------------------------------

def test_backfill_banks_then_skips(tmp_path, capsys):
    root = tmp_path
    (root / "GOODPUT_cpu.json").write_text(json.dumps(
        {"metric": "goodput_pct", "value": 81.0, "unit": "%",
         "platform": "cpu", "mfu_pct": 38.0,
         "overlap_efficiency": 0.92}))
    (root / "SERVE_cpu.json").write_text(json.dumps(
        {"metric": "serve_tokens_per_s_best", "value": 120.0,
         "platform": "cpu",
         "speculative": {"acceptance_rate": 0.7}}))
    (root / "RESHARD_cpu.json").write_text("broken {")
    out = str(root / "BENCH_HISTORY.jsonl")

    rc = history_backfill.main(["--root", str(root), "--out", out])
    assert rc == 0
    summary = json.loads(capsys.readouterr().out)
    by = {s["artifact"]: s for s in summary["rows"]}
    assert by["GOODPUT_cpu.json"]["status"] == "banked"
    assert by["GOODPUT_cpu.json"]["run_id"] == 1
    assert by["SERVE_cpu.json"]["status"] == "banked"
    assert by["RESHARD_cpu.json"]["status"] == "unreadable"

    st = HistoryStore()
    st.load_jsonl(out)
    assert st.latest("goodput", "goodput_pct", "cpu") == (1, 81.0)
    assert st.latest("goodput", "mfu_pct", "cpu") == (1, 38.0)
    assert st.latest("serve", "speculative_acceptance_rate", "cpu") == \
        (1, 0.7)

    # second pass: every artifact already banked, ledger unchanged
    rows_before = st.rows()
    rc = history_backfill.main(["--root", str(root), "--out", out])
    assert rc == 0
    summary2 = json.loads(capsys.readouterr().out)
    assert summary2["banked"] == 0
    assert all(s["status"] in ("already_banked", "unreadable")
               for s in summary2["rows"])
    st2 = HistoryStore()
    st2.load_jsonl(out)
    assert st2.rows() == rows_before


def test_backfill_dry_run_writes_nothing(tmp_path, capsys):
    (tmp_path / "GOODPUT_cpu.json").write_text(json.dumps(
        {"metric": "goodput_pct", "value": 81.0, "platform": "cpu"}))
    out = str(tmp_path / "BENCH_HISTORY.jsonl")
    rc = history_backfill.main(["--root", str(tmp_path), "--out", out,
                                "--dry-run"])
    assert rc == 0
    capsys.readouterr()
    assert not os.path.exists(out)
