"""Continuous performance plane: online collective cost model,
goodput/MFU ledger, perf-regression sentry, learned arm selection, and
the ledger round-trips (ompi_tpu/perf).

Acceptance pins (ISSUE): with ``coll_xla_rules="learned"`` every device
collective dispatched on the 8-device mesh emits exactly ONE decision
event whose reason starts ``learned:`` and whose arm matches the cost
model's best-busbw answer; the disabled path adds no events (the model
stays empty and ``perf.enabled`` is a plain module bool — one attribute
read per call site); a raising span is tagged ``status=error`` and is
never ingested as a latency sample.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

pytestmark = pytest.mark.perf

from ompi_tpu import perf, runtime, spc, trace  # noqa: E402
from ompi_tpu.coll import xla  # noqa: E402
from ompi_tpu.core import var  # noqa: E402
from ompi_tpu.parallel import attach_mesh, make_mesh  # noqa: E402
from ompi_tpu.perf import goodput  # noqa: E402
from ompi_tpu.perf.model import (  # noqa: E402
    CostModel,
    busbw_GBps,
    size_bucket,
)
from ompi_tpu.tools import coll_tune  # noqa: E402

N = 8
_COLLS = ("allreduce", "allgather", "reduce_scatter_block", "bcast",
          "alltoall")
_PERF_VARS = (
    "perf_enabled", "perf_ledger", "coll_xla_rules",
    "perf_sentry_ratio", "perf_sentry_z", "perf_sentry_sustain",
    "perf_sentry_min_samples",
)


@pytest.fixture
def plane():
    """set(name=value, ...) applies perf vars through the CLI layer;
    everything clears (and the plane's process-wide model/ledger/sentry
    zero) on teardown regardless of how the test exits."""
    perf.reset()
    trace.clear()

    def set_vars(**kw):
        for k, v in kw.items():
            var.registry.set_cli(k, str(v))
        var.registry.reset_cache()

    yield set_vars
    for name in _PERF_VARS:
        var.registry.clear_cli(name)
    var.registry.reset_cache()
    perf.disable()
    trace.disable()
    trace.clear()
    perf.reset()


# ---------------------------------------------------------------------------
# cost model: busbw arithmetic, convergence, bucket widening
# ---------------------------------------------------------------------------

def test_busbw_factors_and_bucket():
    # nccl-tests convention, matching trace/analyze._BUSBW_FACTOR
    assert busbw_GBps("allreduce", 1 << 20, 1e-3, 8) == pytest.approx(
        2 * 7 / 8 * (1 << 20) / 1e-3 / 1e9)
    assert busbw_GBps("allgather", 1 << 20, 1e-3, 8) == pytest.approx(
        7 / 8 * (1 << 20) / 1e-3 / 1e9)
    assert busbw_GBps("bcast", 1 << 20, 1e-3, 8) == pytest.approx(
        (1 << 20) / 1e-3 / 1e9)
    # unmeasurable samples carry no signal
    assert busbw_GBps("allreduce", 0, 1e-3, 8) == 0.0
    assert busbw_GBps("allreduce", 1 << 20, 0.0, 8) == 0.0
    assert busbw_GBps("allreduce", 1 << 20, 1e-3, 1) == 0.0
    assert size_bucket(1) == 0
    assert size_bucket(1023) == 9
    assert size_bucket(1024) == 10
    assert size_bucket(1 << 20) == 20


def test_cost_model_convergence_and_widen(plane):
    m = CostModel(window=16, alpha=0.5)
    rng = np.random.default_rng(0)
    for _ in range(50):
        m.record("allreduce", "native", 4096,
                 1e-5 * rng.uniform(0.9, 1.1), N)
        m.record("allreduce", "staged", 4096,
                 1e-3 * rng.uniform(0.9, 1.1), N)
    best, scores = m.best_arm("allreduce", 4096, ("native", "staged"))
    assert best == "native"
    assert scores["native"] > scores["staged"]
    expect = busbw_GBps("allreduce", 4096, 1e-5, N)
    st = m.stats("allreduce", "native", 4096)
    assert st["bw_p50"] == pytest.approx(expect, rel=0.15)
    assert st["count"] == 50
    # sample windows stay bounded at `window`
    assert all(len(c.bw) <= 16 for c in m._cells.values())
    # ±widen bucket search: 16 KiB (bucket 14) reaches the bucket-12
    # samples; 32 KiB (bucket 15) is out of range -> model miss
    assert m.best_arm("allreduce", 1 << 14,
                      ("native", "staged"))[0] == "native"
    assert m.best_arm("allreduce", 1 << 15, ("native", "staged")) is None
    # arms outside `allowed` never win
    assert m.best_arm("allreduce", 4096, ("staged",))[0] == "staged"


def test_learned_reason_format(plane):
    for _ in range(3):
        perf.model.record("allreduce", "staged", 4096, 1e-5, N)
        perf.model.record("allreduce", "native", 4096, 1e-3, N)
    arm, reason = perf.best_arm("allreduce", 4096, ("native", "staged"))
    assert arm == "staged"
    assert reason.startswith("learned:staged=")
    assert "GBps-vs-native=" in reason
    # single modeled arm: the runner-up slot says so
    perf.model.record("bcast", "native", 4096, 1e-5, N)
    arm, reason = perf.best_arm("bcast", 4096, ("native", "staged"))
    assert arm == "native" and reason.endswith("-vs-unmodeled")
    # model miss
    assert perf.best_arm("alltoall", 4096, ("native",)) is None


# ---------------------------------------------------------------------------
# goodput arithmetic vs a hand timeline
# ---------------------------------------------------------------------------

def test_goodput_account_hand_timeline():
    # wall 1.0s = 0.8 compute + 0.1 exposed comm + 0.1 host; total comm
    # 0.4s of which 0.3 hid behind backward
    row = goodput.account(1.0, comm_total_s=0.4, comm_exposed_s=0.1,
                          host_s=0.1, tokens=1000,
                          flops_per_token=2e9, peak_tflops=10.0)
    assert row["compute_s"] == pytest.approx(0.8)
    assert row["goodput_pct"] == pytest.approx(80.0)
    assert row["overlap_efficiency"] == pytest.approx(0.75)
    # 1000 tok x 2 GF / 1 s / 10 TF/s = 20% MFU
    assert row["mfu_pct"] == pytest.approx(20.0)
    # missing split / missing peak -> unmeasured, never fabricated
    bare = goodput.account(1.0)
    assert bare["goodput_pct"] is None
    assert bare["overlap_efficiency"] is None
    assert bare["mfu_pct"] is None
    assert bare["compute_s"] == pytest.approx(1.0)
    # GPipe bubble geometry: (P-1)/(M+P-1)
    assert goodput.pipeline_bubble_s(4, 12, 1.5) == pytest.approx(
        1.5 * 3 / 15)
    assert goodput.pipeline_bubble_s(1, 8, 1.0) == 0.0


def test_goodput_ledger_ewma(plane):
    for _ in range(4):
        perf.record_step(1.0, comm_total_s=0.4, comm_exposed_s=0.1,
                         host_s=0.1, tokens=1000, flops_per_token=2e9,
                         peak_tflops=10.0)
    snap = perf.ledger.snapshot()
    assert snap["steps"] == 4
    assert snap["goodput_pct"] == pytest.approx(80.0)
    assert snap["mfu_pct"] == pytest.approx(20.0)
    assert snap["overlap_efficiency"] == pytest.approx(0.75)
    # wall-only steps (the flagship wrapper) update MFU, not goodput
    perf.ledger.clear()
    perf.record_step(1.0, tokens=1000, flops_per_token=2e9,
                     peak_tflops=10.0)
    assert perf.ledger.ewma("goodput_pct") == 0.0
    assert perf.ledger.ewma("mfu_pct") == pytest.approx(20.0)


# ---------------------------------------------------------------------------
# ledger persistence round-trip
# ---------------------------------------------------------------------------

def test_ledger_round_trip(tmp_path, plane):
    for _ in range(6):
        perf.model.record("allreduce", "native", 1 << 20, 1e-4, N)
        perf.model.record("allreduce", "staged", 1 << 20, 1e-2, N)
        perf.record_step(1.0, comm_total_s=0.4, comm_exposed_s=0.1,
                         tokens=1000, flops_per_token=2e9,
                         peak_tflops=10.0)
    path = str(tmp_path / "PERF_LEDGER_cpu.json")
    doc = perf.save_ledger(path, platform="cpu")
    assert doc["platform"] == "cpu" and len(doc["buckets"]) == 2

    perf.reset()
    assert perf.model.bucket_count() == 0
    got = perf.load_ledger(path)
    assert got["cells"] == 2
    # baselines armed from both the model cells and the goodput window
    assert got["baseline_keys"] == 3
    best, scores = perf.model.best_arm("allreduce", 1 << 20,
                                       ("native", "staged"))
    assert best == "native" and scores["staged"] < scores["native"]
    assert perf.ledger.ewma("goodput_pct") == pytest.approx(90.0)
    assert perf.pvar_value("perf_ledger_buckets") == 2.0

    # enable() autoloads the var-configured ledger path
    perf.reset()
    plane(perf_ledger=path)
    perf.enable()
    assert perf.enabled and perf.model.bucket_count() == 2
    assert perf.default_ledger_path("cpu", root="/x") == \
        "/x/PERF_LEDGER_cpu.json"


# ---------------------------------------------------------------------------
# regression sentry: trip on sustained degradation, quiet on noise
# ---------------------------------------------------------------------------

def _slow(bw_GBps, nbytes=1 << 20, ndev=N):
    """Duration producing the given allreduce busbw at nbytes."""
    return 2 * (ndev - 1) / ndev * nbytes / (bw_GBps * 1e9)


def test_sentry_trip_and_quiet(plane):
    trace.enable()
    s = perf.sentry
    s.load_baseline(
        {"allreduce|native|20": {"bw_GBps": [10.0] * 8}}, [90.0] * 8)
    assert s.baseline_keys() == 2
    # healthy traffic never trips
    for _ in range(5):
        assert s.observe_coll("allreduce", "native", 1 << 20,
                              _slow(10.0), N) is None
    assert s.trips() == 0
    # 2 bad samples are noise; the 3rd (default sustain) trips once
    assert s.observe_coll("allreduce", "native", 1 << 20,
                          _slow(1.0), N) is None
    assert s.observe_coll("allreduce", "native", 1 << 20,
                          _slow(1.0), N) is None
    v = s.observe_coll("allreduce", "native", 1 << 20, _slow(1.0), N)
    assert v is not None and v["sustained"] == 3
    assert v["baseline_p50"] == pytest.approx(10.0)
    # still-degraded traffic inside the same episode: no double count
    assert s.observe_coll("allreduce", "native", 1 << 20,
                          _slow(1.0), N) is None
    assert s.trips() == 1
    # recovery re-arms; a second sustained episode trips again
    s.observe_coll("allreduce", "native", 1 << 20, _slow(10.0), N)
    for _ in range(3):
        s.observe_coll("allreduce", "native", 1 << 20, _slow(1.0), N)
    assert s.trips() == 2
    # goodput degradation judges against the banked distribution too
    for _ in range(3):
        s.observe_goodput(30.0)
    assert s.trips() == 3
    # the trips surfaced as trace instants and the pvar
    evs = [e for e in trace.events() if e["name"] == "perf_regression"]
    assert len(evs) == 3
    assert evs[0]["args"]["busbw_GBps"] == pytest.approx(1.0)
    assert spc.Counters().get("perf_regressions") == 3.0
    # an unknown/thin baseline never judges
    assert s.observe_coll("bcast", "native", 1 << 20,
                          _slow(0.01), N) is None


# ---------------------------------------------------------------------------
# learned arm selection on the 8-device mesh (THE acceptance pin)
# ---------------------------------------------------------------------------

def test_learned_decisions_8dev(plane):
    # seed: staged modeled 100x faster than native at the 1 KiB/rank
    # bucket every dispatch below lands in (per-rank nbytes = 1024)
    for coll in _COLLS:
        for _ in range(4):
            perf.model.record(coll, "staged", 1024, 2e-6, N)
            perf.model.record(coll, "native", 1024, 2e-4, N)
    plane(coll_xla_rules="learned")
    trace.enable()
    trace.clear()

    def fn(ctx):
        c = ctx.comm_world
        attach_mesh(c, make_mesh({"x": N}), "x")
        d = c.device_comm
        vec = d.from_ranks([np.ones(256, np.float32)] * N)
        mat = d.from_ranks([np.ones((N, 32), np.float32)] * N)
        c.coll.allreduce(c, vec)
        c.coll.allgather(c, vec)
        c.coll.reduce_scatter_block(c, vec)
        c.coll.bcast(c, vec)
        c.coll.alltoall(c, mat)
        return True

    assert runtime.run_ranks(1, fn)[0]

    evs = [e for e in trace.events()
           if e["name"].startswith("decide:")]
    for coll in _COLLS:
        recs = [e["args"] for e in evs if e["name"] == f"decide:{coll}"]
        assert len(recs) == 1, \
            f"{coll}: want exactly one decision event, got {len(recs)}"
        a = recs[0]
        assert a["reason"].startswith("learned:"), (coll, a["reason"])
        assert "-vs-" in a["reason"]
        assert a["nbytes"] == 1024
        # the decided arm is the model's best-busbw answer
        expect = perf.model.best_arm(coll, 1024,
                                     ("native", "staged"))[0]
        assert a["arm"] == expect == "staged", (coll, a["arm"])
    explain = trace.explain_last("allreduce")
    assert explain["reason"].startswith("learned:staged=")


def test_learned_miss_falls_through_and_bad_source(plane):
    plane(coll_xla_rules="learned")
    arm, reason, chain = xla.decide_mode(
        "bcast", 1 << 22, N, "cpu", [], ("native", "staged"))
    assert not reason.startswith("learned:")
    assert arm == "native"     # static chain still decides
    assert any("no modeled data" in c for c in chain)
    plane(coll_xla_rules="banana")
    with pytest.raises(ValueError, match="banana"):
        xla.decide_mode("bcast", 4096, N, "cpu", [],
                        ("native", "staged"))


def test_timed_coll_ingestion_8dev(plane):
    plane(perf_enabled="true")
    assert perf.enabled

    def fn(ctx):
        c = ctx.comm_world
        attach_mesh(c, make_mesh({"x": N}), "x")
        d = c.device_comm
        x = d.from_ranks([np.ones(256, np.float32)] * N)
        c.coll.allreduce(c, x)
        return True

    assert runtime.run_ranks(1, fn)[0]
    rows = [r for r in perf.model.table() if r["coll"] == "allreduce"]
    assert len(rows) == 1 and rows[0]["count"] == 1
    assert rows[0]["arm"] in ("native", "staged", "quant")
    # first-dispatch latency includes the executable compile, so busbw
    # can round to 0.000 — the latency window is the robust signal
    assert rows[0]["lat_us_p50"] > 0


# ---------------------------------------------------------------------------
# disabled path: zero events, plain-bool gate
# ---------------------------------------------------------------------------

def test_disabled_path_zero_events(plane):
    # the gate is a plain module attribute (ONE attribute read per call
    # site), not a property/descriptor
    assert perf.enabled is False
    assert isinstance(vars(perf)["enabled"], bool)
    trace.enable()

    def fn(ctx):
        c = ctx.comm_world
        attach_mesh(c, make_mesh({"x": N}), "x")
        d = c.device_comm
        c.coll.allreduce(c, d.from_ranks(
            [np.ones(256, np.float32)] * N))
        c.coll.allreduce(c, np.ones(64, np.float32))
        return True

    assert runtime.run_ranks(1, fn)[0]
    assert perf.model.bucket_count() == 0
    assert perf.ledger.steps == 0
    assert perf.sentry.trips() == 0
    assert not [e for e in trace.events()
                if e["name"] == "perf_regression"]


# ---------------------------------------------------------------------------
# span exception paths; no trace span feeds the cost model
# ---------------------------------------------------------------------------

def test_span_error_tag_and_sink_whitelist(plane):
    trace.enable()
    trace.clear()
    with pytest.raises(RuntimeError, match="boom"):
        with trace.span("grad_sync:run", "overlap",
                        args={"mode": "bucketed"}):
            raise RuntimeError("boom")
    ev = [e for e in trace.events()
          if e["name"] == "grad_sync:run"][-1]
    assert ev["args"]["status"] == "error"
    assert ev["args"]["mode"] == "bucketed"   # original args intact

    # the model learns only from timed dispatches: no recorded span,
    # whatever its name or args, becomes a sample
    plane(perf_enabled="true")
    args = {"arm": "native", "nbytes": 1 << 20, "ndev": N}
    for name, cat in (("grad_sync:bucket", "overlap-buckets"),
                      ("grad_sync:run", "overlap"),
                      ("pipeline:run", "pipeline")):
        trace.record_span(name, cat, 0.0, 1e-4, args=args)
    with trace.span("grad_sync:run", "overlap", args=args):
        pass
    assert perf.model.bucket_count() == 0
    assert perf.model.stats("grad_sync", "native", 1 << 20) is None
    assert not hasattr(trace, "set_span_sink")


# ---------------------------------------------------------------------------
# coll_tune --from-ledger: provenance-tagged DEVICE_RULES round-trip
# ---------------------------------------------------------------------------

def test_from_ledger_provenance_round_trip(tmp_path, plane):
    # measured crossover: staged wins the 1 KiB bucket, native the 1 MiB
    for _ in range(4):
        perf.model.record("allreduce", "staged", 1024, 2e-6, N)
        perf.model.record("allreduce", "native", 1024, 2e-4, N)
        perf.model.record("allreduce", "native", 1 << 20, 1e-4, N)
        perf.model.record("allreduce", "staged", 1 << 20, 1e-2, N)
    ledger = str(tmp_path / "PERF_LEDGER_cpu.json")
    perf.save_ledger(ledger, platform="cpu")

    out = str(tmp_path / "DEVICE_RULES_learned.txt")
    winners = coll_tune.emit_learned_rules(ledger, out)
    assert winners["allreduce"] == {1024: "staged", 1 << 20: "native"}
    # the emitted file parses under the standard loader (first mode
    # opens at min_bytes 0; the crossover row carries the bucket floor)
    rows = xla._load_device_rules(out)
    assert ("allreduce", 1, 0, "staged") in rows
    assert ("allreduce", 1, 1 << 20, "native") in rows
    # provenance header names the source ledger and round-trips re-emit
    prov = coll_tune.rules_provenance(out)
    assert prov is not None and ledger in prov
    assert prov.startswith("# learned from PERF_LEDGER")
    out2 = str(tmp_path / "DEVICE_RULES_reemit.txt")
    coll_tune.emit_device_rules(winners, out2, platform="cpu",
                                provenance=prov)
    assert coll_tune.rules_provenance(out2) == prov
    assert xla._load_device_rules(out2) == rows
    # a sweep-measured file has no provenance
    out3 = str(tmp_path / "DEVICE_RULES_sweep.txt")
    coll_tune.emit_device_rules(winners, out3, platform="cpu")
    assert coll_tune.rules_provenance(out3) is None


# ---------------------------------------------------------------------------
# pvars: spc read-through + Prometheus families
# ---------------------------------------------------------------------------

def test_pvars_in_spc(plane):
    names = [n for n, _ in spc.COUNTERS]
    for p in perf.PVARS:
        assert p in names
    c = spc.Counters()
    perf.model.record("allreduce", "native", 4096, 1e-5, N)
    perf.record_step(1.0, comm_total_s=0.4, comm_exposed_s=0.1,
                     tokens=1000, flops_per_token=2e9, peak_tflops=10.0)
    assert c.get("perf_ledger_buckets") == 1.0
    assert c.get("perf_goodput_pct") == pytest.approx(90.0)
    assert c.get("perf_mfu_pct") == pytest.approx(20.0)
    assert c.get("perf_regressions") == 0.0
    snap = c.snapshot()
    for p in perf.PVARS:
        assert p in snap
    prom = c.export_prometheus(rank=0)
    assert "ompi_tpu_perf_ledger_buckets" in prom
    assert 'ompi_tpu_perf_goodput_pct{rank="0",comm="world"} 90' in prom
    with pytest.raises(KeyError):
        perf.pvar_value("perf_banana")
