"""Request plane: end-to-end per-request tracing (PR 19).

Covers span-tree stitching across the bridge mesh (rid-tagged stage
spans from disjoint lanes merged into one globally ordered tree),
the stage-sum == e2e conservation law re-derived from the trace alone
on a live disaggregated fleet, the deterministic slowest-k + breach
exemplar reservoir, the SLO judge publishing exactly one slo_breach
verdict per episode onto the policy bus (answered by one audited
decide:fleet_route carrying the attributed stage), the Chrome-trace
flow-arrow round-trip, the req_* pvar read-through under the
Prometheus grammar, comm_doctor --requests (live + banked golden under
the v13 schema), and the disabled-path zero-state.
"""

import json
import os
import re

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ompi_tpu import policy, serving, spc, trace, traffic  # noqa: E402
from ompi_tpu.core import var  # noqa: E402
from ompi_tpu.models import transformer as tfm  # noqa: E402
from ompi_tpu.serving import requests  # noqa: E402
from ompi_tpu.serving.fleet import ServingFleet  # noqa: E402
from ompi_tpu.serving.scheduler import poisson_stream  # noqa: E402
from ompi_tpu.tools import comm_doctor  # noqa: E402
from ompi_tpu.trace import critical  # noqa: E402
from ompi_tpu.trace import merge as tmerge  # noqa: E402

pytestmark = pytest.mark.requests


CFG = tfm.Config(vocab=512, d_model=128, n_layers=2, n_heads=8,
                 head_dim=16, d_ff=256, dtype=jnp.float32)


@pytest.fixture(autouse=True)
def _clean_state():
    """Every test leaves the planes and CLI vars as it found them."""
    yield
    for name in ("policy_enabled", "serve_req_enabled",
                 "serve_req_exemplar_k", "serve_req_slo_ttft_ms",
                 "serve_req_slo_itl_ms", "serve_req_slo_e2e_ms",
                 "topo_sim_dcn_axes", "topo_sim_dcn_us_per_mib"):
        var.registry.clear_cli(name)
    var.registry.reset_cache()
    requests.reset()
    requests.disable()
    policy.disable()
    policy.reset()
    serving.reset()
    serving.disable()
    traffic.reset()
    traffic.disable()
    trace.clear()
    trace.disable()


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(jax.random.PRNGKey(0), CFG)


def _stream(n=6, seed=7, max_new=(3, 5)):
    return poisson_stream(n, 200.0, CFG.vocab, seed=seed,
                          prompt_len=(10, 22), max_new=max_new)


def _merge_rings(tmp_path, offsets=None, best_rtt=None):
    """Round-trip this process's per-rank rings through the Chrome
    format and merge them — the path comm_doctor --requests reads."""
    ranks = sorted({e["rank"] for e in trace.events()})
    paths = [trace.save_chrome(str(tmp_path / f"rank{r}.json"), rank=r)
             for r in ranks]
    per_rank = tmerge.load_chrome(paths)
    return tmerge.merge(
        per_rank,
        offsets=offsets or {r: 0.0 for r in ranks},
        best_rtt=best_rtt or {r: 2e-5 for r in ranks})


def _play_one(rid, *, finish=0.050, migrate_end=0.034):
    """One synthetic request crossing lanes 0 (prefill) -> 1 (decode)
    on the virtual clock."""
    requests.note_route(rid, 1, [0.25, 0.75])
    requests.note_admit(rid, 0.0, 0.010, 8, 4, replica=1, rank=0)
    requests.note_stage(rid, "prefill", 0.010, 0.030, rank=0)
    requests.note_stage(rid, "migrate", 0.030, migrate_end, rank=0,
                        src=0, dst=1, wire_bytes=4096)
    requests.note_stage(rid, "join", migrate_end, migrate_end + 0.001,
                        rank=1)
    requests.note_token(rid, migrate_end + 0.002, rank=1)
    requests.note_token(rid, migrate_end + 0.006, rank=1)
    requests.note_finish(rid, finish)


# ---------------------------------------------------------------------------
# span-tree stitching: rid-tagged stages from disjoint lanes, one tree
# ---------------------------------------------------------------------------

def test_span_tree_stitching_across_bridge_mesh(tmp_path):
    """A request whose stages ran on two lanes comes back from the
    merged (offset-aligned) timeline as ONE globally ordered span tree
    with all five stages, the route decision, both tokens and the
    hand-off flow arrows."""
    trace.enable()
    trace.clear()
    requests.reset()
    requests.enable()
    _play_one(7)
    tl = _merge_rings(tmp_path, offsets={0: 0.0, 1: -2e-3},
                      best_rtt={0: 1e-5, 1: 1e-5})
    trees = critical.request_trees(tl)
    assert list(trees) == [7]
    tree = trees[7]
    assert tree["ranks"] == [0, 1]
    assert set(tree["stages"]) == set(requests.STAGES)
    assert tree["tokens"] == 2
    # globally ordered lifecycle, decode-join after the migrate hop
    assert [s["name"] for s in tree["spans"]] == list(
        critical.STAGE_NAMES)
    assert tree["e2e"] is not None
    # the route decision rode along with its weight-snapshot evidence
    routes = [e for e in tree["events"] if e["name"] == "decide:route"]
    assert len(routes) == 1
    assert routes[0]["args"]["weights"] == [0.25, 0.75]
    assert routes[0]["args"]["arm"] == "replica=1"
    # hand-off arrows: start + step on the source lane, finish on the
    # decode lane, all under the request's stable flow id
    assert [f["ph"] for f in tree["flows"]] == ["s", "t", "f"]
    assert {f["id"] for f in tree["flows"]} == {requests.flow_id(7)}
    assert [f["rank"] for f in tree["flows"]] == [0, 0, 1]
    # conservation holds through the chrome round-trip + clock offsets
    cons = critical.conservation(tl, trees=trees)
    assert cons["checked"] == 1 and cons["all_ok"], cons


def test_flow_events_chrome_roundtrip(tmp_path):
    """trace.flow emits Chrome flow rows (id on every phase, binding
    point on the finish) that survive save_chrome -> load_chrome, and
    an unknown phase is rejected loudly."""
    trace.enable()
    trace.clear()
    trace.record_span("req:prefill", "req", 0.010, 0.020, rank=0,
                      args={"rid": 3})
    trace.flow("req:handoff", "req", 3, "s", rank=0, t=0.020)
    trace.flow("req:handoff", "req", 3, "t", rank=0, t=0.024)
    trace.flow("req:handoff", "req", 3, "f", rank=1, t=0.025)
    with pytest.raises(ValueError):
        trace.flow("req:handoff", "req", 3, "x", rank=0, t=0.026)
    p0 = trace.save_chrome(str(tmp_path / "r0.json"), rank=0)
    p1 = trace.save_chrome(str(tmp_path / "r1.json"), rank=1)
    rows0 = json.load(open(p0))["traceEvents"]
    flows0 = [r for r in rows0 if r["ph"] in ("s", "t")]
    assert [r["id"] for r in flows0] == [3, 3]
    assert all("bp" not in r for r in flows0)
    fin = [r for r in json.load(open(p1))["traceEvents"]
           if r["ph"] == "f"]
    assert fin[0]["id"] == 3 and fin[0]["bp"] == "e"
    # flow rows are instantaneous: the per-lane span non-overlap
    # invariant is untouched
    assert all("dur" not in r for r in flows0 + fin)
    per_rank = tmerge.load_chrome([p0, p1])
    evs = [e for e in per_rank[0] + per_rank[1]
           if e["ph"] in ("s", "t", "f")]
    assert [e["id"] for e in evs] == [3, 3, 3]


# ---------------------------------------------------------------------------
# conservation on a live disaggregated fleet
# ---------------------------------------------------------------------------

def test_fleet_stage_sum_conservation(params, tmp_path):
    """Every request served by a real prefill/decode fleet satisfies
    sum(stages) == e2e within clock confidence, re-derived from the
    merged trace alone (no ledger access)."""
    serving.reset()
    serving.enable()
    requests.reset()
    requests.enable()
    trace.enable()
    trace.clear()
    c = spc.Counters()
    fl = ServingFleet(params, CFG, replicas=2, tp=4,
                      prefill_replicas=1, spc=c)
    fl.run(_stream())
    tl = _merge_rings(tmp_path)
    cons = critical.conservation(tl)
    assert cons["checked"] == 6
    assert cons["all_ok"], cons
    trees = critical.request_trees(tl)
    for tree in trees.values():
        # prefill on lane 0, decode on lane 1: a genuine bridge-mesh
        # stitch, with the migrate hop carrying its wire evidence
        assert tree["ranks"] == [0, 1]
        migs = [s for s in tree["spans"] if s["name"] == "req:migrate"]
        assert migs and migs[0]["args"]["link"] == "decide:reshard"
        assert migs[0]["args"]["wire_bytes"] > 0
    rep = requests.report()
    assert rep["completed"] == 6
    assert rep["slo_breaches"] == 0
    for ex in rep["exemplars"]:
        assert (abs(ex["conservation"]["resid_ms"])
                <= 1e-6 * ex["conservation"]["e2e_ms"] + 1e-9)


# ---------------------------------------------------------------------------
# exemplar reservoir: deterministic slowest-k + every breach
# ---------------------------------------------------------------------------

def test_exemplar_reservoir_determinism():
    """Identical request streams keep IDENTICAL exemplars: the k
    slowest clean requests plus every SLO breach, ordered and chosen
    with no wall-clock or hash-order dependence."""
    var.registry.set_cli("serve_req_exemplar_k", "2")
    var.registry.set_cli("serve_req_slo_e2e_ms", "40")
    requests.enable()

    def play():
        requests.reset()
        durs = [0.010, 0.030, 0.020, 0.050, 0.005, 0.025]
        for i, d in enumerate(durs):
            rid = f"q{i}"
            requests.note_admit(rid, 0.0, 0.001, 4, 2, replica=0)
            requests.note_finish(rid, d)
        return [e["rid"] for e in requests.report()["exemplars"]]

    first, second = play(), play()
    assert first == second
    # q3 breached (50ms > 40ms target) and is kept on top of the two
    # slowest clean requests (q1 30ms, q5 25ms)
    assert set(first) == {"q3", "q1", "q5"}
    rep = requests.report()
    assert rep["slo_breaches"] == 1
    assert rep["exemplars_kept"] == 3


# ---------------------------------------------------------------------------
# SLO judge -> policy bus -> one audited decide:fleet_route
# ---------------------------------------------------------------------------

def test_slo_breach_verdict_drives_route_action():
    """The first breach of an excursion publishes ONE slo_breach
    verdict carrying the attributed stage; the pre-verified
    route_weight action answers it with a single audited
    decide:fleet_route; further breaches in the same episode stay
    silent until a within-SLO request re-arms the judge."""
    var.registry.set_cli("policy_enabled", "true")
    var.registry.set_cli("serve_req_slo_e2e_ms", "10")
    var.registry.reset_cache()
    policy.reset()
    policy.enable()
    serving.reset()
    serving.enable()
    serving.set_fleet_replicas(2)
    requests.reset()
    requests.enable()
    trace.enable()
    trace.clear()

    def finish(rid, *, migrate_s, total_s):
        requests.note_admit(rid, 0.0, 0.001, 4, 2, replica=1)
        requests.note_stage(rid, "prefill", 0.001, 0.003, rank=0)
        requests.note_stage(rid, "migrate", 0.003, 0.003 + migrate_s,
                            rank=0, src=0, dst=1)
        requests.note_finish(rid, total_s)

    # clean baseline: the stage histograms learn what "normal" is
    for i in range(3):
        finish(f"c{i}", migrate_s=0.001, total_s=0.006)
    # breach with a fat migration hop -> verdict, attributed migrate
    finish("b1", migrate_s=0.017, total_s=0.025)
    verdicts = [v for v in policy.report()["verdicts"]
                if v["kind"] == "slo_breach"]
    assert len(verdicts) == 1
    assert verdicts[0]["plane"] == "serve"
    assert verdicts[0]["evidence"]["stage"] == "migrate"
    assert verdicts[0]["evidence"]["replica"] == 1
    # exactly one applied action, one audited decision carrying the
    # attributed stage (kind-aware reason, not hot_replica's)
    applied = [r for r in policy.report()["ledger"]
               if r["rule"] == "req_slo_breach"
               and r["outcome"] == "applied"]
    assert len(applied) == 1
    assert applied[0]["effect"]["stage"] == "migrate"
    route_evs = [e for e in trace.events()
                 if e["name"] == "decide:fleet_route"]
    assert len(route_evs) == 1
    assert route_evs[0]["args"]["reason"] == "slo_breach"
    assert route_evs[0]["args"]["stage"] == "migrate"
    # same episode: a second breach publishes nothing new
    finish("b2", migrate_s=0.017, total_s=0.025)
    assert len([v for v in policy.report()["verdicts"]
                if v["kind"] == "slo_breach"]) == 1
    # a within-SLO finish re-arms; the next breach is a new episode
    finish("ok", migrate_s=0.001, total_s=0.006)
    finish("b3", migrate_s=0.017, total_s=0.025)
    assert len([v for v in policy.report()["verdicts"]
                if v["kind"] == "slo_breach"]) == 2
    assert requests.report()["episodes"] == 2
    assert requests.report()["slo_breaches"] == 3


# ---------------------------------------------------------------------------
# req_* pvars: read-through in spc get/snapshot/export_prometheus
# ---------------------------------------------------------------------------

def test_request_pvars_read_through_and_prometheus():
    requests.reset()
    requests.enable()
    var.registry.set_cli("serve_req_slo_e2e_ms", "10")
    requests.note_admit("a", 0.0, 0.001, 4, 2, replica=0)
    requests.note_admit("b", 0.0, 0.002, 4, 2, replica=0)
    requests.note_finish("a", 0.025)          # breach (25ms > 10ms)
    c = spc.Counters()
    assert c.get("req_active") == 1
    assert c.get("req_completed") == 1
    assert c.get("req_slo_breaches") == 1
    assert c.get("req_exemplars_kept") == 1
    snap = c.snapshot()
    for name in requests.PVARS:
        assert name in snap
    text = spc.export_prometheus(c)  # module-level: + stage family
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert re.match(r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
                        r"(\{[^}]*\})? [-+0-9.e]+$", line), line
    assert 'ompi_tpu_req_slo_breaches' in text
    stage_rows = [ln for ln in text.splitlines()
                  if ln.startswith("ompi_tpu_request_stage_seconds")]
    assert stage_rows, text
    for q in ('quantile="0.5"', 'quantile="0.99"'):
        assert any(q in ln for ln in stage_rows)
    assert any('stage="queue"' in ln for ln in stage_rows)


# ---------------------------------------------------------------------------
# comm_doctor --requests: live + banked golden (schema v13)
# ---------------------------------------------------------------------------

def test_comm_doctor_requests_banked_golden(tmp_path, capsys):
    """A banked REQUESTS json renders verbatim under schema v13, with
    the headline counters, stage table, attribution rollups and the
    slowest-exemplar waterfall in the text view."""
    report = {
        "enabled": True, "active": 0, "completed": 2,
        "slo_breaches": 1, "episodes": 1, "exemplars_kept": 2,
        "slo": {"ttft_ms": 0.0, "itl_p99_ms": 0.0, "e2e_ms": 10.0},
        "e2e": {"count": 2, "p50_ms": 8.0, "p99_ms": 25.0},
        "stages": {"queue": {"count": 2, "p50_ms": 1.0, "p99_ms": 1.0},
                   "migrate": {"count": 2, "p50_ms": 9.0,
                               "p99_ms": 17.0}},
        "tail_attribution": {"migrate": 1},
        "breach_attribution": {"migrate": 1},
        "exemplars": [{
            "rid": 9, "replica": 1, "e2e_ms": 25.0, "arrival": 0.0,
            "attributed_stage": "migrate",
            "breach": [{"metric": "e2e_ms", "value_ms": 25.0,
                        "target_ms": 10.0}],
            "spans": [{"stage": "queue", "t0": 0.0, "t1": 0.001,
                       "rank": 0},
                      {"stage": "migrate", "t0": 0.003, "t1": 0.020,
                       "rank": 0}],
            "conservation": {"stage_sum_ms": 25.0, "e2e_ms": 25.0,
                             "resid_ms": 0.0},
        }],
    }
    banked = tmp_path / "REQUESTS_cpu.json"
    banked.write_text(json.dumps({"metric": "request_slo_attribution",
                                  "value": 2.0, "report": report}))
    rc = comm_doctor.main(["--requests", str(banked), "--json"])
    assert rc == 0
    data = json.loads(capsys.readouterr().out)
    assert data["schema_version"] == 14      # the v13 -> v14 pin
    assert data["requests"] == report        # banked report, verbatim
    rc = comm_doctor.main(["--requests", str(banked)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "requests: 2 completed" in out
    assert "1 SLO breach(es) in 1 episode(s)" in out
    assert "SLO: e2e_ms<=10ms" in out
    assert "tail attribution (kept exemplars): migrate=1" in out
    assert "slowest exemplar rid 9" in out and "BREACH" in out
    assert "migrate  r0" in out
    assert "stage sum 25.00 ms vs e2e 25.00 ms" in out


def test_comm_doctor_requests_live_section(capsys):
    requests.reset()
    requests.enable()
    trace.disable()
    requests.note_admit(1, 0.0, 0.001, 4, 2, replica=0)
    requests.note_finish(1, 0.010)
    rc = comm_doctor.main(["--requests", "--json"])
    assert rc == 0
    data = json.loads(capsys.readouterr().out)
    assert data["schema_version"] == 14
    req = data["requests"]
    assert req["completed"] == 1
    assert req["slo_breaches"] == 0
    assert req["exemplars"][0]["rid"] == 1


# ---------------------------------------------------------------------------
# disabled path: one attribute read, zero state
# ---------------------------------------------------------------------------

def test_disabled_plane_leaves_zero_state(params):
    """With the plane off (the default), a full fleet run records no
    request state and emits no req:* events — the call sites gate on
    one `requests.enabled` attribute read."""
    assert requests.enabled is False
    serving.reset()
    serving.enable()
    trace.enable()
    trace.clear()
    c = spc.Counters()
    fl = ServingFleet(params, CFG, replicas=2, tp=4,
                      prefill_replicas=1, spc=c)
    fl.run(_stream(n=3))
    for name in requests.PVARS:
        assert c.get(name) == 0.0
    assert not [e for e in trace.events()
                if e["name"].startswith("req:")]
    rep = requests.report()
    assert rep["completed"] == 0 and rep["exemplars"] == []
