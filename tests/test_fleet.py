"""Disaggregated multi-replica serving fleet (PR 18).

Covers the bitwise KV-page migration round-trip over ``cross_reshard``
(peak within the reshard bound, conservation held), the deficit
round-robin router's admission determinism, greedy token-stream
identity colocated vs disaggregated, the fleet_* pvar read-through
under the Prometheus grammar, comm_doctor --fleet (live + banked
golden under the v12 schema), and the hot_replica sentry driving the
pre-verified route_weight action through one audited
decide:fleet_route.
"""

import json
import os
import re

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ompi_tpu import policy, serving, spc, trace, traffic  # noqa: E402
from ompi_tpu.core import var  # noqa: E402
from ompi_tpu.models import transformer as tfm  # noqa: E402
from ompi_tpu.serving.fleet import ServingFleet  # noqa: E402
from ompi_tpu.serving.scheduler import (FleetRouter,  # noqa: E402
                                        poisson_stream)
from ompi_tpu.tools import comm_doctor  # noqa: E402

pytestmark = pytest.mark.fleet


CFG = tfm.Config(vocab=512, d_model=128, n_layers=2, n_heads=8,
                 head_dim=16, d_ff=256, dtype=jnp.float32)


@pytest.fixture(autouse=True)
def _clean_state():
    """Every test leaves the planes and CLI vars as it found them."""
    yield
    for name in ("topo_sim_dcn_axes", "topo_sim_dcn_us_per_mib",
                 "serve_enabled", "serve_fleet_hot_skew",
                 "serve_fleet_route_scale"):
        var.registry.clear_cli(name)
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


# ---------------------------------------------------------------------------
# KV-page migration: bitwise round-trip under the reshard contract
# ---------------------------------------------------------------------------

def test_migration_bitwise_roundtrip_and_peak_bound(params):
    """Pages prefilled on the prefill replica arrive on the decode
    replica bit-identical, the cross_reshard plan's peak stays within
    the reshard_peak_factor bound, and every migrated byte conserves
    through the traffic matrix."""
    serving.reset()
    serving.enable()
    c = spc.Counters()
    fl = ServingFleet(params, CFG, replicas=2, tp=4,
                      prefill_replicas=1, spc=c)
    pre, dec = fl.replicas[0], fl.replicas[1]
    rng = np.random.default_rng(3)
    prompt = rng.integers(2, CFG.vocab, 17).astype(np.int32)

    pslot = pre.engine.cache.admit(len(prompt), 4)
    pre.engine.prefill(pslot, prompt)
    # conservation window opens AFTER construction + prefill (the
    # convert_params reshard and prefill collectives charge their own
    # ledgers) — the window holds the migration hop alone
    c = spc.Counters()
    fl.spc = c
    for rep_ in fl.replicas:
        rep_.dc.spc = c
    traffic.reset()
    traffic.enable()
    scache = pre.engine.cache
    spages = list(scache._slot_pages[pslot])
    src_vals = [(np.asarray(scache.k[layer])[:, spages],
                 np.asarray(scache.v[layer])[:, spages])
                for layer in range(scache.n_layers)]
    seq_len = int(scache.seq_lens[pslot])

    dslot = fl.migrate(pre, dec, pslot, len(prompt), 4, rid="r0")

    dcache = dec.engine.cache
    dpages = list(dcache._slot_pages[dslot])
    assert int(dcache.seq_lens[dslot]) == seq_len
    for layer, (sk, sv) in enumerate(src_vals):
        dk = np.asarray(dcache.k[layer])[:, dpages]
        dv = np.asarray(dcache.v[layer])[:, dpages]
        assert dk.dtype == sk.dtype and np.array_equal(dk, sk)
        assert np.array_equal(dv, sv)

    rep = serving.fleet_report()
    assert rep["migrations"] == 1
    mig = rep["migration_log"][0]
    assert mig["rid"] == "r0" and mig["within_bound"]
    assert mig["bytes"] > 0
    assert mig["peak_bytes"] <= mig["bound_bytes"]
    # conservation: the migrated bytes all land on audited edges
    assert traffic.matrix.edge_bytes_total() == \
        int(c.get("coll_wire_bytes")) == mig["bytes"]
    assert int(traffic.matrix.unattributed_bytes) == 0
    assert int(c.get("fleet_migrated_bytes")) == mig["bytes"]


def test_migration_charges_simulated_dcn_hop(params):
    """With the bridge's fleet axis classified as DCN, each migration
    pays the modeled wire cost (the replica-internal tp rings do not
    reclassify)."""
    from ompi_tpu.parallel.hierarchy import classify_axes
    var.registry.set_cli("topo_sim_dcn_axes", "fleet")
    serving.reset()
    serving.enable()
    fl = ServingFleet(params, CFG, replicas=2, tp=4,
                      prefill_replicas=1, spc=spc.Counters())
    pre, dec = fl.replicas[0], fl.replicas[1]
    bridge = fl._bridge(pre, dec)
    assert classify_axes(bridge).get("fleet") == "dcn"
    assert classify_axes(pre.dc.mesh).get("tp") != "dcn"


# ---------------------------------------------------------------------------
# Router: deterministic deficit round-robin admission
# ---------------------------------------------------------------------------

def test_router_admission_deterministic():
    """Identical weight history + identical stream => identical
    assignment sequence (a pure function, no RNG)."""
    seqs = []
    for _ in range(2):
        r = FleetRouter(3)
        r.set_weight(0, 2.0)
        r.set_weight(1, 1.0)
        r.set_weight(2, 1.0)
        seqs.append([r.assign(i) for i in range(12)])
    assert seqs[0] == seqs[1]
    # weight 2/1/1 => replica 0 lands half the stream
    assert seqs[0].count(0) == 6
    assert seqs[0].count(1) == 3 and seqs[0].count(2) == 3


def test_router_update_reweights_by_goodput_over_itl():
    r = FleetRouter(2)
    r.update(0, tokens_per_s=100.0, itl_p99_ms=10.0)
    r.update(1, tokens_per_s=100.0, itl_p99_ms=40.0)
    picks = [r.assign(i) for i in range(10)]
    # replica 0's weight is 4x replica 1's: 8 of 10 admissions
    assert picks.count(0) == 8 and picks.count(1) == 2


def test_router_ties_break_to_lowest_replica():
    r = FleetRouter(2)
    assert r.assign(0) == 0                # equal credits: lowest id


# ---------------------------------------------------------------------------
# Token-stream identity: colocated vs disaggregated
# ---------------------------------------------------------------------------

def test_greedy_identity_colocated_vs_disaggregated(params):
    """The SAME stream decodes to identical per-request greedy tokens
    whether a request prefills and decodes on one replica or its KV
    pages migrate prefill -> decode mid-flight."""
    serving.reset()
    serving.enable()
    coloc = ServingFleet(params, CFG, replicas=1, tp=4,
                         devices=jax.devices()[:4], spc=spc.Counters())
    out_c = coloc.run(_stream())
    serving.reset()
    disagg = ServingFleet(params, CFG, replicas=2, tp=4,
                          prefill_replicas=1, spc=spc.Counters())
    out_d = disagg.run(_stream())
    rep = serving.fleet_report()
    assert out_c["completed"] == out_d["completed"] == 6
    for rid, r in out_c["results"].items():
        assert r["tokens"] == out_d["results"][rid]["tokens"], rid
    assert rep["migrations"] > 0
    assert all(m["within_bound"] for m in rep["migration_log"])
    # one serve:migrate span per migration
    trace.enable()


# ---------------------------------------------------------------------------
# fleet_* pvars: read-through in spc get/snapshot/export_prometheus
# ---------------------------------------------------------------------------

def test_fleet_pvars_read_through_and_prometheus():
    serving.reset()
    serving.enable()
    serving.set_fleet_replicas(2)
    serving.note_migration("r1", 0, 1, 3, 4096, 8192, 16384, 0.001)
    serving.update_replica(1, {"role": "decode"})
    assert serving.apply_route_weight(1, 0.5) == pytest.approx(0.5)
    c = spc.Counters()
    assert c.get("fleet_replicas") == 2
    assert c.get("fleet_migrations") == 1
    assert c.get("fleet_migrated_bytes") == 4096
    assert c.get("fleet_rebalances") == 1
    snap = c.snapshot()
    for name in serving.FLEET_PVARS:
        assert name in snap
    text = c.export_prometheus()
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert re.match(r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
                        r"(\{[^}]*\})? [-+0-9.e]+$", line), line
    assert 'ompi_tpu_fleet_migrated_bytes' in text


# ---------------------------------------------------------------------------
# comm_doctor --fleet: live + banked golden (schema v12)
# ---------------------------------------------------------------------------

def test_comm_doctor_fleet_live_section(capsys):
    serving.reset()
    serving.enable()
    serving.set_fleet_replicas(2)
    serving.update_replica(0, {"role": "prefill", "prefills": 4,
                               "prefill_s": 0.1, "clock_s": 0.5})
    serving.update_replica(1, {"role": "decode", "requests": 4,
                               "tokens": 20, "tokens_per_s": 40.0,
                               "occupancy": 0.5, "itl_p50_ms": 5.0,
                               "itl_p99_ms": 9.0})
    serving.note_migration("r2", 0, 1, 2, 2048, 4096, 8192, 0.002)
    serving.note_route("r2", 1, [1.0])
    rc = comm_doctor.main(["--fleet", "--json"])
    assert rc == 0
    data = json.loads(capsys.readouterr().out)
    assert data["schema_version"] == 14
    fl = data["fleet"]
    assert fl["replicas"] == 2
    assert fl["migrations"] == 1 and fl["migrated_bytes"] == 2048
    assert fl["migration_log"][0]["within_bound"]
    assert fl["routes"][0]["replica"] == 1

    rc = comm_doctor.main(["--fleet"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "fleet: 2 replica(s), 1 KV-page migration(s)" in out
    assert "migration ledger" in out
    assert "all within the reshard peak bound" in out
    assert "router decisions" in out


def test_comm_doctor_fleet_banked_json_golden(tmp_path, capsys):
    """--fleet with a banked FLEET json (a report under "report")
    renders standalone and round-trips the report verbatim into the
    structured output, under the v12 schema pin."""
    report = {
        "replicas": 2, "migrations": 2, "migrated_bytes": 339968,
        "rebalances": 0,
        "replica_rows": [
            {"replica": 0, "role": "prefill", "prefills": 2,
             "prefill_s": 0.031, "clock_s": 0.051, "route_bias": 1.0},
            {"replica": 1, "role": "decode", "requests": 2,
             "tokens": 10, "decode_steps": 8, "clock_s": 0.4,
             "tokens_per_s": 25.0, "occupancy": 0.31,
             "itl_p50_ms": 8.1, "itl_p99_ms": 14.2,
             "route_bias": 1.0}],
        "migration_log": [
            {"rid": 0, "src": 0, "dst": 1, "pages": 3,
             "bytes": 169984, "peak_bytes": 169984,
             "bound_bytes": 339968, "within_bound": True,
             "dur_ms": 1.9},
            {"rid": 1, "src": 0, "dst": 1, "pages": 3,
             "bytes": 169984, "peak_bytes": 169984,
             "bound_bytes": 339968, "within_bound": True,
             "dur_ms": 1.7}],
        "routes": [{"rid": 0, "replica": 1, "weights": [1.0]},
                   {"rid": 1, "replica": 1, "weights": [1.0]}],
    }
    banked = tmp_path / "FLEET_cpu.json"
    banked.write_text(json.dumps(
        {"metric": "fleet_tokens_per_s", "value": 25.0,
         "report": report}))

    rc = comm_doctor.main(["--fleet", str(banked), "--json"])
    assert rc == 0
    data = json.loads(capsys.readouterr().out)
    assert data["schema_version"] == 14       # the v13 -> v14 pin
    assert data["fleet"] == report            # banked report, verbatim

    rc = comm_doctor.main(["--fleet", str(banked)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "fleet: 2 replica(s), 2 KV-page migration(s)" in out
    assert "339968 byte(s) migrated" in out
    assert "prefill lane" in out
    assert "rid 0" in out and "r0->r1" in out


# ---------------------------------------------------------------------------
# hot_replica sentry -> pre-verified route_weight action
# ---------------------------------------------------------------------------

def _fabricate_fleet_rows(skewed_p99=40.0):
    serving.set_fleet_replicas(3)
    serving.update_replica(0, {"role": "decode", "tokens_per_s": 50.0,
                               "itl_p99_ms": 10.0})
    serving.update_replica(1, {"role": "decode", "tokens_per_s": 48.0,
                               "itl_p99_ms": 11.0})
    serving.update_replica(2, {"role": "decode", "tokens_per_s": 20.0,
                               "itl_p99_ms": skewed_p99})


def test_hot_replica_sentry_drives_route_weight(params):
    """A replica whose p99 ITL skews >= serve_fleet_hot_skew x the
    fleet median publishes ONE hot_replica verdict (episode semantics),
    the builtin fleet_hot_replica rule applies the pre-verified
    route_weight action (bias *= serve_fleet_route_scale), and exactly
    one decide:fleet_route decision names the verdict."""
    serving.reset()
    serving.enable()
    policy.reset()
    policy.enable()
    trace.enable()
    trace.clear()
    fl = ServingFleet(params, CFG, replicas=1, tp=4,
                      devices=jax.devices()[:4], spc=spc.Counters())
    _fabricate_fleet_rows()
    fl._hot = {}
    fl.check_hot_replicas(step=5)
    fl.check_hot_replicas(step=6)          # episode: no re-fire
    rep = serving.fleet_report()
    rows = {r["replica"]: r for r in rep["replica_rows"]}
    assert rows[2]["route_bias"] == pytest.approx(0.5)
    assert rows[0]["route_bias"] == pytest.approx(1.0)
    assert rep["rebalances"] == 1
    decisions = [e for e in trace.events()
                 if e.get("name") == "decide:fleet_route"]
    assert len(decisions) == 1
    args = decisions[0].get("args", {})
    assert args.get("verdict", {}).get("kind") == "hot_replica" or \
        "hot_replica" in json.dumps(args)
    verdicts = [e for e in trace.events()
                if e.get("name") == "policy_verdict"]
    assert any("hot_replica" in json.dumps(e.get("args", {}))
               for e in verdicts)


def test_hot_replica_sentry_rearms_after_recovery(params):
    serving.reset()
    serving.enable()
    policy.reset()
    policy.enable()
    var.registry.set_cli("serve_fleet_hot_skew", "2.0")
    fl = ServingFleet(params, CFG, replicas=1, tp=4,
                      devices=jax.devices()[:4], spc=spc.Counters())
    _fabricate_fleet_rows(skewed_p99=50.0)
    fl.check_hot_replicas(step=1)
    assert fl._hot.get(2) is True
    _fabricate_fleet_rows(skewed_p99=12.0)     # recovered: < 0.9*thr
    fl.check_hot_replicas(step=2)
    assert not fl._hot.get(2)


def test_route_weight_biases_router_assignment():
    """A halved route bias shifts the deficit round-robin admission
    share without touching the router's own weight state."""
    serving.reset()
    serving.enable()
    serving.set_fleet_replicas(2)
    serving.update_replica(0, {"role": "decode"})
    serving.update_replica(1, {"role": "decode"})
    assert serving.apply_route_weight(1, 0.5) == pytest.approx(0.5)
    r = FleetRouter(2)
    picks = [r.assign(i) for i in range(9)]
    # effective weights 1.0 / 0.5: replica 0 admits 2 of every 3
    assert picks.count(0) == 6 and picks.count(1) == 3
