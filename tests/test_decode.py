"""Decode fast path (PR 16).

Covers the fused collective-matmul decode program (eager-vs-fused
greedy parity, the dispatch collapse from 11 eager collectives/step to
2, the decide-event audit for the in-program rings), the commgraph
static extraction of the fused program with byte-for-byte
static-vs-runtime wire agreement on 2/4/8-device meshes, speculative
draft/verify windows (token-stream identity, measured acceptance
ledger, block-table truncate on reject), the pad-past-native quant
eligibility veto (rule rows AND learned candidacy), learned decode-arm
selection from the perf ledger, MoE expert-parallel decode parity
against the einsum forward, the comm-lint pass over the serving
modules, and comm_doctor --serve's speculative/dispatch sections.
"""

import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ompi_tpu import perf, serving, spc, trace, traffic  # noqa: E402
from ompi_tpu.coll import xla  # noqa: E402
from ompi_tpu.core import var  # noqa: E402
from ompi_tpu.models import transformer as tfm  # noqa: E402
from ompi_tpu.parallel import DeviceComm, make_mesh  # noqa: E402
from ompi_tpu.serving import fused  # noqa: E402
from ompi_tpu.serving.engine import ServingEngine  # noqa: E402
from ompi_tpu.serving.scheduler import (ContinuousBatchingScheduler,  # noqa: E402
                                        poisson_stream)

pytestmark = pytest.mark.decode


CFG = tfm.Config(vocab=512, d_model=128, n_layers=2, n_heads=8,
                 head_dim=16, d_ff=256, dtype=jnp.float32)
CFG_F = tfm.Config(vocab=512, d_model=128, n_layers=2, n_heads=8,
                   head_dim=16, d_ff=256, dtype=jnp.float32,
                   decode_overlap="fused")
# the fused program's in-program ring count: 4 rings per layer
# (qkv AG, wo RS, gate|up AG, down RS) + the logits AG
RINGS = 4 * CFG.n_layers + 1


@pytest.fixture(autouse=True)
def _clean_state():
    yield
    for name in ("coll_xla_decode_ag_mode", "coll_xla_decode_rs_mode",
                 "coll_xla_rules", "coll_quant_block",
                 "coll_quant_min_bytes", "serve_enabled"):
        var.registry.clear_cli(name)
    perf.reset()
    perf.disable()
    serving.reset()
    serving.disable()
    traffic.reset()
    traffic.disable()
    trace.clear()
    trace.disable()


def _dc(n=8):
    mesh = make_mesh({"tp": n}, devices=jax.devices()[:n])
    dc = DeviceComm(mesh, "tp")
    dc.spc = spc.Counters()
    return dc


@pytest.fixture(scope="module")
def shared():
    dc = _dc()
    params = tfm.init_params(jax.random.PRNGKey(0), CFG)
    sharded = tfm.shard_params(params, dc.mesh, CFG)
    return dc, params, sharded


def _engine(dc, sharded, cfg=CFG, **kw):
    kw.setdefault("n_pages", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seqs", 8)
    return ServingEngine(dc, sharded, cfg, **kw)


def _greedy_decode(eng, prompt, steps):
    slot = eng.cache.admit(len(prompt), steps + 1)
    first, _ = eng.prefill(slot, prompt)
    toks, per_step_logits = [first], []
    last = first
    for _ in range(steps):
        t = np.zeros(eng.max_seqs, np.int32)
        p = np.full(eng.max_seqs, -1, np.int64)
        t[slot] = last
        p[slot] = int(eng.cache.seq_lens[slot])
        nxt, lg = eng.decode_step(t, p)
        eng.cache.seq_lens[slot] += 1
        toks.append(int(nxt[slot]))
        per_step_logits.append(np.asarray(lg)[0, slot])
        last = int(nxt[slot])
    eng.cache.release(slot)
    return toks, np.stack(per_step_logits)


def _dense_paged_attn(q, kp, vp, bt, q_pos, mesh=None, axis=None):
    """The dense-gather computation the paged kernel replaced."""
    k = jnp.take(kp, bt, axis=1)
    v = jnp.take(vp, bt, axis=1)
    r, b, pmax, pg, hl, hd = k.shape
    att = tfm.decode_attention(q, k.reshape(r, b, pmax * pg, hl, hd),
                               v.reshape(r, b, pmax * pg, hl, hd), q_pos)
    return att.reshape(r, b, hl * hd)


class TestRingSchedule:
    def test_sites_and_wire_pinned(self):
        n, rows, d, isz = 8, 8, CFG.d_model, 4
        sched = fused.ring_schedule(CFG.n_layers, rows, d, n, isz)
        sites = [s for s, _, _ in sched]
        assert sites == ["L0/qkv_ag", "L0/wo_rs", "L0/gateup_ag",
                         "L0/down_rs", "L1/qkv_ag", "L1/wo_rs",
                         "L1/gateup_ag", "L1/down_rs", "logits_ag"]
        for site, payload, wire in sched:
            # every ring moves the (rows/tp, d_model) residual shard:
            # AG hops in the compute dtype, RS partials in f32
            assert payload == (rows // n) * d * (isz if
                                                 site.endswith("_ag")
                                                 else 4)
            assert wire == (n - 1) * payload

    def test_schedule_scales_with_rows(self):
        a = fused.ring_schedule(2, 8, 128, 8, 4)
        b = fused.ring_schedule(2, 24, 128, 8, 4)
        assert len(a) == len(b) == RINGS
        for (_, pa, wa), (_, pb, wb) in zip(a, b):
            assert pb == 3 * pa and wb == 3 * wa


class TestFusedParity:
    def test_greedy_matches_eager(self, shared):
        dc, _, sharded = shared
        prompt = np.array([3, 17, 99, 254, 7], np.int32)
        toks_e, lg_e = _greedy_decode(_engine(dc, sharded), prompt, 5)
        toks_f, lg_f = _greedy_decode(_engine(dc, sharded, CFG_F),
                                      prompt, 5)
        assert toks_f == toks_e
        relerr = (np.abs(lg_f - lg_e).max()
                  / (np.abs(lg_e).max() + 1e-9))
        assert relerr < 1e-4

    def test_dispatch_collapse_and_decide_audit(self, shared):
        dc, _, sharded = shared
        eng = _engine(dc, sharded, CFG_F)
        trace.enable()
        trace.clear()
        slot = eng.cache.admit(3, 4)
        first, _ = eng.prefill(slot, np.array([5, 6, 7], np.int32))
        base = dict(eng.dispatches)
        n0 = sum(1 for e in trace.events()
                 if e.get("name") == "decide:decode_collmm")
        steps, last = 3, first
        for _ in range(steps):
            t = np.zeros(eng.max_seqs, np.int32)
            p = np.full(eng.max_seqs, -1, np.int64)
            t[slot] = last
            p[slot] = int(eng.cache.seq_lens[slot])
            nxt, _lg = eng.decode_step(t, p)
            eng.cache.seq_lens[slot] += 1
            last = int(nxt[slot])
        eng.cache.release(slot)
        # the tentpole collapse: 11 eager dispatches/step -> 2 (embed
        # AG + logits AG); everything else rides the fused program
        eager = (eng.dispatches["decode_ag"] - base["decode_ag"]
                 + eng.dispatches["decode_rs"] - base["decode_rs"])
        assert eager == 2 * steps
        collmm = (eng.dispatches["decode_collmm"]
                  - base["decode_collmm"])
        assert collmm == RINGS * steps
        # exactly one decision event per in-program ring dispatch
        n_dec = sum(1 for e in trace.events()
                    if e.get("name") == "decide:decode_collmm") - n0
        assert n_dec == collmm
        ev = trace.explain_last("decode_collmm")
        assert ev and ev["arm"] == "native"

    def test_fused_requires_divisible_batch(self, shared):
        dc, _, sharded = shared
        with pytest.raises(ValueError, match="max_seqs"):
            _engine(dc, sharded, CFG_F, max_seqs=3)


class TestCommGraphFusedDecode:
    @pytest.mark.parametrize("ndev", [2, 4, 8])
    def test_static_matches_runtime_bytes(self, ndev):
        dc = _dc(ndev)
        params = tfm.init_params(jax.random.PRNGKey(1), CFG)
        sharded = tfm.shard_params(params, dc.mesh, CFG)
        eng = _engine(dc, sharded, CFG_F)
        rep = eng.verify_decode_program()
        assert rep.ok, rep.summary()
        rows = {r["coll"]: r for r in rep.rows}
        want = sum(w for _, _, w in fused.ring_schedule(
            CFG.n_layers, eng.max_seqs, CFG.d_model, ndev, 4))
        assert rows["decode_collmm"]["static"] == want > 0
        assert rows["decode_collmm"]["runtime"] == want

    def test_extraction_sees_all_rings(self, shared):
        dc, _, sharded = shared
        eng = _engine(dc, sharded, CFG_F)
        rep = eng.verify_decode_program()
        assert rep.ok
        # every ppermute hop of every ring is statically visible:
        # RINGS rings x (n-1) hops each (peeled first hop + scan)
        assert rep.n_records > 0
        assert not rep.host_transfers


class TestSpeculative:
    def _run(self, shared, cfg, spec_k, n=8, seed=21):
        dc, _, sharded = shared
        serving.reset()
        serving.enable()
        eng = _engine(dc, sharded, cfg)
        reqs = poisson_stream(n, qps=50.0, vocab=CFG.vocab, seed=seed)
        out = ContinuousBatchingScheduler(eng, reqs,
                                          spec_k=spec_k).run()
        rep = serving.report()
        assert eng.cache.pages_used == 0
        return out, rep

    @pytest.mark.parametrize("cfg", [CFG, CFG_F],
                             ids=["eager", "fused"])
    def test_stream_identity_and_measured_ledger(self, shared, cfg):
        out_p, _ = self._run(shared, cfg, spec_k=0)
        out_s, rep = self._run(shared, cfg, spec_k=2)
        for rid, r in out_p["results"].items():
            assert r["tokens"] == out_s["results"][rid]["tokens"], rid
        sp = rep["speculative"]
        assert sp["windows"] > 0
        assert sp["drafted"] == sp["windows"]          # k-1 == 1 each
        assert 0 <= sp["accepted"] <= sp["drafted"]
        assert sp["acceptance_rate"] == pytest.approx(
            sp["accepted"] / sp["drafted"])
        # accepted windows emit extra tokens per step: fewer steps
        assert out_s["decode_steps"] <= out_p["decode_steps"]

    def test_reject_truncates_block_table(self, shared):
        dc, _, sharded = shared
        serving.reset()
        serving.enable()
        eng = _engine(dc, sharded)
        reqs = poisson_stream(1, qps=50.0, vocab=CFG.vocab, seed=4)
        reqs[0].max_new = 6
        sched = ContinuousBatchingScheduler(eng, reqs, spec_k=3)
        sched.run()
        rep = serving.report()
        sp = rep["speculative"]
        if sp["accepted"] < sp["drafted"]:
            # at least one reject happened; the run still drained with
            # the identical greedy stream (checked above) — the
            # truncate rolled seq_lens back, so pages fully released
            assert eng.cache.pages_used == 0

    @pytest.mark.parametrize("n", [1, 2], ids=["tp1", "tp2"])
    def test_window_matches_dense_gather(self, shared, n, monkeypatch):
        """A k=3 verify window over two live slots of different lengths
        and two inactive ones: the kernel's greedy tokens and logits
        against the dense gather's."""
        from ompi_tpu.serving import engine as engine_mod
        _, params, _ = shared
        dc = _dc(n)
        sharded = tfm.shard_params(params, dc.mesh, CFG)
        prompts = [np.arange(5, dtype=np.int32) + 3,
                   (np.arange(14, dtype=np.int32) * 11) % CFG.vocab]

        def window():
            eng = _engine(dc, sharded, max_seqs=4)
            slots = [eng.cache.admit(len(p), 4) for p in prompts]
            toks = np.zeros((4, 3), np.int32)
            pos = np.full((4, 3), -1, np.int64)
            for s, p in zip(slots, prompts):
                first, _ = eng.prefill(s, p)
                toks[s] = [first, 7, 42]
                pos[s] = len(p) + np.arange(3)
            return eng.decode_window(toks, pos), slots

        (nxt, lg), slots = window()
        monkeypatch.setattr(engine_mod, "_j_paged_attn", _dense_paged_attn)
        (ref_nxt, ref_lg), _ = window()
        np.testing.assert_array_equal(nxt[slots], ref_nxt[slots])
        rows = np.concatenate([np.arange(3) + 3 * s for s in slots])
        lg, ref_lg = np.asarray(lg)[0, rows], np.asarray(ref_lg)[0, rows]
        assert (np.abs(lg - ref_lg).max()
                / (np.abs(ref_lg).max() + 1e-9)) < 1e-4

    def test_spec_k_validation(self, shared):
        dc, _, sharded = shared
        eng = _engine(dc, sharded)
        with pytest.raises(ValueError, match="spec_k"):
            ContinuousBatchingScheduler(eng, [], spec_k=1)

    def test_draft_ngram_continuation(self):
        d = ContinuousBatchingScheduler._draft
        # bigram (2,3) seen earlier -> continues with 4, then (3,4)->5
        assert d([1, 2, 3, 4, 5, 2, 3], 2) == [4, 5]
        # no bigram match -> repeat last
        assert d([7, 8, 9], 2) == [9, 9]


class TestPadPastNativeVeto:
    def test_model_flags_small_payloads(self):
        # 256 B f32 over 8 devs: 8-element shards pad to the 256-elem
        # default block — int8+scale ships MORE than native
        assert xla._quant_pads_past_native("decode_ag", 256, 8,
                                           np.float32)
        # 8 KiB shards (256 elems) fit the block: quant genuinely wins
        assert not xla._quant_pads_past_native("decode_ag", 8192, 8,
                                               np.float32)

    def test_rule_row_quant_vetoed(self):
        var.registry.set_cli("coll_quant_min_bytes", "0")
        rules = [("decode_ag", 1, 0, "quant")]
        arm, reason, chain = xla.decide_mode(
            "decode_ag", 256, 8, "cpu", rules, ("native", "quant"),
            quant_ok=True, dtype=np.float32)
        assert arm != "quant"
        assert "ineligible:quant:pad-past-native" in reason

    def test_rule_row_quant_survives_above_padding(self):
        var.registry.set_cli("coll_quant_min_bytes", "0")
        rules = [("decode_ag", 1, 0, "quant")]
        arm, reason, _ = xla.decide_mode(
            "decode_ag", 8192, 8, "cpu", rules, ("native", "quant"),
            quant_ok=True, dtype=np.float32)
        assert arm == "quant"
        assert reason == "rule:decode_ag 1 0 quant"

    def test_learned_candidacy_excludes_padded_quant(self):
        var.registry.set_cli("coll_xla_rules", "learned")
        var.registry.set_cli("coll_quant_min_bytes", "0")
        var.registry.set_cli("perf_enabled", "true")
        var.registry.reset_cache()
        perf.reset()
        perf.enable()
        # seed the ledger so quant looks 10x FASTER at this bucket:
        # candidacy, not speed, must exclude it below the padding floor
        for _ in range(4):
            perf.note_sample("decode_ag", "quant", 256, 1e-6, 8)
            perf.note_sample("decode_ag", "native", 256, 1e-5, 8)
        arm, reason, _ = xla.decide_mode(
            "decode_ag", 256, 8, "cpu", [], ("native", "quant"),
            quant_ok=True, dtype=np.float32)
        assert arm == "native"
        assert reason.startswith("learned:native=")


class TestLearnedDecodeArms:
    def test_ledger_drives_decode_colls(self):
        var.registry.set_cli("coll_xla_rules", "learned")
        var.registry.set_cli("coll_quant_min_bytes", "0")
        var.registry.set_cli("coll_quant_block", "32")
        var.registry.set_cli("perf_enabled", "true")
        var.registry.reset_cache()
        perf.reset()
        perf.enable()
        # decode-sized payloads, block 32: no padding veto — the
        # measured GB/s decides, and the reason carries both arms
        for _ in range(4):
            perf.note_sample("decode_ag", "quant", 8192, 1e-6, 8)
            perf.note_sample("decode_ag", "native", 8192, 1e-5, 8)
            perf.note_sample("decode_rs", "native", 8192, 1e-6, 8)
            perf.note_sample("decode_rs", "quant", 8192, 1e-5, 8)
        ag, ag_reason, _ = xla.decide_mode(
            "decode_ag", 8192, 8, "cpu", [], ("native", "quant"),
            quant_ok=True, dtype=np.float32)
        rs, rs_reason, _ = xla.decide_mode(
            "decode_rs", 8192, 8, "cpu", [], ("native", "quant"),
            quant_ok=True, dtype=np.float32)
        assert ag == "quant" and rs == "native"
        assert ag_reason.startswith("learned:quant=")
        assert "-vs-" in ag_reason and "-vs-" in rs_reason


class TestMoEDecode:
    def test_moe_engine_matches_einsum_forward(self, shared):
        dc, _, _ = shared
        cfg = tfm.Config(vocab=512, d_model=128, n_layers=2, n_heads=8,
                         head_dim=16, d_ff=256, dtype=jnp.float32,
                         mlp="moe", n_experts=8, moe_top_k=2,
                         moe_capacity_factor=4.0)
        params = tfm.init_params(jax.random.PRNGKey(2), cfg)
        sharded = tfm.shard_params(params, dc.mesh, cfg)
        eng = ServingEngine(dc, sharded, cfg, n_pages=64, page_size=8,
                            max_seqs=8)
        prompt = np.array([3, 17, 99, 254], np.int32)
        trace.enable()
        trace.clear()
        toks, _ = _greedy_decode(eng, prompt, 4)
        # audited MoE a2a pair runs on every prefill+decode step
        n_disp = sum(1 for e in trace.events()
                     if e.get("name") == "decide:moe_dispatch")
        n_comb = sum(1 for e in trace.events()
                     if e.get("name") == "decide:moe_combine")
        assert n_disp == n_comb > 0
        # 2 MoE layers x (1 prefill + 4 decode steps)
        assert n_disp == cfg.n_layers * 5
        # greedy parity vs the train-layout einsum forward
        ref_toks = list(prompt)
        want = []
        for _ in range(5):
            lg, _aux = tfm.forward(params, jnp.asarray([ref_toks],
                                                       jnp.int32), cfg)
            nxt = int(np.asarray(lg)[0, -1].argmax())
            want.append(nxt)
            ref_toks.append(nxt)
        assert toks == want


class TestCommLint:
    def test_serving_modules_clean(self):
        from ompi_tpu.analysis.lint import lint_paths
        root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        paths = [os.path.join(root, "ompi_tpu", "serving", f)
                 for f in ("engine.py", "fused.py", "scheduler.py",
                           "cache.py", "__init__.py")]
        paths.append(os.path.join(root, "ompi_tpu", "ops",
                                  "collective_matmul.py"))
        findings = [f for f in lint_paths(paths) if not f.waived]
        assert not findings, "\n".join(f.format() for f in findings)


class TestDoctorDecode:
    def test_serve_report_renders_spec_and_dispatches(self):
        from ompi_tpu.tools import comm_doctor
        assert comm_doctor.SCHEMA_VERSION == 14
        serving.reset()
        serving.enable()
        serving.note_admit("r9", 4, 8, 0.0, 0.0)
        serving.note_prefill(0.01, 4)
        serving.note_token("r9", 0.1)
        serving.note_spec(2, 1)
        serving.note_spec(2, 2)
        serving.note_dispatch("eager", 11)
        serving.note_dispatch("fused", 9)
        serving.note_evict("r9", "max_new", 0.2)
        txt, data = comm_doctor.build_serve_report()
        assert "speculative: 2 verify window(s)" in txt
        assert "3/4 draft(s) accepted" in txt
        assert "75.0% measured" in txt
        assert "1 rejected" in txt
        assert "eager 11" in txt and "fused 9" in txt
        sp = data["speculative"]
        assert sp["drafted"] == 4 and sp["accepted"] == 3
        assert data["dispatches"] == {"eager": 11, "fused": 9}
