"""Serving tier: continuous-batching decode (PR 15).

Covers the paged KV cache's admit/evict/page accounting, the
ServingEngine's prefill+paged-decode greedy parity against the train
forward(), the convert_params train<->decode round-trip with the
reshard plan pinned per weight (satellite 1), the continuous vs static
scheduler comparison, the decode_ag/decode_rs decision audit + quant
arm, traffic conservation over the decode stream, the serve_* pvar
read-through under the Prometheus grammar, and comm_doctor --serve
(ompi_tpu/serving plane).
"""

import json
import os
import re

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ompi_tpu import serving, spc, trace, traffic  # noqa: E402
from ompi_tpu.core import var  # noqa: E402
from ompi_tpu.models import transformer as tfm  # noqa: E402
from ompi_tpu.parallel import DeviceComm, make_mesh  # noqa: E402
from ompi_tpu.parallel.reshard import Resharder  # noqa: E402
from ompi_tpu.serving.cache import PagedKVCache  # noqa: E402
from ompi_tpu.serving.engine import ServingEngine  # noqa: E402
from ompi_tpu.serving.scheduler import (ContinuousBatchingScheduler,  # noqa: E402
                                        poisson_stream)

pytestmark = pytest.mark.serve


CFG = tfm.Config(vocab=512, d_model=128, n_layers=2, n_heads=8,
                 head_dim=16, d_ff=256, dtype=jnp.float32)
# audited decode collectives per step/prefill: 1 embed AG + 4 AGs per
# layer + logits RS + logits AG
COLLS_PER_STEP = 1 + 4 * CFG.n_layers + 2


@pytest.fixture(autouse=True)
def _clean_state():
    """Every test leaves the planes and CLI vars as it found them."""
    yield
    for name in ("coll_xla_decode_ag_mode", "coll_xla_decode_rs_mode",
                 "coll_quant_block", "serve_enabled"):
        var.registry.clear_cli(name)
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
    """One parameter tree + engine-free mesh shared across the module
    (engine construction pays a convert_params reshard; per-test
    engines reuse the jit cache via identical shapes)."""
    dc = _dc()
    params = tfm.init_params(jax.random.PRNGKey(0), CFG)
    sharded = tfm.shard_params(params, dc.mesh, CFG)
    return dc, params, sharded


def _engine(dc, sharded, **kw):
    kw.setdefault("n_pages", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seqs", 4)
    return ServingEngine(dc, sharded, CFG, **kw)


def _greedy_decode(eng, prompt, steps, teacher=None):
    """prefill + `steps` single-slot decode steps, greedy; with
    ``teacher`` (a prior run's token list) the fed-back tokens come
    from it instead, so both runs see identical contexts."""
    slot = eng.cache.admit(len(prompt), steps + 1)
    first, logits = eng.prefill(slot, prompt)
    toks = [first]
    last = first if teacher is None else teacher[0]
    per_step_logits = []
    for i in range(steps):
        t = np.zeros(eng.max_seqs, np.int32)
        p = np.full(eng.max_seqs, -1, np.int64)
        t[slot] = last
        p[slot] = int(eng.cache.seq_lens[slot])
        nxt, lg = eng.decode_step(t, p)
        eng.cache.seq_lens[slot] += 1
        toks.append(int(nxt[slot]))
        per_step_logits.append(np.asarray(lg)[0, slot])
        last = int(nxt[slot]) if teacher is None else teacher[i + 1]
    eng.cache.release(slot)
    return toks, np.stack(per_step_logits)


def _reference_greedy(params, prompt, steps):
    """Full-context greedy via the train-layout forward()."""
    toks = list(prompt)
    out, logits = [], []
    for _ in range(steps + 1):
        lg = tfm.forward(params, jnp.asarray([toks], jnp.int32), CFG)
        lg = np.asarray(lg)[0, -1]
        nxt = int(lg.argmax())
        out.append(nxt)
        logits.append(lg)
        toks.append(nxt)
    return out, np.stack(logits)


class TestPagedKVCache:
    def test_admit_release_page_accounting(self, shared):
        dc, _, _ = shared
        c = PagedKVCache(dc, CFG.n_layers, CFG.n_heads, CFG.head_dim,
                         n_pages=9, page_size=4, max_seqs=4)
        assert c.pages_used == 0
        # 8 usable pages (page 0 is the inactive-lane scratch page)
        assert c.can_admit(7, 1)      # 8 positions -> 2 pages
        s0 = c.admit(7, 1)
        assert c.pages_used == 2
        s1 = c.admit(13, 3)           # 16 positions -> 4 pages
        assert c.pages_used == 6
        assert not c.can_admit(9, 4)  # would need 4 more, only 2 left
        c.release(s0)
        assert c.pages_used == 4
        assert c.can_admit(9, 4)
        s2 = c.admit(9, 4)
        assert s2 != s1 and c.pages_used == 8
        c.release(s1)
        c.release(s2)
        assert c.pages_used == 0

    def test_slot_exhaustion_blocks_admit(self, shared):
        dc, _, _ = shared
        c = PagedKVCache(dc, CFG.n_layers, CFG.n_heads, CFG.head_dim,
                         n_pages=64, page_size=8, max_seqs=2)
        a = c.admit(4, 1)
        b = c.admit(4, 1)
        assert not c.can_admit(4, 1)  # pages free, but no slot
        c.release(a)
        assert c.can_admit(4, 1)
        c.release(b)

    def test_inactive_positions_route_to_scratch_page(self, shared):
        dc, _, _ = shared
        c = PagedKVCache(dc, CFG.n_layers, CFG.n_heads, CFG.head_dim,
                         n_pages=8, page_size=4, max_seqs=2)
        slot = c.admit(3, 2)
        page, off = c.write_indices(np.array([slot, 1 - slot]),
                                    np.array([5, -1]))
        page, off = np.asarray(page), np.asarray(off)
        assert page[1] == 0 and off[1] == 0       # inactive -> scratch
        assert page[0] != 0 and off[0] == 5 % 4   # live -> its block
        c.release(slot)


class TestConvertParamsRoundTrip:
    """Satellite 1: the reshard engine's train<->decode conversion is
    bitwise round-trip, and each weight's plan is pinned — catching a
    layout-spec change that silently turns the flip into a different
    (more expensive) collective sequence."""

    def test_round_trip_bitwise(self, shared):
        dc, _, sharded = shared
        dec = tfm.convert_params(sharded, dc.mesh, CFG, to="decode")
        back = tfm.convert_params(dec, dc.mesh, CFG, to="train")
        flat_a, _ = jax.tree_util.tree_flatten(sharded)
        flat_b, _ = jax.tree_util.tree_flatten(back)
        for a, b in zip(flat_a, flat_b):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_per_weight_plan_pinned(self, shared):
        dc, _, _ = shared
        rs = Resharder(dc.mesh)
        train = tfm.param_specs(CFG)
        dec = tfm.decode_param_specs(CFG)
        d = CFG.d_model
        want = {
            # row-parallel -> column-parallel: one all_to_all, no
            # allgather+slice detour
            "embed": ((CFG.vocab, d), ["all_to_all[tp:0->1]"]),
            "wo": ((CFG.n_heads * CFG.head_dim, d),
                   ["all_to_all[tp:0->1]"]),
            "w_down": ((CFG.d_ff, d), ["all_to_all[tp:0->1]"]),
            # already column-parallel (or replicated): empty plan
            "wqkv": ((d, 3 * CFG.n_heads * CFG.head_dim), []),
            "w_gate": ((d, CFG.d_ff), []),
            "w_up": ((d, CFG.d_ff), []),
            "attn_norm": ((d,), []),
            "final_norm": ((d,), []),
        }
        layer_t, layer_d = train["layers"][0], dec["layers"][0]
        for name, (shape, steps) in want.items():
            src = train.get(name, layer_t.get(name))
            dst = dec.get(name, layer_d.get(name))
            plan = rs.plan(shape, jnp.float32, src, dst)
            assert plan.describe() == steps, (name, plan.describe())
            if not steps:
                assert plan.wire_bytes == 0


class TestEngineParity:
    def test_greedy_matches_train_forward(self, shared):
        dc, params, sharded = shared
        eng = _engine(dc, sharded)
        prompt = np.array([3, 17, 99, 254, 7], np.int32)
        toks, lg = _greedy_decode(eng, prompt, 5)
        ref_toks, ref_lg = _reference_greedy(params, prompt, 5)
        assert toks == ref_toks
        relerr = (np.abs(lg - ref_lg[1:]).max()
                  / (np.abs(ref_lg[1:]).max() + 1e-9))
        assert relerr < 1e-4

    def test_audit_counts_and_wire_ledger(self, shared):
        dc, _, sharded = shared
        dc.spc = spc.Counters()
        eng = _engine(dc, sharded)
        eng.wire_bytes = 0
        dc.spc = spc.Counters()
        steps = 3
        _greedy_decode(eng, np.array([5, 6, 7], np.int32), steps)
        total = (steps + 1) * COLLS_PER_STEP  # prefill + decode steps
        assert sum(eng.dispatches.values()) == total
        assert eng.dispatches["decode_rs"] == steps + 1
        assert eng.wire_bytes == int(dc.spc.get("coll_wire_bytes"))
        arms = (dc.spc.get("coll_arm_native_count")
                + dc.spc.get("coll_arm_quant_count"))
        assert int(arms) == total


def _dense_paged_attn(q, kp, vp, bt, q_pos, mesh=None, axis=None):
    """The dense-gather computation the kernel replaced: every page of
    every row's block table gathered, then ``decode_attention``."""
    k = jnp.take(kp, bt, axis=1)
    v = jnp.take(vp, bt, axis=1)
    r, b, pmax, pg, hl, hd = k.shape
    att = tfm.decode_attention(q, k.reshape(r, b, pmax * pg, hl, hd),
                               v.reshape(r, b, pmax * pg, hl, hd), q_pos)
    return att.reshape(r, b, hl * hd)


def _batch_decode(eng, prompts, steps):
    """Prefill ``prompts`` into their own slots, then ``steps`` decode
    steps over all of them at once (the other slots inactive): greedy
    tokens (steps, slots) and logits of the live slots."""
    slots = [eng.cache.admit(len(p), steps + 1) for p in prompts]
    last = [eng.prefill(s, p)[0] for s, p in zip(slots, prompts)]
    toks, lgs = [], []
    for _ in range(steps):
        t = np.zeros(eng.max_seqs, np.int32)
        pos = np.full(eng.max_seqs, -1, np.int64)
        t[slots] = last
        pos[slots] = eng.cache.seq_lens[slots]
        nxt, lg = eng.decode_step(t, pos)
        eng.cache.seq_lens[slots] += 1
        last = [int(nxt[s]) for s in slots]
        toks.append(last)
        lgs.append(np.asarray(lg)[0, slots])
    for s in slots:
        eng.cache.release(s)
    return np.asarray(toks), np.stack(lgs)


class TestPagedDecodeAttention:
    PROMPTS = [np.arange(3, dtype=np.int32) + 5,
               (np.arange(9, dtype=np.int32) * 7) % CFG.vocab,
               (np.arange(17, dtype=np.int32) * 13 + 1) % CFG.vocab]

    @pytest.mark.parametrize("n", [1, 2], ids=["tp1", "tp2"])
    def test_decode_step_matches_dense_gather(self, shared, n,
                                              monkeypatch):
        from ompi_tpu.serving import engine as engine_mod
        _, params, _ = shared
        dc = _dc(n)
        sharded = tfm.shard_params(params, dc.mesh, CFG)
        toks, lg = _batch_decode(_engine(dc, sharded), self.PROMPTS, 6)
        monkeypatch.setattr(engine_mod, "_j_paged_attn", _dense_paged_attn)
        ref_toks, ref_lg = _batch_decode(_engine(dc, sharded),
                                         self.PROMPTS, 6)
        np.testing.assert_array_equal(toks, ref_toks)
        assert (np.abs(lg - ref_lg).max()
                / (np.abs(ref_lg).max() + 1e-9)) < 1e-4

    def test_lowered_attention_gathers_no_pool(self, shared):
        """No gather in the compiled ``_j_paged_attn`` has an output the
        size of a page pool; the dense computation's has (the check can
        see one)."""
        from ompi_tpu.serving.engine import _j_paged_attn
        dc = _dc(1)
        eng = _engine(dc, tfm.shard_params(shared[1], dc.mesh, CFG))
        kp = eng.cache.k[0]
        b = eng.max_seqs
        args = (jnp.zeros((1, b, CFG.n_heads, CFG.head_dim), kp.dtype), kp,
                eng.cache.v[0], jnp.asarray(eng.cache.block_tables),
                jnp.zeros(b, jnp.int32))

        def gathered(fn):
            hlo = fn.lower(*args).compile().as_text()
            return [int(np.prod([int(d) for d in dims.split(",") if d]))
                    for dims in re.findall(r"= \w+\[([\d,]*)\]\S* gather\(",
                                           hlo)]
        pool = int(np.prod(kp.shape))
        assert all(n < pool for n in gathered(_j_paged_attn))
        assert max(gathered(jax.jit(_dense_paged_attn))) >= pool

    def test_pages_read_counter(self, shared):
        dc, _, sharded = shared
        eng = _engine(dc, sharded)
        page, pmax = eng.cache.page_size, eng.cache.max_pages_per_seq
        trace.enable()
        trace.clear()
        _batch_decode(eng, self.PROMPTS, 3)
        steps = [e["args"] for e in trace.events()
                 if e["name"] == "serve:decode_step"]
        assert len(steps) == 3
        lens = np.array([len(p) for p in self.PROMPTS])
        for i, a in enumerate(steps):
            want = int(sum(-(-(n + i + 1) // page) for n in lens))
            assert a["pages_read"] == want
            assert a["pages_table"] == eng.max_seqs * pmax
        assert eng.attn_pages == {
            "read": sum(a["pages_read"] for a in steps),
            "table": 3 * eng.max_seqs * pmax}
        assert eng.attn_pages["read"] < eng.attn_pages["table"]


class TestScheduler:
    def _run(self, shared, policy, n=10, seed=1):
        dc, _, sharded = shared
        serving.reset()
        serving.enable()
        eng = _engine(dc, sharded)
        reqs = poisson_stream(n, qps=50.0, vocab=CFG.vocab, seed=seed)
        out = ContinuousBatchingScheduler(eng, reqs,
                                          policy=policy).run()
        rep = serving.report()
        assert eng.cache.pages_used == 0  # fully drained
        return out, rep

    def test_continuous_vs_static_token_parity(self, shared):
        out_c, rep_c = self._run(shared, "continuous")
        out_s, rep_s = self._run(shared, "static")
        assert set(out_c["results"]) == set(out_s["results"])
        for rid, r in out_c["results"].items():
            assert r["tokens"] == out_s["results"][rid]["tokens"], rid
        # continuous keeps the device batch fuller and finishes in
        # fewer decode steps
        assert rep_c["batch_occupancy"] > rep_s["batch_occupancy"]
        assert out_c["decode_steps"] < out_s["decode_steps"]

    def test_plane_ledger(self, shared):
        n = 10
        out, rep = self._run(shared, "continuous", n=n)
        assert out["completed"] == n
        assert rep["evictions"] == n
        assert rep["active_seqs"] == 0
        assert rep["kv_pages_used"] == 0
        assert rep["prefills"] == n
        assert rep["tokens"] == out["tokens"]
        g = rep["goodput"]
        assert g["total_s"] >= g["prefill_s"] + g["decode_s"]
        assert rep["itl"]["count"] > 0
        assert rep["itl"]["p99_ms"] >= rep["itl"]["p50_ms"]
        states = {r["state"] for r in rep["requests"]}
        assert states == {"done"}

    def test_eos_eviction(self, shared):
        dc, _, sharded = shared
        serving.reset()
        serving.enable()
        eng = _engine(dc, sharded)
        # probe one greedy step to learn a token the model will emit,
        # then use THAT as eos so the request must stop early
        probe, _ = _greedy_decode(eng, np.array([3, 17], np.int32), 1)
        reqs = poisson_stream(1, qps=50.0, vocab=CFG.vocab, seed=9)
        reqs[0].prompt = np.array([3, 17], np.int32)
        reqs[0].max_new = 8
        reqs[0].eos_id = probe[0]
        out = ContinuousBatchingScheduler(eng, reqs).run()
        r = out["results"][reqs[0].rid]
        assert r["reason"] == "eos"
        assert len(r["tokens"]) == 1


class TestDecisionAudit:
    def test_one_decision_event_per_dispatch(self, shared):
        dc, _, sharded = shared
        eng = _engine(dc, sharded)
        trace.enable()
        trace.clear()
        before = dict(eng.dispatches)
        _greedy_decode(eng, np.array([1, 2, 3, 4], np.int32), 2)
        for coll in ("decode_ag", "decode_rs"):
            n_dec = sum(1 for e in trace.events()
                        if e.get("name") == f"decide:{coll}")
            assert n_dec == eng.dispatches[coll] - before[coll]
        ev = trace.explain_last("decode_ag")
        assert ev and ev["arm"] in ("native", "quant")
        assert "chain" in ev and "reason" in ev

    def test_quant_arm_forced_parity(self, shared):
        dc, _, sharded = shared
        eng = _engine(dc, sharded)
        prompt = np.array([3, 17, 99], np.int32)
        toks_n, log_n = _greedy_decode(eng, prompt, 3)
        var.registry.set_cli("coll_xla_decode_ag_mode", "quant")
        var.registry.set_cli("coll_xla_decode_rs_mode", "quant")
        var.registry.set_cli("coll_quant_block", "32")
        trace.enable()
        trace.clear()
        w0 = eng.wire_bytes
        # teacher-force the native stream so every step sees the same
        # context — per-step comparisons stay meaningful even if one
        # near-tie argmax flips under int8
        toks_q, log_q = _greedy_decode(eng, prompt, 3, teacher=toks_n)
        arms = {e["args"]["arm"] for e in trace.events()
                if e["name"].startswith("decide:decode")}
        assert arms == {"quant"}
        assert eng.wire_bytes > w0
        relerr = (np.abs(log_q - log_n).max()
                  / (np.abs(log_n).max() + 1e-9))
        assert relerr < 0.05
        match = np.mean([a == b for a, b in zip(toks_n, toks_q)])
        assert match >= 0.75

    def test_decode_spans_emitted(self, shared):
        dc, _, sharded = shared
        eng = _engine(dc, sharded)
        trace.enable()
        trace.clear()
        _greedy_decode(eng, np.array([8, 9], np.int32), 1)
        names = [e["name"] for e in trace.events()]
        assert "serve:prefill" in names
        assert "serve:decode_step" in names


class TestConservation:
    def test_edge_sum_matches_wire_bytes(self, shared):
        dc, _, sharded = shared
        dc.spc = spc.Counters()
        eng = _engine(dc, sharded)
        # window opens AFTER engine construction: the convert_params
        # reshard at init is audited under coll `reshard`, not here
        dc.spc = spc.Counters()
        eng.wire_bytes = 0
        traffic.reset()
        traffic.enable()
        _greedy_decode(eng, np.array([11, 12, 13], np.int32), 3)
        wire = int(dc.spc.get("coll_wire_bytes"))
        assert wire == eng.wire_bytes > 0
        assert traffic.matrix.edge_bytes_total() == wire
        assert int(traffic.matrix.unattributed_bytes) == 0


class TestServePvars:
    def test_read_through_get_and_snapshot(self, shared):
        serving.reset()
        serving.enable()
        c = spc.Counters()
        assert c.get("serve_tokens") == 0.0
        serving.note_admit("r0", 4, 8, 0.0, 0.0)
        serving.note_token("r0", 0.1)
        serving.note_token("r0", 0.2)
        serving.set_pages_used(3)
        serving.note_evict("r0", "eos", 0.3)
        assert c.get("serve_tokens") == 2.0
        assert c.get("serve_active_seqs") == 0.0
        assert c.get("serve_evictions") == 1.0
        assert c.get("serve_kv_pages_used") == 3.0
        snap = c.snapshot()
        for name in serving.PVARS:
            assert name in snap
        assert snap["serve_tokens"] == 2.0

    def test_prometheus_grammar(self, shared):
        serving.reset()
        serving.enable()
        serving.note_admit("r1", 4, 8, 0.0, 0.0)
        serving.note_token("r1", 0.1)
        text = spc.export_prometheus(spc.Counters(), comm="serve0")
        line = re.compile(r"^[a-z_:][a-z0-9_:]*(\{[^}]*\})? "
                          r"[-+0-9.e]+$")
        seen = set()
        for ln in text.splitlines():
            if not ln or ln.startswith("#"):
                continue
            assert line.match(ln), ln
            seen.add(ln.split("{")[0].split(" ")[0])
        assert any("serve_tokens" in s for s in seen)
        assert any("serve_kv_pages_used" in s for s in seen)


class TestDoctorServe:
    def test_schema_and_live_section(self, shared):
        from ompi_tpu.tools import comm_doctor
        assert comm_doctor.SCHEMA_VERSION == 14
        serving.reset()
        serving.enable()
        serving.note_admit("r2", 4, 8, 0.0, 0.0)
        serving.note_prefill(0.01, 4)
        serving.note_token("r2", 0.1)
        serving.note_evict("r2", "max_new", 0.2)
        txt, data = comm_doctor.build_serve_report()
        assert "prefill" in txt and "eviction" in txt
        assert "r2" in txt
        assert data["tokens"] == 1

    def test_banked_doc_path(self, shared, tmp_path):
        from ompi_tpu.tools import comm_doctor
        serving.reset()
        serving.enable()
        serving.note_admit("r3", 4, 8, 0.0, 0.0)
        serving.note_prefill(0.01, 4)
        serving.note_token("r3", 0.1)
        serving.note_decode_step(0.02, 1, 4)
        serving.note_evict("r3", "eos", 0.2)
        doc = {"report": serving.report(),
               "decisions": {"decode_ag": None, "decode_rs": None}}
        p = tmp_path / "SERVE_test.json"
        p.write_text(json.dumps(doc))
        serving.reset()  # the live plane is now empty ...
        txt, _ = comm_doctor.build_serve_report(str(p))
        assert "r3" in txt  # ... so the rows must come from the doc
        assert "SERVE_test.json" in txt
