"""Comm/compute overlap tier: bucketed backward-overlapped gradient sync
(parallel/overlap) and its decision-layer/observability surface, plus the
tp_overlap='fused' collective-matmul train path.

Acceptance pins (ISSUE): bucketed must be numerically equivalent to
perleaf (EXACT for native buckets — same pmean on the same f32 vector,
just concatenated; documented tolerance on the quant arm), and the
collective-storm collapse is asserted through the trace decision events:
exactly plan.n_buckets decide:grad_sync events per build, with
n_buckets <= ceil(total_grad_bytes / bucket_bytes).
"""

import math

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ompi_tpu import spc, trace  # noqa: E402
from ompi_tpu.core import var  # noqa: E402
from ompi_tpu.models.transformer import (  # noqa: E402
    Config,
    init_params,
    loss_fn,
    make_train_step,
)
from ompi_tpu.parallel import make_mesh  # noqa: E402
from ompi_tpu.parallel import overlap  # noqa: E402


def _toy_batch(rng, cfg, n=4):
    # learnable structure: token t+1 = (t + 1) % vocab
    start = rng.integers(0, cfg.vocab, size=(n, 1))
    ar = (start + np.arange(cfg.seq + 1)) % cfg.vocab
    return jnp.asarray(ar, jnp.int32)


def _small_cfg(**kw):
    base = dict(vocab=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
                d_ff=64, seq=32, dtype=jnp.float32, attn="dense")
    base.update(kw)
    return Config(**base)


def _grads(cfg, mesh, batch):
    """(loss, grads) via make_grad_sync for cfg.grad_sync, fresh params."""
    params = init_params(jax.random.key(0), cfg)
    vg = overlap.make_grad_sync(
        cfg.grad_sync, mesh, lambda p, t: loss_fn(p, t, cfg, None),
        bucket_bytes=cfg.grad_bucket_bytes,
        quant_block=cfg.grad_sync_block)
    return vg(params, batch)


# -- bucket planning ---------------------------------------------------------

class TestBucketPlan:
    def _leaves(self, sizes):
        return [np.zeros(s, np.float32) for s in sizes]

    def test_storm_collapse_bound(self):
        # the guarantee the bench banks on: n_buckets <= ceil(total/target)
        leaves = self._leaves([100, 7, 300, 1, 50, 1024, 3, 900])
        for target in (64, 256, 1024, 4096, 1 << 20):
            plan = overlap.bucket_plan(leaves, target)
            total = sum(x.nbytes for x in leaves)
            assert plan.total_bytes == total
            assert plan.n_buckets <= max(1, math.ceil(total / target))
            assert plan.n_buckets == len(plan.buckets)
            assert plan.max_buckets == max(1, math.ceil(total / target))

    def test_reverse_order_and_coverage(self):
        leaves = self._leaves([10, 20, 30, 40])
        plan = overlap.bucket_plan(leaves, 1)  # one leaf per bucket
        assert plan.n_buckets == 4
        # reverse flatten order: last leaf's bucket first (backward
        # produces the last layer's cotangents first)
        assert [b.indices for b in plan.buckets] == [(3,), (2,), (1,), (0,)]
        covered = sorted(i for b in plan.buckets for i in b.indices)
        assert covered == [0, 1, 2, 3]

    def test_buckets_close_after_target(self):
        # every bucket except possibly the last (leftover) >= target
        leaves = self._leaves([17, 9, 33, 2, 41, 5, 28])
        plan = overlap.bucket_plan(leaves, 100)
        for b in plan.buckets[:-1]:
            assert b.nbytes >= 100

    def test_single_giant_bucket(self):
        plan = overlap.bucket_plan(self._leaves([8, 8]), 1 << 30)
        assert plan.n_buckets == 1
        assert plan.buckets[0].indices == (1, 0)

    def test_rejects_bad_target(self):
        with pytest.raises(ValueError, match="bucket_bytes"):
            overlap.bucket_plan(self._leaves([8]), 0)

    def test_resolve_default_and_override(self):
        assert overlap.resolve_bucket_bytes(None) == (4 << 20)
        assert overlap.resolve_bucket_bytes(12345) == 12345
        with pytest.raises(ValueError, match="grad_bucket_bytes"):
            overlap.resolve_bucket_bytes(0)


# -- numerics ----------------------------------------------------------------

class TestBucketedGradSync:
    def test_bucketed_exactly_matches_perleaf(self):
        # native buckets run the same lax.pmean on the same f32 values,
        # only concatenated — bitwise equality, not allclose
        mesh = make_mesh({"dp": 8})
        cfg_p = _small_cfg(grad_sync="perleaf")
        cfg_b = _small_cfg(grad_sync="bucketed", grad_bucket_bytes=4096)
        batch = _toy_batch(np.random.default_rng(0), cfg_p, n=8)
        loss_p, grads_p = _grads(cfg_p, mesh, batch)
        loss_b, grads_b = _grads(cfg_b, mesh, batch)
        assert float(loss_p) == float(loss_b)
        for gp, gb in zip(jax.tree.leaves(grads_p),
                          jax.tree.leaves(grads_b)):
            np.testing.assert_array_equal(np.asarray(gp), np.asarray(gb))

    def test_bucketed_matches_gspmd_native(self):
        # and both agree with the implicit GSPMD allreduce
        dp_mesh = make_mesh({"dp": 8})
        cfg_b = _small_cfg(grad_sync="bucketed", grad_bucket_bytes=8192)
        batch = _toy_batch(np.random.default_rng(1), cfg_b, n=8)
        _, grads_b = _grads(cfg_b, dp_mesh, batch)

        params = init_params(jax.random.key(0), cfg_b)
        from jax.sharding import NamedSharding, PartitionSpec as P
        toks = jax.device_put(batch,
                              NamedSharding(dp_mesh, P("dp", None)))
        _, grads_n = jax.jit(jax.value_and_grad(loss_fn),
                             static_argnums=(2, 3))(
            params, toks, cfg_b, dp_mesh)
        for gb, gn in zip(jax.tree.leaves(grads_b),
                          jax.tree.leaves(grads_n)):
            np.testing.assert_allclose(np.asarray(gb), np.asarray(gn),
                                       rtol=1e-4, atol=1e-5)

    def test_quant_buckets_within_tolerance(self):
        # forced quant arm: block-quantized buckets track the exact sync
        # within the documented ~1e-2 relative error envelope
        mesh = make_mesh({"dp": 8})
        cfg = _small_cfg(grad_sync="bucketed", grad_bucket_bytes=4096)
        batch = _toy_batch(np.random.default_rng(2), cfg, n=8)
        _, grads_exact = _grads(cfg, mesh, batch)
        var.registry.set_cli("coll_xla_grad_sync_mode", "quant")
        var.registry.reset_cache()
        try:
            trace.clear()
            trace.enable()
            _, grads_q = _grads(cfg, mesh, batch)
            rec = trace.explain_last("grad_sync")
        finally:
            trace.disable()
            var.registry.set_cli("coll_xla_grad_sync_mode", "")
            var.registry.reset_cache()
        assert rec["arm"] == "quant"
        assert rec["reason"] == "force:coll_xla_grad_sync_mode=quant"
        assert "wire_bytes" in rec  # EQuARX accounting rode along
        num = den = 0.0
        for ge, gq in zip(jax.tree.leaves(grads_exact),
                          jax.tree.leaves(grads_q)):
            num += float(jnp.sum((ge - gq) ** 2))
            den += float(jnp.sum(ge ** 2))
        assert math.sqrt(num / max(den, 1e-30)) < 0.05

    def test_unsynced_floor_runs(self):
        # measurement-only arm: loss finite, no exchange to compare
        mesh = make_mesh({"dp": 8})
        cfg = _small_cfg(grad_sync="unsynced")
        loss, grads = _grads(cfg, mesh,
                             _toy_batch(np.random.default_rng(3), cfg, 8))
        assert np.isfinite(float(loss))
        assert all(np.isfinite(np.asarray(g)).all()
                   for g in jax.tree.leaves(grads))


# -- observability -----------------------------------------------------------

class TestGradSyncObservability:
    def test_decision_events_bound_collective_count(self):
        # THE acceptance assertion: one decide:grad_sync event per bucket
        # exchange, and that count respects the storm-collapse cap
        mesh = make_mesh({"dp": 8})
        cfg = _small_cfg(grad_sync="bucketed", grad_bucket_bytes=4096)
        params = init_params(jax.random.key(0), cfg)
        plan = overlap.bucket_plan(jax.tree.leaves(params), 4096)
        trace.clear()
        trace.enable(capacity=4096)
        try:
            _grads(cfg, mesh,
                   _toy_batch(np.random.default_rng(0), cfg, 8))
            evs = [e for e in trace.events(0)
                   if e["name"] == "decide:grad_sync"]
        finally:
            trace.disable()
        assert len(evs) == plan.n_buckets
        assert plan.n_buckets <= plan.max_buckets
        for e in evs:
            assert e["args"]["arm"] in ("native", "quant")
            assert e["args"]["n_buckets"] == plan.n_buckets
            assert e["args"]["total_bytes"] == plan.total_bytes

    def test_run_and_bucket_spans(self):
        mesh = make_mesh({"dp": 8})
        cfg = _small_cfg(grad_sync="bucketed", grad_bucket_bytes=4096)
        trace.clear()
        trace.enable(capacity=4096)
        try:
            _grads(cfg, mesh,
                   _toy_batch(np.random.default_rng(0), cfg, 8))
            evs = trace.events(0)
        finally:
            trace.disable()
        runs = [e for e in evs if e["name"] == "grad_sync:run"]
        assert len(runs) == 1
        assert runs[0]["args"]["mode"] == "bucketed"
        assert runs[0]["dur"] > 0 and "status" not in runs[0]["args"]
        # the bucket count rides on the one measured span; no span is
        # made up per bucket (the host cannot time one inside the step)
        plan = overlap.bucket_plan(
            jax.tree.leaves(init_params(jax.random.key(0), cfg)), 4096)
        assert runs[0]["args"]["buckets"] == plan.n_buckets >= 2
        assert not [e for e in evs if e["name"] == "grad_sync:bucket"]

    def test_explain_last_and_pvars(self):
        mesh = make_mesh({"dp": 8})
        cfg = _small_cfg(grad_sync="bucketed", grad_bucket_bytes=4096)
        params = init_params(jax.random.key(0), cfg)
        plan = overlap.bucket_plan(jax.tree.leaves(params), 4096)
        trace.clear()
        trace.enable()
        try:
            _grads(cfg, mesh,
                   _toy_batch(np.random.default_rng(0), cfg, 8))
            rec = trace.explain_last("grad_sync")
        finally:
            trace.disable()
        assert rec is not None
        assert rec["op"] == "grad_sync"
        assert rec["bucket_bytes"] == 4096
        assert rec["reason"].startswith(("force:", "blanket:", "rule:",
                                         "floor:", "default:"))
        assert "chain" in rec
        # pvars read through spc.Counters (same state every pvar path sees)
        c = spc.Counters()
        assert c.get("grad_bucket_count") == plan.n_buckets
        assert c.get("grad_bucket_bytes") == plan.total_bytes
        snap = c.snapshot()
        assert snap["grad_bucket_count"] == plan.n_buckets
        assert snap["grad_bucket_bytes"] == plan.total_bytes


# -- train-step integration --------------------------------------------------

class TestTrainStepIntegration:
    @pytest.mark.slow
    def test_bucketed_training_reduces_loss(self):
        mesh = make_mesh({"dp": 8})
        cfg = _small_cfg(grad_sync="bucketed", grad_bucket_bytes=16384,
                         vocab=32)
        params = init_params(jax.random.key(0), cfg)
        init_opt, step = make_train_step(cfg, mesh, learning_rate=3e-3)
        opt_state = init_opt(params)
        rng = np.random.default_rng(0)
        losses = []
        for _ in range(12):
            params, opt_state, loss = step(params, opt_state,
                                           _toy_batch(rng, cfg, 8))
            losses.append(float(jax.device_get(loss)))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0] * 0.8, f"no learning: {losses}"

    def test_validation_errors(self):
        dp_tp = make_mesh({"dp": 2, "tp": 4})
        with pytest.raises(ValueError, match="dp-only"):
            overlap.make_grad_sync("bucketed", dp_tp, lambda p, t: 0.0)
        tp_only = make_mesh({"tp": 8})
        with pytest.raises(ValueError, match="'dp' mesh axis"):
            overlap.make_grad_sync("bucketed", tp_only, lambda p, t: 0.0)
        dp = make_mesh({"dp": 8})
        with pytest.raises(ValueError, match="unknown grad sync mode"):
            overlap.make_grad_sync("banana", dp, lambda p, t: 0.0)
        with pytest.raises(ValueError, match="requires a"):
            make_train_step(_small_cfg(grad_sync="bucketed"), mesh=None)
        with pytest.raises(ValueError, match="unknown grad_sync"):
            make_train_step(_small_cfg(grad_sync="nope"), mesh=dp)


# -- tp_overlap='fused' ------------------------------------------------------

class TestFusedTpOverlap:
    # the running (post-target-shift) seq must divide tp — _toy_batch
    # emits seq+1 tokens, so here that is cfg.seq itself
    def _fused_cfg(self, **kw):
        base = dict(vocab=64, d_model=32, n_layers=2, n_heads=4,
                    head_dim=8, d_ff=64, seq=32, dtype=jnp.float32,
                    attn="dense", tp_overlap="fused")
        base.update(kw)
        return Config(**base)

    def test_fused_loss_matches_unfused(self):
        mesh = make_mesh({"dp": 2, "tp": 4})
        cfg_f = self._fused_cfg()
        cfg_u = self._fused_cfg(tp_overlap="none")
        params = init_params(jax.random.key(0), cfg_f)
        batch = _toy_batch(np.random.default_rng(0), cfg_f, n=4)
        lf = float(jax.jit(loss_fn, static_argnums=(2, 3))(
            params, batch, cfg_f, mesh))
        lu = float(jax.jit(loss_fn, static_argnums=(2, 3))(
            params, batch, cfg_u, mesh))
        np.testing.assert_allclose(lf, lu, rtol=2e-4)

    @pytest.mark.slow
    def test_fused_training_reduces_loss_with_collmm_audit(self):
        mesh = make_mesh({"dp": 2, "tp": 4})
        cfg = self._fused_cfg(vocab=32)
        params = init_params(jax.random.key(0), cfg)
        init_opt, step = make_train_step(cfg, mesh, learning_rate=3e-3)
        opt_state = init_opt(params)
        rng = np.random.default_rng(0)
        trace.clear()
        trace.enable(capacity=4096)
        try:
            losses = []
            for _ in range(12):
                params, opt_state, loss = step(params, opt_state,
                                               _toy_batch(rng, cfg, 4))
                losses.append(float(jax.device_get(loss)))
            rec = trace.explain_last("collmm")
        finally:
            trace.disable()
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0] * 0.8, f"no learning: {losses}"
        # the ring-direction arbitration audited each fused call site
        assert rec is not None and rec["arm"] in ("native", "bidir")
        assert rec["op_kind"] in ("qkv", "wo", "gate", "up", "down")

    def test_fused_validation_errors(self):
        dp = make_mesh({"dp": 8})
        batch_shape_cfg = self._fused_cfg()
        params = init_params(jax.random.key(0), batch_shape_cfg)
        tokens = jnp.zeros((2, 33), jnp.int32)
        with pytest.raises(ValueError, match="tp"):
            loss_fn(params, tokens, batch_shape_cfg, dp)
        mesh = make_mesh({"dp": 2, "tp": 4})
        bad_seq = self._fused_cfg(seq=33)  # running seq 33 % 4 != 0
        with pytest.raises(ValueError, match="seq"):
            loss_fn(init_params(jax.random.key(0), bad_seq),
                    jnp.zeros((2, 34), jnp.int32), bad_seq, mesh)
        with pytest.raises(ValueError, match="grad_sync='native'"):
            make_train_step(self._fused_cfg(grad_sync="bucketed"), mesh)
        with pytest.raises(ValueError, match="tp_overlap"):
            loss_fn(params, tokens,
                    self._fused_cfg(tp_overlap="banana"), mesh)
