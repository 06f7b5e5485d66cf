"""Pallas kernel tests (interpret mode on the CPU mesh).

Interpret mode executes the same kernel logic the TPU backend compiles, so
these validate the online-softmax state machine and the ring matmul
schedules; the real-chip numbers come from the benchmark's cells.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.ops import (allgather_matmul, flash_attention,
                          flash_attention_partials, flash_mha,
                          matmul_reduce_scatter)
from ompi_tpu.parallel import make_mesh
from ompi_tpu.parallel.ring import attention_reference


def _qkv(b=2, s=256, h=2, d=16, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


class TestFlashAttention:
    def test_matches_reference(self):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, block_q=64, block_k=64,
                              interpret=True)
        ref = attention_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_causal(self):
        q, k, v = _qkv(s=128)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_single_block(self):
        q, k, v = _qkv(s=64)
        out = flash_attention(q, k, v, block_q=64, block_k=64,
                              interpret=True)
        ref = attention_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_bfloat16_inputs(self):
        q, k, v = _qkv(dtype=jnp.bfloat16)
        out = flash_attention(q, k, v, block_q=128, block_k=128,
                              interpret=True)
        ref = attention_reference(q.astype(jnp.float32),
                                  k.astype(jnp.float32),
                                  v.astype(jnp.float32))
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), rtol=0.06, atol=0.06)


class TestFlashMhaGrad:
    """The differentiable (custom-VJP) flash path vs jax.grad through the
    dense reference — validates the FlashAttention-2 backward kernels."""

    def _grads(self, fn, q, k, v, causal):
        def loss(q, k, v):
            out = fn(q, k, v, causal)
            # non-uniform cotangent so dq/dk/dv all see structure
            w = jnp.arange(out.size, dtype=out.dtype).reshape(out.shape)
            return jnp.sum(out * w) / out.size
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference_grads(self, causal):
        q, k, v = _qkv(s=128)
        flash = lambda q, k, v, c: flash_mha(q, k, v, c, None, 64, 64, True)
        ref = lambda q, k, v, c: attention_reference(q, k, v, causal=c)
        got = self._grads(flash, q, k, v, causal)
        want = self._grads(ref, q, k, v, causal)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-4,
                err_msg=f"d{name} mismatch (causal={causal})")

    def test_forward_matches_and_dtype(self):
        q, k, v = _qkv(s=128, dtype=jnp.bfloat16)
        out = flash_mha(q, k, v, True, None, 64, 64, True)
        ref = attention_reference(q.astype(jnp.float32),
                                  k.astype(jnp.float32),
                                  v.astype(jnp.float32), causal=True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), rtol=0.06, atol=0.06)

    @pytest.mark.parametrize("bwd_blocks", [(32, 32), (64, 32), (128, 64)])
    def test_bwd_blocks_tile_independently(self, bwd_blocks):
        """The dq / dk/dv kernels tile independently of the forward (the
        A/B harness's bwd block sweep): any legal bwd block pair yields
        the SAME gradients as the reference."""
        bq, bk = bwd_blocks
        q, k, v = _qkv(s=128)
        flash = lambda q, k, v, c: flash_mha(q, k, v, c, None, 64, 64,
                                             True, bq, bk)
        ref = lambda q, k, v, c: attention_reference(q, k, v, causal=c)
        got = self._grads(flash, q, k, v, True)
        want = self._grads(ref, q, k, v, True)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-4,
                err_msg=f"d{name} mismatch (bwd blocks {bq}x{bk})")

    def test_grad_under_jit_and_vmap_shapes(self):
        # the train step jits value_and_grad over the whole model; make
        # sure the custom VJP composes with jit + mean-loss cotangents
        q, k, v = _qkv(b=1, s=64, h=2, d=16)

        @jax.jit
        def step(q, k, v):
            return jax.grad(
                lambda a, b, c: jnp.mean(
                    flash_mha(a, b, c, True, None, 32, 32, True) ** 2),
                argnums=(0, 1, 2))(q, k, v)

        dq, dk, dv = step(q, k, v)
        assert dq.shape == q.shape and dk.shape == k.shape \
            and dv.shape == v.shape
        assert np.isfinite(np.asarray(dq)).all()


class TestFlashPartials:
    def test_merge_across_shards_equals_dense(self):
        """Two K/V shards merged with the ring combine == dense attention —
        the exact contract ring attention relies on."""
        b, s, h, d = 1, 128, 2, 16
        q, k, v = _qkv(b=b, s=s, h=h, d=d)
        qf = jnp.moveaxis(q, 2, 1).reshape(b * h, s, d)
        kf = jnp.moveaxis(k, 2, 1).reshape(b * h, s, d)
        vf = jnp.moveaxis(v, 2, 1).reshape(b * h, s, d)

        half = s // 2
        o1, m1, l1 = flash_attention_partials(
            qf, kf[:, :half], vf[:, :half], block_q=64, block_k=64,
            interpret=True)
        o2, m2, l2 = flash_attention_partials(
            qf, kf[:, half:], vf[:, half:], block_q=64, block_k=64,
            interpret=True)
        m = jnp.maximum(m1, m2)
        a1 = jnp.exp(m1 - m)[..., None]
        a2 = jnp.exp(m2 - m)[..., None]
        o = (o1 * jnp.exp(m1 - m)[..., None] + o2 * a2)
        l = l1 * jnp.exp(m1 - m) + l2 * jnp.exp(m2 - m)
        out = (o / l[..., None]).reshape(b, h, s, d)
        out = jnp.moveaxis(out, 1, 2)
        ref = attention_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_causal_offsets(self):
        """Partials with a kv_offset reproduce the causal mask of a shard
        that sits later in the global sequence."""
        b, s, h, d = 1, 128, 1, 16
        q, k, v = _qkv(b=b, s=s, h=h, d=d)
        qf = jnp.moveaxis(q, 2, 1).reshape(b * h, s, d)
        kf = jnp.moveaxis(k, 2, 1).reshape(b * h, s, d)
        vf = jnp.moveaxis(v, 2, 1).reshape(b * h, s, d)
        half = s // 2
        # Q is the SECOND half of a 2s sequence; kv shard 0 = first half.
        o1, m1, l1 = flash_attention_partials(
            qf, kf, vf, causal=True, q_offset=s, kv_offset=0,
            block_q=64, block_k=64, interpret=True)
        # offset s => every kv position is visible: equals non-causal
        o_ref, m_ref, l_ref = flash_attention_partials(
            qf, kf, vf, causal=False, block_q=64, block_k=64, interpret=True)
        np.testing.assert_allclose(np.asarray(o1 / l1[..., None]),
                                   np.asarray(o_ref / l_ref[..., None]),
                                   rtol=2e-5, atol=2e-5)


class TestCollectiveMatmul:
    def test_allgather_matmul(self):
        mesh = make_mesh({"tp": 4, "dp": -1})
        m, k, n = 32, 16, 24
        x = jax.random.normal(jax.random.key(1), (m, k), jnp.float32)
        w = jax.random.normal(jax.random.key(2), (k, n), jnp.float32)
        out = allgather_matmul(x, w, mesh, "tp")
        np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                                   rtol=1e-5, atol=1e-5)

    def test_allgather_matmul_w_column_sharded(self):
        mesh = make_mesh({"sp": 2, "tp": 2, "dp": -1})
        m, k, n = 16, 8, 32
        x = jax.random.normal(jax.random.key(1), (m, k), jnp.float32)
        w = jax.random.normal(jax.random.key(2), (k, n), jnp.float32)
        out = allgather_matmul(x, w, mesh, "sp", w_sharded_axis="tp")
        np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                                   rtol=1e-5, atol=1e-5)

    def test_matmul_reduce_scatter(self):
        mesh = make_mesh({"tp": 4, "dp": -1})
        m, k, n = 32, 64, 24
        x = jax.random.normal(jax.random.key(3), (m, k), jnp.float32)
        w = jax.random.normal(jax.random.key(4), (k, n), jnp.float32)
        out = matmul_reduce_scatter(x, w, mesh, "tp")
        np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                                   rtol=1e-4, atol=1e-4)

    def test_matmul_reduce_scatter_ring2(self):
        mesh = make_mesh({"x": 2, "y": -1})
        m, k, n = 8, 16, 8
        x = jnp.arange(m * k, dtype=jnp.float32).reshape(m, k) / 37.0
        w = jnp.arange(k * n, dtype=jnp.float32).reshape(k, n) / 53.0
        out = matmul_reduce_scatter(x, w, mesh, "x")
        np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("bidir", [False, True])
    def test_allgather_matmul_bidirectional(self, bidir):
        mesh = make_mesh({"tp": 4, "dp": -1})
        x = jax.random.normal(jax.random.key(5), (24, 16), jnp.float32)
        w = jax.random.normal(jax.random.key(6), (16, 20), jnp.float32)
        out = allgather_matmul(x, w, mesh, "tp", bidirectional=bidir)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("bidir", [False, True])
    def test_matmul_reduce_scatter_bidirectional(self, bidir):
        mesh = make_mesh({"tp": 4, "dp": -1})
        x = jax.random.normal(jax.random.key(7), (24, 32), jnp.float32)
        w = jax.random.normal(jax.random.key(8), (32, 20), jnp.float32)
        out = matmul_reduce_scatter(x, w, mesh, "tp", bidirectional=bidir)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("bidir", [False, True])
    def test_batched_3d(self, bidir):
        """The fused transformer path feeds (b, m, k) activations."""
        mesh = make_mesh({"tp": 4, "dp": -1})
        x = jax.random.normal(jax.random.key(9), (2, 16, 12), jnp.float32)
        w = jax.random.normal(jax.random.key(10), (12, 8), jnp.float32)
        out = allgather_matmul(x, w, mesh, "tp", bidirectional=bidir)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                                   rtol=1e-5, atol=1e-5)
        x2 = jax.random.normal(jax.random.key(11), (2, 16, 16),
                               jnp.float32)
        w2 = jax.random.normal(jax.random.key(12), (16, 8), jnp.float32)
        out2 = matmul_reduce_scatter(x2, w2, mesh, "tp",
                                     bidirectional=bidir)
        np.testing.assert_allclose(np.asarray(out2), np.asarray(x2 @ w2),
                                   rtol=1e-4, atol=1e-4)

    def test_bidirectional_odd_halves_raises(self):
        mesh = make_mesh({"tp": 4, "dp": -1})
        x = jnp.ones((12, 8), jnp.float32)    # m_local = 3: odd
        w = jnp.ones((8, 8), jnp.float32)
        with pytest.raises(ValueError, match="even per-rank row count"):
            allgather_matmul(x, w, mesh, "tp", bidirectional=True)
        x2 = jnp.ones((12, 16), jnp.float32)
        w2 = jnp.ones((16, 8), jnp.float32)
        with pytest.raises(ValueError, match="even per-rank row count"):
            matmul_reduce_scatter(x2, w2, mesh, "tp", bidirectional=True)


class TestCollectiveMatmulBackward:
    """jax.grad through the ring schedules under jit — the contract the
    tp_overlap='fused' train step rests on (forward-only coverage would
    let a broken ppermute transpose ship)."""

    def _ag_grads(self, mesh, axis, bidir, m=16, k=12, n=10):
        x = jax.random.normal(jax.random.key(21), (m, k), jnp.float32)
        w = jax.random.normal(jax.random.key(22), (k, n), jnp.float32)

        def loss(fn):
            def f(x, w):
                out = fn(x, w)
                # non-uniform cotangent so dx/dw see structure
                wt = jnp.arange(out.size, dtype=out.dtype).reshape(
                    out.shape)
                return jnp.sum(out * wt) / out.size
            return jax.jit(jax.grad(f, argnums=(0, 1)))(x, w)

        got = loss(lambda x, w: allgather_matmul(
            x, w, mesh, axis, bidirectional=bidir))
        want = loss(lambda x, w: x @ w)
        return got, want

    def _rs_grads(self, mesh, axis, bidir, m=16, k=24, n=10):
        x = jax.random.normal(jax.random.key(23), (m, k), jnp.float32)
        w = jax.random.normal(jax.random.key(24), (k, n), jnp.float32)

        def loss(fn):
            def f(x, w):
                out = fn(x, w)
                wt = jnp.arange(out.size, dtype=out.dtype).reshape(
                    out.shape)
                return jnp.sum(out * wt) / out.size
            return jax.jit(jax.grad(f, argnums=(0, 1)))(x, w)

        got = loss(lambda x, w: matmul_reduce_scatter(
            x, w, mesh, axis, bidirectional=bidir))
        want = loss(lambda x, w: x @ w)
        return got, want

    @pytest.mark.parametrize("ring", [2, 4, 8])
    def test_allgather_matmul_grads(self, ring):
        mesh = make_mesh({"tp": ring, "dp": -1})
        got, want = self._ag_grads(mesh, "tp", bidir=False)
        for g, w, name in zip(got, want, ("dx", "dw")):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-4,
                err_msg=f"{name} mismatch (ring={ring})")

    @pytest.mark.parametrize("ring", [2, 4, 8])
    def test_matmul_reduce_scatter_grads(self, ring):
        mesh = make_mesh({"tp": ring, "dp": -1})
        got, want = self._rs_grads(mesh, "tp", bidir=False)
        for g, w, name in zip(got, want, ("dx", "dw")):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-4,
                err_msg=f"{name} mismatch (ring={ring})")

    @pytest.mark.parametrize("ring", [2, 4])
    def test_bidirectional_grads(self, ring):
        mesh = make_mesh({"tp": ring, "dp": -1})
        for fn, label in ((self._ag_grads, "allgather_matmul"),
                          (self._rs_grads, "matmul_reduce_scatter")):
            got, want = fn(mesh, "tp", bidir=True)
            for g, w, name in zip(got, want, ("dx", "dw")):
                np.testing.assert_allclose(
                    np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-4,
                    err_msg=f"{label} {name} mismatch "
                            f"(bidir, ring={ring})")


class TestRingPallas:
    def test_ring_attention_pallas_block(self):
        from ompi_tpu.parallel.ring import ring_attention
        mesh = make_mesh({"sp": 4, "dp": -1})
        b, s, h, d = 2, 64, 2, 16
        q, k, v = _qkv(b=b, s=s, h=h, d=d)
        out = ring_attention(q, k, v, mesh, axis="sp", block_impl="pallas")
        ref = attention_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_ring_attention_pallas_causal(self):
        from ompi_tpu.parallel.ring import ring_attention
        mesh = make_mesh({"sp": 4, "dp": -1})
        b, s, h, d = 1, 64, 2, 16
        q, k, v = _qkv(b=b, s=s, h=h, d=d, seed=3)
        out = ring_attention(q, k, v, mesh, axis="sp", causal=True,
                             block_impl="pallas")
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


class TestTpuTilingGuard:
    """check_tpu_block: the trace-time Mosaic tiling rule (the invariant
    whose absence let an unlowerable (1, bq) block reach the first
    real-chip compile — commit d5b947d)."""

    def test_rejects_the_d5b947d_shape(self):
        from ompi_tpu.ops.attention import check_tpu_block
        with pytest.raises(ValueError, match="not TPU-lowerable"):
            check_tpu_block((1, 1024), (16, 2048), "m/l")

    def test_accepts_lane_aligned_and_equal_dims(self):
        from ompi_tpu.ops.attention import check_tpu_block
        check_tpu_block((1, 1024, 128), (16, 2048, 128))   # divisible
        check_tpu_block((1, 1024, 1), (16, 2048, 1))       # equal arm
        check_tpu_block((1, 256, 64), (8, 256, 64))        # d == array dim
        check_tpu_block((8,), (64,))                       # 1-D: exempt

    def test_wrappers_enforce_it(self):
        # a hand-forced block that violates the sublane rule must raise on
        # EVERY backend, not just on a real chip
        from ompi_tpu.ops.attention import flash_attention
        q = jnp.ones((1, 64, 2, 128), jnp.float32)
        with pytest.raises(ValueError, match="not TPU-lowerable"):
            # bq=4 divides s_q=64 (so _block_sizes accepts it) but is
            # neither a multiple of 8 sublanes nor equal to s_q
            flash_attention(q, q, q, block_q=4)

    def test_bf16_sublane_tile_is_16(self):
        from ompi_tpu.ops.attention import check_tpu_block
        check_tpu_block((1, 8, 128), (4, 64, 128))            # f32: ok
        with pytest.raises(ValueError, match="multiple of 16"):
            check_tpu_block((1, 8, 128), (4, 64, 128), "q", jnp.bfloat16)

    def test_rank_mismatch_raises(self):
        from ompi_tpu.ops.attention import check_tpu_block
        with pytest.raises(ValueError, match="different ranks"):
            check_tpu_block((1, 8), (4, 64, 1))


@pytest.mark.parametrize("backend,want", [("tpu", False), ("cpu", True),
                                          ("gpu", None)])
def test_default_interpret_never_guesses(monkeypatch, backend, want):
    """Compiled on TPU, interpreted on the CPU, and any other backend is
    refused rather than silently interpreted."""
    from ompi_tpu.ops import attention
    monkeypatch.setattr(attention.jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(RuntimeError, match="gpu"):
            attention._default_interpret()
    else:
        assert attention._default_interpret() is want
