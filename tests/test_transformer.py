"""Flagship transformer: sharded training must run and learn, and the ring
(sp) attention path must agree with the dense path."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ompi_tpu.models.transformer import (  # noqa: E402
    Config,
    forward,
    init_params,
    loss_fn,
    make_train_step,
    shard_params,
)
from ompi_tpu.parallel import make_mesh  # noqa: E402


def _toy_batch(rng, cfg, n=4):
    # learnable structure: token t+1 = (t + 1) % vocab
    start = rng.integers(0, cfg.vocab, size=(n, 1))
    ar = (start + np.arange(cfg.seq + 1)) % cfg.vocab
    return jnp.asarray(ar, jnp.int32)


def test_forward_shapes_single_device():
    cfg = Config(vocab=64, d_model=32, n_layers=1, n_heads=4, head_dim=8,
                 d_ff=64, seq=16)
    params = init_params(jax.random.key(0), cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = forward(params, tokens, cfg)
    assert logits.shape == (2, 16, 64)
    assert logits.dtype == jnp.float32


def test_training_reduces_loss_sharded():
    mesh = make_mesh({"dp": 2, "sp": 2, "tp": 2})
    cfg = Config(vocab=32, d_model=32, n_layers=1, n_heads=4, head_dim=8,
                 d_ff=64, seq=32, attn="ring")
    params = shard_params(init_params(jax.random.key(0), cfg), mesh, cfg)
    init_opt, step = make_train_step(cfg, mesh, learning_rate=3e-3)
    opt_state = init_opt(params)
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(12):
        params, opt_state, loss = step(params, opt_state,
                                       _toy_batch(rng, cfg))
        losses.append(float(jax.device_get(loss)))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.8, f"no learning: {losses}"


def test_flash_and_dense_forward_agree():
    # the flagship attention path (Pallas flash_mha, interpret on CPU)
    # must match the dense reference in full f32
    kw = dict(vocab=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
              d_ff=64, seq=64, dtype=jnp.float32)
    cfg_f = Config(attn="flash", **kw)
    cfg_d = Config(attn="dense", **kw)
    params = init_params(jax.random.key(1), cfg_f)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (2, 64)), jnp.int32)
    lf = forward(params, tokens, cfg_f)
    ld = forward(params, tokens, cfg_d)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(ld),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("remat", ["none", "dots", "full"])
def test_training_flash_remat_reduces_loss(remat):
    # flagship regime in miniature: flash attention + remat in the jitted
    # train step — grads flow through the custom VJP under checkpointing
    cfg = Config(vocab=32, d_model=32, n_layers=1, n_heads=4, head_dim=8,
                 d_ff=64, seq=32, attn="flash", remat=remat)
    params = init_params(jax.random.key(0), cfg)
    init_opt, step = make_train_step(cfg, learning_rate=3e-3)
    opt_state = init_opt(params)
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(12):
        params, opt_state, loss = step(params, opt_state,
                                       _toy_batch(rng, cfg))
        losses.append(float(jax.device_get(loss)))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.8, f"no learning: {losses}"


def test_dp_tp_flash_step_matches_one_device():
    """dp 2 x tp 2, flash under "dots" remat, float32: the step that
    projects q, k and v from head-aligned blocks of wqkv takes the same
    three steps as one device's fused projection, and hands the fused
    leaf back as it stores it: (d, 3h), column-sharded over tp."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = Config(vocab=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
                 d_ff=64, seq=32, attn="flash", remat="dots",
                 dtype=jnp.float32)
    rng = np.random.default_rng(3)
    batches = [jnp.asarray(rng.integers(0, cfg.vocab, (4, cfg.seq + 1)),
                           jnp.int32) for _ in range(3)]
    mesh = make_mesh({"dp": 2, "tp": 2}, jax.devices()[:4])

    def three_steps(mesh):
        params = init_params(jax.random.key(3), cfg)
        if mesh is not None:
            params = shard_params(params, mesh, cfg)
        init_opt, step = make_train_step(cfg, mesh)
        opt = init_opt(params)
        losses = []
        for toks in batches:
            params, opt, loss = step(params, opt, toks)
            losses.append(float(loss))
        return losses, params

    one_losses, one = three_steps(None)
    mesh_losses, sharded = three_steps(mesh)
    np.testing.assert_allclose(mesh_losses, one_losses, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(sharded), jax.tree.leaves(one)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    h = cfg.n_heads * cfg.head_dim
    for layer in sharded["layers"]:
        w = layer["wqkv"]
        assert w.shape == (cfg.d_model, 3 * h)
        assert w.sharding.is_equivalent_to(
            NamedSharding(mesh, P(None, "tp")), w.ndim)


def test_ring_and_dense_forward_agree():
    mesh = make_mesh({"dp": 1, "sp": 8, "tp": 1})
    cfg_ring = Config(vocab=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
                      d_ff=64, seq=64, attn="ring", dtype=jnp.float32)
    cfg_dense = Config(vocab=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
                       d_ff=64, seq=64, attn="dense", dtype=jnp.float32)
    params = init_params(jax.random.key(1), cfg_ring)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 64, size=(2, 64)), jnp.int32)
    ring = forward(params, tokens, cfg_ring, mesh)
    dense = forward(params, tokens, cfg_dense)
    np.testing.assert_allclose(np.asarray(jax.device_get(ring)),
                               np.asarray(jax.device_get(dense)),
                               rtol=2e-4, atol=2e-4)


def test_graft_entry_contract():
    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert np.isfinite(np.asarray(jax.device_get(out))).all()
    g.dryrun_multichip(8)


def test_bf16_adam_moments_train():
    """opt_moment_dtype='bfloat16' (the HBM lever for the MFU staircase):
    loss must still DECREASE over a few steps and the mu buffers must
    actually be bf16."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ompi_tpu.models.transformer import (Config, init_params,
                                             make_train_step)

    cfg = Config(vocab=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
                 d_ff=64, seq=16, opt_moment_dtype="bfloat16")
    params = init_params(jax.random.key(0), cfg)
    init_opt, step = make_train_step(cfg)
    opt = init_opt(params)
    mu_leaves = jax.tree.leaves(opt[0].mu)
    assert all(x.dtype == jnp.bfloat16 for x in mu_leaves)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (4, cfg.seq + 1)),
                       jnp.int32)
    losses = []
    for _ in range(8):
        params, opt, loss = step(params, opt, toks)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize("seq,chunk", [(64, 16), (60, 16)])
def test_chunked_ce_matches_dense(seq, chunk):
    """loss_chunk never changes the math: loss AND gradients match the
    dense logsumexp-form CE (incl. a ragged tail chunk), it only bounds
    the live (b, chunk, vocab) logits slice (jax.checkpoint per slice)."""
    import jax
    from jax.flatten_util import ravel_pytree
    from ompi_tpu.models.transformer import Config, init_params, loss_fn
    base = dict(vocab=512, d_model=64, n_layers=2, n_heads=4, head_dim=16,
                d_ff=128, seq=seq, attn="dense", dtype=jnp.float32)
    # float32 end to end: chunked recompute must be numerically tight;
    # at bf16 the checkpointed recompute adds ~2e-4 rounding noise
    dense_cfg = Config(**base)
    chunk_cfg = Config(**base, loss_chunk=chunk)
    params = init_params(jax.random.key(0), dense_cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 512, size=(2, seq + 1)),
        jnp.int32)
    ld, gd = jax.value_and_grad(loss_fn)(params, tokens, dense_cfg)
    lc, gc = jax.value_and_grad(loss_fn)(params, tokens, chunk_cfg)
    np.testing.assert_allclose(float(ld), float(lc), rtol=1e-6)
    flat_d, _ = ravel_pytree(gd)
    flat_c, _ = ravel_pytree(gc)
    np.testing.assert_allclose(np.asarray(flat_d), np.asarray(flat_c),
                               rtol=1e-4, atol=1e-6)
