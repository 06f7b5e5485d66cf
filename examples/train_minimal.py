"""Minimal sharded training loop with checkpoint/resume.

The "switching user" end-to-end demo: build a mesh, shard the flagship
transformer dp×tp, run a few steps, checkpoint, restore, continue — the
TPU-native shape of what an MPI user would assemble from p2p + collectives
+ app-level checkpointing (SURVEY.md §2.6, §5.4).

Run (virtual 8-device mesh):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/train_minimal.py
"""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from ompi_tpu import ckpt
from ompi_tpu.models.transformer import (
    Config, init_params, make_train_step, shard_params)
from ompi_tpu.parallel import make_mesh


def main() -> int:
    ndev = len(jax.devices())
    tp = 2 if ndev % 2 == 0 else 1
    mesh = make_mesh({"dp": ndev // tp, "tp": tp})
    cfg = Config(vocab=128, d_model=64, n_layers=2, n_heads=4, head_dim=16,
                 d_ff=256, seq=32)
    params = shard_params(init_params(jax.random.key(0), cfg), mesh, cfg)
    init_opt, step = make_train_step(cfg, mesh)
    opt_state = init_opt(params)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab, (ndev, cfg.seq + 1)),
        jnp.int32)

    losses = []
    for i in range(4):
        params, opt_state, loss = step(params, opt_state, tokens)
        losses.append(float(loss))
    print(f"steps 0-3 loss: {losses[0]:.4f} -> {losses[-1]:.4f}", flush=True)
    assert losses[-1] < losses[0], "loss should fall on a memorizable batch"

    # checkpoint, clobber, restore, continue
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt")
        ckpt.save(path, params)
        restored = ckpt.restore(path, like=params)
        _p2, _o2, l2 = step(restored, opt_state, tokens)
        print(f"post-restore step loss: {float(l2):.4f}", flush=True)
        assert float(l2) <= losses[-1] + 1e-3
    print("train/checkpoint/resume PASSED", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
