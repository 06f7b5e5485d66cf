"""The plain float32 reference of ``references/olmo.py``, laid out over
several chips so that a model too large for one chip's reference fits:
the same ``row_loss`` (float32 at ``highest`` matmul precision, dense
causal attention, rows one at a time) and the same AdamW step, imported
unchanged.  Only the placement is this file's own: each leaf of the
params and of both moments is split along its first axis over all the
given devices, and the batch is whole on every device.  It imports
nothing of the program, and its layout is none of the program's.
"""

from __future__ import annotations

import numpy as np

from benchmark.references import olmo as ref


def _cfg_key(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if not isinstance(v, (list, dict))))


def run_reference(cfg: dict, key, batches, devices, steps: int = 3,
                  mm_dtype=None, rows=None) -> dict:
    """``ref.run_reference`` over ``devices``: the first ``steps`` steps
    from the seed's params; losses, the first step's per-leaf gradient
    norms, and the per-leaf norms of the params' change."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("r",))
    split, whole = NamedSharding(mesh, P("r")), NamedSharding(mesh, P())
    init = ref.make_init(cfg)
    tree = jax.tree.map(lambda _: split, jax.eval_shape(init, key))
    rows = rows or int(batches[0].shape[0])
    inner = ref._step_fn(_cfg_key(cfg),
                         None if mm_dtype is None
                         else jnp.dtype(mm_dtype).name, rows)
    step = jax.jit(inner, in_shardings=(tree, tree, tree, whole, whole),
                   out_shardings=(tree, tree, tree, whole, whole),
                   donate_argnums=(0, 1, 2))
    params = jax.jit(init, out_shardings=tree)(key)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    out_shardings=tree)
    mu, nu = zeros(params), zeros(params)
    losses, g1 = [], None
    for i in range(steps):
        params, mu, nu, loss, gn = step(
            params, mu, nu, jax.device_put(jnp.float32(i + 1), whole),
            jax.device_put(batches[i], whole))
        losses.append(float(loss))
        if i == 0:
            g1 = np.asarray(gn)
    del mu, nu
    change = ref.change_norms(init, key, params)
    del params
    return {"losses": losses, "grad_norms": g1, "change_norms": change}
