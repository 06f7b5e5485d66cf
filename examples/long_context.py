"""Long-context sequence parallelism: ring attention + Ulysses.

The first-class long-context story (SURVEY.md §5.7): a sequence too long
for one device's memory is sharded over the ``sp`` mesh axis, and
attention runs as a ring — each step attends the local Q shard against the
visiting K/V shard, then rotates K/V one ICI hop (the identical neighbor-
exchange schedule as the reference's ring collectives,
coll_base_allreduce.c:344). Ulysses instead all-to-alls heads so every
device sees the full sequence for its head subset. Both are verified here
against whole-sequence attention, then timed.

Run (virtual 8-device mesh):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/long_context.py
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ompi_tpu.parallel import make_mesh
from ompi_tpu.parallel.ring import attention_reference, ring_attention
from ompi_tpu.parallel.ulysses import ulysses_attention


def main() -> int:
    ndev = len(jax.devices())
    mesh = make_mesh({"sp": ndev})
    B, S, H, D = 2, 128 * ndev, 8, 32       # seq sharded ndev ways
    rng = jax.random.key(0)
    shape = (B, S, H, D)
    q = jax.random.normal(jax.random.fold_in(rng, 1), shape, jnp.float32)
    k = jax.random.normal(jax.random.fold_in(rng, 2), shape, jnp.float32)
    v = jax.random.normal(jax.random.fold_in(rng, 3), shape, jnp.float32)
    seq_sharded = NamedSharding(mesh, P(None, "sp", None, None))
    qs, ks, vs = (jax.device_put(x, seq_sharded) for x in (q, k, v))

    ref = attention_reference(q, k, v, causal=True)

    out_ring = ring_attention(qs, ks, vs, mesh, axis="sp", causal=True)
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    print(f"ring attention == reference (seq {S} over {ndev} shards)",
          flush=True)

    out_uly = ulysses_attention(qs, ks, vs, mesh, axis="sp", causal=True)
    np.testing.assert_allclose(np.asarray(out_uly), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    print("ulysses attention == reference", flush=True)

    for name, fn in (("ring", lambda: ring_attention(qs, ks, vs, mesh,
                                                     axis="sp", causal=True)),
                     ("ulysses", lambda: ulysses_attention(
                         qs, ks, vs, mesh, axis="sp", causal=True))):
        fn()[0, 0, 0, 0].block_until_ready()       # compile + warm
        t0 = time.perf_counter()
        reps = 5
        for _ in range(reps):
            out = fn()
        float(jnp.ravel(out)[0])
        print(f"{name}: {(time.perf_counter() - t0) / reps * 1e3:.1f} "
              f"ms/call", flush=True)
    print("long-context example PASSED", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
