"""MPI-style halo exchange on a device mesh — the neighborhood-collective
face of the stencil workload (BASELINE.json configs[4]; examples/stencil.py
is the in-program shard_map form of the same physics).

A periodic cart of all visible devices holds one grid block per position
(the canonical (R, rows, cols) layout); each Jacobi sweep ships ONLY the
two facing boundary rows through ``comm.coll.neighbor_alltoall`` — which
the coll/xla component compiles to 2·ndims ``ppermute``s
(DeviceComm.neighbor_alltoall_cart, the halo data motion) — and folds
the received N/S halo rows into the 5-point update. Run:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/halo_exchange.py [n] [iters]
"""

import json
import sys
import time

import numpy as np


def main() -> int:
    import jax
    import jax.numpy as jnp

    from ompi_tpu import runtime
    from ompi_tpu.parallel import attach_mesh, make_mesh
    from ompi_tpu.topo import CartTopo

    n = int(sys.argv[1]) if len(sys.argv) > 1 else 512
    iters = int(sys.argv[2]) if len(sys.argv) > 2 else 20

    ctx = runtime.init()
    c = ctx.comm_world
    ndev = len(jax.devices())
    attach_mesh(c, make_mesh({"x": ndev}), "x")
    c.topo = CartTopo([ndev], [True])          # periodic ring of blocks
    dc = c.device_comm

    rows = max(n // ndev, 4)
    grid = dc.from_ranks([np.full((rows, n), float(i), np.float32)
                          for i in range(ndev)])

    def sweep(g):
        # facing rows only: block 0 (toward -1) = my top row, block 1
        # (toward +1) = my bottom row — 2·n floats per rank, not 2·rows·n
        faces = jnp.stack([g[:, :1, :], g[:, -1:, :]], axis=1)
        halo = c.coll.neighbor_alltoall(c, faces)        # (R, 2, 1, n)
        up = halo[:, 0]        # mirror slot: the block above's BOTTOM row
        down = halo[:, 1]      # the block below's top row
        padded = jnp.concatenate([up, g, down], axis=1)  # (R, rows+2, n)
        left = jnp.pad(g[:, :, :-1], ((0, 0), (0, 0), (1, 0)))
        right = jnp.pad(g[:, :, 1:], ((0, 0), (0, 0), (0, 1)))
        return 0.25 * (padded[:, :-2] + padded[:, 2:] + left + right)

    g = sweep(grid)                                      # warm/compile
    jax.block_until_ready(g)
    t0 = time.perf_counter()
    for _ in range(iters):
        g = sweep(g)
    val = float(jnp.ravel(g)[0])                         # read barrier
    dt = (time.perf_counter() - t0) / iters
    print(f"halo exchange: {ndev} blocks x ({rows}x{n}), "
          f"{iters} Jacobi sweeps, {dt*1e3:.2f} ms/sweep, first={val:.3f}")
    print(json.dumps({"metric": f"halo_jacobi_{ndev}x{rows}x{n}",
                      "value": round(1.0 / dt, 2), "unit": "sweeps/s"}))
    ctx.finalize()
    return 0


if __name__ == "__main__":
    sys.exit(main())
