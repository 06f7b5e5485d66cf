"""HPCG/miniFE-class stencil workload with device-resident reductions
(BASELINE.json configs[4]).

Conjugate gradient on the 2-D 5-point Laplacian, grid rows sharded over
the device mesh: the stencil's halo exchange is a pair of ``lax.ppermute``
neighbor shifts (the reference's MPI halo sendrecvs), and every CG dot
product is a ``lax.psum`` on-device allreduce — the HBM-resident reduction
the reference's coll/accelerator shim would have staged to host
(coll_accelerator_allreduce.c:31-60).

Run:  python examples/stencil.py [n] [iters]
Single-controller over all visible devices; prints residual + iterations/s
and one BENCH json line.
"""

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def cg_solver(mesh: Mesh, n: int, iters: int):
    """Returns jit'd fn(b) -> (x, residuals) running `iters` CG steps;
    ``residuals`` (iters,) is ||r|| after each step."""
    axis = "x"
    ndev = mesh.shape[axis]

    def halo_apply(u):
        """Local (rows, n) block → 5-point Laplacian with ppermute halos."""
        up = lax.ppermute(u[-1:], axis,
                          [(i, (i + 1) % ndev) for i in range(ndev)])
        down = lax.ppermute(u[:1], axis,
                            [(i, (i - 1) % ndev) for i in range(ndev)])
        i = lax.axis_index(axis)
        up = jnp.where(i == 0, jnp.zeros_like(up), up)          # Dirichlet
        down = jnp.where(i == ndev - 1, jnp.zeros_like(down), down)
        padded = jnp.concatenate([up, u, down], axis=0)
        lap = (4.0 * u
               - padded[:-2] - padded[2:]                        # N/S
               - jnp.pad(u[:, 1:], ((0, 0), (0, 1)))             # E
               - jnp.pad(u[:, :-1], ((0, 0), (1, 0))))           # W
        return lap

    def pdot(a, b):
        return lax.psum(jnp.vdot(a, b), axis)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P(axis),
                       out_specs=(P(axis), P()))
    def solve(b):
        x = jnp.zeros_like(b)
        r = b
        p = r
        rr = pdot(r, r)

        def body(carry, _):
            x, r, p, rr = carry
            ap = halo_apply(p)
            alpha = rr / pdot(p, ap)
            x = x + alpha * p
            r = r - alpha * ap
            rr_new = pdot(r, r)
            p = r + (rr_new / rr) * p
            return (x, r, p, rr_new), jnp.sqrt(rr_new)

        (x, _r, _p, _rr), res = lax.scan(body, (x, r, p, rr), None,
                                         length=iters)
        return x, res

    return jax.jit(solve)


def main() -> int:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 2048
    iters = int(sys.argv[2]) if len(sys.argv) > 2 else 50
    devs = jax.devices()
    mesh = Mesh(np.asarray(devs), ("x",))
    n -= n % len(devs)
    b = jax.device_put(jnp.ones((n, n), jnp.float32),
                       NamedSharding(mesh, P("x")))
    solve = cg_solver(mesh, n, iters)
    jax.block_until_ready(solve(b))       # compile + warm
    t0 = time.perf_counter()
    x, res = jax.block_until_ready(solve(b))
    dt = time.perf_counter() - t0
    res_val = float(res[-1])
    # 5-point stencil ≈ 6 flops/pt + CG vector ops ≈ 10 flops/pt per iter
    gflops = 16.0 * n * n * iters / dt / 1e9
    print(f"stencil CG: {n}x{n} grid, {len(devs)} device(s), "
          f"{iters} iters in {dt*1e3:.1f} ms "
          f"({iters/dt:.1f} it/s, ~{gflops:.1f} GF/s), "
          f"residual={res_val:.3e}")
    print(json.dumps({"metric": f"stencil_cg_{n}x{n}_{len(devs)}dev",
                      "value": round(iters / dt, 2), "unit": "iters/s"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
