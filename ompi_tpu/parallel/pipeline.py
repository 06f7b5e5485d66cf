"""Pipeline parallelism — a GPipe-style schedule over a mesh axis.

≙ what PP users build on the reference's p2p/partitioned sends
(pml_ob1_isend.c:249, ompi/mca/part/part.h:30 — SURVEY.md §2.6): stage
boundaries are neighbor exchanges. TPU-natively that is NOT host-driven
send/recv: all stages run ONE compiled SPMD program under ``shard_map``
over the ``pp`` axis, stage-local parameters come from a leading
stages-dimension sharded over that axis, and the boundary transfer is a
``lax.ppermute`` ring shift per schedule tick — the compiler overlaps the
shift with the next tick's compute on the MXU (the same
communication/compute overlap 1F1B hand-schedules on GPU clusters).

Schedule: M microbatches drain through P stages in M+P-1 ticks (GPipe).
Memory for the backward pass is handled by XLA's remat of the tick scan
(``jax.checkpoint`` on the stage function), not by hand-interleaving —
under jax.grad the whole pipeline differentiates as one program, which is
the TPU-first answer to 1F1B's purpose (bounding live activations).

Weight layout: ``stack_stage_params`` pytrees L layers into P stages of
L/P stacked layers; inside the program each stage reads its own slice via
``lax.axis_index``-free shard_map slicing (the leading dim IS the pp
shard), and runs its layers with a ``lax.scan`` (compile once per stage
depth, not per layer).
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import trace


def stack_stage_params(layer_params: list, n_stages: int):
    """[L per-layer pytrees] → pytree with leading (P, L//P) dims, ready to
    shard P over the pp axis."""
    n = len(layer_params)
    if n % n_stages:
        raise ValueError(f"{n} layers do not split into {n_stages} stages")
    per = n // n_stages
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layer_params)
    return jax.tree.map(
        lambda x: x.reshape((n_stages, per) + x.shape[1:]), stacked)


def shard_stage_params(stacked, mesh: Mesh, axis: str = "pp"):
    """Put the stages dimension on the pp axis (everything else replicated;
    compose with tp specs by sharding trailing dims upstream)."""
    return jax.tree.map(
        lambda x: jax.device_put(
            x, NamedSharding(mesh, P(axis, *([None] * (x.ndim - 1))))),
        stacked)


_RUN_CACHE: dict = {}


def _traced_run(jitted: Callable, stage_params, microbatches,
                n_stages: int, m_count: int, axis: str,
                cached: bool) -> jax.Array:
    """Execute the jitted schedule; when tracing is on, record one
    MEASURED run span (block_until_ready bounds it) plus per-tick spans.
    The host cannot observe tick boundaries inside the single compiled
    shard_map program, so tick spans are an even subdivision of the run —
    marked ``synthetic`` — annotating what each tick's ppermute ring
    shift sends and which stage ingests/emits a microbatch."""
    if not trace.enabled or isinstance(microbatches, jax.core.Tracer):
        # under an outer jit/grad trace there is nothing to time: the
        # schedule inlines into the caller's program
        return jitted(stage_params, microbatches)
    t0 = time.perf_counter()
    try:
        out = jax.block_until_ready(jitted(stage_params, microbatches))
    except BaseException:
        trace.record_span("pipeline:run", "pipeline", t0,
                          time.perf_counter(),
                          args={"stages": n_stages,
                                "microbatches": m_count,
                                "axis": axis, "status": "error"})
        raise
    t1 = time.perf_counter()
    ticks = m_count + n_stages - 1
    trace.record_span(
        "pipeline:run", "pipeline", t0, t1,
        args={"stages": n_stages, "microbatches": m_count,
              "ticks": ticks, "axis": axis,
              "cache": "hit" if cached else "miss"})
    per = (t1 - t0) / max(ticks, 1)
    for t in range(ticks):
        trace.record_span(
            "pipeline:tick", "pipeline-ticks",
            t0 + t * per, t0 + (t + 1) * per,
            args={"tick": t, "synthetic": True,
                  "send": "ppermute ring shift (stage i -> i+1)",
                  "ingest": t if t < m_count else None,
                  "emit": t - (n_stages - 1)
                  if t >= n_stages - 1 else None})
    return out


def pipeline(stage_fn: Callable[[Any, jax.Array], jax.Array],
             stage_params, microbatches: jax.Array, mesh: Mesh,
             axis: str = "pp", checkpoint: bool = True) -> jax.Array:
    """Run ``microbatches`` (M, mb, ...) through P pipeline stages.

    ``stage_fn(params_for_stage, x) -> y`` maps one microbatch through one
    stage; activations keep one shape across stages (the transformer
    residual-stream invariant). Returns (M, mb, ...) outputs of the LAST
    stage. Differentiable end-to-end (jax.grad through the tick scan).
    """
    n_stages = mesh.shape[axis]
    m_count = microbatches.shape[0]
    # cache the jitted schedule per (stage_fn, mesh, shape class): a fresh
    # closure per call would defeat jax.jit's cache and retrace every step.
    # Bounded FIFO: per-call stage_fn closures must not leak an executable
    # per step (they still miss — pass a stable stage_fn to actually cache)
    cache_key = (stage_fn, mesh, axis, checkpoint, m_count,
                 microbatches.ndim, jax.tree.structure(stage_params))
    cached = _RUN_CACHE.get(cache_key)
    if cached is not None:
        return _traced_run(cached, stage_params, microbatches,
                           n_stages, m_count, axis, cached=True)
    while len(_RUN_CACHE) >= 32:
        _RUN_CACHE.pop(next(iter(_RUN_CACHE)))
    fn = jax.checkpoint(stage_fn) if checkpoint else stage_fn

    mb_spec = P(*([None] * microbatches.ndim))
    par_spec = jax.tree.map(lambda _: P(axis), stage_params)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(par_spec, mb_spec),
        out_specs=mb_spec, check_vma=False)
    def run(params, mbs):
        # params leaves: (1, L/P, ...) — my stage's slice; mbs: (M, mb, ...)
        my = jax.tree.map(lambda x: x[0], params)
        stage = lax.axis_index(axis)
        last = n_stages - 1
        zero = jnp.zeros(mbs.shape[1:], mbs.dtype)

        def tick(carry, t):
            state, outs = carry
            # stage 0 ingests microbatch t (when one remains); others take
            # the ppermute'd activation from the previous tick
            feed = lax.cond(t < m_count,
                            lambda: lax.dynamic_index_in_dim(
                                mbs, jnp.minimum(t, m_count - 1), 0,
                                keepdims=False),
                            lambda: zero)
            x = jnp.where(stage == 0, feed, state)
            y = fn(my, x)
            # the microbatch leaving the LAST stage at tick t is t-(P-1)
            out_idx = t - last
            outs = lax.cond(
                (stage == last) & (out_idx >= 0),
                lambda: lax.dynamic_update_index_in_dim(
                    outs, y, jnp.maximum(out_idx, 0), 0),
                lambda: outs)
            # shift every stage's output one stage forward
            # comm-lint: disable=CL001 the stage->stage shift IS the 1F1B schedule; traced and span-annotated by _traced_run, not an engine-dispatchable collective
            state = lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (state, outs), None

        outs0 = jnp.zeros_like(mbs)
        (_, outs), _ = lax.scan(
            tick, (zero, outs0), jnp.arange(m_count + n_stages - 1))
        # only the last stage holds real outputs; broadcast them to all
        # stages so the result is replicated over pp (psum of a one-hot)
        # comm-lint: disable=CL001 one-hot broadcast of the last stage's outputs; replication step of the schedule itself, not a tunable reduction
        outs = lax.psum(jnp.where(stage == last, outs, jnp.zeros_like(outs)),
                        axis)
        return outs

    # jit so the schedule compiles as one program even when called eagerly
    # (checkpointed stage_fn inside shard_map requires a surrounding jit;
    # nested jit is a no-op when the caller already traces)
    jitted = jax.jit(run)
    _RUN_CACHE[cache_key] = jitted
    return _traced_run(jitted, stage_params, microbatches,
                       n_stages, m_count, axis, cached=False)
