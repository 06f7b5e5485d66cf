"""Ulysses-style sequence parallelism: all-to-all head/sequence exchange.

The alltoall-backed alternative to ring attention (SURVEY.md §5.7 — "Ulysses
= alltoall of heads", coll_base_alltoall.c): with sequence sharded over the
`sp` axis, two ``lax.all_to_all``s re-shard from sequence-parallel to
head-parallel, run *dense local attention over the full sequence* for the
local head subset, and shard back. Communication is 2 all-to-alls of
activation size versus ring attention's (n-1) K/V hops; on ICI-rich slices
with moderate sequence lengths this usually wins; ring wins at extreme
sequence lengths (K/V streaming, O(seq/n) memory).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .ring import attention_reference


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                      axis: str = "sp", causal: bool = False,
                      scale: Optional[float] = None,
                      attn_fn: Optional[Callable] = None) -> jax.Array:
    """q/k/v: (batch, seq, heads, head_dim), seq sharded over `axis`;
    heads must be divisible by the axis size."""
    n = mesh.shape[axis]
    if q.shape[2] % n != 0:
        raise ValueError(f"heads {q.shape[2]} not divisible by axis size {n}")
    from .. import traffic
    if traffic.enabled and not isinstance(q, jax.core.Tracer) and n > 1:
        # four tiled all_to_alls (q/k/v seq->heads + the output
        # heads->seq), each moving one per-rank shard: wire =
        # (q + k + v + out) / n with out the size of q — the figure
        # the static verifier re-derives from the traced per-shard
        # all_to_all avals (analysis/commgraph), byte-for-byte
        traffic.note_a2a(mesh, axis,
                         (2 * q.nbytes + k.nbytes + v.nbytes) // n,
                         "ulysses")
    return _build_ulysses(mesh, axis, bool(causal), scale, attn_fn)(q, k, v)


import functools


@functools.lru_cache(maxsize=128)
def _build_ulysses(mesh: Mesh, axis: str, causal: bool,
                   scale: Optional[float], attn_fn: Optional[Callable]):
    attn = attn_fn or (lambda qq, kk, vv: attention_reference(
        qq, kk, vv, causal=causal, scale=scale))

    def local(qs, ks, vs):
        # local: (b, s/n, h, d) → exchange → (b, s, h/n, d)
        def seq_to_heads(x):
            # comm-lint: disable=CL001 the tiled alltoall IS the ulysses algorithm (head/seq transpose); wire bytes attributed eagerly via traffic.note_a2a in ulysses_attention
            return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

        def heads_to_seq(x):
            # comm-lint: disable=CL001 inverse transpose of the waived seq_to_heads exchange
            return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

        qh, kh, vh = seq_to_heads(qs), seq_to_heads(ks), seq_to_heads(vs)
        out = attn(qh, kh, vh)            # dense attention, full sequence
        return heads_to_seq(out)

    spec = P(None, axis, None, None)
    # comm-lint: disable=CL001 leaf SPMD kernel: only comm is the waived alltoall pair, statically verified by analysis.commgraph
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                                 out_specs=spec))
