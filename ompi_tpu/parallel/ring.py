"""Ring attention — context parallelism over a mesh axis.

Long-context support is first-class in this framework (SURVEY.md §5.7): the
reference's segmented-ring collectives (coll_base_allreduce.c:344,621) are
exactly the communication schedule of ring attention — neighbor exchange of
K/V blocks around a ring, overlapping compute with ICI transfers. Here that
schedule is expressed TPU-natively: a ``lax.fori_loop`` of
(block attention, ``lax.ppermute``) steps inside ``shard_map``, with online
softmax merging so sequence length scales linearly with ring size at O(seq/n)
memory per chip.

The inner block-attention is a plain jnp function by default (XLA fuses it
well); pass ``block_impl="pallas"`` to use the Pallas flash kernel
(ops/attention.py flash_attention_partials) for the VMEM-resident fast path.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


NEG_INF = -1e30


def _block_attn(q, k, v, scale, mask):
    """One (q-block × kv-block) attention piece → (numerator, max, denom).

    q: (sq, d), k/v: (sk, d), mask: (sq, sk) additive or None.
    Returns o: (sq, d) un-normalized, m: (sq,) row max, l: (sq,) denom.
    """
    s = (q @ k.T) * scale                       # (sq, sk)
    if mask is not None:
        s = s + mask
    m = jnp.max(s, axis=-1)                     # (sq,)
    p = jnp.exp(s - m[:, None])
    l = jnp.sum(p, axis=-1)
    o = p @ v
    return o, m, l


def _merge(o1, m1, l1, o2, m2, l2):
    """Merge two online-softmax partials (the flash-attention combine)."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    o = o1 * a1[:, None] + o2 * a2[:, None]
    l = l1 * a1 + l2 * a2
    return o, m, l


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                   axis: str = "sp", causal: bool = False,
                   scale: Optional[float] = None,
                   batch_axis: Optional[str] = None,
                   head_axis: Optional[str] = None,
                   block_impl: str = "jnp") -> jax.Array:
    """Attention over a sequence sharded on `axis`.

    q/k/v: (batch, seq, heads, head_dim) with seq sharded over `axis`;
    batch/heads may additionally be sharded over dp/tp axes (composes with
    data and tensor parallelism). Each ring step attends the local Q shard
    against the visiting K/V shard, then rotates K/V one hop (``ppermute``)
    — n_axis steps total; the rotation of step i+1 overlaps the compute of
    step i in XLA's schedule.
    """
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    from .. import traffic
    if traffic.enabled and not isinstance(q, jax.core.Tracer):
        # all n ring steps rotate (the schedule permutes after the last
        # block too): per-rank wire = n x its K/V shard = full K+V bytes
        if mesh.shape[axis] > 1:
            traffic.note_ring(mesh, axis, k.nbytes + v.nbytes,
                              "ring_attention")
    return _build_ring(mesh, axis, bool(causal), float(scale),
                       batch_axis, head_axis, block_impl)(q, k, v)


@functools.lru_cache(maxsize=128)
def _build_ring(mesh: Mesh, axis: str, causal: bool, scale: float,
                batch_axis: Optional[str] = None,
                head_axis: Optional[str] = None,
                block_impl: str = "jnp"):
    """Compiled-program cache: one executable per (mesh, axis, causal, scale)
    × (shape, dtype) — the coll/xla cache discipline (SURVEY.md §7)."""
    n = mesh.shape[axis]

    def local(qs, ks, vs):
        # qs/ks/vs: (b, s_local, h, d)
        b, s, h, d = qs.shape
        my = lax.axis_index(axis)
        # fold batch*heads: (bh, s, d)
        qf = jnp.moveaxis(qs, 2, 1).reshape(b * h, s, d)
        kf0 = jnp.moveaxis(ks, 2, 1).reshape(b * h, s, d)
        vf0 = jnp.moveaxis(vs, 2, 1).reshape(b * h, s, d)

        q_pos = my * s + jnp.arange(s)           # global positions of my Q

        def step(i, carry):
            o, m, l, kf, vf = carry
            src = (my - i) % n                   # whose K/V is visiting
            if block_impl == "pallas":
                # VMEM-resident flash kernel (ops/attention.py) with the
                # traced global offsets driving the causal mask
                from ..ops.attention import flash_attention_partials
                bo, bm, bl = flash_attention_partials(
                    qf, kf, vf, causal=causal, scale=scale,
                    q_offset=my * s, kv_offset=src * s,
                    vma=frozenset(a for a in (batch_axis, axis, head_axis)
                                  if a is not None))
                bo = bo.astype(qf.dtype)
                bm = bm.astype(qf.dtype)
                bl = bl.astype(qf.dtype)
            else:
                kv_pos = src * s + jnp.arange(s)
                if causal:
                    mask = jnp.where(q_pos[:, None] >= kv_pos[None, :],
                                     0.0, NEG_INF).astype(qf.dtype)
                else:
                    mask = None
                bo, bm, bl = jax.vmap(
                    lambda qq, kk, vv: _block_attn(qq, kk, vv, scale, mask)
                )(qf, kf, vf)
            o, m, l = jax.vmap(_merge)(o, m, l, bo, bm, bl)
            # rotate K/V to the next ring position
            perm = [(j, (j + 1) % n) for j in range(n)]
            # comm-lint: disable=CL001 the ring hop IS the algorithm (not a reducible collective the engine could re-plan); attributed at the eager boundary via traffic.note_ring
            kf = lax.ppermute(kf, axis, perm)
            vf = lax.ppermute(vf, axis, perm)  # comm-lint: disable=CL001 same ring hop, V plane
            return o, m, l, kf, vf

        # mark the accumulators device-varying over exactly the mesh axes
        # this program's inputs are sharded on, so the fori carry types match
        # the per-shard outputs (vma rules)
        axes = tuple(a for a in (batch_axis, axis, head_axis)
                     if a is not None)

        def vary_all(x):
            if block_impl == "pallas":     # vma tracking is off (see below)
                return x
            missing = tuple(a for a in axes if a not in jax.typeof(x).vma)
            return lax.pcast(x, missing, to="varying") if missing else x

        o0 = vary_all(jnp.zeros_like(qf))
        m0 = vary_all(jnp.full(qf.shape[:2], NEG_INF, qf.dtype))
        l0 = vary_all(jnp.zeros(qf.shape[:2], qf.dtype))
        o, m, l, _, _ = lax.fori_loop(0, n, step, (o0, m0, l0, kf0, vf0))
        out = o / jnp.maximum(l, 1e-20)[:, :, None]
        return jnp.moveaxis(out.reshape(b, h, s, d), 1, 2)

    spec = P(batch_axis, axis, head_axis, None)
    # check_vma off for the pallas block: the interpret-mode pallas_call
    # lowering can't yet propagate varying-manual-axes through its internal
    # dynamic_slice (jax suggests this exact workaround).
    # comm-lint: disable=CL001 ring attention is a leaf SPMD kernel: its only comm is the waived ppermute ring above, verified statically by analysis.commgraph
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                                 out_specs=spec,
                                 check_vma=(block_impl != "pallas")))


def attention_reference(q, k, v, causal: bool = False,
                        scale: Optional[float] = None):
    """Dense single-device attention (ground truth for tests)."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    b, s, h, d = q.shape
    qf = jnp.moveaxis(q, 2, 1)      # (b, h, s, d)
    kf = jnp.moveaxis(k, 2, 1)
    vf = jnp.moveaxis(v, 2, 1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", w, vf)
    return jnp.moveaxis(out, 1, 2)
