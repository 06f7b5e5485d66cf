"""Fused decode program — the whole decode backbone + logits as ONE
jitted shard_map (``Config(decode_overlap="fused")``).

The eager decode path dispatches 11 audited collectives per token step
(1 embed AG + 4 AGs per layer + the logits RS→AG pair) between jitted
pieces — correct, fully audited, and dispatch-bound on the hottest loop
in the system.  This module is the decode-layout extension of the
``tp_overlap="fused"`` training path (ops/collective_matmul): the
residual stream is BATCH-sharded over tp (Megatron sequence parallelism
with sequence ↦ batch — each rank owns B/tp batch rows), so every tp
combine becomes an n−1-hop collective-matmul ring INSIDE one program:

* qkv / gate|up / logits — ``ring_allgather_matmul_local``: the (B/tp,
  d) residual shard rotates around the ring while each rank's
  column-local weight block multiplies the visiting rows (weights never
  move; d_ff/heads/vocab never cross the wire).
* wo / down — ``ring_matmul_reduce_scatter_local``: float32 partial
  sums ride the ring, each hop's matmul block produced just in time,
  and the output lands batch-scattered — the residual add is local.

Per decode step that leaves 4 rings per layer + 1 logits ring (the
gate/up pair shares one ring via a column-concat weight), every ring
carrying the same (B/tp, d) payload for n−1 hops, and exactly TWO eager
dispatches: the embed ``decode_ag`` (the d/tp feature combine that
builds the replicated residual) and the final logits ``decode_ag`` (the
vocab-shard combine).  11 → 2.

The audit moves with the traffic: each ring is decided (coll name
``decode_collmm``) and audited at the engine's dispatch site — one
decide event per ring, wire = (n−1)·payload charged to the ring edges —
and the static verifier (analysis/commgraph) extracts the program's
ppermute trips and proves static == runtime byte-for-byte
(``ServingEngine.verify_decode_program``).  The rings are built on
exactly n−1 ppermutes for this reason: a wasted last hop would break
the byte-for-byte proof, not just the perf.

Speculative decoding (scheduler ``spec_k``) stays on the eager window
path — the fused program is shape-specialized to the continuous batch.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..models.transformer import _rms_norm, decode_attention, rope_rows
from ..ops.collective_matmul import (ring_allgather_matmul_local,
                                     ring_matmul_reduce_scatter_local)

# per-layer ring sites in program order; the logits ring closes the step
LAYER_SITES = ("qkv_ag", "wo_rs", "gateup_ag", "down_rs")
LOGITS_SITE = "logits_ag"


def ring_schedule(n_layers: int, B: int, d_model: int, n: int,
                  itemsize: int) -> List[Tuple[str, int, int]]:
    """The fused program's static ring schedule: one ``(site,
    payload_bytes, wire_bytes)`` row per ring, in dispatch order.
    Every ring rotates a (B/n, d_model) block for n−1 hops — the AG
    rings carry the residual shard in the compute dtype, the RS rings
    carry float32 partial sums — so wire = (n−1)·payload per rank.
    The engine decides + audits one ``decode_collmm`` event per row;
    the commgraph extractor reproduces the summed wire figure from the
    traced ppermute trips byte-for-byte."""
    rows: List[Tuple[str, int, int]] = []
    bl = B // n
    for i in range(n_layers):
        for site in LAYER_SITES:
            size = itemsize if site.endswith("_ag") else 4  # RS rides f32
            payload = bl * d_model * size
            rows.append((f"L{i}/{site}", payload, (n - 1) * payload))
    payload = bl * d_model * itemsize
    rows.append((LOGITS_SITE, payload, (n - 1) * payload))
    return rows


def build_fused_decode(mesh, axis: str, n_layers: int, head_dim: int,
                       rope_base: float):
    """Build the jitted fused decode program over ``mesh``/``axis``.

    Returned callable signature::

        fn(x_can, bt, pos, page_idx, offset, layers, final_norm,
           embed_lg, k_pools, v_pools) -> (logits_can, k_pools, v_pools)

    * ``x_can`` (tp, B, d) — canonical residual, replicated content
      (the eager embed AG's regrouped output).
    * ``bt`` (B, pmax) block tables; ``pos``/``page_idx``/``offset``
      (B,) — replicated host-side indices (pos int32, −1 = inactive).
    * ``layers`` — tuple of per-layer dicts: ``attn_norm``/``mlp_norm``
      (d,) replicated; ``wqkv`` (tp, d, 3h/tp) and ``wgu`` (tp, d,
      2f/tp) canonical column-parallel; ``wo`` (tp, h/tp, d) and ``wd``
      (tp, f/tp, d) canonical ROW-parallel (the train layout's shards —
      the RS ring contracts over the local rows).
    * ``embed_lg`` (tp, d, V/tp) — the tied embedding's transposed
      vocab-block columns (train layout, canonicalized + swapped).
    * ``k_pools``/``v_pools`` — tuples of (tp, n_pages, page, h/tp, hd)
      paged-cache pools, donated: the page writes happen inside the
      program and the pools update in place.

    Output ``logits_can`` is (tp, B, V/tp) with row r = vocab block r —
    one eager ``decode_ag`` + regroup away from full logits.
    """
    n = mesh.shape[axis]

    def body(xc, bt, pos, page_idx, offset, layers, final_norm,
             embed_lg, k_pools, v_pools):
        x = xc[0]                            # (B, d) replicated content
        B = x.shape[0]
        bl = B // n
        my = lax.axis_index(axis)
        xs = lax.dynamic_slice_in_dim(x, my * bl, bl, axis=0)
        new_k: List[Any] = []
        new_v: List[Any] = []
        for lw, kp4, vp4 in zip(layers, k_pools, v_pools):
            kp, vp = kp4[0], vp4[0]
            h = _rms_norm(xs, lw["attn_norm"])
            qkv = ring_allgather_matmul_local(h, lw["wqkv"][0], axis, n)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            hl = q.shape[-1] // head_dim
            q = rope_rows(q.reshape(B, hl, head_dim), pos, rope_base)
            k = rope_rows(k.reshape(B, hl, head_dim), pos, rope_base)
            v = v.reshape(B, hl, head_dim)
            kp = kp.at[page_idx, offset].set(k.astype(kp.dtype))
            vp = vp.at[page_idx, offset].set(v.astype(vp.dtype))
            new_k.append(kp[None])
            new_v.append(vp[None])
            kk = jnp.take(kp, bt, axis=0)    # (B, pmax, page, hl, hd)
            pmax, pg = kk.shape[1], kk.shape[2]
            kk = kk.reshape(B, pmax * pg, hl, head_dim)
            vv = jnp.take(vp, bt, axis=0).reshape(B, pmax * pg, hl,
                                                  head_dim)
            att = decode_attention(q, kk, vv, pos)
            att = att.reshape(B, hl * head_dim)
            o = ring_matmul_reduce_scatter_local(att, lw["wo"][0],
                                                 axis, n)
            xs = xs + o.astype(xs.dtype)
            h2 = _rms_norm(xs, lw["mlp_norm"])
            gu = ring_allgather_matmul_local(h2, lw["wgu"][0], axis, n)
            g, u = jnp.split(gu, 2, axis=-1)
            z = jax.nn.silu(g) * u
            dn = ring_matmul_reduce_scatter_local(z, lw["wd"][0],
                                                  axis, n)
            xs = xs + dn.astype(xs.dtype)
        hf = _rms_norm(xs, final_norm)
        lg = ring_allgather_matmul_local(hf, embed_lg[0], axis, n)
        return (lg[None].astype(jnp.float32), tuple(new_k),
                tuple(new_v))

    lw_spec = {"attn_norm": P(), "mlp_norm": P(), "wqkv": P(axis),
               "wgu": P(axis), "wo": P(axis), "wd": P(axis)}
    pools_spec = (P(axis),) * n_layers
    in_specs = (P(axis), P(), P(), P(), P(),
                tuple(dict(lw_spec) for _ in range(n_layers)),
                P(), P(axis), pools_spec, pools_spec)
    out_specs = (P(axis), pools_spec, pools_spec)
    # outputs are provenance-varying (they flowed through ppermute), so
    # the static VMA check can't type them — same waiver as the train
    # collective-matmul builders
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False),
                   donate_argnums=(8, 9))
