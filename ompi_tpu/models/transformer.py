"""Flagship workload: a GPT-style decoder trained with dp×tp×sp sharding.

Open MPI itself ships no models — its acceptance workloads are ring_c and
the OSU/HPCG-class benchmarks (SURVEY.md §4/§6). This framework's flagship
plays the same role *and* exercises every parallelism strategy the framework
exists to serve (SURVEY.md §2.6): DP (batch sharding → XLA-inserted gradient
allreduce), TP (Megatron-style column/row-parallel matmuls → psum on the
row-parallel projections), SP/CP (ring attention over the `sp` axis —
parallel/ring.py), all over one named mesh.

Pure-jax pytree params (no framework dependency in the data path), bfloat16
activations on the MXU, float32 master params/optimizer, GSPMD sharding via
``NamedSharding`` annotations — the "pick a mesh, annotate, let XLA insert
collectives" recipe.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from .. import trace
from ..parallel.ring import attention_reference, ring_attention


@dataclass(frozen=True)
class Config:
    vocab: int = 512
    d_model: int = 128
    n_layers: int = 2
    n_heads: int = 8
    head_dim: int = 16
    d_ff: int = 512
    seq: int = 128
    dtype: Any = jnp.bfloat16        # activation/compute dtype (MXU-native)
    attn: str = "dense"              # "dense" | "ring" | "flash" (Pallas)
    rope_base: float = 10000.0
    mlp: str = "dense"               # "dense" | "moe" (expert-parallel)
    n_experts: int = 8
    moe_top_k: int = 2
    moe_aux_weight: float = 0.01
    moe_impl: str = "einsum"         # "einsum" | "ragged" — how MoE
    #   dispatch/combine moves: "einsum" is the dense (T, E, C) one-hot
    #   contraction (fully jitted; GSPMD inserts the all-to-alls; wire
    #   bytes scale with experts × capacity), "ragged" exchanges only
    #   the routed tokens over the device-native alltoallv path
    #   (models/moe.moe_block_ep — audited moe_dispatch/moe_combine,
    #   arms native|hier|hier+quant). The jitted train step always
    #   differentiates the einsum form (host-orchestrated exchanges
    #   cannot live under jit); "ragged" selects the EP comm path for
    #   forward/eval/serving — docs/moe.md
    moe_capacity_factor: float = 1.25  # per-expert capacity headroom,
    #   C = ceil(T·k·cf/E); the ragged path reads it through the live
    #   hot-expert adaptation (ompi_tpu.moe.capacity_factor)
    remat: str = "none"              # "none" | "dots" | "full" — see
    #   make_train_step: "full" recomputes each layer in the backward
    #   (cheapest memory, +~1 forward of FLOPs), "dots" saves matmul
    #   outputs and recomputes only elementwise ops (MXU work unchanged)
    attn_block: Optional[int] = None   # flash block_q/block_k override
    #   (None = ops.attention auto-pick); an A/B lever — block size sets
    #   the VMEM-tile / grid-step trade on the MXU
    attn_bwd_block: Optional[int] = None   # BACKWARD-kernel block override
    #   (dq; dk/dv tile independently of the fwd — they carry extra VMEM
    #   accumulators, so their optimum can sit a notch lower); swept by
    #   the A/B harness's "flash bwd block" rows
    loss_chunk: Optional[int] = None   # chunked cross-entropy: process the
    #   sequence in slices of this many positions so the (b, s, vocab)
    #   float32 logits never materialize whole (jax.checkpoint per slice;
    #   ~1 GB HBM at the flagship shape). Single-controller path only —
    #   on a mesh the seq slicing would cross sp shards.
    opt_moment_dtype: str = "float32"  # Adam first-moment dtype; "bfloat16"
    #   halves the mu buffer's HBM (the MFU lever VERDICT r3 item 9 names:
    #   less optimizer traffic on an HBM-bound chip). Second moment stays
    #   fp32 — bf16's 8-bit mantissa loses v's small-magnitude accumulation
    grad_sync: str = "native"          # how the dp gradient allreduce moves:
    #   "native"   — GSPMD inserts the exact allreduce
    #   "quant"    — one block-quantized psum_quant per leaf (coll/quant:
    #                int8 payload + per-block scales, ~4× fewer ICI bytes,
    #                ~1e-2 relative error on unit-scale gradients)
    #   "perleaf"  — one native pmean per leaf after the full backward
    #                (the explicit collective storm; the bench baseline)
    #   "bucketed" — fixed-byte buckets issued DURING backward so each
    #                bucket's exchange overlaps remaining compute; arm per
    #                bucket (native|quant) via the decision layer — see
    #                parallel/overlap.py
    #   "unsynced" — no gradient exchange (measurement-only compute floor)
    #   quant/perleaf/bucketed/unsynced are dp-only — see make_train_step
    grad_sync_block: int = 256         # quantization block for grad_sync
    #   ="quant"; smaller blocks track outliers tighter at more scale
    #   traffic (ratio (1 + 4/block)/4 of native bytes for f32)
    grad_bucket_bytes: Optional[int] = None  # grad_sync="bucketed" bucket
    #   target; None = the coll_xla_grad_bucket_bytes var (~4 MiB).
    #   Bigger buckets amortize dispatch latency, smaller ones start the
    #   first exchange earlier — docs/overlap.md
    tp_overlap: str = "none"           # "none" | "fused" — "fused" carries
    #   the tp-parallel matmuls on the ring-overlap kernels
    #   (ops/collective_matmul): the residual stream is sequence-sharded
    #   over tp (Megatron sequence parallelism), qkv/gate/up run
    #   allgather_matmul, wo/down run matmul_reduce_scatter; ring
    #   direction per call site (native|bidir) via the decision layer.
    #   Needs a tp>=2 mesh, dense attn+mlp, running seq divisible by tp
    decode_overlap: str = "eager"      # "eager" | "fused" — how the
    #   serving engine's decode step moves its tp combines: "eager"
    #   dispatches each decode_ag/decode_rs between jitted pieces (one
    #   audited collective per combine), "fused" runs the whole decode
    #   backbone + logits as ONE jitted program whose combines are the
    #   n−1-hop collective-matmul rings (serving/fused, audited as
    #   ``decode_collmm``) — the residual stream is BATCH-sharded over
    #   tp (sequence parallelism with sequence ↦ batch), so only the
    #   embed + logits combines stay eager. Needs tp>=2, dense mlp,
    #   max_seqs divisible by tp — docs/serving.md "Decode fast path"


def flagship_config(seq: int = 2048) -> Config:
    """The single-chip flagship: sized so the MXU saturates (d_model 2048
    ≥ the 128×128 systolic tile by 16×, head_dim 128 = one lane tile,
    d_ff 4×) and the Pallas flash path carries attention. ~470 M params —
    fp32 master + Adam moments ≈ 5.3 GB, activations with "dots" remat fit
    a 16 GB v5e at batch 4 × seq 2048."""
    return Config(vocab=32768, d_model=2048, n_layers=6, n_heads=16,
                  head_dim=128, d_ff=8192, seq=seq, attn="flash",
                  remat="dots")


def train_flops_per_token(cfg: Config) -> float:
    """Counted model FLOPs per trained token (the MFU numerator), standard
    accounting: 6 × matmul-weight params (fwd 2N + bwd 4N) plus causal
    attention 6·s·h per layer, h = n_heads·head_dim (fwd score+AV = 4·s·h,
    ×3 for train = 12·s·h, halved by causality). Remat recompute is
    hardware work but NOT counted — MFU is model FLOPs / peak, methodology
    per the reference's docs/tuning-apps/benchmarking.rst denominator
    discipline."""
    h = cfg.n_heads * cfg.head_dim
    per_layer = (cfg.d_model * 3 * h          # wqkv
                 + h * cfg.d_model            # wo
                 + 3 * cfg.d_model * cfg.d_ff)  # gate/up/down
    n_mm = cfg.n_layers * per_layer + cfg.d_model * cfg.vocab  # + logits
    attn = 6 * cfg.seq * h * cfg.n_layers                      # causal
    return 6.0 * n_mm + attn


# -- init -------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: Config) -> Dict:
    def dense(key, fan_in, shape):
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(fan_in))

    keys = jax.random.split(rng, 2 + cfg.n_layers)
    params: Dict[str, Any] = {
        "embed": dense(keys[0], cfg.d_model, (cfg.vocab, cfg.d_model)),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        "layers": [],
    }
    h = cfg.n_heads * cfg.head_dim
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[2 + i], 6)
        layer = {
            "attn_norm": jnp.ones((cfg.d_model,), jnp.float32),
            "wqkv": dense(k[0], cfg.d_model, (cfg.d_model, 3 * h)),
            "wo": dense(k[1], h, (h, cfg.d_model)),
            "mlp_norm": jnp.ones((cfg.d_model,), jnp.float32),
        }
        if cfg.mlp == "moe":
            from .moe import init_moe_params
            layer["moe"] = init_moe_params(k[5], cfg.d_model, cfg.d_ff,
                                           cfg.n_experts)
        else:
            layer.update({
                "w_gate": dense(k[2], cfg.d_model, (cfg.d_model, cfg.d_ff)),
                "w_up": dense(k[3], cfg.d_model, (cfg.d_model, cfg.d_ff)),
                "w_down": dense(k[4], cfg.d_ff, (cfg.d_ff, cfg.d_model)),
            })
        params["layers"].append(layer)
    return params


def param_specs(cfg: Config) -> Dict:
    """Megatron-style TP layout: qkv/gate/up column-parallel (shard the
    output features over `tp`), wo/down row-parallel (shard the input
    features; XLA inserts the psum). Embedding sharded over vocab.

    The fused ``wqkv`` leaf stays canonical, ``[q | k | v]`` along its
    columns, so its tp column shards are not whole heads; on a tp mesh
    the projection slices the leaf into head-aligned q, k and v blocks
    (``_qkv_blocks``) rather than the leaf being laid out otherwise."""
    layer = {
        "attn_norm": P(),
        "wqkv": P(None, "tp"),
        "wo": P("tp", None),
        "mlp_norm": P(),
    }
    if cfg.mlp == "moe":
        from .moe import moe_param_specs
        layer["moe"] = moe_param_specs()
    else:
        layer.update({
            "w_gate": P(None, "tp"),
            "w_up": P(None, "tp"),
            "w_down": P("tp", None),
        })
    return {
        "embed": P("tp", None),
        "final_norm": P(),
        "layers": [dict(layer) for _ in range(cfg.n_layers)],
    }


def shard_params(params: Dict, mesh: Mesh, cfg: Config) -> Dict:
    specs = param_specs(cfg)

    def fit(s: P) -> P:
        # drop axes the mesh doesn't have (e.g. no tp on a dp×ep mesh):
        # that dimension is simply replicated
        return P(*(a if a in mesh.axis_names else None for a in s))

    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, fit(s))),
        params, specs, is_leaf=lambda x: isinstance(x, P))


def decode_param_specs(cfg: Config) -> Dict:
    """Decode/serving layout: weight-stationary column-parallel.  Train's
    row-parallel weights (wo, w_down) flip to sharding their OUTPUT
    features over `tp` — decode is a latency-bound GEMV stream, so every
    matmul keeps the per-token activation sharded over tp and defers the
    combine instead of paying a psum mid-layer — and the embedding flips
    from vocab- to model-dim sharding so the logits matmul streams vocab
    columns without an all-gather of the hidden state."""
    layer = {
        "attn_norm": P(),
        "wqkv": P(None, "tp"),
        "wo": P(None, "tp"),
        "mlp_norm": P(),
    }
    if cfg.mlp == "moe":
        from .moe import moe_param_specs
        layer["moe"] = moe_param_specs()
    else:
        layer.update({
            "w_gate": P(None, "tp"),
            "w_up": P(None, "tp"),
            "w_down": P(None, "tp"),
        })
    return {
        "embed": P(None, "tp"),
        "final_norm": P(),
        "layers": [dict(layer) for _ in range(cfg.n_layers)],
    }


def convert_params(params: Dict, mesh: Mesh, cfg: Config,
                   to: str = "decode") -> Dict:
    """Switch a sharded parameter tree between the train and decode
    layouts entirely on device: each leaf whose spec differs moves
    through the compiled minimal-collective reshard engine
    (parallel/reshard) — no host round-trip, every plan step
    decision-audited and traffic-attributed under coll ``reshard``.
    Leaves already in the target layout compile to the empty plan and
    are returned untouched."""
    if to == "decode":
        specs = decode_param_specs(cfg)
    elif to == "train":
        specs = param_specs(cfg)
    else:
        raise ValueError(f"convert_params: to={to!r} (want train|decode)")
    from ..parallel.reshard import reshard as _reshard

    def fit(s: P) -> P:
        return P(*(a if a in mesh.axis_names else None for a in s))

    return jax.tree.map(
        lambda x, s: _reshard(x, NamedSharding(mesh, fit(s)), mesh=mesh),
        params, specs, is_leaf=lambda x: isinstance(x, P))


# -- model ------------------------------------------------------------------

def _rms_norm(x, w):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + 1e-6)).astype(x.dtype) * w.astype(x.dtype)


def _rope(x, positions, base):
    # x: (b, s, h, d) — rotate pairs
    d = x.shape[-1]
    half = d // 2
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # (s, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    rot1 = x1 * cos[None, :, None, :] - x2 * sin[None, :, None, :]
    rot2 = x2 * cos[None, :, None, :] + x1 * sin[None, :, None, :]
    return jnp.concatenate([rot1, rot2], axis=-1).astype(x.dtype)


def rope_rows(x, positions, base):
    """``_rope`` with PER-ROW positions: x (..., b, h, d), positions
    (b,) — the decode-path variant where every batch row sits at its own
    sequence position (the serving tier's continuous batch packs
    unrelated requests into one device batch).  Same rotation math as
    ``_rope``; only the position broadcast differs."""
    d = x.shape[-1]
    half = d // 2
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.expand_dims(jnp.cos(ang), axis=-2)       # (b, 1, half)
    sin = jnp.expand_dims(jnp.sin(ang), axis=-2)
    x1, x2 = x[..., :half], x[..., half:]
    rot1 = x1 * cos - x2 * sin
    rot2 = x2 * cos + x1 * sin
    return jnp.concatenate([rot1, rot2], axis=-1).astype(x.dtype)


def decode_attention(q, k, v, q_pos):
    """One decode step of ``_attn_apply``'s attention core against a
    paged KV view: q (..., b, hl, hd) is the new token per batch slot,
    k/v (..., b, L, hl, hd) the slot's gathered cache pages flattened
    to L key positions, q_pos (b,) the token's absolute position (−1
    for an inactive slot — fully masked, output garbage the scheduler
    discards).  Query b attends key slots l ≤ q_pos[b] (itself
    included: the engine writes the new k/v before attending), which is
    exactly ``attention_reference``'s causal row for position q_pos.
    Heads stay tp-sharded, so the whole op is local per shard."""
    hd = q.shape[-1]
    scores = jnp.einsum("...bnd,...blnd->...bnl", q, k) \
        / jnp.sqrt(jnp.asarray(hd, jnp.float32)).astype(q.dtype)
    L = k.shape[-3]
    mask = jnp.arange(L)[None, :] <= q_pos[:, None]    # (b, L)
    scores = jnp.where(mask[:, None, :], scores,
                       jnp.asarray(-1e30, scores.dtype))
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("...bnl,...blnd->...bnd", w, v)


def _layer_apply_fused(x: jax.Array, layer: Dict, cfg: Config,
                       mesh: Mesh) -> Tuple[jax.Array, jax.Array]:
    """The tp_overlap='fused' decoder layer: Megatron sequence
    parallelism with the collectives fused into the matmuls. The
    residual stream lives sequence-sharded over tp; each column-parallel
    matmul (qkv, gate, up) is an ``allgather_matmul`` (the ring gather
    overlaps the MXU blocks) and each row-parallel one (wo, down) is a
    ``matmul_reduce_scatter`` (partial sums ride the ring), so no
    standalone all-gather/psum ever serializes against the dots. Ring
    direction per call site (native | bidir two half-rings) comes from
    the decision layer under the coll name ``collmm``."""
    from ..ops.collective_matmul import (allgather_matmul,
                                         matmul_reduce_scatter)
    from ..parallel import overlap

    tp = mesh.shape["tp"]
    if tp < 2:
        raise ValueError("tp_overlap='fused' needs a tp mesh axis of "
                         f"size >= 2 (mesh axes: {dict(mesh.shape)})")
    if cfg.attn != "dense" or cfg.mlp != "dense":
        raise ValueError(
            "tp_overlap='fused' supports dense attention + dense MLP "
            f"only (got attn={cfg.attn!r}, mlp={cfg.mlp!r})")
    b, s = x.shape[0], x.shape[1]
    h_dim = cfg.n_heads * cfg.head_dim
    if s % tp:
        raise ValueError(
            f"tp_overlap='fused' sequence-shards the residual over tp: "
            f"running seq {s} must be divisible by tp={tp} (the training "
            f"loss drops one position — pick cfg.seq = k*tp + 1)")
    if cfg.n_heads % tp or cfg.d_ff % tp:
        raise ValueError(
            f"tp_overlap='fused' needs n_heads ({cfg.n_heads}) and d_ff "
            f"({cfg.d_ff}) divisible by tp={tp}")
    batch_axis = ("dp" if "dp" in mesh.axis_names
                  and mesh.shape["dp"] > 1 else None)
    x = lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(batch_axis, "tp", None)))
    positions = jnp.arange(s)
    # per-rank ring payload of the sequence-sharded activations — the
    # byte count DEVICE_RULES rows for `collmm` match against
    shard_bytes = (b * (s // tp) * cfg.d_model
                   * jnp.dtype(cfg.dtype).itemsize)
    if batch_axis is not None:
        shard_bytes //= mesh.shape["dp"]
    bidir_ok = (s // tp) % 2 == 0

    def ring(kind: str) -> bool:
        return overlap.decide_collmm(kind, shard_bytes, mesh, "tp",
                                     bidir_ok) == "bidir"

    h = _rms_norm(x, layer["attn_norm"])
    qkv = allgather_matmul(h, layer["wqkv"].astype(cfg.dtype), mesh, "tp",
                           w_sharded_axis="tp",
                           bidirectional=ring("qkv"),
                           batch_axis=batch_axis)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_heads, cfg.head_dim)
    q = _rope(q, positions, cfg.rope_base)
    k = _rope(k, positions, cfg.rope_base)
    # full-sequence attention with heads tp-sharded under GSPMD — the
    # fused matmuls bracket it, so only the (cheap) head resharding of
    # qkv/att crosses tp here
    att = attention_reference(q, k, v, causal=True)
    att = att.reshape(b, s, h_dim)
    x = x + matmul_reduce_scatter(att, layer["wo"].astype(cfg.dtype),
                                  mesh, "tp",
                                  bidirectional=ring("wo"),
                                  batch_axis=batch_axis)
    h = _rms_norm(x, layer["mlp_norm"])
    gate = jax.nn.silu(
        allgather_matmul(h, layer["w_gate"].astype(cfg.dtype), mesh, "tp",
                         w_sharded_axis="tp",
                         bidirectional=ring("gate"),
                         batch_axis=batch_axis))
    up = allgather_matmul(h, layer["w_up"].astype(cfg.dtype), mesh, "tp",
                          w_sharded_axis="tp",
                          bidirectional=ring("up"),
                          batch_axis=batch_axis)
    down = matmul_reduce_scatter(gate * up,
                                 layer["w_down"].astype(cfg.dtype),
                                 mesh, "tp",
                                 bidirectional=ring("down"),
                                 batch_axis=batch_axis)
    return x + down, jnp.zeros((), jnp.float32)


def _flash_attn(q, k, v, cfg: Config, mesh: Optional[Mesh]) -> jax.Array:
    """Causal Pallas flash attention, fwd + bwd.  Mosaic kernels cannot be
    partitioned by GSPMD, so on a mesh every device runs the kernel on its
    own block — batch over dp, heads over tp — inside a shard_map."""
    from ..ops.attention import flash_mha

    def attend(q, k, v):
        return flash_mha(q, k, v, True, None, cfg.attn_block,
                         cfg.attn_block, None, cfg.attn_bwd_block,
                         cfg.attn_bwd_block)

    if mesh is None:
        return attend(q, k, v)
    names = mesh.axis_names
    spec = P("dp" if "dp" in names else None, None,
             "tp" if "tp" in names else None, None)
    # check_vma off: the kernel body carries no VMA types (as ring.py's
    # Pallas block); the block-local program has no collective to type
    # comm-lint: disable=CL001 block-local flash kernel: no collective inside
    return jax.shard_map(attend, mesh=mesh, in_specs=(spec,) * 3,
                         out_specs=spec, check_vma=False)(q, k, v)


def _attn_apply(x: jax.Array, layer: Dict, cfg: Config,
                mesh: Optional[Mesh]) -> jax.Array:
    """Attention half of the decoder layer, residual included."""
    b, s = x.shape[0], x.shape[1]
    positions = jnp.arange(s)
    h = _rms_norm(x, layer["attn_norm"])
    if isinstance(layer["wqkv"], tuple):
        # on a tp mesh: the column halves of the fused [q | k | v] are
        # not whole heads, so _qkv_blocks sliced it into head-aligned
        # q, k and v blocks
        q, k, v = (h @ w for w in layer["wqkv"])
    else:
        qkv = h @ layer["wqkv"].astype(cfg.dtype)      # (b, s, 3*heads*hd)
        q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_heads, cfg.head_dim)
    q = _rope(q, positions, cfg.rope_base)
    k = _rope(k, positions, cfg.rope_base)
    if cfg.attn == "ring" and mesh is not None and "sp" in mesh.axis_names:
        att = ring_attention(q, k, v, mesh, "sp", causal=True,
                             batch_axis="dp" if "dp" in mesh.axis_names
                             else None,
                             head_axis="tp" if "tp" in mesh.axis_names
                             else None)
    elif cfg.attn == "flash":
        att = _flash_attn(q, k, v, cfg, mesh)
    else:
        att = attention_reference(q, k, v, causal=True)
    att = att.reshape(b, s, cfg.n_heads * cfg.head_dim)
    return x + att @ layer["wo"].astype(cfg.dtype)     # row-parallel → psum


def _qkv_blocks(layer: Dict, cfg: Config, mesh: Optional[Mesh]) -> Dict:
    """On a mesh with tp > 1, the layer with ``wqkv`` replaced by its
    bf16 q, k and v column blocks, each constrained head-sharded over
    tp: GSPMD then moves weight blocks where projecting with the whole
    leaf would reshuffle the q, k and v activations every layer
    (at tp 2 one chip holds all of q and half of k).  Elsewhere the
    layer as it is.  Called outside the checkpointed layer, so remat
    does not move the blocks again in the backward."""
    if (mesh is None or mesh.shape.get("tp", 1) == 1
            or cfg.tp_overlap == "fused"):
        return layer
    w = layer["wqkv"].astype(cfg.dtype)
    n = cfg.n_heads * cfg.head_dim
    col = NamedSharding(mesh, P(None, "tp"))
    return dict(layer, wqkv=tuple(
        lax.with_sharding_constraint(w[:, i * n:(i + 1) * n], col)
        for i in range(3)))


def _layer_apply(x: jax.Array, layer: Dict, cfg: Config,
                 mesh: Optional[Mesh]) -> Tuple[jax.Array, jax.Array]:
    """One decoder layer; returns (x, router_aux)."""
    if cfg.tp_overlap not in ("none", "fused"):
        raise ValueError(f"unknown tp_overlap {cfg.tp_overlap!r} "
                         "(expected 'none' or 'fused')")
    if cfg.tp_overlap == "fused":
        if mesh is None or "tp" not in mesh.axis_names:
            raise ValueError(
                "tp_overlap='fused' needs a mesh with a tp axis "
                f"(got mesh={'set' if mesh is not None else None})")
        return _layer_apply_fused(x, layer, cfg, mesh)
    x = _attn_apply(x, layer, cfg, mesh)
    h = _rms_norm(x, layer["mlp_norm"])
    if "moe" in layer:
        from .moe import moe_block
        mlp_out, aux = moe_block(h, layer["moe"], cfg.n_experts,
                                 cfg.moe_top_k, cfg.moe_capacity_factor)
        return x + mlp_out, aux
    gate = jax.nn.silu(h @ layer["w_gate"].astype(cfg.dtype))
    up = h @ layer["w_up"].astype(cfg.dtype)
    return x + (gate * up) @ layer["w_down"].astype(cfg.dtype), \
        jnp.zeros((), jnp.float32)


def _remat_wrap(fn, mode: str):
    if mode == "full":
        return jax.checkpoint(fn)
    if mode == "dots":
        # keep matmul outputs, recompute elementwise (norms/rope/silu):
        # backward re-does no MXU work, HBM residency drops to the dot
        # outputs — the right trade on HBM-bandwidth-bound chips
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn


def _backbone(params: Dict, tokens: jax.Array, cfg: Config,
              mesh: Optional[Mesh] = None):
    """tokens (b, s) → (hidden (b, s, d) after final norm, router aux)."""
    x = params["embed"].astype(cfg.dtype)[tokens]      # (b, s, d)
    aux_total = jnp.zeros((), jnp.float32)
    layer_fn = _remat_wrap(
        lambda x, layer: _layer_apply(x, layer, cfg, mesh), cfg.remat)
    for layer in params["layers"]:
        x, aux = layer_fn(x, _qkv_blocks(layer, cfg, mesh))
        aux_total = aux_total + aux
    return _rms_norm(x, params["final_norm"]), aux_total


def forward(params: Dict, tokens: jax.Array, cfg: Config,
            mesh: Optional[Mesh] = None) -> jax.Array:
    """tokens: (batch, seq) int32 → logits (batch, seq, vocab); with
    cfg.mlp == "moe" returns (logits, router_aux_loss)."""
    x, aux_total = _backbone(params, tokens, cfg, mesh)
    logits = x @ params["embed"].astype(cfg.dtype).T   # tied embedding
    logits = logits.astype(jnp.float32)
    return (logits, aux_total) if cfg.mlp == "moe" else logits


def _chunked_ce(x: jax.Array, embed: jax.Array, targets: jax.Array,
                chunk: int) -> jax.Array:
    """Mean CE WITHOUT ever materializing the full (b, s, vocab) float32
    logits: the sequence axis is processed in ``chunk``-sized slices, and
    each slice's projection + logsumexp is wrapped in jax.checkpoint so
    the backward recomputes its (b, chunk, vocab) logits from the (b,
    chunk, d) hidden slice instead of saving them. Peak logits memory
    drops from s/chunk× to 1× per slice — at the flagship shape (seq
    2048, vocab 32k, f32) that is ~1 GB of HBM freed for batch/remat
    headroom. The chunked and dense paths are bit-equivalent reductions
    over the same values (logsumexp is per-position)."""
    b, s, d = x.shape
    n = s // chunk
    xs = x[:, :n * chunk].reshape(b, n, chunk, d).swapaxes(0, 1)
    ts = targets[:, :n * chunk].reshape(b, n, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def one(x_c, t_c):                         # (b, chunk, d), (b, chunk)
        logits = (x_c @ embed.T).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, t_c[..., None],
                                   axis=-1)[..., 0]
        return jnp.sum(lse - gold)

    total = jnp.sum(jax.lax.map(lambda a: one(*a), (xs, ts)))
    if n * chunk < s:                          # ragged tail: same
        total = total + one(x[:, n * chunk:],  # checkpointed kernel
                            targets[:, n * chunk:])
    return total / (b * s)


def loss_fn(params: Dict, tokens: jax.Array, cfg: Config,
            mesh: Optional[Mesh] = None) -> jax.Array:
    targets = tokens[:, 1:]
    if cfg.loss_chunk:
        # chunked CE is single-controller, dense-MLP only: seq slicing
        # would cross sp shards on a mesh, and the MoE loss carries the
        # router aux term. A silent dense fallback would record
        # loss_chunk as active while measuring the baseline — refuse
        # instead
        if mesh is not None or cfg.mlp == "moe":
            raise ValueError(
                "loss_chunk is only supported single-controller with "
                "mlp='dense' (got "
                f"mesh={'set' if mesh is not None else None}, "
                f"mlp={cfg.mlp!r}); unset loss_chunk for this path")
        x, _ = _backbone(params, tokens[:, :-1], cfg, mesh)
        ce = _chunked_ce(x, params["embed"].astype(cfg.dtype), targets,
                         int(cfg.loss_chunk))
        return ce
    out = forward(params, tokens[:, :-1], cfg, mesh)
    logits, aux = out if cfg.mlp == "moe" else (out, 0.0)
    # logsumexp-form CE: one (b, s) reduction instead of materializing a
    # second (b, s, vocab) float32 log-probability tensor — at flagship
    # scale that second tensor alone is GBs of HBM
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    if cfg.mlp == "moe":
        # the aux weight reads through the MoE plane's live adaptation
        # (identity while the plane is off). Inside jit this binds at
        # trace time; the ragged eval path below re-reads every call
        from .. import moe as _moe
        return jnp.mean(lse - gold) + _moe.aux_weight(
            cfg.moe_aux_weight) * aux
    return jnp.mean(lse - gold)


# -- ragged expert-parallel forward (Config(moe_impl="ragged")) -------------

def moe_forward_ep(dc, params: Dict, tokens: jax.Array, cfg: Config,
                   step: Optional[int] = None,
                   ) -> Tuple[jax.Array, jax.Array]:
    """Forward pass with every MoE layer on the device-native ragged EP
    path (models/moe.moe_block_ep): token payloads travel the audited
    ``moe_dispatch``/``moe_combine`` exchanges over ``dc``'s comm axis
    instead of the dense einsum block. Host-orchestrated — the per-layer
    pieces (attention, router, expert FFN, gate-combine) are jitted, the
    exchanges are cached device programs — so this is the forward /
    eval / serving arm; the jitted train step differentiates the einsum
    form. Returns (logits, router_aux)."""
    if cfg.mlp != "moe":
        raise ValueError("moe_forward_ep needs cfg.mlp='moe' "
                         f"(got {cfg.mlp!r})")
    from .moe import moe_block_ep
    x = params["embed"].astype(cfg.dtype)[tokens]      # (b, s, d)
    b, s, d = x.shape
    R = dc.n
    if (b * s) % R:
        raise ValueError(
            f"moe_forward_ep: batch·seq {b * s} not divisible by the "
            f"comm size {R}")
    t = (b * s) // R
    aux_total = jnp.zeros((), jnp.float32)
    for layer in params["layers"]:
        x = _attn_apply(x, layer, cfg, None)
        h = _rms_norm(x, layer["mlp_norm"])
        hc = jax.device_put(jnp.reshape(h, (R, t, d)), dc.sharding())
        out, aux, _info = moe_block_ep(
            dc, hc, layer["moe"], cfg.n_experts, cfg.moe_top_k,
            cfg.moe_capacity_factor, step=step)
        x = x + jnp.asarray(np.asarray(out)).reshape(b, s, d)
        aux_total = aux_total + aux
    x = _rms_norm(x, params["final_norm"])
    logits = (x @ params["embed"].astype(cfg.dtype).T).astype(jnp.float32)
    return logits, aux_total


def moe_eval_loss(dc, params: Dict, tokens: jax.Array, cfg: Config,
                  step: Optional[int] = None) -> jax.Array:
    """loss_fn's ragged-arm counterpart: same logsumexp-form CE + aux
    term, with the MoE layers on moe_forward_ep and the aux weight read
    live through the MoE plane each call."""
    from .. import moe as _moe
    targets = tokens[:, 1:]
    logits, aux = moe_forward_ep(dc, params, tokens[:, :-1], cfg,
                                 step=step)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold) + _moe.aux_weight(
        cfg.moe_aux_weight) * aux


# -- training ---------------------------------------------------------------

def _quant_grad_sync(cfg: Config, mesh: Mesh):
    """Build value_and_grad with the dp allreduce carried by the block-
    quantized tier instead of GSPMD's exact one: per-shard grads inside a
    shard_map over dp, each leaf combined with coll/quant.psum_quant
    (quantize → all_to_all int8+scales → f32 accumulate → requantize →
    all_gather), loss pmean'd exactly (it is a scalar — nothing to save).

    dp-only meshes: a shard_map over dp replicates every other axis, which
    would silently undo tp/sp parameter sharding — refuse instead, matching
    the loss_chunk contract above."""
    from ..coll.quant import psum_quant

    if "dp" not in mesh.axis_names:
        raise ValueError(
            "grad_sync='quant' needs a 'dp' mesh axis to sync over "
            f"(mesh axes: {mesh.axis_names})")
    for a in mesh.axis_names:
        if a != "dp" and mesh.shape[a] > 1:
            raise ValueError(
                "grad_sync='quant' is dp-only: the shard_map over dp would "
                f"replicate axis {a!r} (size {mesh.shape[a]}) and undo its "
                "parameter sharding; use grad_sync='native' on dp×tp/sp "
                "meshes")
    n = mesh.shape["dp"]
    data_spec = P(*("dp" if a == "dp" else None for a in mesh.axis_names))

    def local(params, tokens):
        # mesh=None inside: the model sees only its batch shard; the one
        # cross-shard exchange is the gradient sync below (the cast keeps
        # autodiff from summing the grads over dp itself)
        params = lax.pcast(params, "dp", to="varying")
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, cfg, None)
        grads = jax.tree.map(
            lambda g: psum_quant(g, "dp", n, avg=True,
                                 block=cfg.grad_sync_block), grads)
        # comm-lint: disable=CL001 scalar loss average (control plane, excluded from wire models); the payload sync is the audited psum_quant above
        return lax.pmean(loss, "dp"), grads

    # comm-lint: disable=CL001 the quant grad-sync tier: its comm is psum_quant (coll/quant engine) plus the waived scalar pmean
    return jax.shard_map(local, mesh=mesh, in_specs=(P(), data_spec),
                         out_specs=(P(), P()))


def make_train_step(cfg: Config, mesh: Optional[Mesh] = None,
                    learning_rate: float = 1e-3):
    """Returns (init_opt_state, step). step is jit-compiled; with a mesh the
    data batch is dp-sharded and gradients allreduce over dp automatically —
    or through an explicit scheduler per cfg.grad_sync: "quant" (per-leaf
    block-quantized tier), "perleaf"/"bucketed"/"unsynced"
    (parallel/overlap — bucketed is the backward-overlapped tier)."""
    import optax

    tx = optax.adamw(learning_rate,
                     mu_dtype=jnp.dtype(cfg.opt_moment_dtype))

    def init_opt(params):
        state = tx.init(params)
        if mesh is None:
            return state
        # the step count is made on one device; the step returns it
        # replicated over the mesh, and a second sharding of the same
        # argument would compile the whole step a second time
        rep = NamedSharding(mesh, P())
        return jax.tree.map(
            lambda x: jax.device_put(x, rep) if x.ndim == 0 and isinstance(
                getattr(x, "sharding", None), SingleDeviceSharding) else x,
            state)

    _MODES = ("native", "quant", "perleaf", "bucketed", "unsynced")
    if cfg.grad_sync not in _MODES:
        raise ValueError(f"unknown grad_sync {cfg.grad_sync!r} "
                         f"(expected one of {_MODES})")
    if cfg.mlp == "moe" and cfg.moe_impl not in ("einsum", "ragged"):
        raise ValueError(f"unknown moe_impl {cfg.moe_impl!r} "
                         "(expected 'einsum' or 'ragged')")
    if cfg.tp_overlap == "fused" and cfg.grad_sync != "native":
        # the explicit grad-sync schedulers shard_map over dp with
        # mesh=None inside — the fused layer cannot run there
        raise ValueError(
            f"tp_overlap='fused' requires grad_sync='native' "
            f"(got {cfg.grad_sync!r}): the dp-only grad-sync shard_map "
            "would replicate tp and lose the fused layer's mesh")
    custom_vg = None
    if cfg.grad_sync != "native":
        if mesh is None:
            raise ValueError(f"grad_sync={cfg.grad_sync!r} requires a "
                             "mesh (single-controller has no dp axis to "
                             "sync)")
        if cfg.grad_sync == "quant":
            custom_vg = _quant_grad_sync(cfg, mesh)
        else:
            from ..parallel import overlap
            custom_vg = overlap.make_grad_sync(
                cfg.grad_sync, mesh,
                lambda p, t: loss_fn(p, t, cfg, None),
                bucket_bytes=cfg.grad_bucket_bytes,
                quant_block=cfg.grad_sync_block)

    def step(params, opt_state, tokens):
        if custom_vg is not None:
            loss, grads = custom_vg(params, tokens)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens, cfg,
                                                      mesh)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    if mesh is not None:
        # batch dp-sharded; seq dim left unsharded here (tokens carry seq+1
        # for the shifted targets — GSPMD reshards activations onto sp at
        # the ring-attention boundary)
        data_spec = P("dp" if "dp" in mesh.axis_names else None, None)
        jstep = jax.jit(step, in_shardings=(None, None,
                                            NamedSharding(mesh, data_spec)),
                        donate_argnums=(0, 1))
    else:
        jstep = jax.jit(step, donate_argnums=(0, 1))

    fpt = train_flops_per_token(cfg)

    def dispatch(params, opt_state, tokens):
        with trace.region("ompi.train.step"):
            return jstep(params, opt_state, tokens)

    def timed_step(params, opt_state, tokens):
        from .. import numerics, perf
        if isinstance(tokens, jax.core.Tracer):
            return jstep(params, opt_state, tokens)
        if not perf.enabled:
            if numerics.enabled:
                # per-step loss telemetry for the NUMERICS ledger (the
                # grad norm comes from the overlap.vg hook; record_step
                # pairs them on the step row and advances the counter)
                out = dispatch(params, opt_state, tokens)
                numerics.record_step(loss=float(out[2]))
                return out
            return dispatch(params, opt_state, tokens)
        # goodput/MFU ledger: blocked wall per step. Only wall + token
        # FLOPs are measurable from one blocked call — the comm split
        # (exposed vs total) needs a device trace, never fabricated
        # here.
        t0 = time.perf_counter()
        out = dispatch(params, opt_state, tokens)
        jax.block_until_ready(out)
        perf.record_step(time.perf_counter() - t0,
                         tokens=tokens.shape[0] * max(tokens.shape[1] - 1,
                                                      1),
                         flops_per_token=fpt,
                         peak_tflops=perf.peak_tflops())
        if numerics.enabled:
            numerics.record_step(loss=float(out[2]))
        return out

    def comm_graph(params, opt_state, tokens):
        """The step's collectives as compiled for these arguments
        (``analysis.commgraph.from_compiled``): per mesh axis, what
        GSPMD inserted and XLA kept.  It lowers and compiles the step
        again, which the compile cache serves once the step has run."""
        from ..analysis.commgraph import from_compiled
        return from_compiled(jstep.lower(params, opt_state, tokens).compile(),
                             mesh, source="train_step")

    timed_step.jitted = jstep       # for .lower()/.compile() inspection
    timed_step.comm_graph = comm_graph
    return init_opt, timed_step
