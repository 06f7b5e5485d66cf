"""Continuous-batching serving engine — prefill/decode over the decode
weight layout, decode collectives audited as ``decode_ag``/``decode_rs``.

Execution model (the host-orchestrated pattern of
``models/moe.moe_block_ep``): the per-layer compute is a handful of
jitted collective-free pieces over CANONICAL dim-0 arrays — every
weight shard lifted once at init through ``DeviceComm.canonicalize``
(a zero-wire local restack), every activation carried as ``(tp, B, …)``
— and the only cross-device traffic is the eagerly dispatched, audited
decode collectives between pieces.  That structure is what makes "one
decision event per decode collective" true by construction rather than
by instrumentation.

Dataflow per token step, consistent with
``models/transformer.decode_param_specs`` (all weights column-parallel,
output features sharded over ``tp``; the residual stream rides
replicated-content canonical form):

* embed lookup → ``decode_ag`` (combine the d/tp feature shards)
* per layer: qkv (local) → rope → paged-cache write (donated) →
  paged attention (local: heads are tp-sharded) → ``decode_ag`` (head
  combine) → wo (local) → ``decode_ag`` → +residual; mlp gate/up
  (local) → ``decode_ag`` (d_ff combine) → w_down (local) →
  ``decode_ag`` → +residual
* logits: per-device partial over its d/tp slice of the tied embedding
  → ``decode_rs`` + ``decode_ag`` (the bandwidth-bound psum: B×vocab
  float32 — exactly where the EQuARX int8 tier pays for itself)

Every dispatch runs the full decision chain (``coll/xla.decide_mode``:
force vars ``coll_xla_decode_ag_mode``/``coll_xla_decode_rs_mode`` >
blanket > learned > DEVICE_RULES rows > platform default) and fans out
the same audit record as ``coll/xla._audit``: arm/wire pvars, perf
``decode_*`` ledger cells, traffic ring-edge attribution (conservation:
edge-sum == ``coll_wire_bytes``), and the trace decision event.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .. import trace
from ..models.transformer import _rms_norm, rope_rows
from ..ops.paged_attention import paged_attention
from ..parallel.ring import attention_reference
from .cache import PagedKVCache

# -- jitted collective-free pieces (canonical dim-0 layout throughout) ------


def _regroup(y):
    """(tp, tp*B, c) allgather output → (tp, B, tp*c): per-token
    feature concat of the per-device column shards.  Each row is fully
    resident on one device, so this is a local reshape/transpose."""
    r, tb, c = y.shape
    b = tb // r
    return y.reshape(r, r, b, c).transpose(0, 2, 1, 3).reshape(r, b, r * c)


_j_regroup = jax.jit(_regroup)


@jax.jit
def _j_embed(embed_can, tokens):
    """(tp, V, d/tp), (B,) → (tp, B, d/tp) local embedding slices."""
    return jnp.take(embed_can, tokens, axis=1)


@partial(jax.jit, static_argnames=("head_dim", "base"))
def _j_qkv(x, norm_w, wqkv, pos, head_dim, base):
    """Residual (tp, B, d) → roped q, k, v (tp, B, heads/tp, head_dim).
    The qkv matmul is column-parallel: zero comm."""
    h = _rms_norm(x, norm_w)
    qkv = jnp.einsum("rbd,rdc->rbc", h, wqkv)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    r, b, c = q.shape
    q = rope_rows(q.reshape(r, b, c // head_dim, head_dim), pos, base)
    k = rope_rows(k.reshape(r, b, c // head_dim, head_dim), pos, base)
    return q, k, v.reshape(r, b, c // head_dim, head_dim)


@partial(jax.jit, donate_argnums=(0, 1))
def _j_page_write(kp, vp, k_new, v_new, page_idx, offset):
    """Scatter one k/v row per batch slot into its page — donated, so
    the pools update in place and cache data never visits the host."""
    kp = kp.at[:, page_idx, offset].set(k_new.astype(kp.dtype))
    vp = vp.at[:, page_idx, offset].set(v_new.astype(vp.dtype))
    return kp, vp


@partial(jax.jit, static_argnames=("mesh", "axis"))
def _j_paged_attn(q, kp, vp, bt, q_pos, mesh=None, axis=None):
    """Decode attention against the paged pools, read in place: the
    ``ompi_paged_attn`` kernel (ops/paged_attention) reads each row's
    pages up to its ``q_pos`` by block table and attends them with the
    causal bound of ``decode_attention``; an inactive row (q_pos −1)
    reads nothing and yields zeros.  Heads are tp-sharded → fully local:
    at tp > 1 (``mesh``/``axis`` of the engine's comm) every device runs
    the kernel on its own canonical row inside a shard_map, since GSPMD
    cannot partition a Mosaic kernel."""
    lengths = jnp.maximum(q_pos + 1, 0)
    r, b, hl, hd = q.shape
    if mesh is None:
        att = paged_attention(q, kp, vp, bt, lengths)
    else:
        row = P(axis)
        # comm-lint: disable=CL001 block-local paged kernel: no collective inside
        att = jax.shard_map(paged_attention, mesh=mesh,
                            in_specs=(row, row, row, P(), P()),
                            out_specs=row, check_vma=False)(
                                q, kp, vp, bt, lengths)
    return att.reshape(r, b, hl * hd)


@jax.jit
def _j_prefill_attn(q, k, v):
    """Prompt-phase causal attention over the fresh q/k/v (the pages
    were just written; attending the in-register copies avoids the
    gather) — ``attention_reference`` with the tp rows as batch."""
    r, s, hl, hd = q.shape
    att = attention_reference(q, k, v, causal=True)
    return att.reshape(r, s, hl * hd)


@jax.jit
def _j_o_proj(ag_att, wo):
    return jnp.einsum("rbh,rhc->rbc", _regroup(ag_att), wo)


@jax.jit
def _j_mlp_in(ag_o, x, norm_w, wg, wu):
    x = x + _regroup(ag_o)
    h = _rms_norm(x, norm_w)
    g = jax.nn.silu(jnp.einsum("rbd,rdf->rbf", h, wg))
    u = jnp.einsum("rbd,rdf->rbf", h, wu)
    return x, g * u


@jax.jit
def _j_mlp_down(ag_z, wd):
    return jnp.einsum("rbf,rfc->rbc", _regroup(ag_z), wd)


@jax.jit
def _j_residual(ag_d, x):
    return x + _regroup(ag_d)


@jax.jit
def _j_logits_partial(x, norm_w, embed_can):
    """Per-device partial logits: each device multiplies ITS d/tp slice
    of the hidden state against its embedding columns — the partial
    sums then reduce through decode_rs + decode_ag (the audited psum)."""
    h = _rms_norm(x, norm_w)
    r, b, d = h.shape
    hs = h.reshape(r, b, r, d // r)
    idx = jnp.arange(r)
    hloc = hs[idx, :, idx, :]          # row r keeps its own slice
    part = jnp.einsum("rbd,rvd->rbv", hloc, embed_can)
    return part.reshape(r, b * part.shape[-1])


@partial(jax.jit, static_argnames=("b",))
def _j_logits_argmax(ag, b):
    r = ag.shape[0]
    logits = ag.reshape(r, b, -1).astype(jnp.float32)
    return logits, jnp.argmax(logits, axis=-1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("s",))
def _j_last_pos(x, s):
    return x[:, s - 1:s, :]


@jax.jit
def _j_fused_logits_argmax(ag):
    """Fused-path logits: the program returns (tp, B, V/tp) with row r
    = vocab block r; after the eager decode_ag the regroup concats the
    blocks in rank order — full (tp, B, V) logits + greedy argmax."""
    lg = _regroup(ag).astype(jnp.float32)
    return lg, jnp.argmax(lg, axis=-1).astype(jnp.int32)


@jax.jit
def _j_moe_norm(x, w):
    return _rms_norm(x, w)


@jax.jit
def _j_moe_residual(x, add):
    return x + add[None]


# -- decision + audit shims (the moe.models pattern for custom colls) -------

def _decide_serve_coll(dc, coll: str, nbytes: int, dtype,
                       allowed: Tuple[str, ...] = ("native", "quant"),
                       ) -> Tuple[str, str, List[str]]:
    """Decision shim over coll/xla.decide_mode for the decode coll
    names: per-entry/blanket force vars, DEVICE_RULES rows (plane-keyed
    included), the learned source — the full precedence chain.  The
    decode collectives are single-stage (flat tp ring), so the hier
    arms are ineligible by construction.  The fused rings pass
    ``allowed=("native",)`` — the ring schedule has no quantized arm,
    and the decision never names an arm the site cannot execute."""
    from ..coll.xla import _load_device_rules, decide_mode
    from ..op import SUM, quantizable
    from ..parallel.hierarchy import classify_axes
    axes = dc.axis if isinstance(dc.axis, tuple) else (dc.axis,)
    kinds = classify_axes(dc.mesh)
    plane = ("dcn" if any(kinds.get(a) == "dcn" for a in axes)
             else "ici")
    platform = next(iter(dc.mesh.devices.flat)).platform
    return decide_mode(coll, int(nbytes), dc.n, platform,
                       _load_device_rules(), allowed,
                       quant_ok=quantizable(SUM, dtype), dtype=dtype,
                       op=None, plane=plane, hier_ok=False,
                       hier_why="decode collectives are single-stage")


def _audit_serve_coll(dc, coll: str, arm: str, reason: str,
                      chain: List[str], x, dur_s: float,
                      extra: Optional[Dict[str, Any]] = None) -> int:
    """ONE decision-audit record per decode collective — the same
    fan-out as coll/xla._audit: arm + wire pvars, an externally-timed
    perf sample (the ``decode_*`` ledger cells), traffic ring-edge
    attribution of the SAME wire figure (conservation's other half),
    and the trace decision event carrying the precedence chain."""
    from ..coll.quant import wire_bytes
    rows = max(x.shape[0], 1)
    nbytes = x.nbytes // rows
    qcoll = "allgather" if coll == "decode_ag" else "reduce_scatter"
    try:
        wb = wire_bytes(qcoll, max(x.size // rows, 1), dc.n, x.dtype)
    except (ValueError, TypeError):
        wb = None
    ratio = wb["ratio"] if wb is not None else None
    wire = nbytes
    if wb is not None:
        wire = wb["quant_bytes"] if arm == "quant" else wb["native_bytes"]
    spc = dc.spc
    if spc is not None:
        spc.inc(f"coll_arm_{arm}_count")
        spc.inc("coll_wire_bytes", int(wire))
    from ..parallel import simdcn
    if simdcn.us_per_mib() > 0:
        simdcn.charge(int(wire * simdcn.ring_dcn_fraction(dc.mesh,
                                                          dc.axis)))
    from .. import perf, traffic
    if perf.enabled:
        # bank under the LOGICAL payload bytes (what decide_mode sees),
        # not the per-arm wire bytes — otherwise native and quant land
        # in different size buckets and learned lookups never find both
        # arms in one cell
        perf.note_sample(coll, arm, int(nbytes), dur_s, dc.n)
    if traffic.enabled:
        traffic.note_coll(dc, coll, arm, int(wire))
    if trace.enabled:
        bucket = 1 << max(int(nbytes) - 1, 0).bit_length()
        trace.decision(coll, arm=arm, reason=reason, verdict=None,
                       nbytes=int(nbytes),
                       shape_bucket=bucket, shape=tuple(x.shape),
                       dtype=str(x.dtype), ndev=dc.n,
                       wire_bytes=int(wire), quant_ratio=ratio,
                       chain=list(chain), **(extra or {}))
    return int(wire)


class ServingEngine:
    """Prefill + continuous decode over one tp DeviceComm.

    ``params`` arrive in the TRAIN layout by default and are converted
    on device through ``convert_params(to="decode")`` (the reshard
    engine — the serving tier is its first consumer in anger), then
    lifted shard-by-shard into canonical form with zero wire."""

    def __init__(self, dc, params: Dict, cfg, *, n_pages: int = 64,
                 page_size: int = 16, max_seqs: int = 8,
                 max_pages_per_seq: Optional[int] = None,
                 layout: str = "train") -> None:
        from ..models import transformer as tfm
        self.moe = cfg.mlp == "moe"
        dims = [("n_heads", cfg.n_heads), ("d_model", cfg.d_model),
                ("vocab", cfg.vocab)]
        if not self.moe:
            dims.append(("d_ff", cfg.d_ff))
        for name, dim in dims:
            if dim % dc.n:
                raise ValueError(
                    f"ServingEngine: cfg.{name}={dim} not divisible by "
                    f"the {dc.n}-way tp axis")
        if self.moe:
            # moe_block_ep's canonical (R, t, d) layout needs the batch
            # to split evenly across ranks, and rank j owns experts
            # [j·epr, (j+1)·epr)
            if int(max_seqs) % dc.n:
                raise ValueError(
                    f"ServingEngine: moe decode needs max_seqs="
                    f"{max_seqs} divisible by the {dc.n}-way comm axis")
            if cfg.n_experts % dc.n:
                raise ValueError(
                    f"ServingEngine: cfg.n_experts={cfg.n_experts} not "
                    f"divisible by the {dc.n}-way comm axis")
        self.fused = getattr(cfg, "decode_overlap", "eager") == "fused"
        if self.fused:
            if self.moe:
                raise ValueError(
                    "ServingEngine: decode_overlap='fused' is dense-MLP "
                    "only — moe decode stays on the eager path")
            if dc.n < 2:
                raise ValueError(
                    "ServingEngine: decode_overlap='fused' needs tp>=2 "
                    "(the rings are the whole point)")
            if int(max_seqs) % dc.n:
                raise ValueError(
                    f"ServingEngine: decode_overlap='fused' needs "
                    f"max_seqs={max_seqs} divisible by the {dc.n}-way "
                    f"tp axis (batch-sharded residual)")
        if layout == "train":
            params = tfm.convert_params(params, dc.mesh, cfg,
                                        to="decode")
        elif layout != "decode":
            raise ValueError(f"layout={layout!r} (want train|decode)")
        self.dc = dc
        self.cfg = cfg
        self.max_seqs = int(max_seqs)
        cdt = jnp.dtype(cfg.dtype)

        def can(w):
            # weight-stationary: store in the compute dtype (the same
            # cast forward() pays per step) before the zero-wire restack
            return dc.canonicalize(w.astype(cdt), 1)

        def can_qkv(w):
            # the fused (d, 3h) weight is a global [q|k|v] column
            # concat: canonicalizing it whole would hand rank r a
            # contiguous 3h/tp chunk of that concat (all-q on the low
            # ranks), so the per-rank q/k/v split in _j_qkv would slice
            # the wrong columns.  Canonicalize each projection on its
            # own and re-concat per rank: row r = [q_r | k_r | v_r],
            # i.e. global head block r of each.
            h3 = w.shape[1] // 3
            return jnp.concatenate(
                [can(w[:, i * h3:(i + 1) * h3]) for i in range(3)],
                axis=-1)

        self._embed = can(params["embed"])             # (tp, V, d/tp)
        self._final_norm = params["final_norm"]
        self._layers: List[Dict[str, Any]] = []
        for lw in params["layers"]:
            cl: Dict[str, Any] = {"attn_norm": lw["attn_norm"],
                                  "wqkv": can_qkv(lw["wqkv"]),
                                  "wo": can(lw["wo"]),
                                  "mlp_norm": lw["mlp_norm"]}
            if self.moe:
                # moe_block_ep consumes the (E, d, f) expert stacks
                # directly (it reshapes to (R, epr, …) itself) — no
                # canonical lift, same leaves the ragged train arm uses
                cl["moe"] = lw["moe"]
            else:
                cl["w_gate"] = can(lw["w_gate"])
                cl["w_up"] = can(lw["w_up"])
                cl["w_down"] = can(lw["w_down"])
            self._layers.append(cl)
        self.cache = PagedKVCache(
            dc, cfg.n_layers, cfg.n_heads, cfg.head_dim,
            n_pages=n_pages, page_size=page_size, max_seqs=max_seqs,
            max_pages_per_seq=max_pages_per_seq,
            dtype=jnp.dtype(cfg.dtype))
        self.dispatches: Dict[str, int] = {"decode_ag": 0,
                                           "decode_rs": 0,
                                           "decode_collmm": 0}
        self.wire_bytes = 0
        # key pages the decode attention read, and the pages of the block
        # tables it was handed, over every decode step and window
        self.attn_pages: Dict[str, int] = {"read": 0, "table": 0}
        self._attn_comm = ({"mesh": dc.mesh, "axis": dc.axis}
                           if dc.n > 1 else {})
        if self.fused:
            self._init_fused(params, cdt, can)

    def _init_fused(self, params: Dict, cdt, can) -> None:
        """Build the fused decode program + its weight views.  The AG
        rings reuse the canonical COLUMN shards already lifted above
        (gate|up concat into one ``wgu`` so the pair shares a ring); the
        RS rings contract over local ROWS, so wo/w_down/embed are
        re-laid out row-parallel — a one-time audited ``reshard`` at
        init, zero steady-state cost."""
        from jax.sharding import PartitionSpec as P
        from .fused import build_fused_decode, ring_schedule
        dc, cfg = self.dc, self.cfg

        def row_can(w):
            return dc.canonicalize(
                dc.reshard(w.astype(cdt), P(dc.axis, None)), 0)

        self._fused_layers: List[Dict[str, Any]] = []
        for lw, cl in zip(params["layers"], self._layers):
            self._fused_layers.append({
                "attn_norm": jnp.asarray(cl["attn_norm"]),
                "mlp_norm": jnp.asarray(cl["mlp_norm"]),
                "wqkv": cl["wqkv"],
                "wgu": jnp.concatenate([cl["w_gate"], cl["w_up"]],
                                       axis=-1),
                "wo": row_can(lw["wo"]),        # (tp, h/tp, d)
                "wd": row_can(lw["w_down"])})   # (tp, f/tp, d)
        # logits ring: vocab-block columns of the tied embedding —
        # row-parallel over V, transposed to (tp, d, V/tp)
        self._embed_lg = row_can(params["embed"]).swapaxes(1, 2)
        self._fused = build_fused_decode(
            dc.mesh, dc.axis, cfg.n_layers, cfg.head_dim,
            float(cfg.rope_base))
        # per-row-count ring schedules: the continuous batch and each
        # speculative window length get their own (the payloads scale
        # with the row count, the site list does not)
        self._ring_rows: Dict[int, List[Tuple[str, int, int]]] = {
            self.max_seqs: ring_schedule(cfg.n_layers, self.max_seqs,
                                         cfg.d_model, dc.n,
                                         cdt.itemsize)}

    # -- audited collective dispatch ---------------------------------------

    @trace.timed("ompi.engine.decode_ag")
    def _ag(self, x):
        t0 = time.perf_counter()
        arm, reason, chain = _decide_serve_coll(
            self.dc, "decode_ag", x.nbytes // x.shape[0], x.dtype)
        out = (self.dc.quant.allgather(x) if arm == "quant"
               else self.dc.allgather(x))
        dur = time.perf_counter() - t0
        self.wire_bytes += _audit_serve_coll(
            self.dc, "decode_ag", arm, reason, chain, x, dur)
        self.dispatches["decode_ag"] += 1
        from . import enabled as serve_enabled, note_dispatch
        if serve_enabled:
            note_dispatch("eager")
        return out

    @trace.timed("ompi.engine.decode_rs")
    def _rs(self, x):
        t0 = time.perf_counter()
        arm, reason, chain = _decide_serve_coll(
            self.dc, "decode_rs", x.nbytes // x.shape[0], x.dtype)
        out = (self.dc.quant.reduce_scatter(x) if arm == "quant"
               else self.dc.reduce_scatter(x))
        dur = time.perf_counter() - t0
        self.wire_bytes += _audit_serve_coll(
            self.dc, "decode_rs", arm, reason, chain, x, dur)
        self.dispatches["decode_rs"] += 1
        from . import enabled as serve_enabled, note_dispatch
        if serve_enabled:
            note_dispatch("eager")
        return out

    # -- forward pieces ----------------------------------------------------

    def _backbone(self, x, pos_dev, page_idx, offset,
                  attend: Callable) -> Any:
        cfg = self.cfg
        for i, lw in enumerate(self._layers):
            with trace.region("ompi.engine.layer"):
                q, k, v = _j_qkv(x, lw["attn_norm"], lw["wqkv"], pos_dev,
                                 head_dim=cfg.head_dim,
                                 base=float(cfg.rope_base))
                self.cache.k[i], self.cache.v[i] = _j_page_write(
                    self.cache.k[i], self.cache.v[i], k, v, page_idx,
                    offset)
                att = attend(i, q, k, v)
                o = _j_o_proj(self._ag(att), lw["wo"])
                if self.moe:
                    x = _j_residual(self._ag(o), x)
                    x = self._moe_mlp(x, lw)
                else:
                    x, z = _j_mlp_in(self._ag(o), x, lw["mlp_norm"],
                                     lw["w_gate"], lw["w_up"])
                    d = _j_mlp_down(self._ag(z), lw["w_down"])
                    x = _j_residual(self._ag(d), x)
        return x

    def _moe_mlp(self, x, lw):
        """Ragged-MoE MLP for one layer (PR 14's loose end closed):
        hand the normed residual to ``moe_block_ep`` in its canonical
        (R, t, d) row layout — ONLY the routed token payloads travel,
        under the audited ``moe_dispatch``/``moe_combine`` names — and
        add the expert mixture back.  The residual x is (tp, B, d) with
        replicated content, so row 0 is the full batch; B % R == 0 is
        checked at init."""
        from ..models.moe import moe_block_ep
        dc, cfg = self.dc, self.cfg
        h = _j_moe_norm(x, lw["mlp_norm"])
        b, d = h.shape[1], h.shape[2]
        hc = jax.device_put(jnp.reshape(h[0], (dc.n, b // dc.n, d)),
                            dc.sharding())
        out, _aux, _info = moe_block_ep(
            dc, hc, lw["moe"], cfg.n_experts, cfg.moe_top_k,
            cfg.moe_capacity_factor)
        add = jnp.asarray(np.asarray(out)).reshape(b, d)
        return _j_moe_residual(x, add.astype(x.dtype))

    # -- fused decode (decode_overlap="fused") -----------------------------

    def _audit_collmm(self, site: str, payload: int, wire: int,
                      arm: str, reason: str, chain: List[str],
                      rows: int) -> None:
        """One decision-audit record per fused ring — the decode_collmm
        counterpart of ``_audit_serve_coll``.  The ring is an n−1-hop
        ppermute rotation, so the wire figure is exact (no per-arm
        model): it is charged to the ring edges via ``note_ring``
        (``decode_collmm`` is not in traffic's coll→pattern table, and
        ``note_coll`` would file it unattributed) and mirrored into
        ``coll_wire_bytes`` so conservation's two halves still meet.
        No perf sample: the rings run inside one program, so no ring's
        own duration is measured."""
        from .. import traffic
        dc = self.dc
        spc = dc.spc
        if spc is not None:
            spc.inc(f"coll_arm_{arm}_count")
            spc.inc("coll_wire_bytes", int(wire))
        from ..parallel import simdcn
        if simdcn.us_per_mib() > 0:
            simdcn.charge(int(wire * simdcn.ring_dcn_fraction(dc.mesh,
                                                              dc.axis)))
        if traffic.enabled:
            traffic.note_ring(dc.mesh, dc.axis, int(wire),
                              "decode_collmm", "fwd")
        if trace.enabled:
            bucket = 1 << max(int(payload) - 1, 0).bit_length()
            trace.decision("decode_collmm", arm=arm, reason=reason,
                           verdict=None,
                           nbytes=int(payload), shape_bucket=bucket,
                           shape=(rows // dc.n, self.cfg.d_model),
                           dtype=str(self.cfg.dtype), ndev=dc.n,
                           wire_bytes=int(wire), quant_ratio=None,
                           chain=list(chain), site=site)
        self.dispatches["decode_collmm"] += 1
        self.wire_bytes += int(wire)
        from . import enabled as serve_enabled, note_dispatch
        if serve_enabled:
            note_dispatch("fused")

    def _decode_step_fused(self, tokens, positions, page_idx, offset,
                           bt):
        """The fused decode body: ONE jitted program carries the whole
        backbone + logits with every tp combine an n−1-hop collective-
        matmul ring (serving/fused), leaving exactly two eager
        dispatches — the embed ``decode_ag`` and the logits
        ``decode_ag``.  Every ring is still decided (full precedence
        chain, native-only arm set) and audited as ``decode_collmm``
        BEFORE the program runs: one decide event per dispatched decode
        collective, same as the eager path.  ``tokens``/``positions``/
        ``page_idx``/``offset``/``bt`` are flat over any row count
        divisible by tp — the continuous batch (decode_step) and the
        speculative verify window (decode_window) share this body, each
        shape with its own ring schedule and compiled program."""
        from .fused import ring_schedule
        rows = int(tokens.shape[0])
        cdt = jnp.dtype(self.cfg.dtype)
        ring_rows = self._ring_rows.get(rows)
        if ring_rows is None:
            ring_rows = ring_schedule(self.cfg.n_layers, rows,
                                      self.cfg.d_model, self.dc.n,
                                      cdt.itemsize)
            self._ring_rows[rows] = ring_rows
        decided = [(site, payload, wire)
                   + _decide_serve_coll(self.dc, "decode_collmm",
                                        payload, cdt,
                                        allowed=("native",))
                   for site, payload, wire in ring_rows]
        x = _j_regroup(self._ag(_j_embed(
            self._embed,
            jnp.asarray(np.where(positions >= 0, tokens,
                                 0).astype(np.int32)))))
        lg_can, new_k, new_v = self._fused(
            x, jnp.asarray(bt),
            jnp.asarray(positions.astype(np.int32)),
            jnp.asarray(page_idx), jnp.asarray(offset),
            tuple(self._fused_layers), jnp.asarray(self._final_norm),
            self._embed_lg, tuple(self.cache.k), tuple(self.cache.v))
        self.cache.k[:] = list(new_k)
        self.cache.v[:] = list(new_v)
        for site, payload, wire, arm, reason, chain in decided:
            self._audit_collmm(site, payload, wire, arm, reason, chain,
                               rows)
        logits, nxt = _j_fused_logits_argmax(self._ag(lg_can))
        return logits, nxt

    def _logits(self, x, b: int):
        part = _j_logits_partial(x, self._final_norm, self._embed)
        red = self._ag(self._rs(part))
        return _j_logits_argmax(red, b=b)

    def _count_pages(self, positions: np.ndarray) -> Tuple[int, int]:
        """(pages the decode attention reads, pages in its block tables)
        for one step over the flat ``positions`` (−1 = inactive), added
        to ``attn_pages``.  The kernel reads ⌈(q_pos+1)/page⌉ pages of a
        row; the fused program gathers its whole table."""
        table = int(positions.size) * self.cache.max_pages_per_seq
        live = np.maximum(positions.astype(np.int64) + 1, 0)
        read = table if self.fused else int(
            (-(-live // self.cache.page_size)).sum())
        self.attn_pages["read"] += read
        self.attn_pages["table"] += table
        return read, table

    @staticmethod
    def _bucket(n: int) -> int:
        p = 8
        while p < n:
            p *= 2
        return p

    # -- serving entry points ----------------------------------------------

    def prefill(self, slot: int, prompt: np.ndarray,
                rid: Any = None):
        """Run one request's prompt through the decode-layout model:
        writes its KV pages, returns (first greedy token, last-position
        logits (tp, 1, V)).  Prompts pad to a small power-of-2 bucket
        so compilations stay bounded; padded positions write to the
        scratch page and never enter the causal window.  ``rid`` tags
        the emitted span with the owning request (CL008)."""
        prompt = np.asarray(prompt, np.int32)
        s = int(prompt.shape[0])
        spad = self._bucket(s)
        tok = np.zeros(spad, np.int32)
        tok[:s] = prompt
        positions = np.arange(spad, dtype=np.int64)
        live_pos = np.where(positions < s, positions, -1)
        page_idx, offset = self.cache.write_indices(
            np.full(spad, slot), live_pos)
        with trace.region("ompi.engine.prefill", "serve:prefill", "serve",
                          args={"slot": slot, "prompt_len": s, "rid": rid}
                          if trace.enabled else None):
            with trace.region("ompi.engine.prefill.dispatch"):
                x = _j_regroup(self._ag(_j_embed(self._embed,
                                                 jnp.asarray(tok))))
                x = self._backbone(
                    x, jnp.asarray(positions.astype(np.int32)),
                    jnp.asarray(page_idx), jnp.asarray(offset),
                    lambda i, q, k, v: _j_prefill_attn(q, k, v))
                logits, nxt = self._logits(_j_last_pos(x, s=s), b=1)
            with trace.region("ompi.engine.prefill.wait"):
                jax.block_until_ready(nxt)
                first = int(np.asarray(jax.device_get(nxt))[0, 0])
        self.cache.seq_lens[slot] = s
        return first, logits

    def decode_step(self, tokens: np.ndarray, positions: np.ndarray):
        """One continuous-batching decode step over the FULL device
        batch: ``tokens``/``positions`` are (max_seqs,) with
        position −1 marking an inactive slot (its lane writes to the
        scratch page, its attention reads no page, and the rest of it is
        garbage the caller drops — the batch shape never changes, so
        one executable serves every occupancy).  Returns (next greedy
        token per slot (max_seqs,), logits (tp, max_seqs, V))."""
        b = self.max_seqs
        tokens = np.asarray(tokens, np.int32)
        positions = np.asarray(positions, np.int64)
        page_idx, offset = self.cache.write_indices(np.arange(b),
                                                    positions)
        pages_read, pages_table = self._count_pages(positions)
        with trace.region("ompi.engine.decode", "serve:decode_step", "serve",
                          args={"active": int((positions >= 0).sum()),
                                "slots": b,
                                "path": "fused" if self.fused else "eager",
                                "pages_read": pages_read,
                                "pages_table": pages_table}
                          if trace.enabled else None):
            with trace.region("ompi.engine.decode.dispatch"):
                if self.fused:
                    logits, nxt = self._decode_step_fused(
                        tokens, positions, page_idx, offset,
                        self.cache.block_tables)
                else:
                    bt = jnp.asarray(self.cache.block_tables)
                    pos_dev = jnp.asarray(positions.astype(np.int32))
                    x = _j_regroup(self._ag(_j_embed(
                        self._embed,
                        jnp.asarray(np.where(positions >= 0, tokens,
                                             0).astype(np.int32)))))
                    x = self._backbone(
                        x, pos_dev, jnp.asarray(page_idx),
                        jnp.asarray(offset),
                        lambda i, q, k, v: _j_paged_attn(
                            q, self.cache.k[i], self.cache.v[i], bt,
                            pos_dev, **self._attn_comm))
                    logits, nxt = self._logits(x, b=b)
            with trace.region("ompi.engine.decode.wait"):
                jax.block_until_ready(nxt)
                out = np.asarray(jax.device_get(nxt))[0]
        return out, logits

    def decode_window(self, tokens: np.ndarray,
                      positions: np.ndarray):
        """Teacher-forced k-token verify window for speculative
        decoding: ``tokens``/``positions`` are (max_seqs, k) — slot
        s's row is its last accepted token followed by k−1 draft
        tokens, at consecutive positions (−1 = inactive, whole row).
        All k KV rows are written to the slot's pages FIRST, then the
        flattened (max_seqs·k) batch attends with the causal position
        mask — within-window causality falls out of the paged attention
        reading key positions ≤ q_pos only.  Returns (greedy next token per
        window position (max_seqs, k), logits (tp, max_seqs·k, V)).

        Rejection is the caller's job: truncate ``cache.seq_lens`` back
        to the accepted prefix — the stale KV rows beyond it are masked
        by every later query and get overwritten when the position is
        refilled.  The window rides whichever dispatch path the engine
        is configured for — eager (11 audited decode_ag/decode_rs) or
        fused (the same one-program collective-matmul rings at the
        window's row count) — and in both, window cost ≈ one step's
        dispatch cost, which is exactly why speculation wins on a
        dispatch-bound fabric."""
        b = self.max_seqs
        tokens = np.asarray(tokens, np.int32)
        positions = np.asarray(positions, np.int64)
        k = int(tokens.shape[1])
        slots = np.broadcast_to(np.arange(b)[:, None],
                                (b, k))
        page_idx, offset = self.cache.write_indices(slots, positions)
        bt = np.repeat(self.cache.block_tables, k, axis=0)
        flat_tok = np.where(positions >= 0, tokens, 0).reshape(-1)
        flat_pos = positions.reshape(-1)
        pages_read, pages_table = self._count_pages(flat_pos)
        with trace.region("ompi.engine.decode_window", "serve:decode_window",
                          "serve",
                          args={"active": int((positions[:, 0] >= 0).sum()),
                                "slots": b, "k": k,
                                "pages_read": pages_read,
                                "pages_table": pages_table}
                          if trace.enabled else None):
            if self.fused:
                logits, nxt = self._decode_step_fused(
                    flat_tok, flat_pos, page_idx.reshape(-1),
                    offset.reshape(-1), bt)
            else:
                pos_dev = jnp.asarray(flat_pos.astype(np.int32))
                btj = jnp.asarray(bt)
                x = _j_regroup(self._ag(_j_embed(
                    self._embed,
                    jnp.asarray(flat_tok.astype(np.int32)))))
                x = self._backbone(
                    x, pos_dev, jnp.asarray(page_idx.reshape(-1)),
                    jnp.asarray(offset.reshape(-1)),
                    lambda i, q, kk, vv: _j_paged_attn(
                        q, self.cache.k[i], self.cache.v[i], btj,
                        pos_dev, **self._attn_comm))
                logits, nxt = self._logits(x, b=b * k)
            jax.block_until_ready(nxt)
        return (np.asarray(jax.device_get(nxt))[0].reshape(b, k),
                logits)

    # -- static verification (the commgraph proof) -------------------------

    def verify_decode_program(self):
        """Prove the fused decode program's static wire model against
        the runtime audit byte-for-byte: extract the jaxpr's ppermute
        trips (analysis/commgraph — scan trips multiplied through, the
        ring_attention precedent), run ONE real decode step, and
        compare static vs runtime per-coll wire deltas.  Returns the
        commgraph ``VerifyReport``; ``report.ok`` is the acceptance
        gate."""
        if not self.fused:
            raise ValueError("verify_decode_program needs "
                             "decode_overlap='fused'")
        from ..analysis import commgraph
        b = self.max_seqs
        zeros = np.zeros(b, np.int32)
        live = np.arange(b, dtype=np.int64) % 2  # mixed live/inactive
        positions = np.where(live > 0, 0, -1).astype(np.int64)
        page_idx, offset = self.cache.write_indices(np.arange(b),
                                                    positions)
        args = (jnp.zeros((self.dc.n, b, self.cfg.d_model),
                          jnp.dtype(self.cfg.dtype)),
                jnp.asarray(self.cache.block_tables),
                jnp.asarray(positions.astype(np.int32)),
                jnp.asarray(page_idx), jnp.asarray(offset),
                tuple(self._fused_layers),
                jnp.asarray(self._final_norm), self._embed_lg,
                tuple(self.cache.k), tuple(self.cache.v))

        def runner():
            self.decode_step(zeros, positions)

        return commgraph.verify(
            self._fused, args, self.dc.mesh,
            coll_map={"decode_collmm": "ppermute"}, runner=runner,
            source="serving.fused:decode")
