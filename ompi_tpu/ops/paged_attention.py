"""Paged decode attention as a Pallas TPU kernel.

One query token per row attends the keys and values that its block table
points at in the serving tier's page pools (``serving/cache.py``), read in
place: each row's live pages go from the pool in HBM to VMEM by manual
async copies, and no dense ``(rows, pages·page, heads, head_dim)`` view of
the cache is ever built.  A row of length ``n`` reads ``ceil(n / page)``
pages, however long its block table is; a row of length 0 (an inactive
batch slot, ``q_pos = -1``) reads nothing and returns zeros.

Design:
  * grid = (tp row, batch row), both sequential: the copies of the next
    page group, in this row or the next one, are started before the
    current group is waited on, so two buffers (``pages_per_group`` pages
    of K and of V each) alternate and the DMA engine never idles between
    rows.  A group is a handful of pages, not one grid step per page: the
    per-step overhead would outweigh a 64 KiB page.
  * The block table and the lengths are scalar-prefetch operands in SMEM;
    lengths are data, never shapes, so one executable serves every
    occupancy.
  * The pools keep their ``(tp, n_pages, page, heads, head_dim)`` layout:
    one page of one layer is a contiguous block of all local heads.  A
    head's ``(positions, head_dim)`` rows are a strided load from the
    buffer (two heads per 32-bit load for 16-bit dtypes, as
    ``jax.experimental.pallas.ops.tpu.ragged_paged_attention`` reads its
    combined-KV pages).
  * Maths: the QK and PV dots run in the storage dtype with float32
    accumulation; scores are scaled by 1/sqrt(head_dim), positions past
    the row's length are masked (in the scores, and zeroed in V so a dead
    value never reaches the PV contraction), and an online softmax keeps
    float32 running max, sum and accumulator across page groups.

Compiled on TPU, interpreted on the CPU (``attention._default_interpret``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, _default_interpret, check_tpu_block

# pages per DMA group: 8 pages of 16 positions put 128 key positions in the
# lane dimension of a group's scores
PAGES_PER_GROUP = 8


def _head_rows(ref, heads: int, rows: int, mask=None):
    """The ``heads`` per-head ``(rows, head_dim)`` slabs of a
    ``(rows·heads, head_dim)`` buffer whose row ``t·heads + h`` holds
    position t of head h, in the storage dtype.  ``mask`` (rows, 1) zeroes
    the positions where it is false, one select per 32-bit word."""
    dtype = ref.dtype

    def masked(x):
        return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))

    if jnp.dtype(dtype).itemsize == 4 or heads == 1:
        return [masked(ref[pl.ds(h, rows, stride=heads), :])
                for h in range(heads)]
    if jnp.dtype(dtype).itemsize != 2 or heads % 2:
        raise ValueError(
            f"paged_attention: {heads} local heads of {jnp.dtype(dtype).name}"
            f" cannot be split by 32-bit strided loads (need 32-bit storage,"
            f" or an even head count of a 16-bit dtype)")
    words = ref.bitcast(jnp.uint32)       # each word: heads 2i and 2i+1
    out = []
    for i in range(heads // 2):
        w = masked(words[pl.ds(i, rows, stride=heads // 2), :])
        out.append(pltpu.bitcast(w << 16, jnp.float32).astype(dtype))
        out.append(pltpu.bitcast(w & jnp.uint32(0xFFFF0000),
                                 jnp.float32).astype(dtype))
    return out


def _paged_kernel(lens_ref, bt_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sems, base_ref, *, n_rows: int,
                  n_slots: int, pmax: int, page: int, group: int,
                  scale: float):
    r, b = pl.program_id(0), pl.program_id(1)
    step = r * n_slots + b
    heads, hd = q_ref.shape
    span = group * page                   # key positions per group

    def n_pages(slot):
        return jnp.minimum(pl.cdiv(lens_ref[slot], page), pmax)

    def copies(row, slot, g, buf):
        """(live, k copy, v copy) per page of group ``g`` of ``slot``."""
        live_pages = n_pages(slot)
        out = []
        for p in range(group):
            idx = g * group + p
            pid = bt_ref[slot * pmax + jnp.minimum(idx, pmax - 1)]
            out.append((idx < live_pages,
                        pltpu.make_async_copy(k_hbm.at[row, pid],
                                              k_buf.at[buf, p],
                                              sems.at[buf]),
                        pltpu.make_async_copy(v_hbm.at[row, pid],
                                              v_buf.at[buf, p],
                                              sems.at[buf])))
        return out

    def start(row, slot, g, buf):
        for live, ck, cv in copies(row, slot, g, buf):
            @pl.when(live)
            def _():
                ck.start()
                cv.start()

    def wait(row, slot, g, buf):
        for live, ck, cv in copies(row, slot, g, buf):
            @pl.when(live)
            def _():
                ck.wait()
                cv.wait()

    nb = pl.cdiv(n_pages(b), group)
    nxt = step + 1
    has_next = nxt < n_rows * n_slots
    nrow, nslot = nxt // n_slots, nxt % n_slots

    # buffer of group g of this row is (base + g) % 2, base counting every
    # group of the rows before; the first row starts its own first group,
    # every later row finds it started by the row before
    @pl.when(step == 0)
    def _():
        base_ref[0] = 0
        start(r, b, 0, 0)

    base = base_ref[0]
    q = q_ref[...]
    hrow = lax.broadcasted_iota(jnp.int32, (heads, span), 0)
    hrow_o = lax.broadcasted_iota(jnp.int32, (heads, hd), 0)
    col = lax.broadcasted_iota(jnp.int32, (1, span), 1)
    pos = lax.broadcasted_iota(jnp.int32, (span, 1), 0)
    length = lens_ref[b]

    def body(g, carry):
        m, l, acc = carry
        buf = (base + g) % 2

        @pl.when(g + 1 < nb)
        def _():
            start(r, b, g + 1, 1 - buf)

        @pl.when(jnp.logical_and(g + 1 == nb, has_next))
        def _():
            start(nrow, nslot, 0, 1 - buf)

        wait(r, b, g, buf)
        first = g * span                  # key position of the group's start
        ks = _head_rows(k_buf.at[buf].reshape(span * heads, hd), heads,
                        span)
        s = jnp.zeros((heads, span), jnp.float32)
        for h, kh in enumerate(ks):
            sh = lax.dot_general(q, kh, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            s = jnp.where(hrow == h, sh, s)
        s = jnp.where(first + col < length, s * scale, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        pv = p.astype(q.dtype)
        vs = _head_rows(v_buf.at[buf].reshape(span * heads, hd), heads,
                        span, first + pos < length)
        o = jnp.zeros((heads, hd), jnp.float32)
        for h, vh in enumerate(vs):
            oh = lax.dot_general(pv, vh, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            o = jnp.where(hrow_o == h, oh, o)
        return m_new, l, alpha * acc + o

    m0 = jnp.full((heads, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((heads, 1), jnp.float32)
    acc0 = jnp.zeros((heads, hd), jnp.float32)
    _, l, acc = lax.fori_loop(0, nb, body, (m0, l0, acc0))

    # a row with nothing to read still hands the next row its first group
    @pl.when(jnp.logical_and(nb == 0, has_next))
    def _():
        start(nrow, nslot, 0, base % 2)

    base_ref[0] = base + nb
    o_ref[...] = jnp.where(l > 0, acc / jnp.where(l > 0, l, 1.0),
                           0.0).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array, *,
                    pages_per_group: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Decode attention of one query per row against paged K/V pools.

    q (R, B, heads, head_dim) in the canonical tp-row layout; k_pages and
    v_pages (R, n_pages, page, heads, head_dim) of q's dtype; block_tables
    (B, pmax) page ids per row; lengths (B,) the key positions each row
    attends (``q_pos + 1``; 0 reads nothing and yields zeros).  Returns
    (R, B, heads, head_dim) in q's dtype."""
    if interpret is None:
        interpret = _default_interpret()
    return _paged_attention(q, k_pages, v_pages, block_tables, lengths,
                            pages_per_group=pages_per_group,
                            interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("pages_per_group",
                                             "interpret"))
def _paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                     pages_per_group, interpret):
    rows, slots, heads, hd = q.shape
    page = k_pages.shape[2]
    pmax = block_tables.shape[1]
    group = min(pages_per_group or PAGES_PER_GROUP, pmax)
    if k_pages.dtype != q.dtype or v_pages.dtype != q.dtype:
        raise ValueError(
            f"paged_attention: pools of {k_pages.dtype}/{v_pages.dtype} "
            f"against q of {q.dtype} (a cast would copy the whole pool)")
    check_tpu_block((1, 1, heads, hd), q.shape, "paged q/o", q.dtype)
    check_tpu_block((1, 1, page, heads, hd), k_pages.shape, "paged page",
                    k_pages.dtype)
    kernel = functools.partial(
        _paged_kernel, n_rows=rows, n_slots=slots, pmax=pmax, page=page,
        group=group, scale=float(1.0 / np.sqrt(hd)))
    qo_spec = pl.BlockSpec((None, None, heads, hd),
                           lambda r, b, *_: (r, b, 0, 0))
    buf = pltpu.VMEM((2, group, page, heads, hd), k_pages.dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows, slots),
            in_specs=[qo_spec, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=qo_spec,
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="ompi_paged_attn",
    )(jnp.maximum(lengths, 0).astype(jnp.int32),
      block_tables.astype(jnp.int32).reshape(-1), q, k_pages, v_pages)
