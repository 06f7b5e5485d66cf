"""Block flash attention as a Pallas TPU kernel.

Design (pallas_guide.md patterns): grid = (batch*heads, q_blocks, kv_blocks)
with the kv dimension innermost — on TPU the innermost grid dimension is
sequential per core, so the online-softmax state (row max ``m``, denominator
``l``, un-normalized accumulator ``acc``) lives in VMEM scratch and is
carried across kv steps; the final kv step normalizes and writes the output
block. The QK and PV dots run in the storage dtype with float32
accumulation (``preferred_element_type``): bfloat16 inputs stay bfloat16 in
HBM/VMEM and on the MXU operand ports, probabilities are downcast to the
storage dtype for the PV dot, and only the online-softmax state (m, l, acc)
is float32.

Three entry points:
  * ``flash_attention`` — self-contained attention (optionally causal);
  * ``flash_attention_partials`` — returns the *un-normalized* (o, m, l)
    triple for a Q-shard against one visiting K/V shard, with global
    position offsets for the causal mask.  This is the per-step block
    compute of ring attention (parallel/ring.py), which merges partials
    across ring hops — the kernel analog of the reference's segmented ring
    schedule (coll_base_allreduce.c:621).
  * ``flash_mha`` — differentiable (custom-VJP) flash attention for
    training: the forward saves only (o, logsumexp) and the backward
    recomputes probabilities blockwise in two Pallas kernels (dq; dk/dv),
    the FlashAttention-2 scheme — O(seq) residual memory instead of the
    O(seq²) score tensor, which is what lets the flagship train step keep
    long sequences on the MXU at high utilization.

Interpret mode (``interpret=True``) runs the same kernels on CPU for tests.
Left unset, it is the compiled path on TPU and the interpreter on CPU; any
other backend raises rather than guessing.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _default_interpret() -> bool:
    """Compiled on TPU, interpreted on the CPU (the test path); any
    other backend cannot run these Mosaic kernels at all."""
    backend = jax.default_backend()
    if backend in ("tpu", "cpu"):
        return backend == "cpu"
    raise RuntimeError(
        f"Pallas flash kernels need a TPU or the CPU interpreter; backend "
        f"{backend!r} is neither (pass interpret=True to force the "
        f"interpreter)")


def check_tpu_block(block, array_shape, what: str = "block",
                    dtype=jnp.float32) -> None:
    """Enforce the Mosaic TPU tiling rule at trace time, on EVERY backend.

    Real-TPU Pallas requires the last two block dims be divisible by the
    dtype's (sublane, lane) tile — (8, 128) for 4-byte types, sublanes
    doubling as the itemsize halves (16 for bf16, 32 for int8/fp8) — or
    equal to the corresponding array dim. Interpret mode (the CPU test
    path) never checks this, which is how an unlowerable (1, bq) block on
    a (bh, s_q) output survived 500+ green CPU tests and then failed the
    first real-chip flagship compile (commit d5b947d). Calling this in
    the kernel wrappers makes that failure class a CPU-testable
    invariant."""
    if len(block) < 2:
        return                       # 1-D blocks: lane tiling only, exempt
    if len(block) != len(array_shape):
        raise ValueError(
            f"{what}: block {tuple(block)} and array {tuple(array_shape)} "
            f"have different ranks — mis-paired shapes, nothing checked")
    sublane = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    for off, req in ((-2, sublane), (-1, 128)):
        b, a = block[off], array_shape[off]
        if b != a and b % req:
            raise ValueError(
                f"{what}: block {tuple(block)} on array "
                f"{tuple(array_shape)} ({jnp.dtype(dtype).name}) is not "
                f"TPU-lowerable — dim {off} block size {b} is neither a "
                f"multiple of {req} nor equal to the array dim {a}")


def _auto_block(s: int) -> int:
    """Largest power-of-two block ≤1024 dividing the sequence: the v5e
    block sweep (BASELINE.md) shows 1024² blocks run 2.4× faster than 256²
    (fewer grid steps amortize the VMEM scratch round-trips; ~2 MB VMEM at
    d=64 stays well under budget)."""
    for b in (1024, 512, 256, 128, 64, 32):
        if s % b == 0:
            return b
    if s <= 1024:
        return s       # odd short sequence: one full-seq block fits VMEM
    # long and no usable divisor: never auto-pick a full-seq block (a
    # seq² fp32 score tile would blow VMEM) — the caller must choose
    raise ValueError(
        f"no power-of-two block ≤1024 divides sequence length {s}; pass "
        f"block_q/block_k explicitly")


def _auto_block_bwd(s: int) -> int:
    """Block auto-pick for the BACKWARD kernels (dq; dk/dv). Tracked
    separately from the forward pick so an on-chip bwd block sweep (the
    A/B harness's 'flash bwd block' rows) can retune it without touching
    the fwd choice; until chip evidence says otherwise it mirrors the
    forward heuristic (the bwd kernels carry two extra VMEM accumulators,
    so if anything the sweep is expected to prefer the SAME or one notch
    smaller block)."""
    return _auto_block(s)


def _block_sizes(s_q: int, s_k: int, block_q: Optional[int],
                 block_k: Optional[int],
                 auto=None, what: str = "blocks") -> Tuple[int, int]:
    """Resolve (block_q, block_k): explicit override, else ``auto``
    (default ``_auto_block``), clamped to the sequence and checked for
    divisibility — the ONE block-resolution invariant, shared by the fwd
    and bwd paths."""
    auto = auto or _auto_block
    bq = min(block_q or auto(s_q), s_q)
    bk = min(block_k or auto(s_k), s_k)
    if s_q % bq or s_k % bk:
        raise ValueError(f"seq lengths ({s_q},{s_k}) must divide into "
                         f"{what} ({bq},{bk})")
    return bq, bk


def _check_flash_blocks(bh: int, s_q: int, s_k: int, d: int,
                        bq: int, bk: int, with_partials: bool,
                        what: str, dtype=jnp.float32) -> None:
    """The three distinct (block, array) pairs every flash pallas_call in
    this module uses; see check_tpu_block. ``dtype`` is the q/k/v storage
    dtype (the sublane tile is dtype-dependent); m/l/lse/delta are always
    f32."""
    check_tpu_block((1, bq, d), (bh, s_q, d), f"{what} q/o", dtype)
    check_tpu_block((1, bk, d), (bh, s_k, d), f"{what} k/v", dtype)
    if with_partials:
        check_tpu_block((1, bq, 1), (bh, s_q, 1), f"{what} m/l/lse/delta",
                        jnp.float32)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  kv_steps: int, q_off: int, kv_off: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, m_ref.dtype)
        l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    qi = pl.program_id(1)
    # causal: a kv block fully above the diagonal contributes nothing —
    # skip its MXU work entirely (the ~2× flop saving causal promises;
    # the block DMA still happens, which is why the saving shows as ~1.7×)
    visible = True
    if causal:
        last_row = q_off + (qi + 1) * block_q - 1
        first_col = kv_off + ki * block_k
        visible = last_row >= first_col

    @pl.when(visible)
    def _compute():
        # operands stay in their storage dtype: on the MXU a bf16xbf16
        # dot with float32 accumulation (preferred_element_type) runs at
        # full rate, while upcasting inputs to f32 first quarters it (and
        # doubles VMEM); f32 inputs keep exact f32 math as before
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = (q_off + qi * block_q
                    + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
            cols = (kv_off + ki * block_k
                    + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
            s = jnp.where(rows >= cols, s, NEG_INF)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_cur = l_prev * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur[:, None], l_ref.shape)

    @pl.when(ki == kv_steps - 1)
    def _finish():
        denom = jnp.maximum(l_ref[:, 0], 1e-20)
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Attention over (batch, seq, heads, head_dim) inputs.

    q may have a different sequence length than k/v (cross attention);
    ``causal`` assumes both sequences start at position 0.
    """
    if interpret is None:
        interpret = _default_interpret()
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    bq, bk = _block_sizes(s_q, s_k, block_q, block_k)
    _check_flash_blocks(b * h, s_q, s_k, d, bq, bk, False,
                        "flash_attention", q.dtype)
    kv_steps = s_k // bk

    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, s_q, d)
    # kernels run uniform-dtype dots (lax.dot_general does not promote)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * h, s_k, d).astype(q.dtype)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * h, s_k, d).astype(q.dtype)

    kernel = functools.partial(
        _flash_kernel, scale=float(scale), causal=bool(causal), block_q=bq,
        block_k=bk, kv_steps=kv_steps, q_off=0, kv_off=0)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, s_q // bq, kv_steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        name="ompi_flash_fwd",
    )(qf, kf, vf)
    return jnp.moveaxis(out.reshape(b, h, s_q, d), 1, 2)


def _partials_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, m_out, l_out,
                     m_ref, l_ref, acc_ref, *, scale: float, causal: bool,
                     block_q: int, block_k: int, kv_steps: int):
    """Same state machine, but emits un-normalized (o, m, l).

    ``off_ref`` is an SMEM (2,) int32 holding the (q, kv) global position
    offsets — *runtime* values, so ring attention can feed it the traced
    per-hop shard origin (lax.axis_index arithmetic)."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, m_ref.dtype)
        l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    qi = pl.program_id(1)
    # same fully-masked-block skip as _flash_kernel, with RUNTIME offsets:
    # on a ring hop whose kv shard sits entirely in this q block's future,
    # every block is skipped and the hop costs only its DMA
    visible = True
    if causal:
        last_row = off_ref[0] + (qi + 1) * block_q - 1
        first_col = off_ref[1] + ki * block_k
        visible = last_row >= first_col

    @pl.when(visible)
    def _compute():
        q = q_ref[0]                 # native dtype -> full-rate MXU
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = (off_ref[0] + qi * block_q
                    + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
            cols = (off_ref[1] + ki * block_k
                    + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
            s = jnp.where(rows >= cols, s, NEG_INF)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_cur = l_prev * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur[:, None], l_ref.shape)

    @pl.when(ki == kv_steps - 1)
    def _finish():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)
        # m/l blocks are (1, bq, 1): TPU tiling requires the last two block
        # dims be (8k, 128k) or equal to the array dims, so a flat (1, bq)
        # row block is unlowerable — the trailing singleton satisfies the
        # "equal to the array dim" arm while bq covers the sublane arm
        m_out[0] = m_ref[:, :1]
        l_out[0] = l_ref[:, :1]


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret", "vma"))
def flash_attention_partials(q: jax.Array, k: jax.Array, v: jax.Array,
                             causal: bool = False,
                             scale: Optional[float] = None,
                             q_offset=0, kv_offset=0,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             interpret: Optional[bool] = None,
                             vma=None):
    """Un-normalized flash partials for ring attention's merge step.

    q/k/v: (bh, seq, head_dim) — already folded (batch*heads) as in the ring
    loop. ``q_offset``/``kv_offset`` are the *global* positions of the local
    Q shard and the visiting K/V shard — python ints or traced int scalars
    (ring attention passes lax.axis_index arithmetic). Returns (o, m, l):
    o un-normalized (bh, s_q, d) float32, m/l (bh, s_q) float32.
    """
    if interpret is None:
        interpret = _default_interpret()
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    bq, bk = _block_sizes(s_q, s_k, block_q, block_k)
    _check_flash_blocks(bh, s_q, s_k, d, bq, bk, True,
                        "flash_attention_partials", q.dtype)
    kv_steps = s_k // bk
    offs = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(kv_offset, jnp.int32)])

    k = k.astype(q.dtype)      # uniform-dtype dots (no promotion in lax)
    v = v.astype(q.dtype)
    kernel = functools.partial(
        _partials_kernel, scale=float(scale), causal=bool(causal),
        block_q=bq, block_k=bk, kv_steps=kv_steps)
    o, m, l = pl.pallas_call(
        kernel,
        grid=(bh, s_q // bq, kv_steps),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_q, d), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((bh, s_q, 1), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((bh, s_q, 1), jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        name="ompi_flash_partials",
    )(offs, q, k, v)
    return o, m[..., 0], l[..., 0]


# ---------------------------------------------------------------------------
# differentiable flash attention (FlashAttention-2 backward as Pallas kernels)
# ---------------------------------------------------------------------------

def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                     causal: bool, block_q: int, block_k: int, q_steps: int):
    """dK/dV for one KV block: grid = (batch*heads, kv_blocks, q_blocks),
    q innermost-sequential so the (bk, d) accumulators live in VMEM scratch.
    Probabilities are recomputed from the saved logsumexp — no O(s²)
    residual."""
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros(dk_acc.shape, dk_acc.dtype)
        dv_acc[...] = jnp.zeros(dv_acc.shape, dv_acc.dtype)

    ki = pl.program_id(1)
    visible = True
    if causal:
        # any (row ≥ col) pair in this tile?  rows are q, cols are kv
        last_row = (qi + 1) * block_q - 1
        first_col = ki * block_k
        visible = last_row >= first_col

    @pl.when(visible)
    def _compute():
        q = q_ref[0]                 # native dtype -> full-rate MXU
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]                                    # (bq, 1)
        delta = delta_ref[0]                                # (bq, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = (qi * block_q
                    + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
            cols = (ki * block_k
                    + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)                                # (bq, bk) f32
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # pᵀ·dO
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # dsᵀ·Q

    @pl.when(qi == q_steps - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, scale: float, causal: bool,
                   block_q: int, block_k: int, kv_steps: int):
    """dQ for one Q block: grid = (batch*heads, q_blocks, kv_blocks), kv
    innermost-sequential."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros(dq_acc.shape, dq_acc.dtype)

    qi = pl.program_id(1)
    visible = True
    if causal:
        last_row = (qi + 1) * block_q - 1
        first_col = ki * block_k
        visible = last_row >= first_col

    @pl.when(visible)
    def _compute():
        q = q_ref[0]                 # native dtype -> full-rate MXU
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]                                    # (bq, 1)
        delta = delta_ref[0]                                # (bq, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = (qi * block_q
                    + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
            cols = (ki * block_k
                    + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == kv_steps - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_mha(q: jax.Array, k: jax.Array, v: jax.Array,
              causal: bool = False, scale: Optional[float] = None,
              block_q: Optional[int] = None, block_k: Optional[int] = None,
              interpret: Optional[bool] = None,
              bwd_block_q: Optional[int] = None,
              bwd_block_k: Optional[int] = None) -> jax.Array:
    """Differentiable flash attention over (batch, seq, heads, head_dim).

    The train-step entry point: identical math to ``flash_attention`` but
    with a FlashAttention-2 backward (blockwise recompute from the saved
    logsumexp), so ``jax.grad`` through it never materializes the score
    matrix. Residuals are q, k, v, o, logsumexp — O(batch·seq·heads·d).

    ``bwd_block_q``/``bwd_block_k`` tile the BACKWARD kernels
    independently of the forward (None = the fwd override if set, else
    ``_auto_block_bwd`` — so existing callers passing only
    block_q/block_k keep their pre-split behavior): the dq and dk/dv
    kernels hold extra VMEM accumulators, so their optimum block need
    not match the forward's — the A/B harness sweeps them separately
    (the reference's per-path segsize-tuning discipline,
    coll_tuned_dynamic_file.c:58, applied to kernel blocks)."""
    out, _ = _flash_mha_fwd(q, k, v, causal, scale, block_q, block_k,
                            interpret, bwd_block_q, bwd_block_k)
    return out


def _flash_mha_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                   bwd_block_q=None, bwd_block_k=None):
    if interpret is None:
        interpret = _default_interpret()
    if k.dtype != q.dtype or v.dtype != q.dtype:
        # custom_vjp cotangents must match the primal input avals; a cast
        # here would hand jax.grad dk/dv in q.dtype and fail downstream.
        raise TypeError(
            f"flash_mha requires uniform q/k/v dtype, got q={q.dtype} "
            f"k={k.dtype} v={v.dtype}; cast inputs before calling")
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, s_q, d)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * h, s_k, d)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * h, s_k, d)
    o_un, m, l = flash_attention_partials(
        qf, kf, vf, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret)
    l = jnp.maximum(l, 1e-20)
    of = (o_un / l[..., None]).astype(q.dtype)
    lse = m + jnp.log(l)                                    # (bh, s_q)
    out = jnp.moveaxis(of.reshape(b, h, s_q, d), 1, 2)
    return out, (qf, kf, vf, of, lse, (b, h))


def _flash_mha_bwd(causal, scale, block_q, block_k, interpret,
                   bwd_block_q, bwd_block_k, residuals, g):
    qf, kf, vf, of, lse, (b, h) = residuals
    if interpret is None:
        interpret = _default_interpret()
    bh, s_q, d = qf.shape
    s_k = kf.shape[1]
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    # bwd tiles independently of fwd: explicit bwd override, else the fwd
    # override (pre-split behavior for callers that only set
    # block_q/block_k), else the bwd auto-pick
    bq, bk = _block_sizes(s_q, s_k, bwd_block_q or block_q,
                          bwd_block_k or block_k,
                          auto=_auto_block_bwd, what="bwd blocks")
    _check_flash_blocks(bh, s_q, s_k, d, bq, bk, True, "flash_mha_bwd",
                        qf.dtype)
    dof = jnp.moveaxis(g, 2, 1).reshape(bh, s_q, d).astype(qf.dtype)
    # δ_i = Σ_d dO·O — the dS correction term (FlashAttention-2 eq. 4).
    # lse/delta carry a trailing singleton so their blocks are (1, bq, 1)
    # (TPU-lowerable; see _partials_kernel._finish)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1, keepdims=True)                 # (bh, s_q, 1)
    lse3 = lse[..., None]                                   # (bh, s_q, 1)

    dkdv = functools.partial(
        _bwd_dkdv_kernel, scale=float(scale), causal=bool(causal),
        block_q=bq, block_k=bk, q_steps=s_q // bq)
    dk, dv = pl.pallas_call(
        dkdv,
        grid=(bh, s_k // bk, s_q // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh_, ki, qi: (bh_, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh_, ki, qi: (bh_, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh_, ki, qi: (bh_, ki, 0)),
            pl.BlockSpec((1, bq, d), lambda bh_, ki, qi: (bh_, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh_, ki, qi: (bh_, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh_, ki, qi: (bh_, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda bh_, ki, qi: (bh_, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh_, ki, qi: (bh_, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_k, d), kf.dtype),
            jax.ShapeDtypeStruct((bh, s_k, d), vf.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
        name="ompi_flash_bwd_dkv",
    )(qf, kf, vf, dof, lse3, delta)

    dqk = functools.partial(
        _bwd_dq_kernel, scale=float(scale), causal=bool(causal),
        block_q=bq, block_k=bk, kv_steps=s_k // bk)
    dq = pl.pallas_call(
        dqk,
        grid=(bh, s_q // bq, s_k // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh_, qi, ki: (bh_, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh_, qi, ki: (bh_, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh_, qi, ki: (bh_, ki, 0)),
            pl.BlockSpec((1, bq, d), lambda bh_, qi, ki: (bh_, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh_, qi, ki: (bh_, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh_, qi, ki: (bh_, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh_, qi, ki: (bh_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s_q, d), qf.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="ompi_flash_bwd_dq",
    )(qf, kf, vf, dof, lse3, delta)

    unfold = lambda x, s: jnp.moveaxis(x.reshape(b, h, s, d), 1, 2)
    return unfold(dq, s_q), unfold(dk, s_k), unfold(dv, s_k)


flash_mha.defvjp(_flash_mha_fwd, _flash_mha_bwd)
