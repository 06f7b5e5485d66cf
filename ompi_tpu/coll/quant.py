"""coll/quant — block-quantized device collectives (the EQuARX tier).

Large-message reductions on the device plane are wire-bound: the native
tier moves every payload at full operand precision, so busbw is capped by
raw bytes over ICI.  EQuARX ("Efficient Quantized AllReduce in XLA",
arXiv:2506.17615) shows that symmetric per-block int8 quantization inside
the XLA program recovers near-2x effective bandwidth at negligible quality
loss.  This module is that third arm for the decision layer in coll/xla:

  allreduce       quantize -> reduce_scatter wire phase (each peer
                  contribution dequant-accumulated in f32) ->
                  requantize -> allgather -> dequantize
  reduce_scatter  same ring phase, no allgather (output stays exact f32
                  accumulation of dequantized partials)
  allgather       quantize once -> all_gather payload+scales -> dequantize

Every wire transfer carries int8 payload plus one scale per `block`
elements (default 256, f32 scales), so bytes on the wire are
``(1 + scale_bytes/block) / itemsize`` of the native arm — ~0.25x for f32
operands at block 256 (`wire_bytes` below is the exact accounting the
bench asserts against).

Error model: one quantization step has per-element error bounded by
``amax_block / 254`` (symmetric round-to-nearest over [-127, 127]).  The
allreduce quantizes each ORIGINAL contribution once and the reduced
chunk once more for the allgather phase — two roundings on the data path
regardless of device count (a requantize-per-hop ring would grow the
error linearly in n), keeping measured max-abs-err well under 1e-2
relative on unit-scale data (the numerics suite pins this).  All-zero blocks are exact (scale 0 maps to q 0); outliers only
widen their own 256-element block's step.

Only SUM and AVG over real float operands are expressible: int/bool
payloads have no scale to quantize against, MAX/MIN/PROD do not commute
with per-block rescaling, and MAXLOC/MINLOC carry exact indices.  Anything
else raises ``ValueError`` here rather than silently falling through
(``op.quantizable`` is the single gate).

Programs are jitted shard_map executables cached in the wrapped
DeviceComm's cache, keyed on (collective, op, shape-BUCKET, dtype, block,
scale dtype, ndev): per-rank payloads are flattened and zero-padded to a
power-of-two bucket of whole (ndev x block) units *outside* the cached
program, so all shapes within a 2x band share one executable.
"""

from __future__ import annotations

import math

import numpy as np

from .. import trace
from ..core import var as _var
from ..op import SUM, Op, quantizable

_var.register("coll", "quant", "block", 256, type=int, level=3,
              help="Elements per quantization block (one scale each).")
_var.register("coll", "quant", "scale_dtype", "float32", type=str, level=4,
              help="Dtype of the per-block scales on the wire "
                   "(float32|bfloat16).")

# int8 symmetric range: round() maps to [-127, 127] so the grid is
# symmetric (no -128 asymmetry) and amax round-trips exactly
_QMAX = 127.0


def check_quantizable(op: Op, dtype) -> None:
    """Reject (op, dtype) combos the quantized tier cannot carry."""
    if quantizable(op, dtype):
        return
    if op.name in ("maxloc", "minloc"):
        why = "MAXLOC/MINLOC pairs carry exact indices"
    elif op.name not in ("sum", "avg"):
        why = f"op {op.name!r} does not commute with per-block rescaling"
    else:
        why = f"dtype {np.dtype(dtype).name!r} has no scale to quantize"
    raise ValueError(
        f"quantized collectives support SUM/AVG over float operands only: "
        f"{why} (op={op.name!r}, dtype={np.dtype(dtype).name})")


def _params(block, scale_dtype):
    import jax.numpy as jnp

    block = int(block if block is not None
                else _var.get("coll_quant_block", 256))
    if block < 1:
        raise ValueError(f"quantization block must be >= 1, got {block}")
    sdt = scale_dtype if scale_dtype is not None \
        else _var.get("coll_quant_scale_dtype", "float32")
    if isinstance(sdt, str) and sdt == "bfloat16":
        sdt = jnp.bfloat16          # np.dtype can't parse the name alone
    sdt = np.dtype(sdt)
    if sdt.name not in ("float32", "bfloat16"):
        raise ValueError(
            f"scale_dtype must be float32 or bfloat16, got {sdt.name}")
    return block, sdt


# -- pure block codecs (traceable; usable inside any shard_map) -------------

def quantize_blocks(x, block: int, scale_dtype=None):
    """(..., L) with L % block == 0 -> (int8 (..., L), scales (..., L/block)).

    Symmetric per-block quantization: scale = amax/127 computed in f32;
    all-zero blocks get scale 0 and decode exactly to zero."""
    import jax.numpy as jnp

    scale_dtype = scale_dtype if scale_dtype is not None else jnp.float32
    xb = x.reshape(x.shape[:-1] + (x.shape[-1] // block, block))
    xf = xb.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / _QMAX        # (..., nblk)
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(xf / safe[..., None]), -_QMAX, _QMAX)
    return q.astype(jnp.int8).reshape(x.shape), scale.astype(scale_dtype)


def dequantize_blocks(q, scale, block: int, dtype=None):
    """Inverse of :func:`quantize_blocks`; accumulation stays in f32
    unless `dtype` narrows it at the end."""
    import jax.numpy as jnp

    qb = q.reshape(q.shape[:-1] + (q.shape[-1] // block, block))
    x = qb.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]
    x = x.reshape(q.shape)
    return x if dtype is None else x.astype(dtype)


# -- named-axis primitives (for use INSIDE shard_map programs) --------------

def _reduce_scatter_quant(chunks, axis: str, n: int, block: int,
                          scale_dtype):
    """chunks: (n, C) f32 with C % block == 0 -> (C,) f32: this device's
    fully reduced chunk (device d owns chunk d).

    The original local contributions are quantized exactly ONCE, the
    int8 payload + scales travel the all_to_all wire phase, and every
    peer's contribution is dequantized and accumulated in f32.  Unlike a
    requantize-per-hop ring (whose error grows linearly in n because
    partial SUMS get re-rounded n-1 times), the data path here pays a
    single rounding regardless of device count — same (n-1)*C quantized
    elements on the wire per device.
    """
    import jax.numpy as jnp
    from jax import lax

    if n == 1:
        return chunks[0]
    q, s = quantize_blocks(chunks, block, scale_dtype)
    q = lax.all_to_all(q, axis, split_axis=0, concat_axis=0, tiled=True)
    s = lax.all_to_all(s, axis, split_axis=0, concat_axis=0, tiled=True)
    return jnp.sum(dequantize_blocks(q, s, block), axis=0)


def _all_gather_quant(x, axis: str, n: int, block: int, scale_dtype):
    """x: (C,) f32 with C % block == 0 -> (n, C) f32: row j = device j's
    vector, moved over the wire as int8+scales (replicated over ``axis``
    on exit, so an allreduce ending here is typed invariant)."""
    from ..parallel.collectives import all_gather_invariant

    q, s = quantize_blocks(x, block, scale_dtype)
    qg = all_gather_invariant(q, axis, axis=0)        # (n, C) int8
    sg = all_gather_invariant(s, axis, axis=0)        # (n, C/block)
    return dequantize_blocks(qg, sg, block)


def psum_quant(x, axis: str, n: int, avg: bool = False, block: int = None,
               scale_dtype=None, op: Op = None):
    """Block-quantized allreduce of `x` over mesh axis `axis`, for use
    inside shard_map (the gradient-sync primitive).

    quantize -> reduce_scatter wire phase (peer contributions
    dequant-accumulated in f32) -> requantize -> allgather ->
    dequantize.  `n` is the static axis size
    (shard_map bodies cannot read it dynamically on every jax version).
    """
    import jax.numpy as jnp

    if op is not None:
        check_quantizable(op, x.dtype)
        avg = avg or op.name == "avg"
    block, sdt = _params(block, scale_dtype)
    if n == 1:
        return x / n if avg else x
    shape, dtype = x.shape, x.dtype
    L = int(np.prod(shape)) if shape else 1
    unit = n * block
    Lpad = unit * max(1, math.ceil(L / unit))
    flat = x.reshape(-1).astype(jnp.float32)
    if Lpad != L:
        flat = jnp.pad(flat, (0, Lpad - L))
    chunks = flat.reshape(n, Lpad // n)
    acc = _reduce_scatter_quant(chunks, axis, n, block, sdt)
    if avg:
        acc = acc / n
    full = _all_gather_quant(acc, axis, n, block, sdt)   # (n, C)
    return full.reshape(-1)[:L].reshape(shape).astype(dtype)


# -- wire-byte accounting ---------------------------------------------------

def padded_len(count: int, n: int, block: int) -> int:
    """Flattened per-rank element count after padding to whole
    (n x block) units (what actually travels)."""
    unit = n * block
    return unit * max(1, math.ceil(int(count) / unit))


def wire_bytes(coll: str, count: int, n: int, dtype, block: int = None,
               scale_dtype=None) -> dict:
    """Exact per-device wire bytes of the quantized vs native arm for
    `count` elements of `dtype` over an `n`-device axis.

    Ring costs: allreduce = 2(n-1) chunk transfers (reduce_scatter +
    allgather phases), reduce_scatter/allgather = (n-1).  The quantized
    chunk carries int8 payload + one scale per block; the native chunk
    carries full-precision elements.  Returns quant/native byte totals
    and their ratio (the bench's byte-accounting column).
    """
    block, sdt = _params(block, scale_dtype)
    esize = np.dtype(dtype).itemsize
    ssize = sdt.itemsize
    hops = {"allreduce": 2 * (n - 1), "reduce_scatter": n - 1,
            "allgather": n - 1}.get(coll)
    if hops is None:
        raise ValueError(f"no quantized arm for collective {coll!r}")
    chunk = padded_len(count, n, block) // n
    quant = hops * chunk * (1 + ssize / block)
    native = hops * math.ceil(int(count) / n) * esize
    return {"quant_bytes": int(round(quant)), "native_bytes": int(native),
            "ratio": quant / native if native else float("inf")}


# -- canonical-layout engine (mirrors DeviceComm's entry points) ------------

def _span_args(wb: dict, block: int, sdt, roundings: int,
               requantize_count: int) -> dict:
    """Trace-span payload for one quantized execution: the EQuARX
    accounting (wire bytes, block config, how many stochastic roundings
    touch each element, whether an accumulated value is requantized)."""
    ratio = wb["ratio"]
    return {"wire_bytes": wb["quant_bytes"],
            "native_bytes": wb["native_bytes"],
            "ratio": round(ratio, 4) if math.isfinite(ratio) else None,
            "block": block, "scale_dtype": sdt.name,
            "roundings": roundings, "requantize_count": requantize_count}


def grad_bucket_span_args(nbytes: int, n: int, dtype, block: int = None,
                          scale_dtype=None) -> dict:
    """EQuARX accounting for ONE quantized grad-sync bucket of `nbytes`
    raw gradient bytes allreduced over `n` devices — the detail payload
    attached to parallel/overlap's per-bucket decision events and spans.
    psum_quant's path rounds each element twice (quantize + the
    post-accumulate requantize) and requantizes the accumulated value
    once, hence the fixed counts."""
    block, sdt = _params(block, scale_dtype)
    count = max(1, int(nbytes) // np.dtype(dtype).itemsize)
    wb = wire_bytes("allreduce", count, n, dtype, block, sdt)
    return _span_args(wb, block, sdt, roundings=2, requantize_count=1)


class QuantDeviceComm:
    """Quantized collectives over a DeviceComm's mesh axis, same
    canonical (R, *elem) dim-0-sharded layout and executable cache
    (reached as ``dc.quant``)."""

    def __init__(self, dc) -> None:
        self.dc = dc

    # local rows fold in f32 before any wire quantization, so the r
    # co-resident ranks' contribution is exact
    @staticmethod
    def _fold32(xs):
        import jax.numpy as jnp

        return jnp.sum(xs.astype(jnp.float32), axis=0)

    def _padded(self, x, L: int, Lpad: int):
        """Flatten rows + zero-pad OUTSIDE the cached program (cheap ops;
        the heavy executable is shared across every shape in the
        bucket), re-pinned to the canonical sharding."""
        import jax
        import jax.numpy as jnp

        flat = x.reshape((x.shape[0], -1))
        if Lpad != L:
            flat = jnp.pad(flat, ((0, 0), (0, Lpad - L)))
        return jax.device_put(flat, self.dc.sharding())

    def _spc(self, name):
        if self.dc.spc is not None:
            self.dc.spc.inc(name)

    def allreduce(self, x, op: Op = SUM, block: int = None,
                  scale_dtype=None):
        """(R, *e) -> (R, *e): every row <- quantized op over all rows."""
        import jax.numpy as jnp

        check_quantizable(op, x.dtype)
        block, sdt = _params(block, scale_dtype)
        dc, n = self.dc, self.dc.n
        R, elem = x.shape[0], x.shape[1:]
        L = int(np.prod(elem)) if elem else 1
        Lpad = padded_len(L, n, block)
        avg = op.name == "avg"
        key = ("quant_allreduce", op.name, R, Lpad, str(x.dtype),
               block, sdt.name, n)

        def build():
            def inner(xs):                       # (r, Lpad) local rows
                folded = self._fold32(xs)
                if n == 1:
                    out = folded / R if avg else folded
                else:
                    chunks = folded.reshape(n, Lpad // n)
                    acc = _reduce_scatter_quant(chunks, dc.axis, n,
                                                     block, sdt)
                    if avg:
                        # average over CONTRIBUTIONS: R ranks total,
                        # r = R/n of them folded locally per device
                        acc = acc / R
                    out = _all_gather_quant(acc, dc.axis, n, block,
                                            sdt).reshape(-1)
                out = out.astype(x.dtype)
                return jnp.broadcast_to(out[None], xs.shape)
            return dc._shard_map(inner, dc._spec, dc._spec)

        self._spc("device_quant_collectives")
        from .. import numerics
        if numerics.enabled:
            # live SNR of the same per-block rounding the wire applies,
            # measured on the actual payload (numerics quant-SNR sentry)
            numerics.observe_quant_snr("allreduce", x, block, sdt)
        xp = self._padded(x, L, Lpad)
        if trace.enabled:
            # allreduce = quantized reduce_scatter ring (accumulate in
            # f32, requantize once per forward) + quantized allgather
            with trace.span("quant:allreduce", "quant", args=_span_args(
                    wire_bytes("allreduce", L, n, x.dtype, block, sdt),
                    block, sdt, roundings=2, requantize_count=1)):
                out = dc._compiled(key, build)(xp)
        else:
            out = dc._compiled(key, build)(xp)
        return out[:, :L].reshape((R,) + elem)

    def reduce_scatter(self, x, op: Op = SUM, block: int = None,
                       scale_dtype=None):
        """(R, R*b, *e) -> (R, b, *e): row i = quantized-reduced block i
        (the ring phase alone; result is the f32 accumulation of the
        dequantized per-hop partials, never requantized)."""
        import jax.numpy as jnp

        check_quantizable(op, x.dtype)
        block, sdt = _params(block, scale_dtype)
        dc, n = self.dc, self.dc.n
        R = x.shape[0]
        if x.shape[1] % R:
            raise ValueError(
                f"reduce_scatter needs dim 1 divisible by {R} rows, "
                f"got {x.shape}")
        b, elem = x.shape[1] // R, x.shape[2:]
        r = R // n
        E = int(np.prod(elem)) if elem else 1
        # pad per-CHUNK (a chunk = one device's r result rows) so rank
        # boundaries survive the padding
        C = r * b * E
        Cpad = block * max(1, math.ceil(C / block))
        avg = op.name == "avg"
        key = ("quant_reduce_scatter", op.name, R, b, E, Cpad,
               str(x.dtype), block, sdt.name, n)

        def build():
            def inner(xs):                       # (r, R*b*E) flat rows
                folded = self._fold32(xs)        # (R*b*E,)
                chunks = folded.reshape(n, C)
                if Cpad != C:
                    chunks = jnp.pad(chunks, ((0, 0), (0, Cpad - C)))
                acc = _reduce_scatter_quant(chunks, dc.axis, n,
                                                 block, sdt)
                if avg:
                    # R contributions total (r folded locally x n devices)
                    acc = acc / R
                return acc[:C].reshape((r, b * E)).astype(x.dtype)
            return dc._shard_map(inner, dc._spec, dc._spec)

        self._spc("device_quant_collectives")
        from .. import numerics
        if numerics.enabled:
            numerics.observe_quant_snr("reduce_scatter", x, block, sdt)
        flat = self._padded(x, R * b * E, R * b * E)
        if trace.enabled:
            # ring phase alone: one rounding per element, accumulation
            # stays f32 (never requantized)
            with trace.span("quant:reduce_scatter", "quant",
                            args=_span_args(
                    wire_bytes("reduce_scatter", R * b * E, n, x.dtype,
                               block, sdt),
                    block, sdt, roundings=1, requantize_count=0)):
                out = dc._compiled(key, build)(flat)
        else:
            out = dc._compiled(key, build)(flat)
        return out.reshape((R, b) + elem)

    def allgather(self, x, block: int = None, scale_dtype=None):
        """(R, b, *e) -> (R, R*b, *e): every row = concat of all rows,
        each contribution quantized exactly once on the wire."""
        import jax.numpy as jnp

        check_quantizable(SUM, x.dtype)     # dtype gate only
        if x.ndim < 2:
            raise ValueError(
                f"allgather needs the canonical (R, b, *e) layout, "
                f"got shape {x.shape}")
        block, sdt = _params(block, scale_dtype)
        dc, n = self.dc, self.dc.n
        R, b, e = x.shape[0], x.shape[1], x.shape[2:]
        L = b * (int(np.prod(e)) if e else 1)    # elements per rank row
        Lpad = block * max(1, math.ceil(L / block))
        key = ("quant_allgather", R, Lpad, str(x.dtype), block,
               sdt.name, n)

        def build():
            def inner(xs):                       # (r, Lpad)
                flat = xs.reshape(-1)            # r rank rows end to end
                full = _all_gather_quant(flat, dc.axis, n, block, sdt)
                full = full.reshape(-1).astype(x.dtype)   # (R*Lpad,)
                # stay fully padded inside the program: the unpadded L
                # is NOT in the cache key, so two shapes sharing a pad
                # bucket must share this executable verbatim (the trim
                # happens outside, like allreduce)
                return jnp.broadcast_to(full[None],
                                        (xs.shape[0],) + full.shape)
            return dc._shard_map(inner, dc._spec, dc._spec)

        self._spc("device_quant_collectives")
        from .. import numerics
        if numerics.enabled:
            numerics.observe_quant_snr("allgather", x, block, sdt)
        xp = self._padded(x, L, Lpad)
        if trace.enabled:
            # each contribution quantized exactly once on the wire
            with trace.span("quant:allgather", "quant", args=_span_args(
                    wire_bytes("allgather", L, n, x.dtype, block, sdt),
                    block, sdt, roundings=1, requantize_count=0)):
                out = dc._compiled(key, build)(xp)
        else:
            out = dc._compiled(key, build)(xp)
        out = out.reshape(R, R, Lpad)[:, :, :L]
        return out.reshape((R, R * b) + e)
