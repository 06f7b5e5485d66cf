"""Device collectives: named-axis primitives + the DeviceComm engine.

This is the heart of the TPU-native design (BASELINE.json north_star): where
the reference's coll components drive host loops over p2p (§3.2) and its
coll/accelerator component stages HBM→host before reducing
(coll_accelerator_allreduce.c:31-60), here collectives on device-resident
data are XLA collective *programs* executed over ICI — ``lax.psum`` /
``all_gather`` / ``psum_scatter`` / ``all_to_all`` / ``ppermute`` inside
``shard_map`` — with an executable cache playing the role ob1's protocol
state machine plays on the host path ("the analog ... in compilation space",
SURVEY.md §7 hard parts).

Two API levels:
  * free functions (``psum``, ``all_gather_axis``, ...) usable inside any
    user shard_map/jit — the idiomatic JAX face;
  * ``DeviceComm`` — MPI-shaped collectives over one mesh axis on standalone
    arrays, caching one compiled executable per (collective, op, shape,
    dtype) bucket, for OSU-style benchmarking and the coll/xla component.

Layout convention for DeviceComm: an "MPI buffer per rank" is row i of an
array of shape (n, *elem) sharded on dim 0 over the comm axis; results keep
that layout (every row holds that rank's result), so chained collectives
stay on device with no resharding.
"""

from __future__ import annotations

import collections
import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax._src.lax.parallel import all_gather_invariant  # noqa: F401
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import trace
from ..core import var as _var
from ..op import MAX, MIN, SUM, Op

_var.register(
    "coll", "a2av", "slice_cap", 0, type=int, level=4,
    help="Capacity-slice size (elements) for the sliced-scan ragged "
         "alltoallv_from_rows exchange; bounds the per-step transient to "
         "O(R x slice_cap x elem) per device. 0 = auto (~1M elements per "
         "device row). The chosen value and the resulting scan-step count "
         "k are recorded in the decision audit of every collective that "
         "rides this path (alltoallv, moe_dispatch, moe_combine).")

# ---------------------------------------------------------------------------
# named-axis primitives (for use inside shard_map) — thin, explicit wrappers
# ---------------------------------------------------------------------------
#
# ``all_gather_invariant`` (re-exported above; jax 0.9 has no public alias)
# is the gather whose result shard_map's VMA typing knows is replicated over
# the gathered axis: use it wherever a gather ends an allreduce or a
# relayout whose out_specs drop that axis.  ``lax.all_gather`` keeps the
# result device-varying.


def psum(x, axis: str):
    return lax.psum(x, axis)


def pmax(x, axis: str):
    return lax.pmax(x, axis)


def pmin(x, axis: str):
    return lax.pmin(x, axis)


def _op_identity(op: Op, like):
    """Identity element of the named op, shaped like ``like``."""
    if op.name in ("sum", "lor", "bor", "bxor"):
        return jnp.zeros_like(like)
    if op.name in ("prod",):
        return jnp.ones_like(like)
    if op.name == "land":
        return jnp.ones_like(like, dtype=bool).astype(like.dtype)
    if op.name == "band":
        return jnp.full_like(like, ~jnp.zeros((), like.dtype)
                             if jnp.issubdtype(like.dtype, jnp.integer)
                             else 1)
    if op.name in ("max", "min"):
        if jnp.issubdtype(like.dtype, jnp.floating):
            v = -jnp.inf if op.name == "max" else jnp.inf
        elif like.dtype == jnp.bool_:
            v = op.name == "min"
        else:
            info = jnp.iinfo(like.dtype)
            v = info.min if op.name == "max" else info.max
        return jnp.full_like(like, v)
    raise ValueError(f"no identity for op {op.name}")


def preduce(x, axis: str, op: Op):
    """Reduce over a mesh axis with any Op. SUM/MAX/MIN lower to native
    psum/pmax/pmin (single ICI reduction); other ops all_gather + fold."""
    if op.name == "sum":
        return lax.psum(x, axis)
    if op.name == "max":
        return lax.pmax(x, axis)
    if op.name == "min":
        return lax.pmin(x, axis)
    gathered = lax.all_gather(x, axis)           # (n, *x.shape)
    if op.name == "prod":
        return jnp.prod(gathered, axis=0)
    if op.name in ("land", "band"):
        return jnp.all(gathered.astype(bool), axis=0).astype(x.dtype) \
            if op.name == "land" else functools.reduce(
                jnp.bitwise_and, [gathered[i] for i in range(gathered.shape[0])])
    if op.name in ("lor", "bor"):
        return jnp.any(gathered.astype(bool), axis=0).astype(x.dtype) \
            if op.name == "lor" else functools.reduce(
                jnp.bitwise_or, [gathered[i] for i in range(gathered.shape[0])])
    if op.name in ("lxor", "bxor"):
        red = functools.reduce(jnp.bitwise_xor,
                               [gathered[i].astype(jnp.int32)
                                for i in range(gathered.shape[0])])
        return red.astype(x.dtype)
    # generic fold (user op whose fn is jax-traceable)
    acc = gathered[0]
    for i in range(1, gathered.shape[0]):
        acc = op.fn(acc, gathered[i])
    return acc


def all_gather_axis(x, axis: str, tiled: bool = True):
    return lax.all_gather(x, axis, tiled=tiled)


def reduce_scatter_axis(x, axis: str):
    """psum_scatter over dim 0 (must be divisible by axis size)."""
    return lax.psum_scatter(x, axis, tiled=True)


def all_to_all_axis(x, axis: str, split_dim: int = 0, concat_dim: int = 0):
    """Tiled all_to_all over a named axis (or tuple of axes): the local
    ``split_dim`` is scattered across the axis while each peer's block
    concatenates along ``concat_dim``.

    A ``split_dim`` that does not divide by the axis size is handled
    exactly with the zero-pad trick hierarchical_psum uses: the dim is
    padded to the next multiple of the axis size, so every peer receives
    an equal ceil-sized block.  The result follows the padded-block
    convention — position p along the axis holds rows
    ``[p*ceil, (p+1)*ceil)`` of the true extent, zeros past the end — so
    the inverse (``all_gather`` on the same dim + a ``[:L]`` slice)
    reconstructs the original bit-exactly.  Reshard plans lean on this
    to keep ragged exchanges on device instead of bouncing through host.
    """
    n = int(lax.psum(1, axis))     # static axis size under shard_map
    L = x.shape[split_dim]
    if L % n:
        pad = [(0, 0)] * x.ndim
        pad[split_dim] = (0, -(-L // n) * n - L)
        x = jnp.pad(x, pad)
    return lax.all_to_all(x, axis, split_axis=split_dim,
                          concat_axis=concat_dim, tiled=True)


def ppermute(x, axis: str, perm: Sequence[Tuple[int, int]]):
    return lax.ppermute(x, axis, perm=list(perm))


def ring_shift(x, axis: str, n: int, shift: int = 1, steps: int = 1):
    """Neighbor exchange on a ring — the schedule ring attention and the
    ring/segmented-ring collectives share (coll_base_allreduce.c:344,621).

    ``steps > 1`` is the strided variant: the rotation decomposes into
    ``steps`` sequential hops of stride ``shift/steps`` (which must
    divide), the segmented-ring shape that bounds per-hop link pressure
    and gives the overlap tier ``steps`` interleaving points instead of
    one monolithic permute."""
    steps = int(steps)
    if steps <= 1:
        perm = [(i, (i + shift) % n) for i in range(n)]
        return lax.ppermute(x, axis, perm=perm)
    if shift % steps:
        raise ValueError(
            f"ring_shift: shift {shift} does not decompose into "
            f"{steps} equal strides (shift % steps must be 0)")
    stride = shift // steps
    perm = [(i, (i + stride) % n) for i in range(n)]
    for _ in range(steps):
        x = lax.ppermute(x, axis, perm=perm)
    return x


def pbcast(x, axis: str, root: int = 0):
    """Broadcast root's shard to every member of the axis."""
    return lax.all_gather(x, axis)[root]


# ---------------------------------------------------------------------------
# DeviceComm: MPI-shaped device collectives with an executable cache
# ---------------------------------------------------------------------------


class DeviceComm:
    """Collectives over one axis of a mesh, single-controller.

    ``n`` "ranks" = positions along `axis`. Input arrays use the canonical
    (n, *elem) dim-0-sharded layout (see module docstring); `from_ranks`/
    `to_ranks` convert to/from per-rank host arrays.

    ``axis`` may also be a TUPLE of axis names: the comm then spans the
    row-major product of those axes (outer-to-inner order), which is how
    a two-tier ICI×DCN comm presents one flat rank space while the
    hierarchical (`hier`) arm in coll/xla still addresses the individual
    levels by name.  Every flat collective here passes the tuple straight
    into the lax primitive (tuple axis names are first-class in jax);
    the cartesian/ring helpers, which need a single line geometry, keep
    requiring a single named axis.
    """

    def __init__(self, mesh: Mesh, axis) -> None:
        self.mesh = mesh
        if isinstance(axis, (tuple, list)):
            axis = tuple(axis)
            self.n = int(np.prod([mesh.shape[a] for a in axis]))
        else:
            self.n = mesh.shape[axis]
        self.axis = axis
        self._cache: Dict[tuple, Callable] = {}
        # counts → device gather maps, LRU-bounded: repeated patterns (the
        # bench, fixed decompositions) hit; per-step MoE routings churn
        # through without accumulating dead HBM buffers
        self._idx_cache: "collections.OrderedDict[tuple, Any]" = \
            collections.OrderedDict()
        self._idx_cache_cap = 64
        self._spec = P(axis)
        self.spc = None          # optional SPC counters
        self._quant = None       # lazy QuantDeviceComm (coll/quant)
        self._last_a2av = None   # last a2av_plan taken (audit breadcrumb)

    def _idx_cached(self, key: tuple, build: Callable) -> Any:
        hit = self._idx_cache.get(key)
        if hit is not None:
            self._idx_cache.move_to_end(key)
            return hit
        val = build()
        self._idx_cache[key] = val
        if len(self._idx_cache) > self._idx_cache_cap:
            self._idx_cache.popitem(last=False)
        return val

    # -- layout helpers -----------------------------------------------------

    def sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self._spec)

    def from_ranks(self, arrays: Sequence[np.ndarray]) -> jax.Array:
        """Stack per-rank buffers into the canonical device layout."""
        stacked = jnp.stack([jnp.asarray(a) for a in arrays])
        return jax.device_put(stacked, self.sharding())

    def to_ranks(self, x: jax.Array) -> list:
        host = np.asarray(jax.device_get(x))
        return [host[i] for i in range(host.shape[0])]

    def reshard(self, x: jax.Array, dst) -> jax.Array:
        """Device-native relayout of ``x`` onto ``dst`` (a NamedSharding
        or PartitionSpec over this comm's mesh) through the compiled
        minimal-collective plan engine (parallel/reshard) — the
        replacement for ``to_ranks()``/``from_ranks()`` round-trips:
        no host copy, peak live bytes bounded by ``reshard_peak_factor
        × max(src_shard, dst_shard)``, every plan step decision-audited
        and traffic-attributed under coll name ``reshard``."""
        from .reshard import reshard as _reshard
        return _reshard(x, dst, mesh=self.mesh, spc=self.spc)

    def canonicalize(self, x: jax.Array, dim: int) -> jax.Array:
        """Re-layout an array sharded over this comm's axis on dimension
        ``dim`` into the canonical ``(n, *local)`` dim-0 layout.  A pure
        local restack — ZERO wire: each rank lifts its own shard under a
        new leading rank dimension — so a consumer (the serving engine's
        weight-stationary decode pieces) can feed column-parallel shards
        straight into dim-0-batched compute without GSPMD guessing."""
        if not 0 <= dim < x.ndim:
            raise ValueError(f"canonicalize: dim {dim} out of range for "
                             f"rank-{x.ndim} array")
        if x.shape[dim] % self.n:
            raise ValueError(
                f"canonicalize: dim {dim} ({x.shape[dim]}) is not "
                f"divisible by the {self.n}-way comm axis")
        in_spec = P(*(self.axis if d == dim else None
                      for d in range(x.ndim)))
        key = ("canonicalize", dim, tuple(x.shape), str(x.dtype))

        def build():
            return self._shard_map(lambda a: a[None], (in_spec,),
                                   P(self.axis))
        return self._compiled(key, build)(x)

    # -- multi-process (rank-per-chip) layout helpers -----------------------
    # In the device-plane model (parallel/device_plane.py) each process owns
    # only its own rows; the global array is assembled from per-process
    # shards — the multi-process analog of from_ranks/to_ranks.

    def from_local(self, local_rows: np.ndarray) -> jax.Array:
        """This process's rows (r, *e) → the global (R, *e) sharded array."""
        return jax.make_array_from_process_local_data(
            self.sharding(), np.asarray(local_rows))

    def to_local(self, x: jax.Array) -> np.ndarray:
        """This process's rows of a global array, as one host ndarray.
        Deduplicates replicated shards (meshes with extra axes hold one
        copy per replica device)."""
        by_start = {}
        for s in x.addressable_shards:
            by_start.setdefault(s.index[0].start or 0, s)
        return np.concatenate(
            [np.asarray(by_start[k].data) for k in sorted(by_start)], axis=0)

    # -- compiled-collective cache (≙ the coll/xla executable cache,
    #    SURVEY.md §7 "ICI collectives outside a single XLA program") -------

    def _compiled(self, key: tuple, build: Callable) -> Callable:
        fn = self._cache.get(key)
        if fn is None:
            # build() constructs + jits the program; XLA compiles lazily,
            # so the first-execution compile lands inside whatever region
            # surrounds the miss (ompi.coll.launch)
            with trace.region("ompi.coll.build", f"build:{key[0]}",
                              "compile", args={"key": repr(key)}
                              if trace.enabled else None):
                fn = build()
            self._cache[key] = fn
            if self.spc is not None:
                self.spc.inc("device_cache_misses")
                self.spc.inc("cache_miss_count")
        elif trace.enabled:
            trace.instant(f"cache_hit:{key[0]}", "cache",
                          args={"key": repr(key)})
        if self.spc is not None:
            self.spc.inc("device_collectives")
        return fn

    def _shard_map(self, fn, in_specs, out_specs):
        return jax.jit(jax.shard_map(fn, mesh=self.mesh,
                                         in_specs=in_specs,
                                         out_specs=out_specs))

    def cache_info(self) -> Dict[str, int]:
        return {"entries": len(self._cache)}

    @property
    def quant(self):
        """Block-quantized tier over the same axis/cache (coll/quant)."""
        if self._quant is None:
            from ..coll.quant import QuantDeviceComm
            self._quant = QuantDeviceComm(self)
        return self._quant

    # -- collectives --------------------------------------------------------
    #
    # Rows ("MPI ranks") may outnumber mesh positions: with R total rows on
    # an n-device axis each device owns r = R/n local rows (rank-per-chip is
    # r=1; the single-chip bench runs all R rows on one device). Every
    # collective below handles both regimes: local fold/slice over the r
    # rows, ICI collective across devices.

    def _fold_local(self, xs, op: Op):
        """op-reduce the local rows (r, *e) → (*e)."""
        if op.name == "sum":
            return jnp.sum(xs, axis=0)
        if op.name == "max":
            return jnp.max(xs, axis=0)
        if op.name == "min":
            return jnp.min(xs, axis=0)
        if op.name == "prod":
            return jnp.prod(xs, axis=0)
        acc = xs[0]
        for i in range(1, xs.shape[0]):
            acc = op.fn(acc, xs[i])
        return acc

    @trace.timed("ompi.coll.launch")
    def allreduce(self, x: jax.Array, op: Op = SUM) -> jax.Array:
        """Every rank's row ← op over all rows. (R,*e) → (R,*e)."""
        key = ("allreduce", op.name, x.shape, str(x.dtype))

        def build():
            def inner(xs):           # xs: (r, *e) local shard
                red = preduce(self._fold_local(xs, op), self.axis, op)
                return jnp.broadcast_to(red[None], xs.shape)
            return self._shard_map(inner, self._spec, self._spec)

        return self._compiled(key, build)(x)

    def reduce(self, x: jax.Array, op: Op = SUM, root: int = 0) -> jax.Array:
        """MPI semantics only promise the root's row; this returns the
        reduction in every row (same executable as allreduce — on ICI the
        broadcast halves are fused anyway)."""
        return self.allreduce(x, op)

    def bcast(self, x: jax.Array, root: int = 0) -> jax.Array:
        """One-to-all as a masked psum: the root's device contributes its
        row, everyone else zeros — traffic is one element-size reduction
        over ICI instead of the R× blowup of all_gather-then-index (the
        round-1 implementation; VERDICT r1 weak#7)."""
        R = x.shape[0]
        r = R // self.n
        key = ("bcast", int(root), x.shape, str(x.dtype))

        def build():
            root_dev, root_local = divmod(int(root), r)

            def inner(xs):           # (r, *e)
                i = lax.axis_index(self.axis)
                contrib = jnp.where(i == root_dev, xs[root_local],
                                    jnp.zeros_like(xs[root_local]))
                row = lax.psum(contrib, self.axis)
                return jnp.broadcast_to(row[None], xs.shape)
            return self._shard_map(inner, self._spec, self._spec)

        return self._compiled(key, build)(x)

    @trace.timed("ompi.coll.launch")
    def allgather(self, x: jax.Array) -> jax.Array:
        """(R, b, *e) → (R, R*b, *e): every row = concat of all rows.

        The canonical MPI layout: every RANK row holds the full gathered
        vector. When ranks outnumber devices (r = R/n > 1) each device
        writes r identical copies — use :meth:`allgather_dedup` where the
        consumer can share one copy per device (the single-chip regime's
        r× HBM saving; round-4 verdict weak#4)."""
        key = ("allgather", x.shape, str(x.dtype))

        def build():
            def inner(xs):           # (r, b, *e)
                full = lax.all_gather(xs, self.axis, axis=0, tiled=True)
                flat = full.reshape((-1,) + full.shape[2:])   # (R*b, *e)
                return jnp.broadcast_to(flat[None],
                                        (xs.shape[0],) + flat.shape)
            return self._shard_map(inner, self._spec, self._spec)

        return self._compiled(key, build)(x)

    def allgather_dedup(self, x: jax.Array) -> jax.Array:
        """(R, b, *e) → (n, R*b, *e): ONE gathered copy per DEVICE.

        Same information as :meth:`allgather` — dim 0 is mesh position,
        not rank; the r ranks co-resident on a device share its row (the
        reference's ring allgather memory discipline,
        coll_base_allgather.c:330: each process stores the result once).
        Identical to the canonical layout when r == 1; r× less HBM
        traffic when ranks share a device (single-chip: R× less)."""
        key = ("allgather_dedup", x.shape, str(x.dtype))

        def build():
            def inner(xs):           # (r, b, *e)
                full = lax.all_gather(xs, self.axis, axis=0, tiled=True)
                return full.reshape((1, -1) + full.shape[2:])  # (1,R*b,*e)
            return self._shard_map(inner, self._spec, self._spec)

        return self._compiled(key, build)(x)

    def dedup_to_ranks(self, x: jax.Array, ranks: int) -> list:
        """Per-rank host views of an ``allgather_dedup`` result: with
        r = ranks/n ranks per device, rank i reads its device's single
        copy, row i // r (no second materialization — numpy views)."""
        host = np.asarray(jax.device_get(x))
        n = host.shape[0]
        if n == 0 or ranks % n:
            raise ValueError(
                f"ranks ({ranks}) must be a positive multiple of the "
                f"result's device rows ({n})")
        r = ranks // n
        return [host[i // r] for i in range(ranks)]

    @trace.timed("ompi.coll.launch")
    def reduce_scatter(self, x: jax.Array, op: Op = SUM) -> jax.Array:
        """(R, R*b, *e) → (R, b, *e): row i = op-reduced i-th block."""
        R = x.shape[0]
        b = x.shape[1] // R
        r = R // self.n
        key = ("reduce_scatter", op.name, x.shape, str(x.dtype))

        def build():
            def inner(xs):           # (r, R*b, *e)
                folded = self._fold_local(xs, op)          # (R*b, *e)
                if op.name == "sum":
                    mine = lax.psum_scatter(folded, self.axis,
                                            scatter_dimension=0, tiled=True)
                else:
                    red = preduce(folded, self.axis, op)   # (R*b, *e)
                    i = lax.axis_index(self.axis)
                    mine = lax.dynamic_slice_in_dim(red, i * r * b, r * b, 0)
                return mine.reshape((r, b) + xs.shape[2:])
            return self._shard_map(inner, self._spec, self._spec)

        return self._compiled(key, build)(x)

    @trace.timed("ompi.coll.launch")
    def alltoall(self, x: jax.Array) -> jax.Array:
        """(R, R, b, *e) → (R, R, b, *e): out[i, j] = in[j, i]."""
        R = x.shape[0]
        r = R // self.n
        key = ("alltoall", x.shape, str(x.dtype))

        def build():
            if r == 1:
                def inner(xs):       # (1, R, b, *e): native ICI all-to-all
                    return lax.all_to_all(xs, self.axis, split_axis=1,
                                          concat_axis=1, tiled=True)
            else:
                def inner(xs):       # (r, R, b, *e): native all-to-all of
                    # r-row column blocks — each device exchanges only the
                    # blocks destined for each peer (n× less traffic than
                    # the old full all_gather; VERDICT r1 weak#7).
                    # received block from device k = in[k's rows, my cols]
                    mixed = lax.all_to_all(xs, self.axis, split_axis=1,
                                           concat_axis=0, tiled=True)
                    return jnp.swapaxes(mixed, 0, 1)   # (r, R, b, *e)
            return self._shard_map(inner, self._spec, self._spec)

        return self._compiled(key, build)(x)

    def ring_shift(self, x: jax.Array, shift: int = 1,
                   steps: int = 1) -> jax.Array:
        """(R,*e) → (R,*e) with row i moved to row (i+shift)%R — the ppermute
        ring primitive (context-parallel neighbor exchange).

        ``steps > 1`` runs the strided decomposition: ``steps``
        sequential hops of stride ``shift/steps`` (must divide), each a
        cached one-hop executable with its own traffic attribution — the
        segmented-ring schedule whose intermediate rows an overlap tier
        can consume between hops."""
        if int(steps) > 1:
            if shift % int(steps):
                raise ValueError(
                    f"ring_shift: shift {shift} does not decompose into "
                    f"{steps} equal strides (shift % steps must be 0)")
            stride = shift // int(steps)
            for _ in range(int(steps)):
                x = self.ring_shift(x, stride)
            return x
        R = x.shape[0]
        r = R // self.n
        key = ("ring", int(shift), x.shape, str(x.dtype))

        def build():
            if r == 1:
                def inner(xs):
                    return ring_shift(xs, self.axis, self.n, shift)
            else:
                # global row shift = at most two neighbor ppermutes: the
                # source rows of any device's block span exactly two peers
                # (offset is the same on every device, so both permutations
                # are static ring shifts) — O(row) traffic instead of the
                # old full all_gather (VERDICT r1 weak#7)
                s = shift % R
                off = (-s) % r                 # intra-block source offset
                q = (-s - off) // r            # uniform source-device delta
                n = self.n

                def inner(xs):                 # (r, *e)
                    a = lax.ppermute(
                        xs[off:], self.axis,
                        [((d + q) % n, d) for d in range(n)])
                    if off == 0:
                        return a
                    b = lax.ppermute(
                        xs[:off], self.axis,
                        [((d + q + 1) % n, d) for d in range(n)])
                    return jnp.concatenate([a, b], axis=0)
            return self._shard_map(inner, self._spec, self._spec)

        from .. import traffic
        if traffic.enabled and not isinstance(x, jax.core.Tracer):
            # charge the same static perms `build` lowers to; per-rank
            # bytes, and note_ppermute banks the matching coll_wire_bytes
            row = x.nbytes // max(R, 1)
            if r == 1:
                traffic.note_ppermute(
                    self.mesh, self.axis,
                    [(i, (i + shift) % self.n) for i in range(self.n)],
                    row, spc=self.spc, coll="ring_shift")
            else:
                s = shift % R
                off = (-s) % r
                q = (-s - off) // r
                n = self.n
                traffic.note_ppermute(
                    self.mesh, self.axis,
                    [((d + q) % n, d) for d in range(n)],
                    (r - off) * row, spc=self.spc, coll="ring_shift")
                if off:
                    traffic.note_ppermute(
                        self.mesh, self.axis,
                        [((d + q + 1) % n, d) for d in range(n)],
                        off * row, spc=self.spc, coll="ring_shift")
        return self._compiled(key, build)(x)

    # -- cartesian neighborhood exchange (halo / stencil) -------------------
    #
    # ≙ the neighborhood collectives (coll_basic_neighbor_*.c) specialized
    # to PERIODIC cartesian topologies — the torus halo exchange stencil
    # codes live on (BASELINE.json configs[4], HPCG/miniFE). On a periodic
    # cart every neighbor slot (dim d, direction ±1) is ONE static ring
    # permutation of the whole rank set, so the exchange compiles to
    # 2·ndims ppermutes — no per-rank send/recv loops. Non-periodic carts
    # have ragged boundary neighborhoods; those stay on the host path.

    def _cart_perms(self, topo) -> list:
        """[(dim, dir, [(src, dst), ...])] in the standard's slot order
        (per dim: -1 then +1). Requires a fully periodic cart of exactly
        R ranks."""
        R = self.mesh.shape[self.axis]
        rows = R  # perms act on mesh positions; rows==R enforced by caller
        perms = []
        for dim in range(len(topo.dims)):
            for disp in (-1, 1):
                pairs = []
                for i in range(rows):
                    c = topo.coords(i)
                    c[dim] += disp           # periodic wrap in rank_of
                    # value FROM the disp-neighbor lands AT i
                    pairs.append((topo.rank_of(c), i))
                perms.append((dim, disp, pairs))
        return perms

    def _check_cart(self, x, topo) -> None:
        if not all(topo.periods):
            raise ValueError("device cart exchange requires a fully "
                             "periodic topology (host path otherwise)")
        if topo.size != x.shape[0] or x.shape[0] != self.n:
            raise ValueError(
                f"cart size {topo.size} / rows {x.shape[0]} / mesh "
                f"{self.n} disagree (rank-per-position layout required)")

    def neighbor_allgather_cart(self, x: jax.Array, topo) -> jax.Array:
        """(R, b, *e) → (R, k, b, *e): slot j of row i is neighbor j's
        row (k = 2·ndims, dim-major, -1 then +1)."""
        self._check_cart(x, topo)
        key = ("neighbor_ag", tuple(topo.dims), x.shape, str(x.dtype))

        def build():
            # perm construction lives inside build(): the key (dims,
            # shape) fully determines it, so cache hits on the stencil
            # hot path skip the O(R·ndims) coordinate math entirely
            perms = self._cart_perms(topo)

            def inner(xs):           # (1, b, *e) per position (r == 1)
                slots = [lax.ppermute(xs, self.axis, pairs)
                         for _d, _s, pairs in perms]
                return jnp.stack(slots, axis=1)   # (1, k, b, *e)
            return self._shard_map(inner, self._spec, self._spec)

        return self._compiled(key, build)(x)

    def neighbor_alltoall_cart(self, x: jax.Array, topo) -> jax.Array:
        """(R, k, b, *e) → (R, k, b, *e): block j of rank i travels to
        neighbor j, landing in the MIRROR slot (dim's -1 block arrives in
        the receiver's +1 slot) — the halo-exchange data motion."""
        self._check_cart(x, topo)
        k = 2 * len(topo.dims)
        if x.shape[1] != k:
            raise ValueError(f"block dim {x.shape[1]} != {k} neighbors")
        key = ("neighbor_a2a", tuple(topo.dims), x.shape, str(x.dtype))

        def build():
            perms = self._cart_perms(topo)

            def inner(xs):           # (1, k, b, *e)
                slots = []
                for j, (_d, _s, pairs) in enumerate(perms):
                    mirror = j ^ 1   # (-1, +1) pair within the dim
                    slots.append(lax.ppermute(xs[:, mirror], self.axis,
                                              pairs))
                return jnp.stack(slots, axis=1)
            return self._shard_map(inner, self._spec, self._spec)

        return self._compiled(key, build)(x)

    def neighbor_allgather_graph(self, x: jax.Array, topo) -> jax.Array:
        """General-topology neighborhood allgather on device: (R, b, *e) →
        (R, maxdeg, b, *e), slot j of row i = in-neighbor j's row (rows
        past row i's degree are zeros). One all_gather + a cached masked
        gather-map — O(R·b) traffic rather than the periodic cart's
        neighbor-sparse 2·ndims ppermutes, but it serves ARBITRARY graphs
        and ragged degrees (coll_basic_neighbor_allgather.c generality).
        Degrees are host metadata; callers slice by topo.in_neighbors."""
        R = x.shape[0]
        if R != self.n or getattr(topo, "size",
                                  getattr(topo, "nnodes", R)) != R:
            raise ValueError(
                f"graph exchange needs rank-per-position layout (rows "
                f"{R} == mesh {self.n} == topo size)")
        # topologies are immutable: memoize the neighbor index ON the
        # topo so steady-state halo steps skip the O(R·maxdeg) rebuild
        idx = getattr(topo, "_dc_nbr_idx", None)
        if idx is None:
            nbrs = [list(topo.in_neighbors(i)) for i in range(R)]
            maxdeg = max((len(nb) for nb in nbrs), default=0)
            idx = np.full((R, max(maxdeg, 0)), -1, np.int32)
            for i, nb in enumerate(nbrs):
                idx[i, :len(nb)] = nb
            topo._dc_nbr_idx = idx
        maxdeg = idx.shape[1]
        if maxdeg == 0:
            return jnp.zeros((R, 0) + x.shape[1:], x.dtype)

        def build_idx():
            return jax.device_put(jnp.asarray(idx), self.sharding())

        idx_dev = self._idx_cached(
            ("neighbor_graph", idx.tobytes()), build_idx)
        key = ("neighbor_graph", maxdeg, x.shape, str(x.dtype))

        def build():
            def inner(xs, idxs):     # (1, b, *e), (1, maxdeg)
                full = lax.all_gather(xs, self.axis, axis=0,
                                      tiled=True)    # (R, b, *e)
                safe = jnp.maximum(idxs[0], 0)
                out = jnp.take(full, safe, axis=0)   # (maxdeg, b, *e)
                mask = (idxs[0] >= 0).reshape(
                    (maxdeg,) + (1,) * (out.ndim - 1))
                return jnp.where(mask, out, jnp.zeros_like(out))[None]
            return self._shard_map(inner, (self._spec, self._spec),
                                   self._spec)

        return self._compiled(key, build)(x, idx_dev)

    def neighbor_alltoall_graph(self, x: jax.Array, topo) -> jax.Array:
        """General-topology neighborhood alltoall: x (R, outdeg_max, b,
        *e) — block p of rank i goes to its p-th OUT-neighbor — →
        (R, indeg_max, b, *e), slot k of rank j from its k-th
        IN-neighbor (zeros past each rank's degree). Composed from the
        existing primitives: a per-row scatter onto destination ranks
        (row_gather), the dense-block ragged alltoallv, and a per-row
        reorder into in-neighbor slot order. Maps are memoized on the
        immutable topology per block size."""
        R = x.shape[0]
        if R != self.n or getattr(topo, "size", R) != R:
            raise ValueError(
                f"graph exchange needs rank-per-position layout (rows "
                f"{R} == mesh {self.n} == topo size)")
        K, b = x.shape[1], x.shape[2]
        elem = x.shape[3:]
        memo = getattr(topo, "_dc_a2a_maps", None)
        if memo is None or memo[0] != (K, b):
            outs = [list(topo.out_neighbors(i)) for i in range(R)]
            ins = [list(topo.in_neighbors(i)) for i in range(R)]
            if max((len(o) for o in outs), default=0) > K:
                raise ValueError(
                    f"block dim {K} < max out-degree "
                    f"{max(len(o) for o in outs)}")
            for o in outs:
                if len(set(o)) != len(o):
                    raise ValueError("repeated edges are not supported "
                                     "on the device graph path")
            # dst_map[i, j] = position of dst j in i's out-list (else -1)
            dst_map = np.full((R, R), -1, np.int32)
            for i, o in enumerate(outs):
                for p, j in enumerate(o):
                    dst_map[i, j] = p
            C = np.zeros((R, R), np.int64)     # elements i → j
            for i, o in enumerate(outs):
                for j in o:
                    C[i, j] = b
            # receiver: alltoallv concatenates by ASCENDING source; slot
            # k must hold in_neighbors[k] — element-level reorder map
            indeg_max = max((len(s) for s in ins), default=0)
            rd = np.full((R, indeg_max * b), -1, np.int32) \
                if indeg_max else np.zeros((R, 0), np.int32)
            for j, srcs in enumerate(ins):
                ordered = sorted(srcs)
                for k, s in enumerate(srcs):
                    pos = ordered.index(s)
                    rd[j, k * b:(k + 1) * b] = pos * b + np.arange(b)
            topo._dc_a2a_maps = memo = ((K, b), dst_map, C, rd, indeg_max)
        _kb, dst_map, C, rd, indeg_max = memo
        if indeg_max == 0:
            return jnp.zeros((R, 0, b) + elem, x.dtype)
        # static topology → the two device maps upload ONCE (LRU cache),
        # not per halo step like row_gather's per-call EP-routing form
        dst_dev = self._idx_cached(
            ("ga2a_dst", dst_map.tobytes()),
            lambda: jax.device_put(jnp.asarray(dst_map), self.sharding()))
        rd_dev = self._idx_cached(
            ("ga2a_rd", rd.tobytes()),
            lambda: jax.device_put(jnp.asarray(rd), self.sharding()))
        flat_blocks = x.reshape(R, K, -1)
        by_dst = self._row_gather_dev(flat_blocks, dst_dev,
                                      dst_map.shape[1])  # (R, R, b·e)
        blocks = by_dst.reshape((R, R, b) + elem)
        recv, _tot = self.alltoallv(blocks, C)           # (R, out_cap, *e)
        slot_elems = self._row_gather_dev(recv, rd_dev,
                                          rd.shape[1])   # (R, indeg·b, *e)
        return slot_elems.reshape((R, indeg_max, b) + elem)

    def push_row(self, x: jax.Array, src: int, dst: int) -> jax.Array:
        """ICI p2p: (R, *e) → (R, *e) with row dst ← row src's data, other
        rows unchanged — the one-hop collective-permute program behind
        device-payload send/recv on mesh comms (≙ the device-direct role of
        btl/smcuda GPU-IPC vs pml_ob1_accelerator.c host staging; SURVEY §7
        phase 4c). Only the one row crosses ICI; the executable is cached
        per (src, dst, shape, dtype), so a pipeline's stage→stage handoff
        compiles once."""
        R = x.shape[0]
        r = R // self.n
        key = ("push_row", int(src), int(dst), x.shape, str(x.dtype))

        def build():
            src_dev, src_loc = divmod(int(src), r)
            dst_dev, dst_loc = divmod(int(dst), r)

            def inner(xs):           # (r, *e)
                row = xs[src_loc]
                if src_dev != dst_dev:
                    row = lax.ppermute(row, self.axis,
                                       [(src_dev, dst_dev)])
                i = lax.axis_index(self.axis)
                updated = lax.dynamic_update_index_in_dim(
                    xs, row.astype(xs.dtype), dst_loc, 0)
                return jnp.where(i == dst_dev, updated, xs)
            return self._shard_map(inner, self._spec, self._spec)

        from .. import traffic
        if traffic.enabled and not isinstance(x, jax.core.Tracer):
            src_dev = int(src) // r
            dst_dev = int(dst) // r
            if src_dev != dst_dev:
                # exactly one row crosses ICI (the [(src_dev, dst_dev)]
                # perm inner lowers to)
                traffic.note_ppermute(
                    self.mesh, self.axis, [(src_dev, dst_dev)],
                    x.nbytes // max(R, 1), spc=self.spc, coll="push_row")
        return self._compiled(key, build)(x)

    def scan(self, x: jax.Array, op: Op = SUM, exclusive: bool = False
             ) -> jax.Array:
        """Prefix reduction across ranks: row i ← op(rows 0..i)."""
        R = x.shape[0]
        r = R // self.n
        key = ("scan", op.name, bool(exclusive), x.shape, str(x.dtype))

        cum_local = {"sum": lax.cumsum, "max": lax.cummax,
                     "min": lax.cummin, "prod": lax.cumprod}.get(op.name)

        def build():
            if cum_local is not None:
                def inner(xs):       # (r, *e)
                    # local prefix + tiny exchange: only the per-DEVICE
                    # totals cross ICI (n rows, not R — the bandwidth shape
                    # VERDICT r1 weak#7 asked for), then each device offsets
                    # its local prefix by the scan of lower devices' totals
                    loc = cum_local(xs, axis=0)            # (r, *e)
                    totals = lax.all_gather(loc[-1], self.axis)  # (n, *e)
                    csum = cum_local(totals, axis=0)       # inclusive
                    i = lax.axis_index(self.axis)
                    base_idx = jnp.maximum(i - 1, 0)
                    base = jnp.where(i > 0, csum[base_idx],
                                     _op_identity(op, totals[0]))
                    out = op.fn(jnp.broadcast_to(base[None], loc.shape), loc)
                    if exclusive:
                        prev = jnp.concatenate(
                            [jnp.broadcast_to(base[None], loc[:1].shape),
                             out[:-1]], axis=0)
                        return prev
                    return out
            else:
                def inner(xs):       # general op: gather + associative scan
                    full = lax.all_gather(xs, self.axis, axis=0, tiled=True)
                    csum = lax.associative_scan(
                        lambda a, b: op.fn(a, b), full, axis=0)
                    if exclusive:
                        try:
                            z = _op_identity(op, csum[:1])
                        except ValueError:
                            # user op without a registered identity: MPI
                            # leaves exclusive row 0 undefined; zeros keep
                            # the historical behavior
                            z = jnp.zeros_like(csum[:1])
                        csum = jnp.concatenate([z, csum[:-1]], axis=0)
                    i = lax.axis_index(self.axis)
                    return lax.dynamic_slice_in_dim(csum, i * r, r, 0)
            return self._shard_map(inner, self._spec, self._spec)

        return self._compiled(key, build)(x)

    # -- ragged (v-variant) collectives ------------------------------------
    #
    # TPU-first shape for the reference's v-collectives
    # (coll_base_alltoallv.c:194 pairwise, coll_base_allgatherv.c:95 bruck,
    # coll_base_gather.c:41, coll_base_scatter.c:63): ragged buffers live on
    # device as PADDED blocks — (R, cap, *e) with row i holding counts[i]
    # valid elements — and the ragged structure travels as a DEVICE ARGUMENT
    # (a host-computed int32 gather map + mask), never as a baked constant.
    # Executables are therefore keyed on bucketed shapes only: an MoE router
    # whose per-expert counts change every step reuses one compiled program
    # as long as the capacity bucket and total are stable (token routing
    # conserves the total), which is the whole game for the EP hot path.

    @staticmethod
    def _bucket(n: int) -> int:
        """Next power-of-two capacity bucket (≥1)."""
        return 1 << max(int(n) - 1, 0).bit_length() if n > 1 else 1

    @staticmethod
    def pack_ragged_blocks(rows: np.ndarray, C: np.ndarray,
                           cap: int) -> np.ndarray:
        """Host helper: dense per-rank rows (R, total, *e) + counts matrix
        C (C[i, j] = elements rank i sends to j, row sums ≤ total) → the
        padded (R, R, cap, *e) block layout alltoallv consumes. One
        implementation shared by the bench, the tuner, and tests."""
        rows = np.asarray(rows)
        R = C.shape[0]
        out = np.zeros((R, R, cap) + rows.shape[2:], rows.dtype)
        for i in range(R):
            off = 0
            for j in range(R):
                c = int(C[i, j])
                out[i, j, :c] = rows[i, off:off + c]
                off += c
        return out

    @staticmethod
    def compact_ragged_blocks(blocks: np.ndarray, C: np.ndarray,
                              out_cap: int) -> np.ndarray:
        """Host helper: the inverse compaction — padded (R, R, cap, *e)
        blocks → (R, out_cap, *e) rows, row j the dense concatenation of
        every source's valid elements for j (the staged arm of
        alltoallv, and the expected-value oracle in tests)."""
        blocks = np.asarray(blocks)
        R = C.shape[0]
        out = np.zeros((R, out_cap) + blocks.shape[3:], blocks.dtype)
        for j in range(R):
            pos = 0
            for i in range(R):
                c = int(C[i, j])
                out[j, pos:pos + c] = blocks[i, j, :c]
                pos += c
        return out

    def pad_ragged(self, arrays: Sequence[np.ndarray]
                   ) -> Tuple[jax.Array, list]:
        """Per-rank ragged host buffers → ((R, cap_bucket, *e) padded device
        array, counts). The ragged analog of from_ranks."""
        counts = [int(np.asarray(a).shape[0]) for a in arrays]
        cap = self._bucket(max(counts) if counts else 1)
        elem = np.asarray(arrays[0]).shape[1:]
        out = np.zeros((len(arrays), cap) + elem,
                       dtype=np.asarray(arrays[0]).dtype)
        for i, a in enumerate(arrays):
            out[i, :counts[i]] = a
        return jax.device_put(jnp.asarray(out), self.sharding()), counts

    def unpad_ragged(self, x: jax.Array, counts: Sequence[int]) -> list:
        """Padded (R, cap, *e) → list of exact per-rank host arrays."""
        host = np.asarray(jax.device_get(x))
        return [host[i, :int(c)] for i, c in enumerate(counts)]

    def _replicated(self, a: np.ndarray) -> jax.Array:
        return jax.device_put(jnp.asarray(a),
                              NamedSharding(self.mesh, P()))

    def allgatherv(self, x: jax.Array, counts: Sequence[int]) -> jax.Array:
        """(R, cap, *e) padded + counts → (R, total, *e): every row is the
        dense concatenation of all ranks' valid elements (MPI_Allgatherv
        with default contiguous displacements)."""
        R, cap = x.shape[0], x.shape[1]
        counts = [int(c) for c in counts]
        total = sum(counts)
        def build_idx():
            # gather map: output position → flattened (rank, offset) source;
            # cached on device so a repeated counts pattern pays the host
            # build + H2D once, not per call
            idx = np.concatenate(
                [np.arange(c, dtype=np.int32) + i * cap
                 for i, c in enumerate(counts)]) if total else \
                np.zeros((0,), np.int32)
            return self._replicated(idx)

        idx_dev = self._idx_cached(("allgatherv", cap, tuple(counts)),
                                   build_idx)
        key = ("allgatherv", x.shape, total, str(x.dtype))

        def build():
            def inner(xs, idxs):     # xs (r, cap, *e); idxs (total,) replic.
                full = lax.all_gather(xs, self.axis, axis=0, tiled=True)
                flat = full.reshape((-1,) + full.shape[2:])   # (R*cap, *e)
                out = jnp.take(flat, idxs, axis=0)            # (total, *e)
                return jnp.broadcast_to(out[None],
                                        (xs.shape[0],) + out.shape)
            return self._shard_map(inner, (self._spec, P()), self._spec)

        return self._compiled(key, build)(x, idx_dev)

    def gather(self, x: jax.Array, root: int = 0) -> jax.Array:
        """Rooted gather: MPI promises only the root's row; on ICI the
        allgather executable IS the gather (result replicated is free
        relative to the ring traffic) — same collapse as reduce≡allreduce."""
        return self.allgather(x)

    def gatherv(self, x: jax.Array, counts: Sequence[int],
                root: int = 0) -> jax.Array:
        return self.allgatherv(x, counts)

    def scatter(self, x: jax.Array, root: int = 0) -> jax.Array:
        """(R, R, b, *e) — row `root` holds R blocks — → (R, b, *e): row i
        gets root's block i. Root's row crosses ICI once (masked psum, the
        bcast trick), then every device slices its own blocks locally."""
        R = x.shape[0]
        r = R // self.n
        key = ("scatter", int(root), x.shape, str(x.dtype))

        def build():
            root_dev, root_local = divmod(int(root), r)

            def inner(xs):           # (r, R, b, *e)
                i = lax.axis_index(self.axis)
                contrib = jnp.where(i == root_dev, xs[root_local],
                                    jnp.zeros_like(xs[root_local]))
                full = lax.psum(contrib, self.axis)       # (R, b, *e)
                return lax.dynamic_slice_in_dim(full, i * r, r, 0)
            return self._shard_map(inner, self._spec, self._spec)

        return self._compiled(key, build)(x)

    def scatterv(self, x: jax.Array, counts: Sequence[int],
                 root: int = 0) -> jax.Array:
        """(R, R, cap, *e) padded blocks in row `root` → (R, cap, *e):
        row i gets root's block i (counts[i] valid elements, still padded —
        unpad_ragged for exact rows)."""
        return self.scatter(x, root)

    def alltoallv(self, x: jax.Array, counts) -> Tuple[jax.Array, list]:
        """Ragged all-to-all. x: (R, R, cap, *e) padded blocks — block
        [i, j] holds counts[i][j] valid elements from rank i to rank j.
        Returns ((R, out_cap, *e) padded, recv_counts): row j is the dense
        concatenation over sources of their valid elements for j.

        The dense ICI all-to-all moves the padded blocks (same program as
        alltoall); compaction happens target-side via a per-row gather map
        passed as a sharded device argument. One executable per
        (in-shape, out_cap-bucket, dtype) — routing patterns that keep the
        capacity bucket stable share it.
        """
        C = np.asarray(counts, dtype=np.int64)
        R, cap = x.shape[0], x.shape[2]
        r = R // self.n
        recv_tot = C.sum(axis=0)                  # per-destination totals
        out_cap = self._bucket(int(recv_tot.max()) if R else 1)
        def build_idx():
            # per-destination gather map over the post-exchange (R*cap)
            # flat block layout; -1 = padding (masked to zero). Cached on
            # device per counts matrix.
            idx = np.full((R, out_cap), -1, np.int32)
            for j in range(R):
                pos = 0
                for i in range(R):
                    c = int(C[i, j])
                    idx[j, pos:pos + c] = np.arange(c, dtype=np.int32) \
                        + i * cap
                    pos += c
            return jax.device_put(jnp.asarray(idx), self.sharding())

        idx_dev = self._idx_cached(("alltoallv", cap, C.tobytes()),
                                   build_idx)
        key = ("alltoallv", x.shape, out_cap, str(x.dtype))

        def build():
            def inner(xs, idxs):     # xs (r, R, cap, *e); idxs (r, out_cap)
                if r == 1:
                    mixed = lax.all_to_all(xs, self.axis, split_axis=1,
                                           concat_axis=1, tiled=True)
                else:
                    mixed = lax.all_to_all(xs, self.axis, split_axis=1,
                                           concat_axis=0, tiled=True)
                    mixed = jnp.swapaxes(mixed, 0, 1)     # (r, R, cap, *e)
                flat = mixed.reshape((mixed.shape[0], -1) + mixed.shape[3:])
                safe = jnp.maximum(idxs, 0)
                out = jax.vmap(lambda f, i: jnp.take(f, i, axis=0))(
                    flat, safe)                           # (r, out_cap, *e)
                mask = (idxs >= 0).reshape(idxs.shape + (1,) * (out.ndim - 2))
                return jnp.where(mask, out, jnp.zeros_like(out))
            return self._shard_map(inner, (self._spec, self._spec),
                                   self._spec)

        out = self._compiled(key, build)(x, idx_dev)
        return out, [int(t) for t in recv_tot]

    @staticmethod
    def compact_from_rows(rows: np.ndarray, C: np.ndarray,
                          out_cap: int) -> np.ndarray:
        """Host oracle/staged arm for :meth:`alltoallv_from_rows`: dense
        per-rank send rows + counts matrix → the compact padded receive
        rows, by direct O(total) segment copies (no padded block
        intermediate). One implementation shared by the coll/xla staged
        arm, the bench, and tests."""
        rows = np.asarray(rows)
        C = np.asarray(C, dtype=np.int64)
        R = C.shape[0]
        soff = np.zeros((R, R), np.int64)
        soff[:, 1:] = np.cumsum(C, axis=1)[:, :-1]
        out = np.zeros((R, int(out_cap)) + rows.shape[2:], rows.dtype)
        for j in range(R):
            pos = 0
            for i in range(R):
                c = int(C[i, j])
                out[j, pos:pos + c] = rows[i, soff[i, j]:soff[i, j] + c]
                pos += c
        return out

    def a2av_plan(self, shape: tuple, counts,
                  slice_cap: Optional[int] = None) -> Dict[str, int]:
        """The (slice_cap, scan_steps, out_cap) figures the sliced ragged
        exchange takes for a (R, L, *e) send of ``shape`` + counts matrix
        — pure shape math, no dispatch. An explicit ``slice_cap`` wins;
        else the ``coll_a2av_slice_cap`` var; else the ~1M-element
        transient heuristic. Decision audits record these figures so the
        footprint/padding trade is visible per collective."""
        C = np.asarray(counts, dtype=np.int64)
        R = shape[0]
        cap = self._bucket(int(C.max()) if C.size else 1)
        out_cap = self._bucket(int(C.sum(axis=0).max()) if C.size else 1)
        elem = int(np.prod(shape[2:])) if len(shape) > 2 else 1
        if slice_cap is None:
            cfgd = int(_var.get("coll_a2av_slice_cap", 0) or 0)
            if cfgd > 0:
                slice_cap = min(cap, cfgd)
            else:
                # bound the per-step transient (the (R, S, *e) gather) to
                # ~1M ELEMENTS per device row — trailing elem dims count
                slice_cap = min(cap, max(64, self._bucket(
                    max(1, (1 << 20) // max(R * elem, 1)))))
        slice_cap = max(1, int(slice_cap))
        return {"slice_cap": int(slice_cap),
                "scan_steps": int(-(-cap // slice_cap)),
                "out_cap": int(out_cap)}

    @trace.timed("ompi.coll.launch")
    def alltoallv_from_rows(self, x: jax.Array, counts,
                            slice_cap: Optional[int] = None
                            ) -> Tuple[jax.Array, list]:
        """Ragged all-to-all straight from DENSE rows: (R, L, *e) + counts
        matrix C → ((R, out_cap, *e) padded-dense, recv_counts), the same
        result as ``pack_ragged_blocks`` + :meth:`alltoallv` — but the
        (R, R, cap) padded block tensor NEVER materializes anywhere.
        The capacity dimension is processed in ``slice_cap``-sized slices
        inside one ``lax.scan``. Each (source, destination) segment is one
        contiguous run in the sender's row and one in the receiver's, so a
        step moves it with per-peer contiguous slices, never per-element
        index work: the sender takes ``S = slice_cap`` rows of its row at
        ``soff + min(base, C)`` for each destination (``dynamic_slice``,
        offsets from device-cached cumsum maps), one dense ``all_to_all``
        exchanges them, and the receiver writes each source's slice at
        ``roff + min(base, C)`` as a read-modify-write that keeps the
        slice's invalid tail from clobbering the next source's run. The
        send row is padded with ``S`` zero rows once, before the scan:
        ``dynamic_slice`` clamps its start so the window fits, and a
        window near the row's end would otherwise shift and send the
        wrong elements. The output carry is ``out_cap + S`` long for the
        same reason. Peak extra HBM
        per device is O(R·slice_cap·r) instead of O(R·cap·r) — at the
        bench's 16 MB/rank ragged shape that is the difference between a
        256 MiB resident padding blowup (the round-2→5 sweep truncation)
        and a few-MB transient. Wire traffic is the same padded-slice
        volume the block form sends (ragged rows mean some slice padding;
        the scan trades that for footprint).

        Row i of ``x`` holds its sends dense and concatenated in
        destination order (sum_j C[i,j] valid elements). recv row j is
        the dense concatenation over sources, like :meth:`alltoallv`."""
        C = np.asarray(counts, dtype=np.int64)
        R = x.shape[0]
        r = R // self.n
        plan = self.a2av_plan(x.shape, C, slice_cap)
        slice_cap = plan["slice_cap"]
        k = plan["scan_steps"]
        out_cap = plan["out_cap"]
        # stash the footprint/padding trade this call actually took so the
        # caller's decision audit can record it
        self._last_a2av = dict(plan)
        # k is BAKED into the compiled scan: it must be in the cache key
        # (bucketed cap keeps nearby routings sharing one executable;
        # without k in the key a smaller-cap executable would be reused
        # and silently drop the tail slices)

        def build_maps():
            soff = np.zeros((R, R), np.int32)  # send offsets in row i
            soff[:, 1:] = np.cumsum(C, axis=1)[:, :-1]
            roff = np.zeros((R, R), np.int32)  # recv offsets in row j
            roff[1:, :] = np.cumsum(C, axis=0)[:-1, :]
            put = lambda a: jax.device_put(jnp.asarray(a),
                                           self.sharding())
            return (put(soff), put(C.astype(np.int32)),
                    put(roff.T.copy()), put(C.T.astype(np.int32).copy()))

        soff_d, crow_d, rofft_d, ccolt_d = self._idx_cached(
            ("a2av_rows", C.tobytes()), build_maps)
        key = ("alltoallv_from_rows", x.shape, out_cap, slice_cap, k,
               str(x.dtype))

        def build():
            S = slice_cap
            e_shape = x.shape[2:]

            def inner(xs, soff, crow, rofft, ccolt):
                # xs (r, L, *e); soff/crow: send offsets/counts for the
                # LOCAL source rows; rofft/ccolt: recv offsets/counts for
                # the LOCAL destination rows (transposed views). Every
                # slice below has a static row and a scalar start: no
                # per-element gather or scatter (a vmapped dynamic_slice
                # would batch its start and lower to a gather).
                rr = xs.shape[0]
                zeros_e = (0,) * len(e_shape)
                p = jnp.arange(S, dtype=jnp.int32).reshape(
                    (S,) + (1,) * len(e_shape))
                win = (1, S) + e_shape
                # S zero rows past the end: a window that starts near the
                # row's end would otherwise be clamped back and shifted
                xp = jnp.pad(xs, ((0, 0), (0, S)) + ((0, 0),) * len(e_shape))

                def window(buf, a, st):
                    return lax.dynamic_slice(buf, (a, st) + zeros_e, win)[0]

                def body(out, s):
                    base = s * S
                    # send side: block j's next S elements, one contiguous
                    # run of the row from soff + min(base, C); positions
                    # past C - base are never kept by the receiver
                    sst = soff + jnp.minimum(base, crow)      # (rr, R)
                    g = jnp.stack([jnp.stack([
                        window(xp, a, sst[a, j]) for j in range(R)])
                        for a in range(rr)])                  # (rr, R, S, *e)
                    if r == 1:
                        mixed = lax.all_to_all(g, self.axis, split_axis=1,
                                               concat_axis=1, tiled=True)
                    else:
                        mixed = lax.all_to_all(g, self.axis, split_axis=1,
                                               concat_axis=0, tiled=True)
                        mixed = jnp.swapaxes(mixed, 0, 1)  # (rr, R, S, *e)
                    # receive side: source i's valid prefix lands at
                    # roff + min(base, C); its invalid tail keeps what the
                    # window holds (the next source's run), so sources go
                    # in order as read-modify-writes
                    rst = rofft + jnp.minimum(base, ccolt)    # (rr, R)
                    nv = jnp.clip(ccolt - base, 0, S)
                    for a in range(rr):
                        for i in range(R):
                            st = rst[a, i]
                            kept = jnp.where(p < nv[a, i], mixed[a, i],
                                             window(out, a, st))
                            out = lax.dynamic_update_slice(
                                out, kept[None], (a, st) + zeros_e)
                    return out, None

                out0 = jnp.zeros((rr, out_cap + S) + e_shape, xs.dtype)
                # the body's all_to_all makes the carry VARYING over the
                # mesh axis; the zeros init must match (shard_map VMA)
                out0 = lax.pcast(out0, self.axis, to="varying")
                out, _ = lax.scan(body, out0,
                                  jnp.arange(k, dtype=jnp.int32))
                return out[:, :out_cap]

            return self._shard_map(
                inner, (self._spec,) * 5, self._spec)

        out = self._compiled(key, build)(x, soff_d, crow_d, rofft_d,
                                         ccolt_d)
        return out, [int(t) for t in C.sum(axis=0)]

    def _row_gather_dev(self, x: jax.Array, idx_dev, m: int) -> jax.Array:
        """row_gather against an ALREADY-device-resident (R, m) map —
        the zero-upload form static-topology callers use."""
        key = ("row_gather", x.shape, m, str(x.dtype))

        def build():
            def inner(xs, idxs):     # (r, T, *e), (r, M)
                safe = jnp.maximum(idxs, 0)
                out = jax.vmap(lambda f, i: jnp.take(f, i, axis=0))(
                    xs, safe)
                mask = (idxs >= 0).reshape(
                    idxs.shape + (1,) * (out.ndim - 2))
                return jnp.where(mask, out, jnp.zeros_like(out))
            return self._shard_map(inner, (self._spec, self._spec),
                                   self._spec)

        return self._compiled(key, build)(x, idx_dev)

    def row_gather(self, x: jax.Array, idx: np.ndarray) -> jax.Array:
        """Per-row device gather: (R, T, *e) + host map idx (R, M) →
        (R, M, *e), out[i, m] = x[i, idx[i, m]] (idx −1 → zeros). The map
        travels as a sharded device argument, so one executable per
        (shape, M, dtype) serves every permutation — the building block the
        ragged EP pipeline uses to form/unform alltoallv blocks. The map
        uploads per call (EP routing changes every step); static-topology
        callers cache the device map and use _row_gather_dev."""
        idx = np.asarray(idx, np.int32)
        return self._row_gather_dev(
            x, jax.device_put(jnp.asarray(idx), self.sharding()),
            idx.shape[1])

    def reduce_scatter_v(self, x: jax.Array, counts: Sequence[int],
                         op: Op = SUM) -> jax.Array:
        """(R, total, *e) + counts → (R, cap, *e) padded: row i holds the
        op-reduction of every rank's block [displ_i : displ_i+counts_i].
        SUM rides psum_scatter (traffic-optimal, the Rabenseifner half);
        other ops reduce fully then slice."""
        counts = [int(c) for c in counts]
        R = x.shape[0]
        r = R // self.n
        cap = self._bucket(max(counts) if counts else 1)
        def build_idx():
            displs = np.concatenate(
                [[0], np.cumsum(counts)[:-1]]).astype(np.int64)
            # block map: (R, cap) position → source offset in the dense row
            idx = np.full((R, cap), -1, np.int32)
            for i, c in enumerate(counts):
                idx[i, :c] = np.arange(c, dtype=np.int32) + int(displs[i])
            return (self._replicated(np.maximum(idx, 0)),
                    self._replicated(idx >= 0))

        safe_dev, mask_dev = self._idx_cached(
            ("reduce_scatter_v", cap, tuple(counts)), build_idx)
        key = ("reduce_scatter_v", op.name, x.shape, cap, str(x.dtype))

        def build():
            if op.name == "sum":
                def inner(xs, safe, mask):   # xs (r, total, *e)
                    folded = self._fold_local(xs, op)        # (total, *e)
                    # rearrange into padded blocks (R*cap, *e), zeros in pad
                    blocks = jnp.take(folded, safe.reshape(-1), axis=0)
                    m = mask.reshape((-1,) + (1,) * (blocks.ndim - 1))
                    blocks = jnp.where(m, blocks, jnp.zeros_like(blocks))
                    mine = lax.psum_scatter(blocks, self.axis,
                                            scatter_dimension=0, tiled=True)
                    return mine.reshape((r, cap) + xs.shape[2:])
                return self._shard_map(inner, (self._spec, P(), P()),
                                       self._spec)

            def inner(xs, safe, mask):
                red = preduce(self._fold_local(xs, op), self.axis, op)
                i = lax.axis_index(self.axis)
                my_safe = lax.dynamic_slice_in_dim(safe, i * r, r, 0)
                my_mask = lax.dynamic_slice_in_dim(mask, i * r, r, 0)
                mine = jax.vmap(lambda s: jnp.take(red, s, axis=0))(my_safe)
                m = my_mask.reshape(my_mask.shape + (1,) * (mine.ndim - 2))
                return jnp.where(m, mine, jnp.zeros_like(mine))
            return self._shard_map(inner, (self._spec, P(), P()), self._spec)

        return self._compiled(key, build)(x, safe_dev, mask_dev)

    def barrier(self) -> None:
        """A real cross-device sync: tiny psum + block."""
        key = ("barrier",)

        def build():
            def inner(xs):
                return lax.psum(xs, self.axis)
            return self._shard_map(inner, P(self.axis), P())

        # from_local works in both the single-controller and multi-process
        # (rank-per-chip) regimes — device_put would reject the
        # non-addressable devices of other processes
        pid = jax.process_index()
        n_local = sum(1 for d in self.mesh.devices.flat
                      if d.process_index == pid)
        token = self.from_local(np.zeros((n_local,), np.int32))
        self._compiled(key, build)(token).block_until_ready()
