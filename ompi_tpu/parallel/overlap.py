"""parallel/overlap — bucketed backward-overlapped gradient sync.

The dp gradient allreduce is the framework's highest-volume collective,
and the seed issued it in the worst possible shape: one collective per
parameter leaf AFTER the full backward (``_quant_grad_sync``), so tiny
leaves (norms, biases) pay the dispatch latency floor and the ICI sits
idle during all of backward.  This module is the DDP-style answer:
gradients are flattened into fixed-byte BUCKETS (default ~4 MiB,
``coll_xla_grad_bucket_bytes`` / ``Config(grad_bucket_bytes=...)``) in
reverse flatten order — the order the backward pass produces them — and
each bucket's allreduce is issued the moment its last cotangent exists,
so bucket *i*'s exchange overlaps the remaining backward compute (XLA's
latency-hiding scheduler interleaves the collective with the ongoing
dots) instead of serializing after it.

Mechanism: an identity ``jax.custom_vjp`` "tag" wraps each bucket's
parameter leaves on the way INTO the loss; its backward rule therefore
receives exactly that bucket's cotangents at the point in the backward
graph where they are produced, concatenates them into one flat f32
vector, runs ONE allreduce — native ``lax.pmean`` or the block-quantized
``coll/quant.psum_quant`` (EQuARX tier), chosen per bucket by the same
decision layer that arbitrates every other device collective
(``coll/xla.decide_mode`` with coll name ``grad_sync``: force var >
blanket switch > DEVICE_RULES rows > platform default) — and splits the
result back into per-leaf gradients.  The per-leaf collective storm
collapses to at most ``ceil(total_grad_bytes / bucket_bytes)`` exchanges.

Like ``_quant_grad_sync``, the shard_map here runs over ``dp`` only: on
a dp×tp/sp mesh it would replicate the other axes and silently undo
their parameter sharding, so such meshes are refused loudly.

Observability: one ``trace.decision("grad_sync", ...)`` per bucket per
build (``explain_last("grad_sync")`` names the chosen arm + bucket
size), pvars ``grad_bucket_count`` / ``grad_bucket_bytes`` (read-through
from :mod:`ompi_tpu.spc`), and — when the sync runs outside a jit trace
with tracing on — one measured ``grad_sync:run`` span carrying the bucket
count (the host cannot see bucket boundaries inside the compiled
program, so it records no per-bucket span).
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .. import trace
from ..core import var as _var

GRAD_SYNC_MODES = ("perleaf", "bucketed", "unsynced")

# pvar state (read-through from spc.Counters): the most recently built
# grad-sync plan — how many bucket exchanges it issues and the total
# gradient bytes they carry
_PVARS = {"grad_bucket_count": 0, "grad_bucket_bytes": 0}
_last_plan: Optional[Tuple["BucketPlan", Tuple[str, ...]]] = None


def pvar_value(name: str) -> int:
    """MPI_T read-through accessor (spc.Counters.get/snapshot)."""
    return _PVARS[name]


# -- post-sync hooks ---------------------------------------------------------
# callables(grads) invoked after every eager grad sync, right before the
# (loss, grads) return — the piggyback point low-rate maintenance work
# rides on the sync cadence (ft/elastic's peer-shadow ring_shift refresh
# is the canonical rider).  Hooks run on the host, outside any trace; a
# raising hook is logged with attribution and dropped for the step
# rather than poisoning the training loop.

_post_sync_hooks: List[Callable] = []


def add_post_sync_hook(fn: Callable) -> Callable:
    _post_sync_hooks.append(fn)
    return fn


def remove_post_sync_hook(fn: Callable) -> None:
    try:
        _post_sync_hooks.remove(fn)
    except ValueError:
        pass


def _run_post_sync(grads) -> None:
    if not _post_sync_hooks:
        return
    from ..core.output import output
    for fn in list(_post_sync_hooks):
        try:
            fn(grads)
        except Exception as err:
            name = getattr(fn, "__qualname__",
                           getattr(fn, "__name__", repr(fn)))
            output.verbose(1, "overlap",
                           f"post-sync hook {name} raised "
                           f"{type(err).__name__}: {err}")


# -- bucket planning ---------------------------------------------------------

@dataclass(frozen=True)
class Bucket:
    indices: Tuple[int, ...]     # leaf indices into the FLATTEN order
    nbytes: int


@dataclass(frozen=True)
class BucketPlan:
    buckets: Tuple[Bucket, ...]
    total_bytes: int
    bucket_bytes: int            # the target size buckets close at
    n_leaves: int

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    @property
    def max_buckets(self) -> int:
        """The storm-collapse guarantee: ceil(total / bucket_bytes)."""
        return max(1, math.ceil(self.total_bytes / self.bucket_bytes))


def bucket_plan(leaves: Sequence, bucket_bytes: int) -> BucketPlan:
    """Group leaves (anything with .shape/.dtype, flatten order) into
    fixed-byte buckets walking the list in REVERSE — the approximate
    order the backward pass finalizes their cotangents (last layer
    first).  A bucket closes only AFTER its cumulative bytes reach the
    target, so every closed bucket carries >= bucket_bytes and the count
    is provably <= ceil(total_bytes / bucket_bytes)."""
    bucket_bytes = int(bucket_bytes)
    if bucket_bytes < 1:
        raise ValueError(f"bucket_bytes must be >= 1, got {bucket_bytes}")
    sizes = [int(np.prod(x.shape) if x.shape else 1)
             * np.dtype(x.dtype).itemsize for x in leaves]
    buckets: List[Bucket] = []
    group: List[int] = []
    acc = 0
    for i in reversed(range(len(sizes))):
        group.append(i)
        acc += sizes[i]
        if acc >= bucket_bytes:
            buckets.append(Bucket(tuple(group), acc))
            group, acc = [], 0
    if group:
        buckets.append(Bucket(tuple(group), acc))
    return BucketPlan(tuple(buckets), sum(sizes), bucket_bytes, len(sizes))


def resolve_bucket_bytes(bucket_bytes: Optional[int] = None) -> int:
    """Config override, else the coll_xla_grad_bucket_bytes var (~4 MiB)."""
    nb = int(bucket_bytes if bucket_bytes is not None
             else _var.get("coll_xla_grad_bucket_bytes", 4 << 20))
    if nb < 1:
        raise ValueError(f"grad_bucket_bytes must be >= 1, got {nb}")
    return nb


# -- decision + audit --------------------------------------------------------

def _mesh_platform(mesh: Mesh) -> str:
    return next(iter(mesh.devices.flat)).platform


def _decide_buckets(plan: BucketPlan, ndev: int, platform: str,
                    block: int, plane: Optional[str] = None,
                    hier_ok: bool = False,
                    hier_why: str = "") -> Tuple[str, ...]:
    """One decision-layer pass per bucket (coll name ``grad_sync``,
    arms native|quant|hier|hier+quant — the hier arms only when the
    sync spans a two-tier dpo×dp split) + the audit record feeding
    explain_last and the bucket pvars.  Runs at trace/build time — once
    per compiled program, which is exactly how often the arm can
    change."""
    from ..coll import xla as _xla

    rules = _xla._load_device_rules()
    arms = []
    for i, b in enumerate(plan.buckets):
        arm, reason, chain = _xla.decide_mode(
            "grad_sync", b.nbytes, ndev, platform, rules,
            allowed=("native", "quant"), quant_ok=True, dtype=np.float32,
            plane=plane, hier_ok=hier_ok, hier_why=hier_why)
        arms.append(arm)
        if trace.enabled:
            details = dict(bucket=i, n_buckets=plan.n_buckets,
                           bucket_bytes=plan.bucket_bytes,
                           leaves=len(b.indices), ndev=ndev,
                           total_bytes=plan.total_bytes, chain=list(chain))
            if arm == "quant":
                from ..coll.quant import grad_bucket_span_args
                details.update(grad_bucket_span_args(
                    b.nbytes, ndev, np.float32, block))
            trace.decision("grad_sync", arm=arm, reason=reason,
                           verdict=None, nbytes=b.nbytes, **details)
    _PVARS["grad_bucket_count"] = plan.n_buckets
    _PVARS["grad_bucket_bytes"] = plan.total_bytes
    return tuple(arms)


# -- the custom_vjp bucket tag ----------------------------------------------

def _make_bucket_tag(shapes, dtypes, arm: str, axis, n: int,
                     block: int, levels=None):
    """Identity on a tuple of leaves whose backward rule syncs the
    bucket: concatenate the cotangents into one flat f32 vector, ONE
    allreduce (native pmean, psum_quant, or the two-tier hierarchical
    form per the decided arm), split back.  The rule fires exactly when
    the backward pass has produced every cotangent in the bucket — the
    overlap point.  ``axis`` may be a tuple of mesh axis names (the
    dpo×dp sync domain); ``levels`` is ``(inner, outer, n_outer)`` for
    the hier arms.

    Under shard_map's VMA typing the tag is the leaves' replicated ->
    per-device cast: the forward marks them varying over ``axis`` (so
    autodiff inserts no psum of its own) and the backward's allreduce is
    that cast's transpose, returning replicated cotangents."""
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)

    def sync(cts):
        parts = [jnp.reshape(c, (-1,)).astype(jnp.float32) for c in cts]
        flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        if arm in ("hier", "hier+quant"):
            # HAN shape over the two-tier sync domain: RS(inner ICI) →
            # allreduce(outer DCN, 1/n_inner of the bytes, quantized
            # for hier+quant) → AG(inner ICI); mean via the static n
            inner, outer, n_outer = levels
            from .hierarchy import (hierarchical_psum,
                                    hierarchical_psum_quant)
            if arm == "hier+quant":
                flat = hierarchical_psum_quant(flat, inner, outer,
                                               n_outer, block=block) / n
            else:
                flat = hierarchical_psum(flat, inner, outer) / n
        elif arm == "quant":
            from ..coll.quant import psum_quant
            flat = psum_quant(flat, axis, n, avg=True, block=block)
        else:
            flat = lax.pmean(flat, axis)
        out, off = [], 0
        for shape, size, dt in zip(shapes, sizes, dtypes):
            out.append(jnp.reshape(
                lax.dynamic_slice_in_dim(flat, off, size), shape)
                .astype(dt))
            off += size
        return tuple(out)

    @jax.custom_vjp
    def tag(group):
        return lax.pcast(group, axis, to="varying")

    def fwd(group):
        return tag(group), None

    def bwd(_, cts):
        return (sync(cts),)

    tag.defvjp(fwd, bwd)
    return tag


def _apply_bucket_tags(leaves: list, plan: BucketPlan,
                       arms: Sequence[str], axis, n: int,
                       block: int, levels=None) -> list:
    out = list(leaves)
    for b, arm in zip(plan.buckets, arms):
        group = tuple(out[j] for j in b.indices)
        tag = _make_bucket_tag(
            tuple(tuple(x.shape) for x in group),
            tuple(jnp.result_type(x) for x in group),
            arm, axis, n, block, levels=levels)
        for j, t in zip(b.indices, tag(group)):
            out[j] = t
    return out


# -- grad-sync builders ------------------------------------------------------

def dp_sync_axes(mesh: Mesh):
    """The sync domain: ``("dpo", "dp")`` when the mesh carries an
    outer data-parallel axis (the two-tier ICI×DCN shape the hier arms
    address by level), else plain ``"dp"``."""
    return ("dpo", "dp") if "dpo" in mesh.axis_names else "dp"


def check_dp_mesh(mesh: Mesh, what: str) -> int:
    """dp-only contract shared with _quant_grad_sync: a shard_map over
    the data-parallel axes replicates every other axis, which would
    silently undo tp/sp parameter sharding — refuse instead.  An
    optional ``dpo`` outer data-parallel axis (slice-of-slices DP over
    DCN) is part of the sync domain, not a sharded dimension."""
    if "dp" not in mesh.axis_names:
        raise ValueError(
            f"{what} needs a 'dp' mesh axis to sync over "
            f"(mesh axes: {mesh.axis_names})")
    n = mesh.shape["dp"]
    for a in mesh.axis_names:
        if a == "dpo":
            n *= mesh.shape[a]
        elif a != "dp" and mesh.shape[a] > 1:
            raise ValueError(
                f"{what} is dp-only: the shard_map over dp would "
                f"replicate axis {a!r} (size {mesh.shape[a]}) and undo "
                "its parameter sharding; use grad_sync='native' on "
                "dp×tp/sp meshes")
    return n


def make_grad_sync(mode: str, mesh: Mesh, local_loss: Callable,
                   bucket_bytes: Optional[int] = None,
                   quant_block: int = 256) -> Callable:
    """Build ``(params, batch) -> (loss, grads)`` with the dp gradient
    sync carried by the requested scheduler:

      * ``perleaf``  — one native ``lax.pmean`` per leaf after the full
        backward (the explicit form of the seed's storm; the baseline
        the bucketed arm is benched and numerically pinned against).
      * ``bucketed`` — fixed-byte buckets in reverse flatten order, each
        synced by ONE allreduce the moment its cotangents exist; the
        arm per bucket (native|quant) comes from the decision layer.
      * ``unsynced`` — no gradient exchange at all (loss still pmean'd).
        MEASUREMENT-ONLY: its step time is the pure-compute floor the
        bench's overlap-efficiency column divides against; training
        with it diverges the replicas.

    ``local_loss(params, batch)`` must evaluate the PER-SHARD loss with
    no mesh inside (the one cross-shard exchange is the sync built
    here).
    """
    if mode not in GRAD_SYNC_MODES:
        raise ValueError(f"unknown grad sync mode {mode!r} "
                         f"(expected one of {GRAD_SYNC_MODES})")
    n = check_dp_mesh(mesh, f"grad_sync={mode!r}")
    platform = _mesh_platform(mesh)
    nb = resolve_bucket_bytes(bucket_bytes)
    sync_axis = dp_sync_axes(mesh)
    if isinstance(sync_axis, tuple):
        # batch dim 0 shards over the row-major dpo×dp product; the
        # two-tier context feeds the hier arms + '@<plane>' rule rows
        data_spec = P(sync_axis)
        from .hierarchy import classify_axes, hier_axes
        h_inner, h_outer, h_why = hier_axes(mesh, sync_axis)
        kinds = classify_axes(mesh)
        plane = ("dcn" if any(kinds.get(a) == "dcn" for a in sync_axis)
                 else "ici")
        levels = ((h_inner, h_outer, mesh.shape[h_outer])
                  if h_inner is not None else None)
    else:
        data_spec = P(*("dp" if a == "dp" else None
                        for a in mesh.axis_names))
        h_inner, h_why = None, "single-axis comm (no inner/outer levels)"
        plane, levels = None, None

    def local(params, batch):
        if mode != "bucketed":
            # differentiate this shard's own copy, so grads stay the
            # per-device partials until the sync below (the bucket tags
            # do this cast themselves)
            params = lax.pcast(params, sync_axis, to="varying")
        if mode == "bucketed":
            leaves, _ = jax.tree_util.tree_flatten(params)
            plan = bucket_plan(leaves, nb)
            arms = _decide_buckets(plan, n, platform, quant_block,
                                   plane=plane,
                                   hier_ok=(h_inner is not None),
                                   hier_why=h_why or "")
            global _last_plan
            _last_plan = (plan, arms)

            def tagged_loss(p, t):
                lv, td = jax.tree_util.tree_flatten(p)
                lv = _apply_bucket_tags(lv, plan, arms, sync_axis, n,
                                        quant_block, levels=levels)
                return local_loss(jax.tree_util.tree_unflatten(td, lv), t)

            loss, grads = jax.value_and_grad(tagged_loss)(params, batch)
        else:
            loss, grads = jax.value_and_grad(local_loss)(params, batch)
            if mode == "perleaf":
                grads = jax.tree.map(
                    lambda g: lax.pmean(g, sync_axis), grads)
        return lax.pmean(loss, sync_axis), grads

    # unsynced returns per-device grads under a replicated out_spec: a
    # measurement-only lie the VMA check would (rightly) refuse
    inner = jax.shard_map(local, mesh=mesh, in_specs=(P(), data_spec),
                          out_specs=(P(), P()),
                          check_vma=(mode != "unsynced"))

    def _note_traffic(grads):
        # ring-allreduce model of the sync over the (possibly two-tier)
        # sync domain: 2(n-1)/n x grad bytes per rank (the bucketed
        # arm's quant buckets send less — the matrix keeps the
        # native-wire convention the busbw factors use).  Buckets the
        # decision layer routed to a hier arm charge the HAN stage
        # split instead: inner RS/AG rings + the outer ring on the
        # scattered 1/n_inner fraction.
        from .. import traffic
        if not traffic.enabled or mode == "unsynced" or n < 2:
            return
        tot = sum(g.nbytes for g in jax.tree_util.tree_leaves(grads))
        hier_b = 0
        if (mode == "bucketed" and _last_plan is not None
                and levels is not None):
            plan, arms = _last_plan
            hier_b = sum(b.nbytes for b, a in zip(plan.buckets, arms)
                         if a in ("hier", "hier+quant"))
            hier_b = min(hier_b, tot)
            if hier_b:
                traffic.note_hierarchical(mesh, levels[0], levels[1],
                                          hier_b)
        flat_b = tot - hier_b
        if flat_b:
            traffic.note_ring(mesh, sync_axis,
                              2 * (n - 1) * flat_b // n, "grad_sync")

    def _note_numerics(grads):
        # payload fingerprints at the grad-sync boundary: grad-norm /
        # non-finite telemetry with bucket attribution when the bucketed
        # plan is in hand (ompi_tpu/numerics).  Callers gate on
        # numerics.enabled — the disabled path stays one attribute read.
        from .. import numerics
        if mode == "unsynced":
            return
        leaves = jax.tree_util.tree_leaves(grads)
        plan, arms = ((_last_plan if mode == "bucketed" and
                       _last_plan is not None else (None, None)))
        numerics.observe_grad_sync(leaves, mode, n, plan=plan, arms=arms)

    def vg(params, batch):
        from .. import numerics
        if isinstance(batch, jax.core.Tracer):
            # under an outer jit/grad trace there is nothing to time or
            # attribute: the sync inlines into the caller's program
            return inner(params, batch)
        if not trace.enabled:
            loss, grads = inner(params, batch)
            _note_traffic(grads)
            if numerics.enabled:
                _note_numerics(grads)
            _run_post_sync(grads)
            return loss, grads
        t0 = time.perf_counter()
        try:
            loss, grads = inner(params, batch)
            jax.block_until_ready(grads)
        except BaseException:
            # a raising sync (revoked comm, watchdog timeout) still
            # closes its span, tagged error — never open-ended
            trace.record_span(
                "grad_sync:run", "overlap", t0, time.perf_counter(),
                args={"mode": mode, "ndev": n, "status": "error"})
            raise
        trace.record_span(
            "grad_sync:run", "overlap", t0, time.perf_counter(),
            args={"mode": mode, "ndev": n,
                  "buckets": _PVARS["grad_bucket_count"]
                  if mode == "bucketed" else None,
                  "total_bytes": _PVARS["grad_bucket_bytes"]
                  if mode == "bucketed" else None})
        _note_traffic(grads)
        if numerics.enabled:
            _note_numerics(grads)
        _run_post_sync(grads)
        return loss, grads

    return vg


# -- collective-matmul ring arbitration --------------------------------------

def decide_collmm(kind: str, nbytes: int, mesh: Mesh, axis: str,
                  eligible_bidir: bool) -> str:
    """Ring-direction pick for one collective-matmul call site via the
    shared decision layer (coll name ``collmm``, arms native = one ring
    | bidir = two half-rings on both ICI directions).  Shapes whose
    per-rank row count is odd drop the bidir arm — the decision never
    names a schedule the op cannot execute.  One audit event per
    compiled call site feeds ``explain_last("collmm")``."""
    from ..coll import xla as _xla

    n = mesh.shape[axis]
    allowed = ("native", "bidir") if eligible_bidir else ("native",)
    arm, reason, chain = _xla.decide_mode(
        "collmm", int(nbytes), n, _mesh_platform(mesh),
        _xla._load_device_rules(), allowed, quant_ok=False)
    if trace.enabled:
        trace.decision("collmm", arm=arm, reason=reason, verdict=None,
                       nbytes=int(nbytes), ndev=n, op_kind=kind,
                       chain=list(chain))
    return arm
