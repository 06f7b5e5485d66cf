"""Device-native array redistribution: compiled minimal-collective reshard().

Every sharding-layout transition used to be either a bespoke shard_map or
a full host round-trip through ``DeviceComm.to_ranks``/``from_ranks`` —
the staging anti-pattern the coll layer exists to avoid.  Following
"Memory-efficient array redistribution through portable collective
communication" (arXiv 2112.01075, PAPERS.md), an arbitrary
NamedSharding→NamedSharding transition decomposes into a short sequence
of the device collectives the stack already has, with bounded peak
memory:

  plan grammar (one collective per step, docs/resharding.md):
    all_to_all[a:d->e]   move axis ``a`` from array dim d to dim e
                         (flat memory: in == out == shard bytes)
    all_gather[a@d]      unshard dim d over axis ``a`` (grow)
    slice[a@d]           shard a replicated dim d over axis ``a``
                         (shrink, zero wire bytes — a local slice)
    ppermute[g~b@..]     exchange same-sized axes g and b (a pure device
                         transposition: flat memory, one hop per device)
    device_put           the whole-array XLA resharding transfer — the
                         device-native fallback for ragged/irregular
                         specs the step grammar cannot express exactly,
                         and for plans whose step sequence would breach
                         the peak-memory bound

  ordering discipline: shrinking slices fire as soon as their dim's
  prefix is ready, moves/swaps run flat, gathers are deferred to last —
  so intermediate shards never exceed max(src_shard, dst_shard) and the
  per-step live set (input + output) stays within
  ``reshard_peak_factor × max(src_shard, dst_shard)``.  A plan that
  would breach the bound (e.g. a transposition of unequal-sized axes,
  which needs a gather-sized intermediate) is REPLACED by the
  single-step device_put plan, whose live set is src+dst ≤ 2×max by
  construction — the bound is a contract, not a hint.

``reduce_scatter_axis`` is part of the vocabulary for future
partial-sum redistribution (reducing while resharding); pure layout
plans never emit it — a layout change has nothing to reduce.

First-class citizenship in the PR 1–9 stack:

* plans are cached by ``(src_spec, dst_spec, shape, dtype)`` per mesh
  and each step's executable goes through the same cache discipline as
  ``DeviceComm._compiled`` (build:* compile spans, cache_hit:*
  instants, device_cache_misses pvars);
* every step dispatches under coll name ``reshard`` through
  ``coll.xla.decide_mode`` (force var ``coll_xla_reshard_mode``,
  DEVICE_RULES ``reshard`` rows, ``learned`` consulting the perf
  ledger) and emits exactly ONE decision-audit event naming the plan;
* traffic attribution charges each step's real edge set (ring for
  gathers, bipartite for all_to_all, explicit perm pairs for
  ppermute) so the conservation invariant ``edge-sum ==
  coll_wire_bytes`` spans resharding traffic;
* the perf ledger grows ``reshard`` and ``reshard@<plane>`` cells from
  measured step durations, which is what ``coll_xla_rules=learned``
  reads back.
"""

from __future__ import annotations

import math
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import trace
from ..core import var as _var
from .collectives import all_gather_invariant, all_to_all_axis
from .mesh import classify_axes

_var.register("reshard", "", "peak_factor", 2.0, type=float, level=3,
              help="Peak-live-bytes bound for compiled reshard plans, "
                   "as a multiple of max(src_shard, dst_shard) per "
                   "device (arXiv 2112.01075).  A plan whose step "
                   "accounting would breach the bound is replaced by "
                   "the single-step device_put plan (live set src+dst "
                   "<= 2x max by construction).")

PVARS = ("reshard_plans", "reshard_steps", "reshard_bytes")

_lock = threading.Lock()
_counts: Dict[str, int] = {"reshard_plans": 0, "reshard_steps": 0,
                           "reshard_bytes": 0}
# compiled-plan summaries + the last executed plan's audit, for
# comm_doctor --reshard (bounded: the doctor renders a cache view, not
# a history)
_plan_log: "deque" = deque(maxlen=32)
_last_run: Optional[Dict[str, Any]] = None


class ReshardError(ValueError):
    """A (src, dst, mesh, shape) tuple the plan compiler rejects loudly
    (unknown/repeated mesh axes — never a silent host fallback)."""


# ---------------------------------------------------------------------------
# plan representation
# ---------------------------------------------------------------------------

Placement = Tuple[Tuple[str, ...], ...]     # per-dim axis groups


@dataclass(frozen=True)
class PlanStep:
    op: str                       # all_to_all|all_gather|slice|ppermute|device_put
    axes: Tuple[str, ...]         # mesh axes driving the step
    dim: int                      # array dim acted on / move target dim
    src_dim: int                  # move/exchange source dim (== dim otherwise)
    in_spec: P
    out_spec: P
    in_bytes: int                 # per-device live bytes entering the step
    out_bytes: int                # per-device live bytes leaving the step
    wire_bytes: int               # modeled per-rank wire bytes
    perm: Tuple[Tuple[int, int], ...] = ()   # ppermute pairs (flat positions)

    def describe(self) -> str:
        if self.op == "all_to_all":
            return (f"all_to_all[{'+'.join(self.axes)}:"
                    f"{self.src_dim}->{self.dim}]")
        if self.op == "all_gather":
            return f"all_gather[{self.axes[0]}@{self.dim}]"
        if self.op == "slice":
            return f"slice[{self.axes[0]}@{self.dim}]"
        if self.op == "ppermute":
            g, b = self.axes
            if self.src_dim == self.dim:
                return f"ppermute[{g}~{b}@{self.dim}]"
            return f"ppermute[{g}@{self.src_dim}~{b}@{self.dim}]"
        return self.op


@dataclass(frozen=True)
class ReshardPlan:
    key: tuple
    shape: Tuple[int, ...]
    dtype: str
    src: Placement
    dst: Placement
    steps: Tuple[PlanStep, ...]
    src_shard_bytes: int
    dst_shard_bytes: int
    peak_bytes: int               # max per-step (in + out) live bytes
    wire_bytes: int               # sum of step wire figures
    bound_bytes: int              # factor * max(src_shard, dst_shard)
    fallback_reason: str = ""     # non-empty when device_put replaced steps

    def describe(self) -> List[str]:
        return [s.describe() for s in self.steps]

    @property
    def label(self) -> str:
        return (f"{_fmt_placement(self.src)}->{_fmt_placement(self.dst)}"
                f"/{self.dtype}{list(self.shape)}")


def _fmt_placement(pl: Placement) -> str:
    parts = []
    for grp in pl:
        if not grp:
            parts.append("_")
        elif len(grp) == 1:
            parts.append(grp[0])
        else:
            parts.append("(" + "+".join(grp) + ")")
    return "[" + ",".join(parts) + "]"


def _norm(spec, ndim: int) -> Placement:
    """PartitionSpec/sequence → per-dim tuples of axis names."""
    parts: Sequence = tuple(spec) if spec is not None else ()
    out: List[Tuple[str, ...]] = []
    for d in range(ndim):
        e = parts[d] if d < len(parts) else None
        if e is None:
            out.append(())
        elif isinstance(e, (tuple, list)):
            out.append(tuple(str(a) for a in e))
        else:
            out.append((str(e),))
    return tuple(out)


def _spec_of(pl: Placement) -> P:
    ents = []
    for grp in pl:
        if not grp:
            ents.append(None)
        elif len(grp) == 1:
            ents.append(grp[0])
        else:
            ents.append(tuple(grp))
    return P(*ents)


# ---------------------------------------------------------------------------
# plan compiler
# ---------------------------------------------------------------------------

def compile_plan(shape: Sequence[int], dtype, src_spec, dst_spec,
                 mesh: Mesh, peak_factor: Optional[float] = None
                 ) -> ReshardPlan:
    """Compile a (src, dst, mesh) triple into a minimal collective
    sequence.  Pure host math — no device work, no caches, no audit;
    the Resharder wraps this with caching and per-step dispatch."""
    shape = tuple(int(s) for s in shape)
    dt = np.dtype(jnp.dtype(dtype).name) if not isinstance(dtype, np.dtype) \
        else dtype
    src = _norm(src_spec, len(shape))
    dst = _norm(dst_spec, len(shape))
    sizes = {a: int(mesh.shape[a]) for a in mesh.axis_names}
    for pl, which in ((src, "src"), (dst, "dst")):
        seen = set()
        for grp in pl:
            for a in grp:
                if a not in sizes:
                    raise ReshardError(
                        f"reshard: {which} spec names axis {a!r} not on "
                        f"mesh {tuple(mesh.axis_names)}")
                if a in seen:
                    raise ReshardError(
                        f"reshard: {which} spec uses axis {a!r} on more "
                        "than one dim")
                seen.add(a)
    factor = float(peak_factor if peak_factor is not None
                   else _var.get("reshard_peak_factor", 2.0))
    itemsize = dt.itemsize
    total = itemsize * int(np.prod(shape)) if shape else itemsize

    def nshards(grp: Tuple[str, ...]) -> int:
        n = 1
        for a in grp:
            n *= sizes[a]
        return n

    def shard_bytes(pl: Placement) -> int:
        b = total
        for d, grp in enumerate(pl):
            n = nshards(grp)
            b = b // n if b % n == 0 else int(math.ceil(b / n))
        return max(b, itemsize)

    src_b, dst_b = shard_bytes(src), shard_bytes(dst)
    bound = int(factor * max(src_b, dst_b))
    key = (src, dst, shape, dt.name)

    def _plan(steps, peak, wire, why=""):
        return ReshardPlan(key=key, shape=shape, dtype=dt.name,
                           src=src, dst=dst, steps=tuple(steps),
                           src_shard_bytes=src_b, dst_shard_bytes=dst_b,
                           peak_bytes=peak, wire_bytes=wire,
                           bound_bytes=bound, fallback_reason=why)

    if src == dst:
        return _plan((), 0, 0)

    def _device_put_plan(why: str) -> ReshardPlan:
        # single XLA resharding transfer: device-native, live set
        # src+dst, wire modeled as the destination shard each device
        # must assemble
        step = PlanStep(op="device_put", axes=tuple(mesh.axis_names),
                        dim=0, src_dim=0, in_spec=_spec_of(src),
                        out_spec=_spec_of(dst), in_bytes=src_b,
                        out_bytes=dst_b, wire_bytes=dst_b)
        return _plan((step,), src_b + dst_b, dst_b, why)

    divisible = all(
        shape[d] % nshards(src[d]) == 0 and shape[d] % nshards(dst[d]) == 0
        for d in range(len(shape)))
    if not divisible:
        return _device_put_plan(
            "ragged: a dim does not divide by its sharding axes "
            "(shard_map steps need even shards)")

    placement: List[Tuple[str, ...]] = list(src)
    ndim = len(shape)
    dst_dim_of: Dict[str, int] = {a: d for d, grp in enumerate(dst)
                                  for a in grp}
    steps: List[PlanStep] = []
    cur_b = src_b
    peak = 0
    wire_total = 0

    def placed_anywhere(a: str) -> bool:
        return any(a in grp for grp in placement)

    def emit(op: str, axes: Tuple[str, ...], dim: int, src_dim: int,
             before: Placement, after: Placement, wire: int,
             perm: Tuple = ()) -> None:
        nonlocal cur_b, peak, wire_total
        in_b, out_b = shard_bytes(before), shard_bytes(after)
        steps.append(PlanStep(op=op, axes=axes, dim=dim, src_dim=src_dim,
                              in_spec=_spec_of(before),
                              out_spec=_spec_of(after), in_bytes=in_b,
                              out_bytes=out_b, wire_bytes=int(wire),
                              perm=perm))
        cur_b = out_b
        peak = max(peak, in_b + out_b)
        wire_total += int(wire)

    def _transpose_perm(n: int) -> Tuple[Tuple[int, int], ...]:
        # device (i, j) over the joint (g, b) space receives from (j, i)
        return tuple((j * n + i, i * n + j)
                     for i in range(n) for j in range(n))

    guard = 0
    while tuple(placement) != dst:
        guard += 1
        if guard > 8 * ndim * (len(sizes) + 1):
            return _device_put_plan("scheduler found no step sequence")
        progress = False
        before = tuple(placement)

        # 1) ppermute: same-dim axis substitution g -> b (equal sizes,
        #    g leaving the layout entirely, b entering it) — flat memory
        #    where gather+slice would blow up n-fold
        for d in range(ndim):
            cur, want = placement[d], dst[d]
            if (cur and want and len(cur) == len(want)
                    and cur[:-1] == want[:-1] and cur[-1] != want[-1]):
                g, b = cur[-1], want[-1]
                if (sizes[g] == sizes[b] and g not in dst_dim_of
                        and not placed_anywhere(b)):
                    after = list(placement)
                    after[d] = want
                    n = sizes[g]
                    w = cur_b * (n * n - n) // (n * n)
                    emit("ppermute", (g, b), d, d, tuple(placement),
                         tuple(after), w, _transpose_perm(n))
                    placement[d] = want
                    progress = True

        # 2) ppermute: dim-pair exchange g@d <-> b@e (equal sizes) —
        #    the cyclic-move deadlock resolved in one flat hop
        for d in range(ndim):
            for e in range(ndim):
                if d == e:
                    continue
                cd, wd = placement[d], dst[d]
                ce, we = placement[e], dst[e]
                if not (cd and ce and wd and we):
                    continue
                g, b = cd[-1], ce[-1]
                if (g != b and sizes[g] == sizes[b]
                        and wd == cd[:-1] + (b,) and we == ce[:-1] + (g,)):
                    after = list(placement)
                    after[d], after[e] = wd, we
                    n = sizes[g]
                    w = cur_b * (n * n - n) // (n * n)
                    emit("ppermute", (g, b), e, d, tuple(placement),
                         tuple(after), w, _transpose_perm(n))
                    placement[d], placement[e] = wd, we
                    progress = True

        # 3) moves: an innermost suffix of dim d's axes belongs — in
        #    order — on dim e whose prefix is ready: one all_to_all
        #    over the (joint) axis group, flat memory.  Longest suffix
        #    first, so a whole group like ("x","y") moves in a single
        #    step instead of two.
        for d in range(ndim):
            cur = placement[d]
            for k in range(len(cur), 0, -1):
                grp = cur[-k:]
                e = dst_dim_of.get(grp[0])
                if e is None or e == d:
                    continue
                q = len(placement[e])
                if (placement[e] == dst[e][:q]
                        and dst[e][q:q + k] == grp):
                    after = list(placement)
                    after[d] = cur[:-k]
                    after[e] = placement[e] + grp
                    m = nshards(grp)
                    w = cur_b * (m - 1) // m
                    emit("all_to_all", grp, e, d, tuple(placement),
                         tuple(after), w)
                    placement[d], placement[e] = after[d], after[e]
                    progress = True
                    break

        # 4) slices: the next wanted axis of a ready dim is currently
        #    unplaced — shard it locally (shrinks, zero wire)
        for d in range(ndim):
            cur, want = placement[d], dst[d]
            if cur == want[:len(cur)] and len(want) > len(cur):
                b = want[len(cur)]
                if not placed_anywhere(b):
                    after = list(placement)
                    after[d] = cur + (b,)
                    emit("slice", (b,), d, d, tuple(placement),
                         tuple(after), 0)
                    placement[d] = after[d]
                    progress = True

        if progress:
            continue

        # 5) gathers, last: remove the innermost axis past some dim's
        #    common prefix (also breaks move deadlocks — a gathered
        #    axis becomes re-addable by slice, since the data is then
        #    replicated over it)
        for d in range(ndim):
            cur, want = placement[d], dst[d]
            p = 0
            while p < min(len(cur), len(want)) and cur[p] == want[p]:
                p += 1
            if len(cur) > p:
                g = cur[-1]
                after = list(placement)
                after[d] = cur[:-1]
                m = sizes[g]
                w = cur_b * (m - 1)
                emit("all_gather", (g,), d, d, tuple(placement),
                     tuple(after), w)
                placement[d] = after[d]
                progress = True
                break
        if not progress:
            return _device_put_plan("scheduler found no step sequence")

    if peak > bound:
        return _device_put_plan(
            f"peak {peak}B over bound {bound}B "
            f"(reshard_peak_factor={factor:g})")
    return _plan(steps, peak, wire_total)


# ---------------------------------------------------------------------------
# executor
# ---------------------------------------------------------------------------

class Resharder:
    """Per-mesh plan cache + step executor.

    Mirrors DeviceComm's executable-cache discipline exactly: one
    compiled program per (step, shape, dtype) key, build:* compile
    spans and cache_hit:* instants under trace, device_cache_misses /
    cache_miss_count pvars when an SPC table is attached."""

    def __init__(self, mesh: Mesh, spc=None) -> None:
        self.mesh = mesh
        self.spc = spc
        self._plans: Dict[tuple, ReshardPlan] = {}
        self._plan_hits = 0
        self._cache: Dict[tuple, Callable] = {}
        self._sizes = {a: int(mesh.shape[a]) for a in mesh.axis_names}
        self._axis_plane = classify_axes(mesh)
        self._platform = jax.devices()[0].platform

    # -- caches ---------------------------------------------------------

    def cache_info(self) -> Dict[str, int]:
        return {"plans": len(self._plans), "plan_hits": self._plan_hits,
                "executables": len(self._cache)}

    def _compiled(self, key: tuple, build: Callable) -> Callable:
        fn = self._cache.get(key)
        if fn is None:
            if trace.enabled:
                t0 = time.perf_counter()
                try:
                    fn = build()
                except BaseException:
                    trace.record_span(f"build:{key[0]}", "compile", t0,
                                      time.perf_counter(),
                                      args={"key": repr(key),
                                            "status": "error"})
                    raise
                trace.record_span(f"build:{key[0]}", "compile", t0,
                                  time.perf_counter(),
                                  args={"key": repr(key)})
            else:
                fn = build()
            self._cache[key] = fn
            if self.spc is not None:
                self.spc.inc("device_cache_misses")
                self.spc.inc("cache_miss_count")
        elif trace.enabled:
            trace.instant(f"cache_hit:{key[0]}", "cache",
                          args={"key": repr(key)})
        return fn

    def plan(self, shape, dtype, src_spec, dst_spec) -> ReshardPlan:
        dt = jnp.dtype(dtype).name
        key = (_norm(src_spec, len(shape)), _norm(dst_spec, len(shape)),
               tuple(int(s) for s in shape), dt)
        hit = self._plans.get(key)
        if hit is not None:
            self._plan_hits += 1
            if trace.enabled:
                trace.instant("cache_hit:reshard_plan", "cache",
                              args={"plan": hit.label})
            return hit
        t0 = time.perf_counter()
        try:
            plan = compile_plan(shape, dtype, src_spec, dst_spec, self.mesh)
        except BaseException:
            if trace.enabled:
                trace.record_span("reshard:compile_plan", "compile", t0,
                                  time.perf_counter(),
                                  args={"status": "error"})
            raise
        self._plans[key] = plan
        with _lock:
            _counts["reshard_plans"] += 1
            _plan_log.append({
                "plan": plan.label, "steps": plan.describe(),
                "wire_bytes": plan.wire_bytes,
                "peak_bytes": plan.peak_bytes,
                "bound_bytes": plan.bound_bytes,
                "src_shard_bytes": plan.src_shard_bytes,
                "dst_shard_bytes": plan.dst_shard_bytes,
                "fallback_reason": plan.fallback_reason,
                "mesh": dict(self.mesh.shape)})
        if trace.enabled:
            trace.record_span("reshard:compile_plan", "compile", t0,
                              time.perf_counter(),
                              args={"plan": plan.label,
                                    "steps": plan.describe(),
                                    "peak_bytes": plan.peak_bytes,
                                    "wire_bytes": plan.wire_bytes})
        return plan

    # -- per-step programs ---------------------------------------------

    def _exe(self, plan: ReshardPlan, i: int) -> Callable:
        step = plan.steps[i]
        key = ("reshard_" + step.op, step.axes, step.dim, step.src_dim,
               plan.shape, plan.dtype, str(step.in_spec),
               str(step.out_spec))
        mesh, sizes = self.mesh, self._sizes

        def build():
            if step.op == "device_put":
                dst = NamedSharding(mesh, step.out_spec)
                return jax.jit(lambda v: v, out_shardings=dst)
            if step.op == "all_to_all":
                d, e = step.src_dim, step.dim
                ax = step.axes[0] if len(step.axes) == 1 else step.axes

                def inner(xs):
                    return all_to_all_axis(xs, ax, split_dim=e,
                                           concat_dim=d)
            elif step.op == "all_gather":
                ax, d = step.axes[0], step.dim

                def inner(xs):
                    # the gathered dim is replicated over ``ax`` on exit:
                    # the Varying -> Invariant gather types it so
                    return all_gather_invariant(xs, ax, axis=d, tiled=True)
            elif step.op == "slice":
                ax, d = step.axes[0], step.dim
                m = sizes[ax]

                def inner(xs):
                    blk = xs.shape[d] // m
                    idx = lax.axis_index(ax)
                    return lax.dynamic_slice_in_dim(xs, idx * blk, blk, d)
            elif step.op == "ppermute":
                axes, perm = step.axes, list(step.perm)

                def inner(xs):
                    return lax.ppermute(xs, axes, perm=perm)
            else:                   # pragma: no cover — grammar is closed
                raise ReshardError(f"unknown plan op {step.op!r}")
            return jax.jit(jax.shard_map(inner, mesh=mesh,
                                         in_specs=step.in_spec,
                                         out_specs=step.out_spec))
        return self._compiled(key, build)

    # -- decision + audit ----------------------------------------------

    def _decide(self, step: PlanStep, ndev: int) -> Tuple[str, str, list]:
        from ..coll import xla as _xla
        plane = ("dcn" if any(self._axis_plane.get(a) == "dcn"
                              for a in step.axes) else "ici")
        return _xla.decide_mode(
            "reshard", step.wire_bytes, ndev, self._platform,
            _xla._load_device_rules(), allowed=("native",),
            quant_ok=False, dtype=None, op=None, plane=plane,
            hier_ok=False,
            hier_why="reshard steps are single layout-pure collectives")

    def _audit_step(self, plan: ReshardPlan, i: int, arm: str,
                    reason: str, chain: list, ndev: int,
                    dur_s: Optional[float]) -> None:
        from .. import perf, traffic
        step = plan.steps[i]
        wire = int(step.wire_bytes)
        with _lock:
            _counts["reshard_steps"] += 1
            _counts["reshard_bytes"] += wire
        if self.spc is not None:
            self.spc.inc(f"coll_arm_{arm}_count")
            if wire:
                self.spc.inc("coll_wire_bytes", wire)
        planes: Dict[str, int] = {}
        if traffic.enabled and wire:
            kind = {"all_to_all": "a2a", "all_gather": "ring",
                    "ppermute": "perm", "device_put": "a2a"}.get(step.op)
            if kind is not None:
                planes = traffic.note_reshard_step(
                    self.mesh, kind, step.axes, wire,
                    pairs=step.perm or None)
        if perf.enabled and dur_s is not None and wire and ndev >= 2:
            perf.note_sample("reshard", arm, wire, dur_s, ndev,
                             planes=planes)
        if trace.enabled:
            trace.decision(
                "reshard", arm=arm, reason=reason, verdict=None,
                nbytes=wire,
                step=i, step_op=step.describe(), plan=plan.label,
                plan_steps=len(plan.steps), peak_bytes=plan.peak_bytes,
                bound_bytes=plan.bound_bytes, ndev=ndev,
                wire_bytes=wire, chain=chain)
        return planes

    # -- execution ------------------------------------------------------

    def run(self, x: jax.Array, dst_spec) -> jax.Array:
        from .. import perf
        global _last_run
        src_spec = x.sharding.spec
        plan = self.plan(x.shape, x.dtype, src_spec, dst_spec)
        if not plan.steps:
            return x
        audit: List[Dict[str, Any]] = []
        for i, step in enumerate(plan.steps):
            ndev = 1
            for a in step.axes:
                ndev *= self._sizes[a]
            arm, reason, chain = self._decide(step, ndev)
            exe = self._exe(plan, i)
            t0 = time.perf_counter()
            x = exe(x)
            dur = None
            if perf.enabled:
                jax.block_until_ready(x)
                dur = time.perf_counter() - t0
            self._audit_step(plan, i, arm, reason, chain, ndev, dur)
            audit.append({"step": i, "op": step.describe(), "arm": arm,
                          "reason": reason, "wire_bytes": step.wire_bytes,
                          "dur_us": (round(dur * 1e6, 1)
                                     if dur is not None else None)})
        with _lock:
            _last_run = {"plan": plan.label, "steps": audit,
                         "wire_bytes": plan.wire_bytes,
                         "peak_bytes": plan.peak_bytes,
                         "bound_bytes": plan.bound_bytes,
                         "fallback_reason": plan.fallback_reason}
        return x


# ---------------------------------------------------------------------------
# module-level face
# ---------------------------------------------------------------------------

_resharders: Dict[Mesh, Resharder] = {}
_RESHARDER_CAP = 8


def resharder(mesh: Mesh, spc=None) -> Resharder:
    """The per-mesh Resharder (bounded registry; the newest SPC table
    attaches — the latest Context wins, like DeviceComm.spc)."""
    with _lock:
        r = _resharders.get(mesh)
        if r is None:
            if len(_resharders) >= _RESHARDER_CAP:
                _resharders.pop(next(iter(_resharders)))
            r = _resharders[mesh] = Resharder(mesh, spc=spc)
        if spc is not None:
            r.spc = spc
    return r


def reshard(x, dst, mesh: Optional[Mesh] = None, spc=None) -> jax.Array:
    """Redistribute ``x`` onto ``dst`` (NamedSharding or PartitionSpec)
    through a compiled minimal-collective plan — entirely on device.

    An input that is not already a NamedSharding-on-this-mesh array (a
    host ndarray, a fresh single-device array) is ingested with one
    ``device_put`` — that is a placement, not a redistribution, and is
    not audited as one."""
    if isinstance(dst, NamedSharding):
        mesh = mesh if mesh is not None else dst.mesh
        dst_spec = dst.spec
    elif isinstance(dst, P):
        dst_spec = dst
    elif isinstance(dst, (tuple, list)):
        dst_spec = P(*dst)
    else:
        raise TypeError(f"reshard: dst must be a NamedSharding or "
                        f"PartitionSpec, got {type(dst).__name__}")
    if mesh is None:
        s = getattr(x, "sharding", None)
        mesh = getattr(s, "mesh", None)
    if mesh is None:
        raise ReshardError("reshard: no mesh — pass one, or a "
                           "NamedSharding dst")
    if isinstance(mesh, jax.sharding.AbstractMesh):     # tracing context
        raise ReshardError("reshard: needs a concrete Mesh (called "
                           "under tracing?)")
    s = getattr(x, "sharding", None)
    if not (isinstance(x, jax.Array) and isinstance(s, NamedSharding)
            and s.mesh == mesh):
        return jax.device_put(x, NamedSharding(mesh, dst_spec))
    return resharder(mesh, spc=spc).run(x, dst_spec)


# ---------------------------------------------------------------------------
# cross-mesh planning mode (source mesh ⊃ dest mesh)
# ---------------------------------------------------------------------------
#
# compile_plan above assumes ONE fixed mesh: every step is a collective
# over axes both layouts share.  Elastic recovery (ft/elastic) needs the
# other shape: the array lives on the FULL mesh, some of whose devices
# are dead, and must land on a survivor mesh that is a strict subset of
# the original devices.  The cross plan decomposes that transition into
# per-destination-device piece moves — each destination shard is tiled
# by whole source shards (or crops of replicas), moved point-to-point
# and assembled in place with donated dynamic_update_slice programs so
# the per-device live set stays within the same peak contract as the
# single-mesh planner: resident src shard + assembled dst shard + one
# in-flight piece <= reshard_peak_factor * max(src_shard, dst_shard)
# when the factor is the default 2.  Pieces whose source device is dead
# are sourced from caller-provided REPLACEMENTS (ft/elastic's in-memory
# peer shadows) — the dead device's buffers are never read, and no
# filesystem round-trip happens.

@dataclass(frozen=True)
class CrossPiece:
    """One source block of a destination shard."""
    dst_pos: int                  # flat position in the SOURCE mesh
    src_pos: int                  # flat position in the SOURCE mesh
    start: Tuple[int, ...]        # piece origin in global index space
    sizes: Tuple[int, ...]        # piece extent per dim
    nbytes: int
    from_shadow: bool             # sourced from a replacement, not x


@dataclass(frozen=True)
class CrossMeshPlan:
    key: tuple
    shape: Tuple[int, ...]
    dtype: str
    src: Placement
    dst: Placement
    pieces: Tuple[CrossPiece, ...]
    src_shard_bytes: int
    dst_shard_bytes: int
    peak_bytes: int               # modeled per-device live-set maximum
    wire_bytes: int               # modeled cross-device piece bytes
    bound_bytes: int
    n_src: int
    n_dst: int
    fallback_reason: str = ""     # non-empty when device_put replaced pieces

    @property
    def label(self) -> str:
        return (f"{_fmt_placement(self.src)}x{self.n_src}->"
                f"{_fmt_placement(self.dst)}x{self.n_dst}"
                f"/{self.dtype}{list(self.shape)}")

    def describe(self) -> List[str]:
        if self.fallback_reason:
            return ["device_put"]
        return [f"cross_migrate[{len(self.pieces)} piece(s), "
                f"{sum(1 for p in self.pieces if p.from_shadow)} shadow]"]


def _region(idx, shape) -> Tuple[Tuple[int, int], ...]:
    """A devices_indices_map entry -> ((start, stop), ...) per dim."""
    out = []
    for d, s in enumerate(idx):
        start = 0 if s.start is None else int(s.start)
        stop = int(shape[d]) if s.stop is None else int(s.stop)
        out.append((start, stop))
    return tuple(out)


def _contains(outer, inner) -> bool:
    return all(o0 <= i0 and i1 <= o1
               for (o0, o1), (i0, i1) in zip(outer, inner))


def _rsize(reg) -> int:
    n = 1
    for a, b in reg:
        n *= max(b - a, 0)
    return n


def compile_cross_plan(shape: Sequence[int], dtype, src_spec, dst_spec,
                       src_mesh: Mesh, dst_mesh: Mesh,
                       dead: Sequence[int] = (),
                       peak_factor: Optional[float] = None
                       ) -> CrossMeshPlan:
    """Compile a source-mesh ⊃ dest-mesh transition into per-device piece
    moves.  ``dead`` holds flat positions (into ``src_mesh.devices``) of
    devices whose shards must never be read — those pieces are marked
    ``from_shadow`` and the executor sources them from caller
    replacements.  Pure host math, like :func:`compile_plan`."""
    shape = tuple(int(s) for s in shape)
    dt = np.dtype(jnp.dtype(dtype).name) if not isinstance(dtype, np.dtype) \
        else dtype
    itemsize = dt.itemsize
    src = _norm(src_spec, len(shape))
    dst = _norm(dst_spec, len(shape))
    src_devs = list(np.asarray(src_mesh.devices).flat)
    dst_devs = list(np.asarray(dst_mesh.devices).flat)
    pos_of = {d: i for i, d in enumerate(src_devs)}
    dead_set = frozenset(int(p) for p in dead)
    missing = [d for d in dst_devs if d not in pos_of]
    if missing:
        raise ReshardError(
            "cross_reshard: dest mesh is not a subset of the source mesh "
            f"(devices {missing} not on the source mesh)")
    bad = [pos_of[d] for d in dst_devs if pos_of[d] in dead_set]
    if bad:
        raise ReshardError(
            f"cross_reshard: dest mesh includes dead device position(s) "
            f"{sorted(bad)} — shrink to survivors first")
    src_sh = NamedSharding(src_mesh, _spec_of(src))
    dst_sh = NamedSharding(dst_mesh, _spec_of(dst))
    src_map = {pos_of[d]: _region(idx, shape)
               for d, idx in src_sh.devices_indices_map(shape).items()}
    dst_map = {pos_of[d]: _region(idx, shape)
               for d, idx in dst_sh.devices_indices_map(shape).items()}
    total = itemsize * int(np.prod(shape)) if shape else itemsize
    src_b = max(max((_rsize(r) for r in src_map.values()), default=1)
                * itemsize, itemsize)
    dst_b = max(max((_rsize(r) for r in dst_map.values()), default=1)
                * itemsize, itemsize)
    factor = float(peak_factor if peak_factor is not None
                   else _var.get("reshard_peak_factor", 2.0))
    bound = int(factor * max(src_b, dst_b))
    key = (src, dst, shape, dt.name,
           tuple(id(d) for d in src_devs), tuple(id(d) for d in dst_devs),
           dead_set)

    def _fallback(why: str) -> CrossMeshPlan:
        if dead_set:
            raise ReshardError(
                f"cross_reshard: {why} — and dead position(s) "
                f"{sorted(dead_set)} rule out the whole-array device_put "
                "fallback (it would read their shards)")
        return CrossMeshPlan(
            key=key, shape=shape, dtype=dt.name, src=src, dst=dst,
            pieces=(), src_shard_bytes=src_b, dst_shard_bytes=dst_b,
            peak_bytes=src_b + dst_b, wire_bytes=dst_b, bound_bytes=bound,
            n_src=len(src_devs), n_dst=len(dst_devs), fallback_reason=why)

    # group source holders by region (partial replication: several
    # devices may hold identical blocks)
    holders: Dict[Tuple, List[int]] = {}
    for p, reg in src_map.items():
        holders.setdefault(reg, []).append(p)

    pieces: List[CrossPiece] = []
    peak = 0
    wire = 0
    for dpos, R in sorted(dst_map.items()):
        cand = [(reg, ps) for reg, ps in holders.items()
                if _contains(R, reg)]
        if sum(_rsize(reg) for reg, _ in cand) != _rsize(R):
            return _fallback(
                "irregular tiling: a source shard straddles a dest shard "
                "boundary (cross plans need dest shards tiled by whole "
                "source blocks)")
        dev_pieces: List[CrossPiece] = []
        for reg, ps in sorted(cand):
            alive = [p for p in sorted(ps) if p not in dead_set]
            shadow = not alive
            if shadow:
                p = min(ps)                     # replacement keyed here
            elif dpos in alive:
                p = dpos                        # local copy: zero wire
            else:
                p = alive[0]
            nb = _rsize(reg) * itemsize
            dev_pieces.append(CrossPiece(
                dst_pos=dpos, src_pos=p,
                start=tuple(a for a, _ in reg),
                sizes=tuple(b - a for a, b in reg),
                nbytes=nb, from_shadow=shadow))
            if shadow or p != dpos:
                wire += nb
        pieces.extend(dev_pieces)
        # live-set model per dest device: resident src shard + the
        # assembled dst shard + one in-flight piece (assembly is
        # sequential donated update_slice, never a full concat)
        max_piece = max((pc.nbytes for pc in dev_pieces), default=0)
        if len(dev_pieces) == 1 and dev_pieces[0].src_pos == dpos \
                and not dev_pieces[0].from_shadow \
                and dev_pieces[0].nbytes == _rsize(src_map[dpos]) * itemsize:
            live = src_b                        # pure alias, no assembly
        else:
            live = src_b + _rsize(R) * itemsize + max_piece
        peak = max(peak, live)
    if peak > bound:
        return _fallback(
            f"peak {peak}B over bound {bound}B "
            f"(reshard_peak_factor={factor:g})")
    return CrossMeshPlan(
        key=key, shape=shape, dtype=dt.name, src=src, dst=dst,
        pieces=tuple(pieces), src_shard_bytes=src_b, dst_shard_bytes=dst_b,
        peak_bytes=peak, wire_bytes=wire, bound_bytes=bound,
        n_src=len(src_devs), n_dst=len(dst_devs))


_cross_plans: Dict[tuple, CrossMeshPlan] = {}
_cross_exe: Dict[tuple, Callable] = {}
_CROSS_CAP = 256


def _cross_compiled(key: tuple, build: Callable, spc=None) -> Callable:
    """Executable-cache discipline for cross-plan piece programs (same
    build:*/cache_hit:* spans and pvars as Resharder._compiled)."""
    fn = _cross_exe.get(key)
    if fn is None:
        if len(_cross_exe) >= _CROSS_CAP:
            _cross_exe.pop(next(iter(_cross_exe)))
        if trace.enabled:
            t0 = time.perf_counter()
            try:
                fn = build()
            except BaseException:
                trace.record_span(f"build:{key[0]}", "compile", t0,
                                  time.perf_counter(),
                                  args={"key": repr(key),
                                        "status": "error"})
                raise
            trace.record_span(f"build:{key[0]}", "compile", t0,
                              time.perf_counter(), args={"key": repr(key)})
        else:
            fn = build()
        _cross_exe[key] = fn
        if spc is not None:
            spc.inc("device_cache_misses")
            spc.inc("cache_miss_count")
    elif trace.enabled:
        trace.instant(f"cache_hit:{key[0]}", "cache",
                      args={"key": repr(key)})
    return fn


def _cross_plan(shape, dtype, src_spec, dst_spec, src_mesh, dst_mesh,
                dead) -> CrossMeshPlan:
    dt = jnp.dtype(dtype).name
    key = (_norm(src_spec, len(shape)), _norm(dst_spec, len(shape)),
           tuple(int(s) for s in shape), dt,
           tuple(id(d) for d in np.asarray(src_mesh.devices).flat),
           tuple(id(d) for d in np.asarray(dst_mesh.devices).flat),
           frozenset(int(p) for p in dead))
    hit = _cross_plans.get(key)
    if hit is not None:
        if trace.enabled:
            trace.instant("cache_hit:reshard_cross_plan", "cache",
                          args={"plan": hit.label})
        return hit
    if len(_cross_plans) >= _CROSS_CAP:
        _cross_plans.pop(next(iter(_cross_plans)))
    t0 = time.perf_counter()
    try:
        plan = compile_cross_plan(shape, dtype, src_spec, dst_spec,
                                  src_mesh, dst_mesh, dead=dead)
    except BaseException:
        if trace.enabled:
            trace.record_span("reshard:compile_cross_plan", "compile", t0,
                              time.perf_counter(),
                              args={"status": "error"})
        raise
    if trace.enabled:
        trace.record_span("reshard:compile_cross_plan", "compile", t0,
                          time.perf_counter(),
                          args={"plan": plan.label,
                                "pieces": len(plan.pieces),
                                "peak_bytes": plan.peak_bytes,
                                "wire_bytes": plan.wire_bytes})
    _cross_plans[key] = plan
    with _lock:
        _counts["reshard_plans"] += 1
        _plan_log.append({
            "plan": plan.label, "steps": plan.describe(),
            "wire_bytes": plan.wire_bytes, "peak_bytes": plan.peak_bytes,
            "bound_bytes": plan.bound_bytes,
            "src_shard_bytes": plan.src_shard_bytes,
            "dst_shard_bytes": plan.dst_shard_bytes,
            "fallback_reason": plan.fallback_reason,
            "cross": True, "dead": sorted(int(p) for p in dead),
            "mesh": {"src": dict(src_mesh.shape),
                     "dst": dict(dst_mesh.shape)}})
    return plan


def cross_reshard(x: jax.Array, dst: NamedSharding, *,
                  dead: Sequence[int] = (), replacements=None,
                  spc=None) -> jax.Array:
    """Redistribute ``x`` from its (larger) source mesh onto ``dst``'s
    survivor mesh.  ``dead`` flat source positions are never read; each
    of their blocks must be covered by ``replacements[pos]`` — a
    device-resident array equal to that position's lost shard (the
    peer-shadow copy ft/elastic maintains).  Audited exactly like a
    single-mesh plan: one decide:reshard event for the migrate step,
    per-pair traffic attribution on the source mesh's edge space, and
    the reshard_* pvars."""
    global _last_run
    if not isinstance(dst, NamedSharding):
        raise TypeError("cross_reshard: dst must be a NamedSharding "
                        f"(got {type(dst).__name__})")
    s = getattr(x, "sharding", None)
    if not (isinstance(x, jax.Array) and isinstance(s, NamedSharding)):
        raise ReshardError("cross_reshard: x must be a mesh-sharded "
                           "jax.Array (got an uncommitted input)")
    src_mesh = s.mesh
    if src_mesh == dst.mesh and not dead:
        return resharder(src_mesh, spc=spc).run(x, dst.spec)
    replacements = dict(replacements or {})
    plan = _cross_plan(x.shape, x.dtype, s.spec, dst.spec,
                       src_mesh, dst.mesh, dead)
    from .. import perf
    from ..coll import xla as _xla
    src_devs = list(np.asarray(src_mesh.devices).flat)
    itemsize = np.dtype(plan.dtype).itemsize
    t0 = time.perf_counter()
    if plan.fallback_reason:
        out = jax.device_put(x, dst)
        pair_bytes: Dict[Tuple[int, int], int] = {}
        wire = plan.wire_bytes
    else:
        shards = {}
        for sh in x.addressable_shards:
            shards[src_devs.index(sh.device)] = sh.data
        by_dst: Dict[int, List[CrossPiece]] = {}
        for pc in plan.pieces:
            by_dst.setdefault(pc.dst_pos, []).append(pc)
        pair_bytes = {}
        wire = 0
        blocks = []
        order = []
        src_sh_map = {src_devs.index(d): _region(idx, x.shape)
                      for d, idx in
                      NamedSharding(src_mesh, s.spec)
                      .devices_indices_map(x.shape).items()}
        for dev, idx in dst.devices_indices_map(x.shape).items():
            dpos = src_devs.index(dev)
            R = _region(idx, x.shape)
            pcs = by_dst[dpos]
            whole = (len(pcs) == 1 and not pcs[0].from_shadow
                     and pcs[0].src_pos == dpos
                     and pcs[0].sizes == tuple(b - a for a, b in
                                               src_sh_map[dpos]))
            if whole:
                blocks.append(shards[dpos])
                order.append(dev)
                continue
            rshape = tuple(b - a for a, b in R)
            zkey = ("reshard_cross_zeros", rshape, plan.dtype, id(dev))
            zfn = _cross_compiled(
                zkey,
                lambda rs=rshape, dv=dev: jax.jit(
                    lambda: jnp.zeros(rs, plan.dtype),
                    out_shardings=jax.sharding.SingleDeviceSharding(dv)),
                spc=spc)
            block = zfn()
            for pc in pcs:
                if pc.from_shadow:
                    repl = replacements.get(pc.src_pos)
                    if repl is None:
                        raise ReshardError(
                            f"cross_reshard: dead position {pc.src_pos} "
                            "has no replacement shard (peer shadow "
                            "missing) — cannot recover its block")
                    base = src_sh_map[pc.src_pos]
                    arr = repl
                    holder = next(iter(arr.devices())) \
                        if hasattr(arr, "devices") else dev
                    src_pos_real = (src_devs.index(holder)
                                    if holder in src_devs else pc.src_pos)
                else:
                    base = src_sh_map[pc.src_pos]
                    arr = shards[pc.src_pos]
                    src_pos_real = pc.src_pos
                crop = tuple(
                    slice(st - b0, st - b0 + sz)
                    for st, sz, (b0, _b1) in zip(pc.start, pc.sizes, base))
                if any(c != slice(0, sh) for c, sh in zip(crop, arr.shape)):
                    arr = arr[crop]
                moved = jax.device_put(arr, dev)
                if src_pos_real != dpos:
                    nb = int(np.prod(pc.sizes)) * itemsize
                    wire += nb
                    pair_bytes[(src_pos_real, dpos)] = \
                        pair_bytes.get((src_pos_real, dpos), 0) + nb
                offs = tuple(st - a for st, (a, _b) in zip(pc.start, R))
                ukey = ("reshard_cross_update", rshape, moved.shape, offs,
                        plan.dtype, id(dev))
                ufn = _cross_compiled(
                    ukey,
                    lambda o=offs, dv=dev: jax.jit(
                        lambda b, p: lax.dynamic_update_slice(
                            b, p, o),
                        donate_argnums=(0,),
                        out_shardings=jax.sharding.SingleDeviceSharding(
                            dv)),
                    spc=spc)
                block = ufn(block, moved)
            blocks.append(block)
            order.append(dev)
        out = jax.make_array_from_single_device_arrays(
            x.shape, dst, blocks)
    dur = None
    if perf.enabled:
        jax.block_until_ready(out)
        dur = time.perf_counter() - t0
    # -- audit: one decision + counters + per-pair traffic ---------------
    plane = ("dcn" if any(classify_axes(src_mesh).get(a) == "dcn"
                          for a in src_mesh.axis_names) else "ici")
    arm, reason, chain = _xla.decide_mode(
        "reshard", wire, plan.n_src, jax.devices()[0].platform,
        _xla._load_device_rules(), allowed=("native",), quant_ok=False,
        dtype=None, op=None, plane=plane, hier_ok=False,
        hier_why="cross-mesh migrate is a fixed point-to-point schedule")
    with _lock:
        _counts["reshard_steps"] += 1
        _counts["reshard_bytes"] += int(wire)
    if spc is not None:
        spc.inc(f"coll_arm_{arm}_count")
        if wire:
            spc.inc("coll_wire_bytes", int(wire))
    planes: Dict[str, int] = {}
    from .. import traffic
    if traffic.enabled and wire:
        if pair_bytes:
            axes = tuple(src_mesh.axis_names)
            for (sp, dp), nb in sorted(pair_bytes.items()):
                part = traffic.note_reshard_step(
                    src_mesh, "perm", axes, nb, pairs=[(sp, dp)])
                for k, v in part.items():
                    planes[k] = planes.get(k, 0) + v
        else:       # device_put fallback: full exchange on the dst mesh
            planes = traffic.note_reshard_step(
                dst.mesh, "a2a", tuple(dst.mesh.axis_names), wire)
    if perf.enabled and dur is not None and wire and plan.n_src >= 2:
        perf.note_sample("reshard", arm, wire, dur, plan.n_src,
                         planes=planes)
    step_op = plan.describe()[0]
    if trace.enabled:
        trace.decision(
            "reshard", arm=arm, reason=reason, verdict=None,
            nbytes=int(wire),
            step=0, step_op=step_op, plan=plan.label, plan_steps=1,
            peak_bytes=plan.peak_bytes, bound_bytes=plan.bound_bytes,
            ndev=plan.n_src, wire_bytes=int(wire), chain=chain,
            cross=True, dead=sorted(int(p) for p in dead))
    with _lock:
        _last_run = {"plan": plan.label,
                     "steps": [{"step": 0, "op": step_op, "arm": arm,
                                "reason": reason, "wire_bytes": int(wire),
                                "dur_us": (round(dur * 1e6, 1)
                                           if dur is not None else None)}],
                     "wire_bytes": int(wire),
                     "peak_bytes": plan.peak_bytes,
                     "bound_bytes": plan.bound_bytes,
                     "fallback_reason": plan.fallback_reason}
    return out


# ---------------------------------------------------------------------------
# pvars + report
# ---------------------------------------------------------------------------

def pvar_value(name: str) -> float:
    with _lock:
        return float(_counts[name])


def report() -> Dict[str, Any]:
    """Structured snapshot for comm_doctor --reshard:
    the compiled-plan cache view and the last executed plan's per-step
    audit."""
    with _lock:
        return {"counters": dict(_counts),
                "plans": list(_plan_log),
                "last": dict(_last_run) if _last_run else None}


def reset() -> None:
    global _last_run
    with _lock:
        for k in _counts:
            _counts[k] = 0
        _plan_log.clear()
        _last_run = None
    _resharders.clear()
    _cross_plans.clear()
    _cross_exe.clear()
