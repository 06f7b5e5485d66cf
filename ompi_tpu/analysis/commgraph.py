"""Static collective-program extraction + SPMD verification.

MPI's static collective-matching verifiers (MPI-Checker, MUST) prove a
communication program well-formed before it runs: every rank issues
the same (op, communicator, dtype, count) sequence, point-to-point
patterns pair up, nothing escapes the accounted path.  The jaxpr is
this repo's communication program: one traced SPMD program whose
collective eqns (psum / ppermute / all_to_all / all_gather /
reduce_scatter) carry axis names, dtypes and per-shard shapes — so the
same discipline applies *before dispatch*, which is exactly the proof
obligation the observe->decide->act loop (ROADMAP item 5) needs under
it: a policy layer may only rewrite arms live over a program that is
statically known to be well-formed.

Three consumers:

* ``extract(fn, *args)`` — walk the closed jaxpr of any jittable
  callable into a ``CommGraph`` of ``CollRecord``s (recursing through
  pjit / shard_map / scan / while / cond / remat / custom-vjp bodies,
  multiplying scan trip counts through).
* ``from_reshard_plan(plan)`` — the reshard plan compiler's step list
  is already a static collective program; lift it into the same
  representation so bijection/axis checks and wire prediction apply.
* ``from_compiled(compiled, mesh)`` — the collectives of a compiled,
  partitioned program, read from its optimized HLO: op, mesh axes
  (from the replica groups or source-target pairs), dtype and per-shard
  payload of every instruction, each executed once per call.
* ``verify(fn, args, mesh)`` — checks + static wire prediction + a
  live run under the traffic plane, comparing the static figure with
  the runtime per-coll attribution **byte-for-byte** (same integer
  expressions as the runtime note models, same 2(r-1)/r-style factors
  as ``perf/model.py`` — ``tests/test_analysis.py`` pins the factor
  agreement against ``perf.model._FACTOR``).

What the extractor can and cannot see: explicit collectives (shard_map
programs, pmean/psum under vmap-style axes) appear as eqns; the psums
GSPMD *inserts* during SPMD partitioning of an auto-sharded jit do
not exist at trace time and are invisible to ``extract`` — consistently
with the runtime side, which never attributes them either (the traffic
plane charges through wrapper-level note models and the audited
dispatch layer, both of which run outside XLA's partitioner).  Both
ledgers therefore cover the same program: the explicitly-dispatched
collectives.  ``from_compiled`` sees the partitioned program instead:
GSPMD's all-reduces, the resharding it inserts and the explicit
collectives alike, after XLA has combined and scheduled them.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

# collective primitives -> canonical op name (jax names reduce_scatter's
# primitive "reduce_scatter"; lax.psum_scatter builds it; under
# shard_map's VMA typing a psum of a varying value binds psum_invariant)
_COLL_PRIMS = {
    "psum": "psum",
    "psum_invariant": "psum",
    "pmin": "pmin",
    "pmax": "pmax",
    "ppermute": "ppermute",
    "all_to_all": "all_to_all",
    "all_gather": "all_gather",
    "all_gather_invariant": "all_gather",
    "reduce_scatter": "reduce_scatter",
    "psum_scatter": "reduce_scatter",
}

# primitives that move device data through the host inside a traced
# program — a device->host round-trip hiding in a device path
_HOST_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "infeed", "outfeed", "host_local_array_to_global_array",
})

# eqn params that hold subjaxprs we recurse into (plus 'branches' for
# cond/switch, handled specially for divergence detection)
_SUBJAXPR_PARAMS = ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr",
                    "fun_jaxpr", "fwd_jaxpr_thunk")


@dataclass(frozen=True)
class CollRecord:
    """One collective eqn operand in program order."""
    op: str                          # canonical op name
    axes: Tuple[str, ...]            # mesh axis names the eqn reduces over
    dtype: str
    shape: Tuple[int, ...]           # per-shard payload shape (inside
    #                                  shard_map avals are per-device)
    nbytes: int                      # payload bytes per executed call
    trips: int = 1                   # enclosing-scan length product
    perm: Tuple[Tuple[int, int], ...] = ()   # ppermute (src, dst) pairs
    path: str = ""                   # eqn nesting, e.g. pjit/shard_map/scan
    bounded: bool = True             # False under a data-dependent while

    @property
    def total_bytes(self) -> int:
        return self.nbytes * self.trips

    @property
    def control(self) -> bool:
        """Scalar payloads are control-plane figures (loss means, flags):
        the runtime note models exclude them from wire attribution, so
        the static wire models do too (they ride the same wire in O(1)
        bytes)."""
        return self.shape == ()

    def signature(self) -> Tuple[str, Tuple[str, ...], str, int]:
        """The MPI-Checker matching tuple: (op, axes, dtype, count)."""
        count = int(np.prod(self.shape)) if self.shape else 1
        return (self.op, self.axes, self.dtype, count * self.trips)


@dataclass(frozen=True)
class Issue:
    kind: str        # bijection|mismatch|hier-cover|host-transfer|
    #                  unknown-axis|unbounded
    msg: str
    severity: str = "error"          # error | warn


@dataclass
class CommGraph:
    """The extracted collective program."""
    records: List[CollRecord] = field(default_factory=list)
    host_transfers: List[str] = field(default_factory=list)
    divergent_conds: List[str] = field(default_factory=list)
    source: str = ""

    # -- extraction helpers -------------------------------------------

    def signatures(self) -> List[Tuple]:
        return [r.signature() for r in self.records]

    def by_op(self) -> Dict[str, List[CollRecord]]:
        out: Dict[str, List[CollRecord]] = {}
        for r in self.records:
            out.setdefault(r.op, []).append(r)
        return out

    # -- SPMD well-formedness checks ----------------------------------

    def check(self, mesh=None) -> List[Issue]:
        """All static checks; ``mesh`` (a jax Mesh or {axis: size}
        mapping) enables the axis-existence / permutation-range /
        hier-cover checks."""
        sizes = _axis_sizes(mesh)
        issues: List[Issue] = []
        issues += self._check_bijections(sizes)
        issues += self._check_axes(sizes)
        issues += self._check_hier_cover(sizes)
        for p in self.divergent_conds:
            issues.append(Issue(
                "mismatch",
                f"collective sequence differs across cond branches at "
                f"{p}: ranks taking different branches would issue "
                "different (op, axes, dtype, count) sequences "
                "(MPI-Checker's matching violation)"))
        for p in self.host_transfers:
            issues.append(Issue(
                "host-transfer",
                f"device->host transfer inside a device path at {p}: "
                "a callback serializes the program against the host "
                "and escapes every plane's accounting"))
        for r in self.records:
            if not r.bounded:
                issues.append(Issue(
                    "unbounded",
                    f"{r.op} over {r.axes} at {r.path} executes under "
                    "a data-dependent while: trip count (and wire "
                    "bytes) are not statically bounded", "warn"))
        return issues

    def _check_bijections(self, sizes) -> List[Issue]:
        issues = []
        for r in self.records:
            if r.op != "ppermute" or not r.perm:
                continue
            srcs = [s for s, _ in r.perm]
            dsts = [d for _, d in r.perm]
            if len(set(srcs)) != len(srcs) or len(set(dsts)) != len(dsts):
                issues.append(Issue(
                    "bijection",
                    f"ppermute over {r.axes} at {r.path} is not a "
                    f"bijection: perm {r.perm} repeats a "
                    f"{'source' if len(set(srcs)) != len(srcs) else 'destination'}"
                    " (two ranks would send to / receive from the same "
                    "peer in one step)"))
                continue
            if sizes and all(a in sizes for a in r.axes):
                dom = int(np.prod([sizes[a] for a in r.axes]))
                bad = [p for p in r.perm
                       if not (0 <= p[0] < dom and 0 <= p[1] < dom)]
                if bad:
                    issues.append(Issue(
                        "bijection",
                        f"ppermute over {r.axes} at {r.path}: pairs "
                        f"{bad} fall outside the axis domain [0, {dom})"))
        return issues

    def _check_axes(self, sizes) -> List[Issue]:
        if not sizes:
            return []
        issues = []
        for r in self.records:
            missing = [a for a in r.axes if a not in sizes]
            if missing:
                issues.append(Issue(
                    "unknown-axis",
                    f"{r.op} at {r.path} names axis "
                    f"{missing[0]!r} not on the mesh "
                    f"({tuple(sizes)})"))
        return issues

    def _check_hier_cover(self, sizes) -> List[Issue]:
        """The hier arm's shape is reduce_scatter(inner) ->
        reduce(outer) -> all_gather(inner); the two stages must cover
        the comm's axis product — an outer stage reusing an inner axis
        reduces twice over one plane and never over the other."""
        issues = []
        recs = [r for r in self.records if not r.control]
        for i, r in enumerate(recs):
            if r.op != "reduce_scatter":
                continue
            outer = next((x for x in recs[i + 1:]
                          if x.op in ("psum", "pmin", "pmax")), None)
            gather = next((x for x in recs[i + 1:]
                           if x.op == "all_gather"), None)
            if outer is None or gather is None:
                continue
            if gather.axes != r.axes:
                continue          # not the hier shape
            if set(outer.axes) & set(r.axes):
                issues.append(Issue(
                    "hier-cover",
                    f"hier split at {r.path}: outer stage reduces over "
                    f"{outer.axes} which reuses inner axis(es) "
                    f"{tuple(set(outer.axes) & set(r.axes))} — the "
                    "split does not cover the axis product (one plane "
                    "reduced twice, the other never)"))
            elif sizes:
                uncovered = [a for a in sizes
                             if a not in r.axes and a not in outer.axes
                             and sizes[a] > 1]
                # axes genuinely outside the comm (e.g. tp during a dp
                # sync) are legitimate; only warn so two-tier meshes
                # with a typo'd outer axis surface
                if uncovered:
                    issues.append(Issue(
                        "hier-cover",
                        f"hier split at {r.path} covers "
                        f"{r.axes + outer.axes}; mesh axes "
                        f"{tuple(uncovered)} are outside the split "
                        "(fine for a partial-mesh comm, wrong for a "
                        "full allreduce)", "warn"))
        return issues

    def match(self, other: "CommGraph") -> List[Issue]:
        """Cross-program matching (MPMD-style: one extracted program
        per rank group).  SPMD single-program repos hit this through
        tests and through cond-divergence above."""
        a, b = self.signatures(), other.signatures()
        issues = []
        for i, (sa, sb) in enumerate(zip(a, b)):
            if sa != sb:
                issues.append(Issue(
                    "mismatch",
                    f"collective #{i} differs: {sa} vs {sb}"))
                break
        if not issues and len(a) != len(b):
            issues.append(Issue(
                "mismatch",
                f"collective count differs: {len(a)} vs {len(b)} "
                f"(first extra: "
                f"{(a + b)[min(len(a), len(b))]})"))
        return issues

    # -- static wire prediction ---------------------------------------

    def psum_ring_bytes(self, mesh, axes: Optional[Tuple[str, ...]] = None
                        ) -> int:
        """Ring-allreduce wire model over the non-control psum records:
        2(n-1)/n x payload bytes per rank — the same expression
        ``perf/model._FACTOR['allreduce']`` prices and
        ``overlap._note_traffic`` charges (one floor-division over the
        summed payload, so the figures agree byte-for-byte)."""
        sizes = _axis_sizes(mesh)
        groups: Dict[Tuple[str, ...], int] = {}
        for r in self.records:
            if r.op == "psum" and not r.control:
                if axes is None or r.axes == tuple(axes):
                    groups[r.axes] = groups.get(r.axes, 0) + r.total_bytes
        total = 0
        for ax, payload in groups.items():
            n = int(np.prod([sizes.get(a, 1) for a in ax])) if sizes else 1
            if n > 1:
                total += 2 * (n - 1) * payload // n
        return total

    def ppermute_bytes(self) -> int:
        """ppermute moves the full payload once per trip (factor 1 —
        the traffic plane's note_ring/note_ppermute convention)."""
        return sum(r.total_bytes for r in self.records
                   if r.op == "ppermute" and not r.control)

    def all_to_all_bytes(self) -> int:
        """all_to_all wire = the per-rank shard payload (factor 1 —
        the audited dispatch convention: the (n-1)/n on-wire discount
        lives in the busbw factor table, not the byte ledger)."""
        return sum(r.total_bytes for r in self.records
                   if r.op == "all_to_all" and not r.control)

    def gather_scatter_bytes(self, mesh) -> int:
        """all_gather / reduce_scatter: (n-1)/n x the gathered (full)
        buffer == (n-1) x the per-shard payload for all_gather, and
        (n-1)/n x the per-rank buffer for reduce_scatter — the
        ``perf/model._FACTOR`` (r-1)/r family."""
        sizes = _axis_sizes(mesh)
        total = 0
        for r in self.records:
            if r.control:
                continue
            n = int(np.prod([sizes.get(a, 1) for a in r.axes])) \
                if sizes else 1
            if n <= 1:
                continue
            if r.op == "all_gather":
                total += (n - 1) * r.total_bytes
            elif r.op == "reduce_scatter":
                total += (n - 1) * r.total_bytes // n
        return total

    def reshard_bytes(self) -> int:
        """Plan-lifted graphs: the step wire figures the plan compiler
        modeled (and the reshard executor charges verbatim)."""
        return sum(r.total_bytes for r in self.records
                   if r.path.startswith("reshard-plan"))

    def wire_by_axes(self, mesh) -> Dict[Tuple[str, ...], int]:
        """Static wire bytes per group of mesh axes, each record priced
        by the models above (psum ring, ppermute, all_to_all, gather /
        scatter); pmin/pmax and scalar payloads are not priced."""
        out: Dict[Tuple[str, ...], int] = {}
        for ax in dict.fromkeys(r.axes for r in self.records):
            sub = CommGraph(records=[r for r in self.records
                                     if r.axes == ax])
            out[ax] = (sub.psum_ring_bytes(mesh) + sub.ppermute_bytes()
                       + sub.all_to_all_bytes()
                       + sub.gather_scatter_bytes(mesh))
        return out


# ---------------------------------------------------------------------------
# extraction
# ---------------------------------------------------------------------------

def _axis_sizes(mesh) -> Dict[str, int]:
    if mesh is None:
        return {}
    if isinstance(mesh, dict):
        return {str(k): int(v) for k, v in mesh.items()}
    return {str(a): int(mesh.shape[a]) for a in mesh.axis_names}


def _axes_of(params: Dict[str, Any]) -> Tuple[str, ...]:
    ax = params.get("axes", params.get("axis_name", ()))
    if isinstance(ax, (tuple, list)):
        return tuple(str(a) for a in ax)
    return (str(ax),)


def _subjaxprs(v) -> List[Any]:
    """Jaxpr-like values inside one eqn param value."""
    if hasattr(v, "eqns"):
        return [v]
    if hasattr(v, "jaxpr"):
        return [v.jaxpr]
    if isinstance(v, (tuple, list)):
        out = []
        for x in v:
            if hasattr(x, "eqns"):
                out.append(x)
            elif hasattr(x, "jaxpr"):
                out.append(x.jaxpr)
        return out
    return []


def _walk(jaxpr, g: CommGraph, trips: int, path: str, bounded: bool
          ) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _COLL_PRIMS:
            op = _COLL_PRIMS[name]
            axes = _axes_of(eqn.params)
            perm = tuple(tuple(int(x) for x in p)
                         for p in eqn.params.get("perm", ()))
            for iv in eqn.invars:
                aval = getattr(iv, "aval", None)
                if aval is None or not hasattr(aval, "shape"):
                    continue
                shape = tuple(int(s) for s in aval.shape)
                dt = np.dtype(aval.dtype)
                g.records.append(CollRecord(
                    op=op, axes=axes, dtype=dt.name, shape=shape,
                    nbytes=int(np.prod(shape)) * dt.itemsize if shape
                    else dt.itemsize,
                    trips=trips, perm=perm, path=path or "<top>",
                    bounded=bounded))
            continue
        if name in _HOST_PRIMS:
            g.host_transfers.append(f"{path or '<top>'}/{name}")
            # fall through: callbacks can still carry subjaxprs
        sub_path = f"{path}/{name}" if path else name
        if name in ("cond", "switch"):
            branches = eqn.params.get("branches", ())
            sub_sigs = []
            for br in branches:
                bg = CommGraph()
                for bj in _subjaxprs(br):
                    _walk(bj, bg, trips, sub_path, bounded)
                sub_sigs.append((bg, bg.signatures()))
            if sub_sigs:
                first_g, first_sig = sub_sigs[0]
                if any(sig != first_sig for _, sig in sub_sigs[1:]):
                    g.divergent_conds.append(sub_path)
                # merge the first branch so prediction sees one arm;
                # divergence itself is already a matching error
                g.records.extend(first_g.records)
                g.host_transfers.extend(
                    h for bg, _ in sub_sigs for h in bg.host_transfers)
            continue
        sub_trips = trips
        sub_bounded = bounded
        if name == "scan":
            sub_trips = trips * int(eqn.params.get("length", 1))
        elif name == "while":
            sub_bounded = False
        for key, v in eqn.params.items():
            if key == "branches":
                continue
            for sj in _subjaxprs(v):
                _walk(sj, g, sub_trips, sub_path, sub_bounded)


def extract(fn: Callable, *args, source: str = "", **kwargs) -> CommGraph:
    """Trace ``fn(*args, **kwargs)`` (jitted or plain) and extract its
    collective program."""
    import jax

    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    g = CommGraph(source=source or getattr(fn, "__name__", "<fn>"))
    _walk(closed.jaxpr, g, 1, "", True)
    return g


def from_reshard_plan(plan) -> CommGraph:
    """Lift a compiled ``ReshardPlan`` into a CommGraph: the plan's
    step list is a static collective program whose wire figures the
    executor charges verbatim, so the plan-side record carries
    ``step.wire_bytes`` and the usual checks (bijection, axis
    existence) apply to its ppermute steps."""
    g = CommGraph(source=f"reshard-plan:{plan.label}")
    step_ops = {"all_to_all": "all_to_all", "all_gather": "all_gather",
                "ppermute": "ppermute", "device_put": "device_put",
                "slice": "slice"}
    for i, step in enumerate(plan.steps):
        op = step_ops.get(step.op, step.op)
        if op == "slice":
            continue              # local, no wire
        g.records.append(CollRecord(
            op=op, axes=tuple(step.axes), dtype=plan.dtype,
            shape=(), nbytes=int(step.wire_bytes), trips=1,
            perm=tuple(tuple(int(x) for x in p) for p in step.perm),
            path=f"reshard-plan/step{i}:{step.describe()}"))
    return g


# HLO opcode -> canonical op (an all-reduce's is refined by its reducer)
_HLO_COLL = {"all-reduce": "psum", "all-gather": "all_gather",
             "reduce-scatter": "reduce_scatter", "all-to-all": "all_to_all",
             "collective-permute": "ppermute"}
_HLO_REDUCER = {"maximum": "pmax", "minimum": "pmin"}
_HLO_DTYPES = {"pred": "bool", "s8": "int8", "s16": "int16", "s32": "int32",
               "s64": "int64", "u8": "uint8", "u16": "uint16",
               "u32": "uint32", "u64": "uint64", "f16": "float16",
               "bf16": "bfloat16", "f32": "float32", "f64": "float64",
               "f8e4m3fn": "float8_e4m3fn", "f8e5m2": "float8_e5m2"}
_HLO_ITEMSIZE = {"bool": 1, "bfloat16": 2, "float8_e4m3fn": 1,
                 "float8_e5m2": 1}
_HLO_COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{\s*$")
_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = (.+?) (all-reduce|all-gather|"
    r"reduce-scatter|all-to-all|collective-permute)(-start)?\(")
_HLO_ARRAY = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_HLO_CALLEE = re.compile(r"(?:calls|to_apply|body|condition|"
                         r"branch_computations)=(\{[^}]*\}|%[\w.\-]+)")
_HLO_GROUPS_LIST = re.compile(r"replica_groups=\{((?:\{[\d,]*\},?)*)\}")
_HLO_GROUPS_IOTA = re.compile(
    r"replica_groups=\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_HLO_PAIRS = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")


def _hlo_groups(line: str, n_devices: int) -> List[List[int]]:
    """Device groups of one collective instruction (logical device ids,
    which index the mesh's devices in order)."""
    m = _HLO_GROUPS_IOTA.search(line)
    if m:
        shape = [int(x) for x in m.group(1).split(",")]
        dims = [int(x) for x in m.group(2).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(3):
            ids = ids.transpose([int(x) for x in m.group(3).split(",")])
        return ids.reshape(shape).tolist()
    m = _HLO_GROUPS_LIST.search(line)
    if m and m.group(1):
        return [[int(x) for x in g.split(",") if x]
                for g in re.findall(r"\{([\d,]*)\}", m.group(1))]
    m = _HLO_PAIRS.search(line)
    if m:
        return [[int(a), int(b)] for a, b in
                re.findall(r"\{(\d+),(\d+)\}", m.group(1))]
    return [list(range(n_devices))]


def _hlo_axes(groups: List[List[int]], shape: Tuple[int, ...],
              names: Tuple[str, ...]) -> Tuple[str, ...]:
    """The mesh axes along which the members of the groups differ."""
    varying = set()
    for g in groups:
        coords = np.array(np.unravel_index(np.asarray(g), shape))
        varying |= {i for i in range(len(shape))
                    if len(set(coords[i].tolist())) > 1}
    return tuple(names[i] for i in sorted(varying))


def from_compiled(compiled, mesh, source: str = "") -> CommGraph:
    """Lift the collectives of a compiled, partitioned program into a
    CommGraph.  ``compiled`` is a ``jax.stages.Compiled`` (or its HLO
    text) and ``mesh`` the mesh its shardings name.  One record per
    payload array of each collective instruction: ``axes`` are the mesh
    axes its replica groups (source-target pairs, for a permute) span,
    ``nbytes`` the per-shard operand bytes, ``path`` the computation
    and instruction name, as the profiler's op events carry it.  An
    instruction runs once per call (``trips`` 1) unless it sits under a
    while loop, where it is marked unbounded."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    sizes = _axis_sizes(mesh)
    names, shape = tuple(sizes), tuple(sizes.values())
    n_dev = int(np.prod(shape)) if shape else 1
    bodies, callees, reducers = {}, {}, {}
    comp = ""
    for line in text.splitlines():
        m = _HLO_COMP.match(line)
        if m:
            comp = m.group(1)
            continue
        if " = " not in line:
            continue
        bodies.setdefault(comp, []).append(line)
        for val in _HLO_CALLEE.findall(line):
            callees.setdefault(comp, set()).update(
                re.findall(r"%([\w.\-]+)", val))
        if line.lstrip().startswith("ROOT"):
            op = re.search(r"\s([a-z][\w\-]*)\(", line.split(" = ", 1)[1])
            reducers[comp] = _HLO_REDUCER.get(op and op.group(1), "psum")
    # computations run under a while loop: its body and all it calls
    looped, todo = set(), [b for ls in bodies.values() for ln in ls
                           for b in re.findall(r"body=%([\w.\-]+)", ln)]
    while todo:
        c = todo.pop()
        if c not in looped:
            looped.add(c)
            todo.extend(callees.get(c, ()))
    g = CommGraph(source=source or "compiled")
    for comp, lines in bodies.items():
        for line in lines:
            m = _HLO_INSTR.match(line)
            if m is None:
                continue
            name, result, opcode, start = m.groups()
            op = _HLO_COLL[opcode]
            if op == "psum":
                red = re.search(r"to_apply=%([\w.\-]+)", line)
                op = reducers.get(red.group(1), "psum") if red else op
            groups = _hlo_groups(line, n_dev)
            axes = _hlo_axes(groups, shape, names) if shape else ()
            arrays = [(_HLO_DTYPES.get(dt, dt),
                       tuple(int(x) for x in dims.split(",") if x))
                      for dt, dims in _HLO_ARRAY.findall(result)]
            n = max(len(grp) for grp in groups)
            if start and opcode != "all-reduce":
                # (operands, outputs[, u32 contexts]): keep the operands
                arrays = [a for a in arrays if a[1]] or arrays
                arrays = arrays[:max(1, len(arrays) // 2)]
                result_shaped = False
            else:
                result_shaped = True
            perm = ()
            if op == "ppermute" and axes:
                pos = [names.index(a) for a in axes]
                perm = tuple(sorted({
                    tuple(int(np.ravel_multi_index(
                        [np.unravel_index(i, shape)[p] for p in pos],
                        [shape[p] for p in pos])) for i in pair)
                    for pair in groups}))
            for dt, shp in arrays:
                count = int(np.prod(shp)) if shp else 1
                item = _HLO_ITEMSIZE.get(dt) or np.dtype(dt).itemsize
                nbytes = count * item
                if result_shaped and op == "all_gather":
                    nbytes //= n
                elif result_shaped and op == "reduce_scatter":
                    nbytes *= n
                g.records.append(CollRecord(
                    op=op, axes=axes, dtype=dt, shape=shp, nbytes=nbytes,
                    trips=1, perm=perm, path=f"{comp}/{name}",
                    bounded=comp not in looped))
    return g


# ---------------------------------------------------------------------------
# verify: static prediction vs runtime attribution
# ---------------------------------------------------------------------------

# runtime per-coll ledger key -> static wire model.  The traffic plane
# files its charges under wrapper-chosen coll names; each maps to the
# static model that reproduces the wrapper's byte expression exactly.
_DEFAULT_COLL_MAP = {
    "grad_sync": "psum_ring",
    "ring_attention": "ppermute",
    "ulysses": "all_to_all",
    "reshard": "reshard",
    # the fused decode program's collective-matmul rings: n−1 ppermute
    # hops per ring, charged per-ring by the serving engine — the
    # ppermute trip model reproduces the schedule's wire column exactly
    "decode_collmm": "ppermute",
}


@dataclass
class VerifyReport:
    """``verify()``'s typed result."""
    source: str
    n_records: int
    issues: List[Issue]
    rows: List[Dict[str, Any]]       # coll / static / runtime / ok
    host_transfers: List[str]

    @property
    def ok(self) -> bool:
        return (all(r["ok"] for r in self.rows)
                and not any(i.severity == "error" for i in self.issues))

    def to_json(self) -> Dict[str, Any]:
        return {
            "source": self.source, "ok": self.ok,
            "n_records": self.n_records,
            "issues": [{"kind": i.kind, "msg": i.msg,
                        "severity": i.severity} for i in self.issues],
            "rows": self.rows,
            "host_transfers": list(self.host_transfers),
        }

    def summary(self) -> str:
        lines = [f"commgraph: {self.source}: {self.n_records} collective "
                 f"record(s), {len(self.issues)} issue(s), "
                 f"{'OK' if self.ok else 'FAIL'}"]
        for r in self.rows:
            lines.append(
                f"  {r['coll']}: static {r['static']} B vs runtime "
                f"{r['runtime']} B {'==' if r['ok'] else '!='}")
        for i in self.issues:
            lines.append(f"  [{i.severity}] {i.kind}: {i.msg}")
        return "\n".join(lines)


def _static_bytes(g: CommGraph, mesh, model: str) -> int:
    if model == "psum_ring":
        return g.psum_ring_bytes(mesh)
    if model == "ppermute":
        return g.ppermute_bytes()
    if model == "all_to_all":
        return g.all_to_all_bytes()
    if model == "gather_scatter":
        return g.gather_scatter_bytes(mesh)
    if model == "reshard":
        return g.reshard_bytes()
    raise ValueError(f"unknown static wire model {model!r}")


def verify(fn: Callable, args: Sequence[Any], mesh,
           coll_map: Optional[Dict[str, str]] = None,
           graph: Optional[CommGraph] = None,
           runner: Optional[Callable[[], Any]] = None,
           source: str = "") -> VerifyReport:
    """Static checks + byte-for-byte static-vs-runtime wire agreement.

    Extracts ``fn``'s collective program (or takes a pre-built
    ``graph``, e.g. a plan-lifted one), runs the well-formedness
    checks, then executes ``runner()`` (default: ``fn(*args)`` blocked
    to completion) under the traffic plane and compares the runtime
    per-coll byte deltas against the static models named by
    ``coll_map`` (default ``_DEFAULT_COLL_MAP``).  The traffic plane's
    prior enabled state is restored."""
    import jax

    from .. import traffic

    g = graph if graph is not None else extract(
        fn, *args, source=source or getattr(fn, "__name__", "<fn>"))
    issues = g.check(mesh)
    cmap = dict(_DEFAULT_COLL_MAP if coll_map is None else coll_map)

    was_enabled = traffic.enabled
    if not was_enabled:
        traffic.enable()
    try:
        before = traffic.matrix.per_coll()
        out = runner() if runner is not None else fn(*args)
        jax.block_until_ready(out)
        after = traffic.matrix.per_coll()
    finally:
        if not was_enabled:
            traffic.disable()

    rows: List[Dict[str, Any]] = []
    for coll, model in cmap.items():
        static = _static_bytes(g, mesh, model)
        runtime = int(after.get(coll, 0)) - int(before.get(coll, 0))
        if static == 0 and runtime == 0:
            continue
        rows.append({"coll": coll, "model": model, "static": int(static),
                     "runtime": runtime, "ok": static == runtime})
    return VerifyReport(source=g.source, n_records=len(g.records),
                        issues=issues, rows=rows,
                        host_transfers=list(g.host_transfers))


# -- policy action verification ----------------------------------------------

# the decided-dispatch vocabulary a policy action may retarget, with the
# flat native arm's per-device hop factor (fraction of the payload, the
# same 2(n-1)/n-family expressions as perf/model._FACTOR and the
# runtime note models)
_ACTION_COLL_FACTORS: Dict[str, Callable[[int], float]] = {
    "allreduce": lambda n: 2.0 * (n - 1) / n,
    "grad_sync": lambda n: 2.0 * (n - 1) / n,        # bucketed allreduce
    "reduce_scatter": lambda n: (n - 1) / n,
    "allgather": lambda n: (n - 1) / n,
    "alltoall": lambda n: (n - 1) / n,
    "broadcast": lambda n: (n - 1) / n,
    "ppermute": lambda n: 1.0,
    "collmm": lambda n: (n - 1) / n,
    "moe_dispatch": lambda n: (n - 1) / n,
    "moe_combine": lambda n: (n - 1) / n,
    "decode_ag": lambda n: (n - 1) / n,
    "decode_rs": lambda n: (n - 1) / n,
}

# ops with a quantized wire format (coll/quant.wire_bytes vocabulary,
# plus the bucketed-allreduce alias)
_QUANTIZABLE = {"allreduce": "allreduce", "grad_sync": "allreduce",
                "reduce_scatter": "reduce_scatter",
                "allgather": "allgather"}


def verify_action(coll: str, arm: str, nbytes: int = 1 << 20,
                  ndev: int = 8, dtype: str = "float32"
                  ) -> Dict[str, Any]:
    """Statically verify one policy-reachable ``(coll, arm)`` retarget.

    The policy engine calls this at CONSTRUCTION for every arm its
    rules can reach — an action that cannot be verified here is
    rejected at registration, never at 3 a.m.  Checks the arm against
    the DEVICE_RULES mode vocabulary, the op against the decided
    dispatch vocabulary, and that the arm has a wire format for the op
    (``quant`` on an op with no quantized codec is structurally
    impossible, not a runtime surprise).  Returns the wire-byte
    prediction for a ``nbytes`` payload over ``ndev`` devices — the
    figure the decision ledger records next to the measured effect.

    Raises ``ValueError`` with the full (coll, arm) context on any
    unverifiable action.
    """
    from . import rules as _rules

    if arm not in _rules.MODES:
        raise ValueError(
            f"policy action retargets {coll!r} to unknown arm {arm!r} "
            f"— not in the DEVICE_RULES mode vocabulary {_rules.MODES}")
    if coll not in _ACTION_COLL_FACTORS:
        raise ValueError(
            f"policy action retargets unknown op {coll!r} (arm {arm!r}) "
            f"— not in the decided dispatch vocabulary "
            f"{tuple(sorted(_ACTION_COLL_FACTORS))}")
    n = max(int(ndev), 2)
    esize = int(np.dtype(dtype).itemsize)
    native = int(round(_ACTION_COLL_FACTORS[coll](n) * int(nbytes)))
    wire = native
    quant_ratio = None
    if arm in ("quant", "hier+quant"):
        qcoll = _QUANTIZABLE.get(coll)
        if qcoll is None:
            raise ValueError(
                f"policy action retargets {coll!r} to arm {arm!r} but "
                f"{coll!r} has no quantized wire format "
                f"(quantizable: {tuple(sorted(_QUANTIZABLE))})")
        from ..coll.quant import wire_bytes
        wb = wire_bytes(qcoll, max(int(nbytes) // esize, 1), n, dtype)
        wire, native = int(wb["quant_bytes"]), int(wb["native_bytes"])
        quant_ratio = round(float(wb["ratio"]), 4)
    return {"coll": coll, "arm": arm, "ndev": n, "nbytes": int(nbytes),
            "predicted_wire_bytes": wire, "native_wire_bytes": native,
            "quant_ratio": quant_ratio, "ok": True}
