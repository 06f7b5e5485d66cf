"""coll/xla — ICI-native device collectives for the MPI-style comm API.

The component the whole design exists for (BASELINE.json north_star): when a
collective's buffers are device-resident (jax Arrays), dispatch to compiled
XLA collective programs over the communicator's mesh instead of staging
HBM→host like the reference's coll/accelerator shim
(ompi/mca/coll/accelerator/coll_accelerator_allreduce.c:31-60). Host (numpy)
buffers fall through to the host algorithms — the same buffer-type dispatch
the reference does with accelerator.check_addr (accelerator.h:171), with the
fast path inverted: device is native here, host is the staged case.

Selection: query() succeeds only for communicators with an attached device
mesh (``parallel.attach_mesh(comm, mesh, axis)``); priority 80 outranks
tuned(30)/basic(10), exactly how the north star requires coll/xla to win
MCA priority over coll/tuned for device buffers.
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

from .. import trace
from ..core import var as _var
from ..core.component import Component, component
from ..op import SUM, Op
from .framework import CollModule
from .tuned import TunedModule


def _is_device(x) -> bool:
    from .. import accelerator

    return accelerator.check_addr(x) is not None


# -- device decision layer (≙ coll_tuned_decision_fixed.c:55-104 +
#    coll_tuned_dynamic_file.c:58, applied to the DEVICE path) --------------
#
# The host components pick an algorithm per (comm size, msg size); the
# device component picks a MODE per (collective, device count, msg size):
# "native" runs the ICI program, "staged" takes the explicit D2H → host op
# → H2D round trip (the coll/accelerator shim as a *measured choice*, not
# a fallback). Fixed defaults come from the recorded sweep
# (BENCH_SWEEP_cpu_8dev.json): on the CPU test fabric the shard_map
# dispatch overhead loses to one memcpy for dense alltoall below ~32 MB
# (0.8-0.99x), while every other entry wins native at every size; on real
# accelerator platforms staging crosses the host bridge so native always
# wins — the platform gates the default.

_var.register("coll", "xla", "mode", "", type=str, level=3,
              help="Force device-collective mode for every entry: "
                   "native|staged|quant|hier|hier+quant (empty = "
                   "per-entry decision; quant/hier apply to entries "
                   "with that arm, others keep the auto decision).")
_var.register("coll", "xla", "dynamic_rules", "", type=str, level=4,
              help="Path to a device decision rules file: lines of "
                   "'<coll>[@<plane>] <min_ndev> <min_bytes> "
                   "<native|staged|quant|bidir|hier|hier+quant>' "
                   "(plane in {ici,dcn}; plane-keyed rows beat plain "
                   "rows on comms spanning that plane).")
_var.register("coll", "xla", "grad_bucket_bytes", 4 << 20, type=int, level=3,
              help="Target bytes per gradient-sync bucket for the "
                   "bucketed overlap tier (parallel/overlap): grads are "
                   "flattened into fixed-byte buckets in reverse-layer "
                   "order and each bucket allreduces as soon as its "
                   "leaves are produced in the backward pass.")
# the blanket quantization switch (env OMPI_TPU_COLL_QUANT):
#   on/force -> quantize every eligible reduction at any size
#   off      -> never pick quant, even when a rules file says so
#   (empty)  -> rules decide, subject to the min_bytes floor below
_var.register("COLL_QUANT", "", "", "", type=str, level=2,
              help="Blanket switch for the block-quantized device tier: "
                   "on/force | off | empty (rules decide).")
_var.register("coll", "quant", "min_bytes", 1 << 20, type=int, level=3,
              help="Per-rank byte floor below which rule-selected quant "
                   "keeps the exact arm (small messages are latency-, "
                   "not wire-bound; quantization error buys nothing).")

_DECIDED = ("allreduce", "reduce", "bcast", "allgather", "alltoall",
            "reduce_scatter_block", "scan", "exscan", "allgatherv",
            "gather", "gatherv", "scatter", "scatterv", "alltoallv",
            "reduce_scatter")
# entries with a quantized arm (coll/quant engine entry points; grad_sync
# buckets ride psum_quant so they carry one too, and the serving tier's
# decode combines ride the same allgather/reduce_scatter quant engines)
_QUANT_COLLS = ("allreduce", "reduce_scatter_block", "reduce_scatter",
                "allgather", "grad_sync", "decode_ag", "decode_rs")
for _c in _DECIDED:
    _var.register("coll", "xla", f"{_c}_mode", "", type=str, level=3,
                  help=f"Force the {_c} device mode (native|staged"
                       + ("|quant" if _c in _QUANT_COLLS else "")
                       + "; empty = auto).")
# overlap-tier decision points (not XlaModule entries): the bucketed
# gradient sync (parallel/overlap) and the collective-matmul ring
# direction (ops/collective_matmul via Config(tp_overlap="fused"))
_var.register("coll", "xla", "grad_sync_mode", "", type=str, level=3,
              help="Force the gradient-sync bucket arm (native|quant|"
                   "hier|hier+quant; empty = auto via DEVICE_RULES "
                   "grad_sync rows).")
_var.register("coll", "xla", "collmm_mode", "", type=str, level=3,
              help="Force the collective-matmul ring schedule "
                   "(native = unidirectional ring | bidir = two "
                   "half-rings on both ICI directions; empty = auto "
                   "via DEVICE_RULES collmm rows).")
_var.register("coll", "xla", "reshard_mode", "", type=str, level=3,
              help="Force the reshard plan-step arm (native; empty = "
                   "auto via DEVICE_RULES reshard rows / the learned "
                   "ledger). Plan steps are layout-pure single "
                   "collectives, so native is the only executable arm "
                   "today; the var exists so the decision chain stays "
                   "uniform and future staged/quant step arms slot in.")
_var.register("coll", "xla", "moe_dispatch_mode", "", type=str, level=3,
              help="Force the MoE token-dispatch exchange arm (native|"
                   "hier|hier+quant; empty = auto via DEVICE_RULES "
                   "moe_dispatch rows). hier splits the ragged exchange "
                   "into same-outer-group and cross-DCN lanes; dispatch "
                   "payloads are never quantized (hier+quant decays to "
                   "hier here — quant applies to the combine only).")
_var.register("coll", "xla", "decode_ag_mode", "", type=str, level=3,
              help="Force the serving decode allgather arm (native|"
                   "quant; empty = auto via DEVICE_RULES decode_ag "
                   "rows). Carries every decode-path feature combine "
                   "(embed, attention heads, o/mlp projections) plus "
                   "the logits-psum gather half; quant rides the "
                   "EQuARX int8 block tier.")
_var.register("coll", "xla", "decode_rs_mode", "", type=str, level=3,
              help="Force the serving decode reduce-scatter arm "
                   "(native|quant; empty = auto via DEVICE_RULES "
                   "decode_rs rows). Carries the logits-psum reduce "
                   "half — the B×vocab float32 payload that dominates "
                   "decode wire bytes.")
_var.register("coll", "xla", "moe_combine_mode", "", type=str, level=3,
              help="Force the MoE expert-output combine exchange arm "
                   "(native|hier|hier+quant; empty = auto via "
                   "DEVICE_RULES moe_combine rows). hier+quant sends "
                   "the cross-DCN lane on the EQuARX int8 block tier; "
                   "the same-outer-group lane stays full precision.")
_var.register("coll", "xla", "rules", "", type=str, level=3,
              help="Arm-selection source: empty/'static' = platform "
                   "default + DEVICE_RULES rows; 'learned' = consult "
                   "the perf cost-model ledger first (best modeled "
                   "busbw at the observed size, reason "
                   "'learned:<a>=..GBps-vs-<b>=..GBps'), falling "
                   "through to the static chain on a model miss. "
                   "Force vars and blanket switches still outrank.")

# every mode any decision point can name (rules-file vocabulary);
# "hier" = the two-tier HAN arm (reduce_scatter ICI -> allreduce DCN on
# the scattered 1/n_inner -> allgather ICI), "hier+quant" the same shape
# with ONLY the outer (DCN) stage on the EQuARX quantized tier.
# The authoritative copies live in analysis/rules.py (the grammar
# module CI shares); the asserts keep the two import paths in lockstep.
from ..analysis import rules as _rules_grammar

_MODES = _rules_grammar.MODES
_PLANES = _rules_grammar.PLANES
assert _MODES == ("native", "staged", "quant", "bidir", "hier",
                  "hier+quant")
assert _PLANES == ("ici", "dcn")


def _load_device_rules(path: Optional[str] = None):
    """Parse a device decision rules file into (coll, min_ndev,
    min_bytes, mode) rows.  With no argument the configured
    ``coll_xla_dynamic_rules`` path is read (the dispatch-time caller);
    an explicit path serves offline consumers — the trace analyzer's
    decision-drift check re-evaluates audited arms against any rules
    file, e.g. the repo's DEVICE_RULES.txt.

    The coll column may be plane-keyed: ``<coll>@<plane>`` (plane in
    {ici, dcn}) rows apply only to communicators whose axes include
    that plane and BEAT plain rows for the same coll at decision time
    (decide_mode's two-lane rule walk).  An unknown plane is a loud
    ValueError — a typo must not silently deactivate a row.  Parsing
    is delegated to ``analysis.rules`` (the grammar module CI shares),
    which also rejects an exactly-duplicated
    ``(coll[@plane], min_ndev, min_bytes)`` key naming both lines —
    before that validator the later row silently won the rule walk."""
    if path is None:
        path = _var.get("coll_xla_dynamic_rules", "")
    if not path:
        return []
    return _rules_grammar.parse_file(path)


def _quant_pads_past_native(coll: str, nbytes: int, ndev: int,
                            dtype) -> bool:
    """True when the quantized arm's BLOCK PADDING pushes its wire
    bytes past the native arm's for this payload: the per-rank shard
    pads up to ``coll_quant_block`` elements before the int8 cast, so a
    small-payload/large-block combination (the decode footgun:
    KB-scale decode_ag shards under ``coll_quant_block=32``… or worse,
    the 256 default) can make "compression" a strict loss.  The
    decision layer records ``ineligible:quant:pad-past-native`` instead
    of silently shipping more bytes than native would."""
    if dtype is None:
        return False
    from .quant import wire_bytes
    try:
        count = max(int(nbytes) // np.dtype(dtype).itemsize, 1)
        qcoll = ("allreduce" if coll == "allreduce" else
                 "reduce_scatter" if ("reduce_scatter" in coll
                                      or coll.endswith("_rs"))
                 else "allgather")
        wb = wire_bytes(qcoll, count, max(int(ndev), 1), dtype)
    except (ValueError, TypeError, KeyError):
        return False     # no quant wire model for this coll/dtype
    return wb["quant_bytes"] > wb["native_bytes"]


def decide_mode(coll: str, nbytes: int, ndev: int, platform: str,
                rules, allowed, quant_ok: bool = False,
                dtype=None, op: Op = None, plane: Optional[str] = None,
                hier_ok: bool = False, hier_why: str = "") -> tuple:
    """The device decision-precedence chain as a reusable module-level
    function, returned as (arm, reason, chain): per-entry force var >
    blanket coll_xla_mode > blanket COLL_QUANT > platform default, then
    DEVICE_RULES rows (later lines win; quant rows vetoed by the off
    switch, the coll_quant_min_bytes floor, or op/dtype/layout
    ineligibility).  ``reason`` is the link that decided; ``chain``
    records every vetoed/skipped link so trace.explain_last can show the
    full evaluation.

    ``allowed`` is the set of arms the calling entry can actually execute
    for this buffer/op — the decision never names an arm the entry would
    silently ignore.  XlaModule dispatches funnel through here (via
    ``_decide``); the overlap tier calls it directly with the coll names
    ``grad_sync`` (bucketed dp gradient sync, native|quant|hier) and
    ``collmm`` (collective-matmul ring direction, native|bidir).

    Two-tier extensions: ``plane`` is the calling comm's plane context
    ('dcn' when any comm axis crosses a DCN boundary, else 'ici') —
    ``<coll>@<plane>`` rule rows match only their plane and BEAT plain
    rows for the same coll (their vetoes included).  The hierarchical
    arms (hier, hier+quant) are gated by ``hier_ok`` instead of
    ``allowed``: an ineligible comm (flat mesh, single axis, non-sum
    op) records the audited ``ineligible:hier:<hier_why>`` veto, and an
    explicit per-entry force of an impossible hier raises."""
    from .quant import check_quantizable

    chain: list = []
    qvar = str(_var.get("COLL_QUANT", "") or "").strip().lower()
    ent = _var.get(f"coll_xla_{coll}_mode", "")
    forced = ent or _var.get("coll_xla_mode", "")
    src = f"coll_xla_{coll}_mode" if ent else "coll_xla_mode"
    if forced:
        if forced not in _MODES:
            raise ValueError(
                f"coll_xla mode for {coll!r} is {forced!r} "
                f"(want one of {', '.join(_MODES)})")
        if forced == "quant":
            if coll in _QUANT_COLLS:
                if "quant" in allowed:
                    # invalid op/dtype under an explicit quant force
                    # must fail loudly, not silently take the exact
                    # path
                    check_quantizable(op or SUM,
                                      dtype if dtype is not None
                                      else np.float32)
                    return "quant", f"force:{src}=quant", chain
                chain.append(f"force:{src}=quant skipped "
                             "(layout has no quantized arm)")
            elif ent:
                raise ValueError(
                    f"collective {coll!r} has no quantized arm "
                    f"(quant applies to {', '.join(_QUANT_COLLS)})")
            else:
                chain.append("force:coll_xla_mode=quant skipped "
                             "(entry has no quantized arm)")
            # global quant force: entries without a quantized arm
            # keep the auto decision below
        elif forced in ("hier", "hier+quant"):
            if not hier_ok:
                if ent:
                    # a per-entry force of an impossible hier must fail
                    # loudly, not silently take the flat path
                    raise ValueError(
                        f"coll_xla mode for {coll!r} forces {forced} "
                        f"but the comm is ineligible: {hier_why}")
                chain.append(f"force:{src}={forced} skipped "
                             f"(ineligible:hier:{hier_why})")
            elif forced == "hier+quant" and not quant_ok:
                if ent:
                    check_quantizable(op or SUM,
                                      dtype if dtype is not None
                                      else np.float32)
                chain.append(f"force:{src}={forced} skipped "
                             "(op/dtype has no quantized outer stage)")
            else:
                return forced, f"force:{src}={forced}", chain
        elif forced in allowed:
            return forced, f"force:{src}={forced}", chain
        else:
            chain.append(f"force:{src}={forced} skipped "
                         f"(no {forced} kernel for this op/layout)")
    q_ok = quant_ok and "quant" in allowed
    if qvar in ("1", "on", "true", "yes", "force"):
        if q_ok:
            return "quant", f"blanket:COLL_QUANT={qvar}", chain
        if coll in _QUANT_COLLS:
            chain.append(f"blanket:COLL_QUANT={qvar} skipped "
                         "(op/dtype/layout ineligible)")
    quant_off = qvar in ("0", "off", "false", "no")
    floor = int(_var.get("coll_quant_min_bytes", 1 << 20))
    source = str(_var.get("coll_xla_rules", "") or "").strip().lower()
    if source == "learned":
        # cost-model source (ompi_tpu/perf): best modeled busbw at this
        # size wins.  Quant stays subject to the same eligibility gates
        # as a quant rules row; a model miss falls through to the static
        # chain below so a cold ledger never strands a collective.
        from .. import perf
        cand = tuple(m for m in allowed
                     if m != "quant"
                     or (q_ok and not quant_off and nbytes >= floor
                         and not _quant_pads_past_native(
                             coll, nbytes, ndev, dtype)))
        if hier_ok:
            cand = cand + ("hier",)
            if quant_ok and not quant_off:
                cand = cand + ("hier+quant",)
        learned = perf.best_arm(coll, nbytes, cand)
        if learned is not None:
            return learned[0], learned[1], chain
        chain.append(f"learned: no modeled data for {coll}@{nbytes}B "
                     "(falling through to static chain)")
    elif source and source != "static":
        raise ValueError(f"coll_xla_rules is {source!r} "
                         "(want 'learned', 'static' or empty)")
    if platform == "cpu":
        # sweep-derived (BENCH_SWEEP_cpu_8dev.json): dense alltoall
        # staged wins 1KB-16MB/rank on the CPU fabric; all else native
        pick = "staged" if (coll == "alltoall"
                            and nbytes < (32 << 20)) else "native"
    else:
        pick = "native"       # staging crosses the host bridge
    if pick not in allowed:
        pick = "native"
    reason = f"default:platform={platform}"

    def _veto_of(mode: str, rule: str) -> Optional[str]:
        """Gates shared by plain and plane-keyed rows.  The quant floor
        deliberately does NOT veto hier+quant: only the scattered
        1/n_inner fraction is quantized there, so the flat-arm latency
        calculus behind the floor does not carry over."""
        if mode in ("quant", "hier+quant"):
            if quant_off:
                return f"off:COLL_QUANT={qvar} (vetoed {rule})"
            if not (q_ok if mode == "quant" else quant_ok):
                return f"ineligible:op/dtype/layout (vetoed {rule})"
            if mode == "quant" and nbytes < floor:
                return (f"floor:coll_quant_min_bytes={floor}"
                        f">{nbytes} (vetoed {rule})")
            if mode == "quant" and _quant_pads_past_native(
                    coll, nbytes, ndev, dtype):
                return (f"ineligible:quant:pad-past-native "
                        f"(block padding exceeds native bytes at "
                        f"{nbytes}B; vetoed {rule})")
        if mode in ("hier", "hier+quant") and not hier_ok:
            return f"ineligible:hier:{hier_why} (vetoed {rule})"
        return None

    # two-lane walk: plain rows accumulate as before; '<coll>@<plane>'
    # rows matching the comm's plane accumulate separately and override
    # the plain lane at the end (vetoes included — a vetoed plane row's
    # reason still beats a plain row's pick)
    p_pick: Optional[str] = None
    p_reason: Optional[str] = None
    for c, mn, mb, mode in rules:
        base_coll, _, row_plane = c.partition("@")
        if base_coll != coll or ndev < mn or nbytes < mb:
            continue
        if row_plane and row_plane != (plane or ""):
            continue
        rule = f"rule:{c} {mn} {mb} {mode}"
        veto = _veto_of(mode, rule)
        if veto is not None:
            # vetoed rule: keep the prior pick, but the veto IS the
            # deciding word unless a later rule overrides it
            chain.append(veto)
            if row_plane:
                p_reason = veto
            else:
                reason = veto
            continue
        if mode not in ("hier", "hier+quant") and mode not in allowed:
            chain.append(f"{rule} skipped (no {mode} kernel)")
            continue
        if row_plane:
            p_pick, p_reason = mode, rule
        else:
            pick, reason = mode, rule
        chain.append(rule)
    if p_reason is not None:
        reason = p_reason
    if p_pick is not None:
        pick = p_pick
    return pick, reason, chain


# numpy reduction kernels for the staged arm (standard MPI ops only; a
# custom op keeps the native path regardless of decision — its fn is
# jax-traceable, not a host kernel)
_NP_FOLD = {"sum": np.add.reduce, "max": np.maximum.reduce,
            "min": np.minimum.reduce, "prod": np.multiply.reduce}


def _staged_allgather(h: np.ndarray) -> np.ndarray:
    """Host allgather on the canonical layout (staged arm of both
    allgather and gather — MPI promises only the root's row for gather)."""
    flat = h.reshape((-1,) + h.shape[2:]) if h.ndim > 2 else h.reshape(-1)
    return np.broadcast_to(flat[None], (h.shape[0],) + flat.shape)


def _staged_allgatherv(h: np.ndarray, counts) -> np.ndarray:
    """Host allgatherv on the padded canonical layout (also the gatherv
    staged arm)."""
    cat = np.concatenate([h[i, :int(c)] for i, c in enumerate(counts)])
    return np.broadcast_to(cat[None], (h.shape[0],) + cat.shape)


class XlaModule(CollModule):
    def __init__(self, comm) -> None:
        from ..parallel.collectives import DeviceComm

        self.dc: "DeviceComm" = comm.device_comm
        self.dc.spc = getattr(comm.ctx, "spc", None)
        self.host = TunedModule(comm)   # fallback for host buffers
        self._comm = comm               # decision-audit wire accounting
        self._rules = _load_device_rules()
        self._platform = next(iter(self.dc.mesh.devices.flat)).platform
        # two-tier context, fixed at attach time: whether the comm's
        # axis (tuple) spans an inner ICI + outer DCN split (the hier
        # arm's eligibility) and which plane keys '<coll>@<plane>' rows
        from ..parallel.hierarchy import classify_axes, hier_axes
        self._hier_inner, self._hier_outer, self._hier_why = hier_axes(
            self.dc.mesh, self.dc.axis)
        axes = (self.dc.axis if isinstance(self.dc.axis, tuple)
                else (self.dc.axis,))
        kinds = classify_axes(self.dc.mesh)
        self._plane = ("dcn" if any(kinds.get(a) == "dcn" for a in axes)
                       else "ici")

    # Device layout contract: x is (n, *elem) sharded on dim 0 over the comm
    # axis — row i is "rank i"'s buffer (parallel/collectives.py docstring).

    # -- decision (native ICI program vs measured host staging) -------------

    _ALL_ARMS = ("native", "staged", "quant")

    def _mode(self, coll: str, x, op: Op = None,
              allowed=_ALL_ARMS, weights=None, extra=None) -> str:
        """Pick per (collective, PER-RANK bytes, dtype) — the unit the
        sweep measures and the rules file records (a canonical array's
        row 0 is one rank's buffer), so thresholds line up with the
        evidence. Three arms: native ICI program, measured host staging,
        and the block-quantized tier (coll/quant) for float reductions.

        ``allowed`` is the set of arms the CALLING entry can actually
        execute for this buffer/op (a non-foldable op has no host staging
        kernel; a 1-D allgather has no quantized layout) — the decision
        never names an arm the entry would silently ignore, so the audit
        event always matches the executed path.  Every device dispatch
        funnels through here exactly once: one decision-audit record per
        collective."""
        with trace.region("ompi.coll.decide"):
            pick, reason, chain = self._decide(coll, x, op, allowed)
        with trace.region("ompi.coll.audit"):
            self._audit(coll, x, op, pick, reason, chain, weights=weights,
                        extra=extra)
        return pick

    def _decide(self, coll: str, x, op: Op, allowed) -> tuple:
        """Module-entry shim over :func:`decide_mode`: per-RANK bytes from
        the canonical layout, quant eligibility from the op/dtype gate,
        hier eligibility from the comm's two-tier context."""
        nbytes = x.nbytes // max(x.shape[0], 1)
        hier_ok, hier_why = self._hier_eligible(coll, op)
        return decide_mode(coll, nbytes, self.dc.n, self._platform,
                           self._rules, allowed,
                           quant_ok=self._quant_ok(coll, x, op),
                           dtype=x.dtype, op=op, plane=self._plane,
                           hier_ok=hier_ok, hier_why=hier_why)

    def _hier_eligible(self, coll: str, op: Op = None) -> tuple:
        """(ok, why-not) for the hierarchical arm on this entry: only
        allreduce has a hier kernel, the comm must span a real two-tier
        axis split (hier_axes), and the staged shape reduces via psum —
        sum only."""
        if coll != "allreduce":
            return False, "entry has no hierarchical kernel"
        if self._hier_inner is None:
            return False, self._hier_why
        if (op or SUM).name != "sum":
            return False, (f"op {(op or SUM).name} has no hierarchical "
                           "reduce (psum stages are sum-only)")
        return True, ""

    # modeled wire-byte collectives: coll -> coll/quant hop-table name
    _WIRE_MODEL = {"allreduce": "allreduce",
                   "reduce_scatter_block": "reduce_scatter",
                   "reduce_scatter": "reduce_scatter",
                   "allgather": "allgather"}

    def _audit(self, coll: str, x, op: Op, arm: str, reason: str,
               chain: list, weights=None, extra=None) -> None:
        """ONE decision-audit record per device-dispatched collective.
        Always: the arm-count + wire-byte pvars (plain dict adds, same
        cost class as every other SPC site) and the monitoring wire-byte
        correction when the quant arm will carry the call (the logical
        f32 size the dispatch layer recorded is not what travels).
        When tracing is on: the full decision event with the precedence
        chain, feeding trace.explain_last."""
        rows = max(x.shape[0], 1)
        nbytes = x.nbytes // rows
        wire = nbytes
        ratio = None
        hier_split = None
        if arm in ("hier", "hier+quant"):
            # the HAN stage math is the wire model: inner RS + AG at
            # (ni-1)/ni each, outer allreduce on the scattered 1/ni
            # fraction (quantized for hier+quant — the inner stages
            # stay native, so only the outer figure shrinks)
            from ..parallel.hierarchy import hier_wire_bytes
            ni = self.dc.mesh.shape[self._hier_inner]
            no = self.dc.mesh.shape[self._hier_outer]
            hw = hier_wire_bytes(max(x.size // rows, 1), x.dtype, ni, no,
                                 quant=(arm == "hier+quant"))
            wire = hw["total_bytes"]
            ratio = hw["ratio"]
            hier_split = (self._hier_inner, self._hier_outer,
                          hw["inner_stage_bytes"], hw["outer_bytes"],
                          hw["outer_native_bytes"])
            if arm == "hier+quant":
                from .. import monitoring
                monitoring.coll_wire_event(self._comm, coll, wire,
                                           x.nbytes)
        else:
            qcoll = self._WIRE_MODEL.get(coll)
            if qcoll is not None:
                from .quant import wire_bytes
                try:
                    wb = wire_bytes(qcoll, max(x.size // rows, 1),
                                    self.dc.n, x.dtype)
                except (ValueError, TypeError):
                    wb = None
                if wb is not None:
                    ratio = wb["ratio"]
                    if arm == "quant":
                        wire = wb["quant_bytes"]
                    elif arm == "native":
                        wire = wb["native_bytes"]
                    if arm == "quant":
                        from .. import monitoring
                        # satellite fix: record_coll logged the logical
                        # size; correct the coll matrix to
                        # int8-payload+scales
                        monitoring.coll_wire_event(
                            self._comm, coll, wb["quant_bytes"], x.nbytes)
        spc = self.dc.spc
        if spc is not None:
            spc.inc(f"coll_arm_{arm}_count")
            spc.inc("coll_wire_bytes", wire)
        from ..parallel import simdcn
        if simdcn.us_per_mib() > 0:
            # simulated-DCN delay shim: charge the bytes this arm's
            # geometry moves across the simulated slow plane (hier pays
            # only its outer stage — the skew the hier arm exists for)
            if hier_split is not None:
                simdcn.charge(hier_split[3])
            elif arm != "staged":
                simdcn.charge(int(wire * simdcn.ring_dcn_fraction(
                    self.dc.mesh, self.dc.axis)))
        from .. import health, numerics, perf
        if health.enabled:
            # fold the decided arm into the in-flight entry's signature —
            # the last field of the flight-recorder hash (op, dtype,
            # count, reduction, arm)
            health.note_arm(arm)
        if numerics.enabled:
            # annotate the in-flight fingerprint entry so the non-finite
            # verdict names the executed arm (compare semantics differ:
            # bitwise on native, tolerance-bounded on quant)
            numerics.note_arm(arm)
        if perf.enabled:
            # annotate the in-flight timing entry (coll/framework's
            # dispatch wrapper) with the executed arm + audited per-rank
            # wire bytes; only annotated samples fold into the model
            perf.note_arm(arm, nbytes=wire, ndev=self.dc.n)
        from .. import traffic
        if traffic.enabled:
            # per-edge attribution of the SAME wire figure the pvar just
            # banked — the conservation invariant's other half (hier
            # passes its stage split so the matrix charges inner RS/AG
            # rings + the outer ring instead of one flat ring)
            traffic.note_coll(self.dc, coll, arm, wire, weights=weights,
                              hier=hier_split)
        if trace.enabled:
            bucket = 1 << max(int(nbytes) - 1, 0).bit_length()
            ctx = getattr(self._comm, "ctx", None)
            extra = dict(extra or {})
            if hier_split is not None:
                extra.update({"hier_inner": hier_split[0],
                              "hier_outer": hier_split[1],
                              "hier_inner_bytes": 2 * hier_split[2],
                              "hier_outer_bytes": hier_split[3]})
            trace.decision(
                coll, arm=arm, reason=reason, verdict=None,
                nbytes=nbytes, rank=getattr(ctx, "rank", 0),
                shape_bucket=bucket, shape=tuple(x.shape),
                dtype=str(x.dtype),
                reduce_op=getattr(op, "name", None),
                ndev=self.dc.n, wire_bytes=wire, quant_ratio=ratio,
                chain=list(chain), **extra)

    def _quant_ok(self, coll: str, x, op: Op = None) -> bool:
        """Whether the quantized arm can carry this call at all
        (decision-level gate; the engine re-checks and raises)."""
        from ..op import quantizable

        return coll in _QUANT_COLLS and quantizable(op or SUM, x.dtype)

    def _stage_out(self, x) -> np.ndarray:
        """The explicit D2H half of the staged arm (SPC-accounted);
        accepts a raw jax array or a DeviceBuffer holder."""
        import jax

        from .. import accelerator

        if isinstance(x, accelerator.DeviceBuffer):
            x = x.array
        spc = self.dc.spc
        h = np.asarray(jax.device_get(x))
        if spc is not None:
            spc.inc("device_stage_out_bytes", h.nbytes)
            spc.inc("coll_staged_fallbacks")
        return h

    def _stage_in(self, h: np.ndarray):
        """H2D back onto the canonical sharding."""
        import jax
        import jax.numpy as jnp

        spc = self.dc.spc
        if spc is not None:
            spc.inc("device_stage_in_bytes", h.nbytes)
        return jax.device_put(jnp.asarray(h), self.dc.sharding())

    def allreduce(self, comm, sendbuf, recvbuf=None, op: Op = None):
        op = op or SUM
        if not _is_device(sendbuf):
            return self.host.allreduce(comm, sendbuf, recvbuf, op)
        mode = self._mode("allreduce", sendbuf, op,
                          allowed=self._ALL_ARMS
                          if op.name in _NP_FOLD
                          else ("native", "quant"))
        if mode in ("hier", "hier+quant"):
            return self._hier_allreduce(sendbuf, op,
                                        quant=(mode == "hier+quant"))
        if mode == "quant":
            return self.dc.quant.allreduce(sendbuf, op)
        if mode == "staged":
            h = self._stage_out(sendbuf)
            red = _NP_FOLD[op.name](h, axis=0)
            return self._stage_in(np.broadcast_to(red, h.shape))
        return self.dc.allreduce(sendbuf, op)

    def _hier_allreduce(self, x, op: Op, quant: bool):
        """The two-tier HAN arm: reduce_scatter(inner ICI) →
        allreduce(outer DCN, on the scattered 1/n_inner — quantized
        when ``quant``) → allgather(inner ICI), compiled through the
        same executable cache as every flat arm.  Only reachable when
        the decision layer said so, i.e. the comm spans a two-tier axis
        split and op is sum."""
        import jax.numpy as jnp

        from ..parallel.hierarchy import (hierarchical_psum,
                                          hierarchical_psum_quant)
        dc = self.dc
        inner, outer = self._hier_inner, self._hier_outer
        no = dc.mesh.shape[outer]
        key = ("hier_allreduce", bool(quant), inner, outer, x.shape,
               str(x.dtype))

        def build():
            def fn(xs):              # (r, *e) local rows
                red = dc._fold_local(xs, op)
                shape = red.shape
                flat = red.reshape(-1)
                if quant:
                    out = hierarchical_psum_quant(flat, inner, outer, no)
                else:
                    out = hierarchical_psum(flat, inner, outer)
                return jnp.broadcast_to(out.reshape(shape)[None],
                                        xs.shape)
            return dc._shard_map(fn, dc._spec, dc._spec)

        return dc._compiled(key, build)(x)

    def reduce(self, comm, sendbuf, recvbuf=None, op: Op = None, root: int = 0):
        op = op or SUM
        if not _is_device(sendbuf):
            return self.host.reduce(comm, sendbuf, recvbuf, op, root)
        mode = self._mode("reduce", sendbuf, op,
                          allowed=("native", "staged")
                          if op.name in _NP_FOLD else ("native",))
        if mode == "staged":
            h = self._stage_out(sendbuf)
            red = _NP_FOLD[op.name](h, axis=0)
            return self._stage_in(np.broadcast_to(red, h.shape))
        return self.dc.reduce(sendbuf, op, root)

    def bcast(self, comm, buf, root: int = 0):
        if not _is_device(buf):
            return self.host.bcast(comm, buf, root)
        if self._mode("bcast", buf) == "staged":
            h = self._stage_out(buf)
            return self._stage_in(np.broadcast_to(h[root], h.shape))
        return self.dc.bcast(buf, root)

    def allgather(self, comm, sendbuf, recvbuf=None):
        if not _is_device(sendbuf):
            return self.host.allgather(comm, sendbuf, recvbuf)
        mode = self._mode("allgather", sendbuf,
                          allowed=self._ALL_ARMS if sendbuf.ndim >= 2
                          else ("native", "staged"))
        if mode == "quant":
            return self.dc.quant.allgather(sendbuf)
        if mode == "staged":
            return self._stage_in(_staged_allgather(self._stage_out(sendbuf)))
        return self.dc.allgather(sendbuf)

    def alltoall(self, comm, sendbuf, recvbuf=None):
        if not _is_device(sendbuf):
            return self.host.alltoall(comm, sendbuf, recvbuf)
        if self._mode("alltoall", sendbuf) == "staged":
            h = self._stage_out(sendbuf)           # (R, R, b, *e)
            return self._stage_in(np.ascontiguousarray(
                np.swapaxes(h, 0, 1)))
        return self.dc.alltoall(sendbuf)

    def reduce_scatter_block(self, comm, sendbuf, recvbuf=None, op: Op = None):
        op = op or SUM
        if not _is_device(sendbuf):
            return self.host.reduce_scatter_block(comm, sendbuf, recvbuf, op)
        mode = self._mode("reduce_scatter_block", sendbuf, op,
                          allowed=self._ALL_ARMS
                          if op.name in _NP_FOLD
                          else ("native", "quant"))
        if mode == "quant":
            return self.dc.quant.reduce_scatter(sendbuf, op)
        if mode == "staged":
            h = self._stage_out(sendbuf)           # (R, R*b, *e)
            R = h.shape[0]
            b = h.shape[1] // R
            red = _NP_FOLD[op.name](h, axis=0)
            return self._stage_in(red.reshape((R, b) + h.shape[2:]))
        return self.dc.reduce_scatter(sendbuf, op)

    def scan(self, comm, sendbuf, recvbuf=None, op: Op = None):
        op = op or SUM
        if not _is_device(sendbuf):
            return self.host.scan(comm, sendbuf, recvbuf, op)
        mode = self._mode("scan", sendbuf, op,
                          allowed=("native", "staged")
                          if op.name in ("sum", "prod") else ("native",))
        if mode == "staged":
            h = self._stage_out(sendbuf)
            fn = np.cumsum if op.name == "sum" else np.cumprod
            return self._stage_in(fn(h, axis=0))
        return self.dc.scan(sendbuf, op)

    def exscan(self, comm, sendbuf, recvbuf=None, op: Op = None):
        op = op or SUM
        if not _is_device(sendbuf):
            return self.host.exscan(comm, sendbuf, recvbuf, op)
        mode = self._mode("exscan", sendbuf, op,
                          allowed=("native", "staged")
                          if op.name == "sum" else ("native",))
        if mode == "staged":
            h = self._stage_out(sendbuf)
            out = np.zeros_like(h)
            out[1:] = np.cumsum(h, axis=0)[:-1]
            return self._stage_in(out)
        return self.dc.scan(sendbuf, op, exclusive=True)

    def barrier(self, comm):
        # host barrier still needed for rank processes; device barrier syncs
        # the mesh. Do both: host ranks agree, devices quiesce.
        self.host.barrier(comm)
        self.dc.barrier()

    # -- neighborhood collectives (halo exchange) ---------------------------
    # Periodic cartesian topologies compile to 2·ndims ppermutes
    # (DeviceComm cart section ≙ coll_basic_neighbor_*.c specialized to
    # the torus); graph / non-periodic topologies keep the host path.

    def _cart_ok(self, comm, x, need_ndim: int) -> bool:
        topo = getattr(comm, "topo", None)
        return (topo is not None and getattr(topo, "kind", "") == "cart"
                and all(topo.periods) and self._rows_ok(x, need_ndim)
                and topo.size == x.shape[0] == self.dc.n)

    def _reject_canonical_noncart(self, comm, sendbuf) -> None:
        """In the single-controller regime (comm size 1, mesh of R) ANY
        canonical (R·k, ...) device layout that found no device path must
        not reach the host path — basic.neighbor_* would irecv from
        phantom ranks of a size-1 comm and hang. Fail loudly. Multi-rank
        comms with per-rank buffers keep the working host path."""
        if comm.size == 1 and self._rows_ok(sendbuf, 2):
            raise ValueError(
                "no device path for this neighborhood exchange (needs a "
                "cart or graph topology matching the mesh, default "
                "recvbuf, and rank-per-position rows); the host path "
                "cannot express a canonical device layout on a "
                "single-controller comm")

    def neighbor_allgather(self, comm, sendbuf, recvbuf=None):
        if recvbuf is None and self._cart_ok(comm, sendbuf, 2):
            return self.dc.neighbor_allgather_cart(sendbuf, comm.topo)
        if recvbuf is None and self._graph_ok(comm, sendbuf, 2):
            # arbitrary graphs / non-periodic carts: all_gather + masked
            # gather-map (padded to max degree; zeros past each degree)
            return self.dc.neighbor_allgather_graph(sendbuf, comm.topo)
        self._reject_canonical_noncart(comm, sendbuf)
        return self.host.basic.neighbor_allgather(
            comm, self._to_host(sendbuf), recvbuf)

    def _graph_ok(self, comm, x, need_ndim: int) -> bool:
        """The graph-path gate shared by the neighbor_* entries: cart or
        graph topology, canonical layout, rank-per-position rows."""
        topo = getattr(comm, "topo", None)
        return (topo is not None
                and getattr(topo, "kind", "") in ("cart", "graph")
                and self._rows_ok(x, need_ndim)
                and x.shape[0] == self.dc.n)

    def neighbor_allgatherv(self, comm, sendbuf, recvbuf=None, counts=None,
                            displs=None):
        """Ragged neighborhood allgather. COUNTS CONTRACT DIFFERS BY
        REGIME (the same canonical-vs-per-rank split as allgatherv):
        canonical device layout (R, cap, *e) takes PER-GLOBAL-RANK counts
        (length R) and returns (R, maxdeg, cap, *e) padded slots — slice
        slot k of row j by counts[in_neighbors(j)[k]]; the per-rank host
        path keeps MPI's per-in-neighbor counts/displs contract."""
        if (counts is not None and displs is None and recvbuf is None
                and self._graph_ok(comm, sendbuf, 2)
                and len(counts) == sendbuf.shape[0]
                and sendbuf.shape[1] >= max(int(c) for c in counts)):
            if self._cart_ok(comm, sendbuf, 2):
                # torus: padded rows travel whole on the neighbor-sparse
                # ppermute path (cart slot order == in_neighbors order)
                return self.dc.neighbor_allgather_cart(sendbuf, comm.topo)
            return self.dc.neighbor_allgather_graph(sendbuf, comm.topo)
        self._reject_canonical_noncart(comm, sendbuf)
        return self.host.basic.neighbor_allgatherv(
            comm, self._to_host(sendbuf), recvbuf, counts, displs)

    def neighbor_alltoall(self, comm, sendbuf, recvbuf=None):
        if recvbuf is None and self._cart_ok(comm, sendbuf, 3) \
                and sendbuf.shape[1] == 2 * len(comm.topo.dims):
            return self.dc.neighbor_alltoall_cart(sendbuf, comm.topo)
        if recvbuf is None and self._graph_ok(comm, sendbuf, 3):
            # ragged degrees (graphs, open carts): row-scatter +
            # alltoallv + slot reorder (DeviceComm graph section)
            return self.dc.neighbor_alltoall_graph(sendbuf, comm.topo)
        self._reject_canonical_noncart(comm, sendbuf)
        return self.host.basic.neighbor_alltoall(
            comm, self._to_host(sendbuf), recvbuf)

    # -- ragged / rooted entries: NATIVE ICI programs when the caller
    # presents the canonical padded device layout (DeviceComm docstring),
    # staged-host fallback otherwise. The reference implements these as
    # first-class host algorithms (coll_base_alltoallv.c:194 pairwise,
    # coll_base_allgatherv.c:95 bruck, coll_base_gather.c:41 binomial,
    # coll_base_scatter.c:63); the TPU-first shape is padded blocks + a
    # gather-map device argument (parallel/collectives.py ragged section),
    # so the EP/MoE alltoallv hot path never leaves ICI.

    def _to_host(self, x):
        """Host view of a maybe-device buffer: non-canonical layouts keep
        the host algorithm chain; ONE accounting path with _stage_out."""
        return self._stage_out(x) if _is_device(x) else x

    def _rows_ok(self, x, need_ndim: int) -> bool:
        """Canonical-layout gate: device buffer whose row dim covers the
        mesh axis (R % n == 0). Per-rank host-style buffers (the size>1
        process regime) miss the gate and stage — the same buffer-type
        dispatch check_addr does for host vs device."""
        if not _is_device(x) or x.ndim < need_ndim:
            return False
        R = x.shape[0]
        return R > 0 and R % self.dc.n == 0

    def allgatherv(self, comm, sendbuf, recvbuf=None, counts=None,
                   displs=None):
        if (counts is not None and displs is None and recvbuf is None
                and self._rows_ok(sendbuf, 2)
                and len(counts) == sendbuf.shape[0]
                and sendbuf.shape[1] >= max(int(c) for c in counts)):
            if self._mode("allgatherv", sendbuf) == "staged":
                return self._stage_in(_staged_allgatherv(
                    self._stage_out(sendbuf), counts))
            return self.dc.allgatherv(sendbuf, counts)
        return self.host.allgatherv(comm, self._to_host(sendbuf), recvbuf,
                                    counts, displs)

    def gather(self, comm, sendbuf, recvbuf=None, root: int = 0):
        if recvbuf is None and self._rows_ok(sendbuf, 2):
            if self._mode("gather", sendbuf) == "staged":
                # shared helper, NOT self.allgather: its own decision
                # would override this entry's staged pick
                return self._stage_in(
                    _staged_allgather(self._stage_out(sendbuf)))
            return self.dc.gather(sendbuf, root)
        return self.host.gather(comm, self._to_host(sendbuf), recvbuf, root)

    def gatherv(self, comm, sendbuf, recvbuf=None, counts=None, displs=None,
                root: int = 0):
        if (counts is not None and displs is None and recvbuf is None
                and self._rows_ok(sendbuf, 2)
                and len(counts) == sendbuf.shape[0]
                and sendbuf.shape[1] >= max(int(c) for c in counts)):
            if self._mode("gatherv", sendbuf) == "staged":
                return self._stage_in(_staged_allgatherv(
                    self._stage_out(sendbuf), counts))
            return self.dc.gatherv(sendbuf, counts, root)
        return self.host.basic.gatherv(comm, self._to_host(sendbuf), recvbuf,
                                       counts, displs, root)

    def scatter(self, comm, sendbuf, recvbuf=None, root: int = 0):
        if (recvbuf is None and self._rows_ok(sendbuf, 3)
                and sendbuf.shape[0] == sendbuf.shape[1]):
            if self._mode("scatter", sendbuf) == "staged":
                h = self._stage_out(sendbuf)       # (R, R, b, *e)
                return self._stage_in(np.ascontiguousarray(h[root]))
            return self.dc.scatter(sendbuf, root)
        return self.host.scatter(comm, self._to_host(sendbuf), recvbuf, root)

    def scatterv(self, comm, sendbuf, recvbuf, counts, displs=None,
                 root: int = 0):
        if (recvbuf is None and displs is None
                and self._rows_ok(sendbuf, 3)
                and sendbuf.shape[0] == sendbuf.shape[1]
                and len(counts) == sendbuf.shape[0]
                and sendbuf.shape[2] >= max(int(c) for c in counts)):
            if self._mode("scatterv", sendbuf) == "staged":
                h = self._stage_out(sendbuf)
                return self._stage_in(np.ascontiguousarray(h[root]))
            return self.dc.scatterv(sendbuf, counts, root)
        return self.host.basic.scatterv(comm, self._to_host(sendbuf),
                                        recvbuf, counts, displs, root)

    @staticmethod
    def _check_recvcounts(C, recvcounts):
        if recvcounts is None:
            return
        RC = np.asarray(recvcounts)
        # accept either the per-destination totals vector or the stacked
        # per-rank matrix (row j = what j receives from each source, C.T)
        ok = (np.array_equal(RC, C.T) if RC.ndim == 2
              else np.array_equal(RC.ravel(), C.sum(axis=0)))
        if not ok:
            raise ValueError(
                "alltoallv: recvcounts disagree with sendcounts "
                f"({recvcounts} vs column sums "
                f"{C.sum(axis=0).tolist()})")

    def alltoallv(self, comm, sendbuf, recvbuf, sendcounts, recvcounts,
                  sdispls=None, rdispls=None):
        C = np.asarray(sendcounts)
        if (recvbuf is None and sdispls is None and rdispls is None
                and C.ndim == 2 and C.shape[0] == C.shape[1]
                and self._rows_ok(sendbuf, 2) and sendbuf.ndim in (2, 3)
                and (sendbuf.ndim == 2
                     or sendbuf.shape[1] != sendbuf.shape[0])
                and sendbuf.shape[0] == C.shape[0]
                and sendbuf.shape[1] >= int(C.sum(axis=1).max())):
            # DENSE-ROWS form — MPI's actual buffer layout (contiguous
            # sends in destination order, default displacements), with
            # optional trailing elem dims (the EP token shape): the
            # sliced exchange never materializes the (R, R, cap) padded
            # blocks (alltoallv_from_rows; round-5). The one ambiguous
            # 3-D shape (L == R, indistinguishable from padded blocks)
            # keeps the block interpretation below.
            self._check_recvcounts(C, recvcounts)
            plan = self.dc.a2av_plan(sendbuf.shape, C)
            if self._mode("alltoallv", sendbuf, weights=C,
                          extra={"a2av_slice_cap": plan["slice_cap"],
                                 "a2av_scan_steps": plan["scan_steps"]},
                          ) == "staged":
                h = self._stage_out(sendbuf)           # (R, L, *e)
                out_cap = self.dc._bucket(
                    int(C.sum(axis=0).max()) if C.size else 1)
                return self._stage_in(
                    self.dc.compact_from_rows(h, C, out_cap))
            out, _tot = self.dc.alltoallv_from_rows(sendbuf, C)
            return out
        if (recvbuf is None and sdispls is None and rdispls is None
                and C.ndim == 2 and C.shape[0] == C.shape[1]
                and self._rows_ok(sendbuf, 3)
                and sendbuf.shape[0] == sendbuf.shape[1] == C.shape[0]
                and sendbuf.shape[2] >= int(C.max())):
            self._check_recvcounts(C, recvcounts)
            if self._mode("alltoallv", sendbuf, weights=C) == "staged":
                h = self._stage_out(sendbuf)       # (R, R, cap, *e)
                out_cap = self.dc._bucket(
                    int(C.sum(axis=0).max()) if h.shape[0] else 1)
                return self._stage_in(
                    self.dc.compact_ragged_blocks(h, C, out_cap))
            out, _tot = self.dc.alltoallv(sendbuf, C)
            return out
        return self.host.alltoallv(comm, self._to_host(sendbuf), recvbuf,
                                   sendcounts, recvcounts, sdispls, rdispls)

    def reduce_scatter(self, comm, sendbuf, recvbuf, counts, op: Op = None):
        op = op or SUM
        if (recvbuf is None and self._rows_ok(sendbuf, 2)
                and len(counts) == sendbuf.shape[0]
                and int(np.sum(counts)) == sendbuf.shape[1]):
            cs = [int(c) for c in counts]
            allowed = ["native"]
            if op.name in _NP_FOLD:
                allowed.append("staged")
            if len(set(cs)) == 1 and cs[0] > 0:
                allowed.append("quant")   # ragged counts: no quant layout
            mode = self._mode("reduce_scatter", sendbuf, op,
                              allowed=tuple(allowed))
            if mode == "quant":
                import jax.numpy as jnp
                out = self.dc.quant.reduce_scatter(sendbuf, op)
                cap = self.dc._bucket(cs[0])
                if cap != cs[0]:   # match reduce_scatter_v's padded cap
                    pad = [(0, 0), (0, cap - cs[0])]
                    pad += [(0, 0)] * (out.ndim - 2)
                    out = jnp.pad(out, pad)
                return out
            if mode == "staged":
                h = self._stage_out(sendbuf)       # (R, total, *e)
                red = _NP_FOLD[op.name](h, axis=0)
                cap = self.dc._bucket(max(int(c) for c in counts))
                out = np.zeros((h.shape[0], cap) + h.shape[2:], h.dtype)
                off = 0
                for i, c in enumerate(int(c) for c in counts):
                    out[i, :c] = red[off:off + c]
                    off += c
                return self._stage_in(out)
            return self.dc.reduce_scatter_v(sendbuf, counts, op)
        return self.host.reduce_scatter(comm, self._to_host(sendbuf),
                                        recvbuf, counts, op)


@component("coll", "xla", priority=80)
class XlaColl(Component):
    name = "xla"

    def query(self, comm):
        if getattr(comm, "device_comm", None) is None:
            return None, None
        try:
            import jax  # noqa: F401
        except ImportError:  # pragma: no cover
            return None, None
        return self.priority, XlaModule(comm)
