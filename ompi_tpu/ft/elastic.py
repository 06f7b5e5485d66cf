"""Elastic fault-tolerant training: survive a rank death end-to-end.

The stack *detects* everything — heartbeat ring (ft/detector), desync
sentinel + watchdog (health), revoke/shrink/agree (ft/ulfm), per-shard
checkpoint checksums (ckpt) — and this module is the first subsystem
that *acts* on those observations.  The recovery choreography:

    trip ──────► shrink ─────► reshard ─────► resume
    watchdog /   ULFM revoke   cross-mesh     same step fn on the
    ProcFailed   + shrink      reshard from   survivor mesh, rolled
    verdict      (agree)       peer shadows   back to the shadow epoch

State never touches the filesystem on the way through: every device
keeps (a) a SNAPSHOT of its own state shards from the last shadow epoch
and (b) its LEFT NEIGHBOR's snapshot shards, refreshed by a low-rate
``ring_shift`` (one ppermute hop) piggybacked on the training loop.
When position ``p`` dies, its block survives on position ``(p+1) % n``,
and ``parallel.reshard.cross_reshard`` re-lays the whole tree onto the
survivor mesh sourcing dead blocks from those shadows — zero checkpoint
reads, wire and peak bytes under the same contracts as any reshard.

Memory cost of the shadows, per device: one snapshot shard + one
neighbor shard per dp-sharded leaf ≈ ``2/n`` of total state (replicated
leaves add one snapshot replica).  An adjacent double failure — ``p``
and ``(p+1) % n`` dead inside one shadow epoch — defeats the single
ring hop and is reported loudly (that is the checkpoint plane's job).

Every recovery emits one audited ``ft_recovery`` decision naming the
dead rank, bracketed by ``ft_trip`` / ``ft_shrink`` / ``ft_reshard`` /
``ft_resume`` trace instants, and banks a timeline record comm_doctor
--ft renders.  Deterministic fault injection lives in ft/chaos.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

import importlib

from .. import trace
from ..parallel.mesh import make_mesh

# the parallel package re-exports the reshard FUNCTION under the same
# name as the submodule — resolve the module itself
_reshard = importlib.import_module("ompi_tpu.parallel.reshard")
from .ulfm import (
    ProcFailedError,
    ProcFailedPendingError,
    WatchdogTimeoutError,
    failed_ranks,
    revoke,
    shrink,
)

PVARS = ("ft_recoveries", "ft_steps_lost", "ft_shadow_refreshes")

_lock = threading.Lock()
_counts: Dict[str, int] = {"ft_recoveries": 0, "ft_steps_lost": 0,
                           "ft_shadow_refreshes": 0}
_recovery_log: List[Dict[str, Any]] = []
_last_recovery: Optional[Dict[str, Any]] = None


def pvar_value(name: str) -> float:
    with _lock:
        return float(_counts[name])


def report() -> Dict[str, Any]:
    """Structured snapshot for comm_doctor --ft: the
    recovery timeline records plus the shadow/recovery counters."""
    with _lock:
        return {"counters": dict(_counts),
                "recoveries": [dict(r) for r in _recovery_log],
                "last": dict(_last_recovery) if _last_recovery else None}


def reset() -> None:
    global _last_recovery
    with _lock:
        for k in _counts:
            _counts[k] = 0
        _recovery_log.clear()
        _last_recovery = None


# ---------------------------------------------------------------------------
# elastic sharding: the ZeRO-style dim-0 layout every mesh size can host
# ---------------------------------------------------------------------------

def elastic_spec(leaf, n: int, axis: str = "dp") -> P:
    """dim-0 sharding over ``axis`` when it divides evenly, else
    replicated — the layout rule applied uniformly to params AND
    optimizer state so any divisor-sized survivor mesh can host the
    same tree."""
    shape = getattr(leaf, "shape", ())
    if len(shape) >= 1 and shape[0] >= n and shape[0] % n == 0:
        return P(axis)
    return P()


def elastic_shard(tree, mesh, axis: str = "dp"):
    n = int(np.asarray(mesh.devices).size)
    return jax.tree.map(
        lambda x: jax.device_put(
            x, NamedSharding(mesh, elastic_spec(x, n, axis))), tree)


def survivor_positions(n: int, dead: Sequence[int]) -> List[int]:
    """The largest divisor-of-n prefix of surviving flat positions: a
    divisor keeps every elastic-sharded dim 0 evenly divisible on the
    smaller mesh (n | dim0 and m | n ⇒ m | dim0)."""
    ds = set(int(p) for p in dead)
    alive = [i for i in range(n) if i not in ds]
    if not alive:
        raise ProcFailedError(-1, "elastic: no survivors left")
    m = max(d for d in range(1, n + 1) if n % d == 0 and d <= len(alive))
    return alive[:m]


def survivor_mesh(mesh, dead: Sequence[int], axis: str = "dp"):
    """Shrink a 1-D mesh to its survivor subset (divisor-sized)."""
    devs = list(np.asarray(mesh.devices).flat)
    keep = survivor_positions(len(devs), dead)
    return make_mesh({axis: len(keep)}, devices=[devs[i] for i in keep])


# ---------------------------------------------------------------------------
# trip classification: any wait-interrupting ft error -> one verdict shape
# ---------------------------------------------------------------------------

def trip_verdict(exc: BaseException) -> Dict[str, Any]:
    """Classify a failure signal into the audited trip verdict.  The
    watchdog arm carries the blocked op's (cid, seq, op) attribution
    plus the desync sentinel's suspect rank when the report named one;
    the detector arm carries the failed rank directly."""
    if isinstance(exc, WatchdogTimeoutError):
        return {"kind": "watchdog", "rank": int(getattr(exc, "suspect", -1)),
                "cid": int(exc.cid), "seq": int(exc.seq), "op": str(exc.op),
                "msg": str(exc)}
    if isinstance(exc, (ProcFailedError, ProcFailedPendingError)):
        return {"kind": "proc_failed", "rank": int(exc.rank),
                "msg": str(exc)}
    return {"kind": "unknown", "rank": -1, "msg": str(exc)}


def comm_recover(comm, verdict: Optional[Dict[str, Any]] = None):
    """The host-plane half of a recovery: ULFM revoke (reliable flood)
    then shrink to the survivor communicator via the agree consensus.
    Returns ``(new_comm, dead_world_ranks, info)``; every survivor gets
    the same cid and membership out of the agreement."""
    try:
        revoke(comm)
    except Exception:
        pass                      # a revoked/failed comm still shrinks
    new_comm = shrink(comm)
    dead = sorted(set(comm.group.world_ranks)
                  - set(new_comm.group.world_ranks))
    info = {"old_cid": int(comm.cid), "cid": int(new_comm.cid),
            "name": new_comm.name,
            "survivors": list(new_comm.group.world_ranks),
            "dead": dead}
    if verdict is not None:
        info["verdict"] = dict(verdict)
    return new_comm, dead, info


# ---------------------------------------------------------------------------
# peer-replicated shadows
# ---------------------------------------------------------------------------

class ShadowStore:
    """In-memory peer-replicated shadows of the training state.

    ``refresh(state, step)`` banks (a) ``snap`` — a private copy of the
    whole tree (the training step donates its inputs, so references
    into the live tree would dangle) and (b) ``shifted`` — each
    dp-sharded leaf pushed one ring hop (+1) by a compiled shard_map
    ppermute, so position ``j`` holds block ``(j-1) % n``.  Dead
    position ``p``'s block is then the ``shifted`` shard resident on
    ``(p+1) % n``."""

    def __init__(self, mesh, axis: str = "dp", spc=None):
        self.mesh = mesh
        self.axis = axis
        self.spc = spc
        self.n = int(np.asarray(mesh.devices).size)
        self.epoch = -1
        self.snap = None
        self.shifted = None
        self._shift_fns: Dict[tuple, Callable] = {}

    def _is_ring_sharded(self, leaf) -> bool:
        s = getattr(leaf, "sharding", None)
        if not isinstance(s, NamedSharding) or self.n < 2:
            return False
        spec = tuple(s.spec)
        return bool(spec) and spec[0] == self.axis

    def _shift(self, leaf):
        key = (tuple(leaf.shape), str(leaf.dtype))
        fn = self._shift_fns.get(key)
        if fn is None:
            n, ax = self.n, self.axis
            perm = [(i, (i + 1) % n) for i in range(n)]
            # comm-lint: disable=CL001 the +1 ring shift IS the shadow-replication scheme (each device parks its block on its ring neighbor), not an engine-dispatchable collective; wire bytes attributed at the eager boundary via note_ppermute (coll ft_shadow) in refresh()
            fn = jax.jit(jax.shard_map(
                lambda v: lax.ppermute(v, ax, perm=perm),  # comm-lint: disable=CL001 same ring shift, kernel body
                mesh=self.mesh, in_specs=P(ax), out_specs=P(ax)))
            self._shift_fns[key] = fn
        return fn(leaf)

    @staticmethod
    def _copy(leaf):
        if not isinstance(leaf, jax.Array):
            return leaf
        out = jnp.copy(leaf)
        s = getattr(leaf, "sharding", None)
        if s is not None and not out.sharding.is_equivalent_to(s, leaf.ndim):
            out = jax.device_put(out, s)
        return out

    def refresh(self, state, step: int) -> None:
        from .. import traffic
        snap = jax.tree.map(self._copy, state)
        wire = 0
        leaves = 0

        def shadow(leaf):
            nonlocal wire, leaves
            if not self._is_ring_sharded(leaf):
                return leaf       # replicated: snap's live replicas suffice
            leaves += 1
            wire += int(leaf.nbytes) // self.n
            return self._shift(leaf)

        shifted = jax.tree.map(shadow, snap)
        if traffic.enabled and wire and self.n >= 2:
            # the refresh IS a ppermute ring hop: attribute its edges so
            # the conservation invariant covers shadow traffic too
            traffic.note_ppermute(
                self.mesh, self.axis,
                [(i, (i + 1) % self.n) for i in range(self.n)],
                wire, spc=self.spc, coll="ft_shadow")
        self.snap = snap
        self.shifted = shifted
        self.epoch = int(step)
        with _lock:
            _counts["ft_shadow_refreshes"] += 1
        if trace.enabled:
            trace.instant("ft_shadow_refresh", "ft",
                          args={"step": int(step), "leaves": leaves,
                                "wire_bytes": wire, "mesh": self.n})

    def replacement(self, shifted_leaf, dead_pos: int):
        """The single-device array holding dead position ``dead_pos``'s
        block: the shifted leaf's shard on ``(dead_pos+1) % n``."""
        holder = (int(dead_pos) + 1) % self.n
        devs = list(np.asarray(self.mesh.devices).flat)
        for sh in shifted_leaf.addressable_shards:
            if sh.device == devs[holder]:
                return sh.data
        raise ProcFailedError(
            dead_pos, f"elastic: shadow holder position {holder} has no "
                      "resident shard")


# ---------------------------------------------------------------------------
# the trainer
# ---------------------------------------------------------------------------

def default_data_fn(cfg, batch: int = 8):
    """Deterministic per-step token batches: a resumed run replays the
    exact stream an uninterrupted run saw, so post-recovery loss is
    comparable step-for-step."""
    def fn(step: int):
        r = np.random.default_rng(1_000_003 + int(step))
        return jnp.asarray(
            r.integers(0, cfg.vocab, size=(batch, cfg.seq + 1)),
            dtype=jnp.int32)
    return fn


class ElasticTrainer:
    """``make_train_step`` wrapped in the trip → shrink → reshard →
    resume choreography.

    Two planes, independently optional: the DEVICE plane (the 1-D dp
    mesh carrying params/opt/shadows — always present) and the HOST
    plane (``comm=`` a Communicator whose detector-observed failures
    are polled each step and answered with revoke+shrink via
    :func:`comm_recover`).  Without a comm, failure signals arrive as
    exceptions out of the step body — ``ProcFailedError`` (chaos or the
    detector), ``WatchdogTimeoutError`` (a blocked wait's watchdog
    trip), ``ProcFailedPendingError`` — which is what makes the whole
    loop CI-drivable single-controller on the 8-dev CPU mesh."""

    ERRORS = (ProcFailedError, ProcFailedPendingError, WatchdogTimeoutError)

    def __init__(self, cfg, mesh=None, *, axis: str = "dp",
                 learning_rate: float = 1e-3, shadow_interval: int = 4,
                 data_fn: Optional[Callable[[int], jax.Array]] = None,
                 batch: int = 8, comm=None, chaos=None, spc=None,
                 recovery_budget: Optional[int] = None, seed: int = 0):
        from ..models import transformer as _tf
        if mesh is None:
            mesh = make_mesh({axis: len(jax.devices())})
        if tuple(mesh.axis_names) != (axis,):
            raise ValueError(
                "ElasticTrainer needs a 1-D mesh over its data axis "
                f"(got axes {tuple(mesh.axis_names)}, want ({axis!r},))")
        self.cfg = cfg
        self.axis = axis
        self.lr = float(learning_rate)
        self.shadow_interval = max(int(shadow_interval), 1)
        self.recovery_budget = (int(recovery_budget)
                                if recovery_budget is not None
                                else self.shadow_interval)
        self.comm = comm
        self.chaos = chaos
        self.spc = spc
        self.batch = int(batch)
        self.data_fn = data_fn or default_data_fn(cfg, self.batch)
        self._tf = _tf
        self.step = 0
        self.losses: List[tuple] = []          # (step, loss) append log
        self.loss_by_step: Dict[int, float] = {}
        self.recoveries: List[Dict[str, Any]] = []
        params = elastic_shard(
            _tf.init_params(jax.random.key(seed), cfg), mesh, axis)
        self._bind(mesh, params, None)

    # -- mesh (re)binding ---------------------------------------------------

    def _bind(self, mesh, params, opt_state) -> None:
        self.mesh = mesh
        self.n = int(np.asarray(mesh.devices).size)
        init_opt, self._step_fn = self._tf.make_train_step(
            self.cfg, mesh, self.lr)
        if opt_state is None:
            opt_state = elastic_shard(init_opt(params), mesh, self.axis)
        self.params = params
        self.opt_state = opt_state
        self.shadows = ShadowStore(mesh, self.axis, spc=self.spc)

    def _enforce(self, tree):
        """Pin the elastic layout after a step: jit leaves output
        shardings to GSPMD, and a drifted leaf would starve the shadow
        ring.  Equivalent shardings pass through untouched."""
        def fix(x):
            if not isinstance(x, jax.Array):
                return x
            want = NamedSharding(self.mesh,
                                 elastic_spec(x, self.n, self.axis))
            s = getattr(x, "sharding", None)
            if s is not None and s.is_equivalent_to(want, x.ndim):
                return x
            return jax.device_put(x, want)
        return jax.tree.map(fix, tree)

    # -- failure polling (host plane) ---------------------------------------

    def _poll_comm(self) -> None:
        if self.comm is None:
            return
        ctx = self.comm.ctx
        try:
            ctx.engine.progress()
        except Exception:
            pass
        dead = sorted(set(failed_ranks(ctx))
                      & set(self.comm.group.world_ranks))
        if dead:
            raise ProcFailedError(
                dead[0], f"detector: rank {dead[0]} failed")

    # -- the loop -----------------------------------------------------------

    def run(self, n_steps: int) -> "ElasticTrainer":
        target = self.step + int(n_steps)
        while self.step < target:
            try:
                self._poll_comm()
                if (self.shadows.epoch < 0
                        or self.step - self.shadows.epoch
                        >= self.shadow_interval):
                    self.shadows.refresh((self.params, self.opt_state),
                                         self.step)
                if self.chaos is not None:
                    self.chaos.on_step(self, self.step)
                tokens = self.data_fn(self.step)
                p, o, loss = self._step_fn(self.params, self.opt_state,
                                           tokens)
                self.params = self._enforce(p)
                self.opt_state = self._enforce(o)
                val = float(loss)
                self.losses.append((self.step, val))
                self.loss_by_step[self.step] = val
                self.step += 1
            except self.ERRORS as exc:
                self._recover(exc)
        return self

    # -- recovery choreography ----------------------------------------------

    def _recover(self, exc: BaseException) -> None:
        from .. import ckpt as _ckpt
        t0 = time.perf_counter()
        trip_step = self.step
        verdict = trip_verdict(exc)
        reads0 = _ckpt.restore_count()
        if trace.enabled:
            trace.instant("ft_trip", "ft",
                          args=dict(verdict, step=trip_step))
        if self.shadows.epoch < 0 or self.shadows.snap is None:
            raise ProcFailedError(
                verdict.get("rank", -1),
                "elastic: trip before the first shadow epoch — nothing "
                "to recover from (kill injected at step 0?)") from exc
        # 1. host plane: revoke + shrink to the survivor comm
        shrink_info: Dict[str, Any] = {}
        if self.comm is not None:
            new_comm, dead_world, shrink_info = comm_recover(self.comm,
                                                             verdict)
            self.comm = new_comm
            dead_pos = [w for w in dead_world if w < self.n]
        else:
            dead_pos = ([int(verdict["rank"])]
                        if int(verdict.get("rank", -1)) >= 0 else [])
        if not dead_pos:
            raise ProcFailedError(
                -1, "elastic: trip carries no attributable dead rank "
                    f"(verdict {verdict})") from exc
        bad = [p for p in dead_pos if (p + 1) % self.n in dead_pos]
        if bad:
            raise ProcFailedError(
                bad[0], "elastic: adjacent double failure defeats the "
                        f"single-hop shadow ring (dead {sorted(dead_pos)})"
                        " — fall back to checkpoint restore") from exc
        t_shrink = time.perf_counter()
        if trace.enabled:
            trace.instant("ft_shrink", "ft",
                          args=dict(shrink_info, dead=sorted(dead_pos)))
        # 2. device plane: survivor mesh + cross-mesh reshard from shadows
        new_mesh = survivor_mesh(self.mesh, dead_pos, self.axis)
        epoch = self.shadows.epoch
        bytes0 = _reshard.pvar_value("reshard_bytes")
        leaves = 0

        def migrate(snap_leaf, shifted_leaf):
            nonlocal leaves
            if not isinstance(snap_leaf, jax.Array):
                return snap_leaf
            leaves += 1
            new_n = int(np.asarray(new_mesh.devices).size)
            dst = NamedSharding(
                new_mesh, elastic_spec(snap_leaf, new_n, self.axis))
            repl = {}
            if self.shadows._is_ring_sharded(snap_leaf):
                for p in dead_pos:
                    repl[p] = self.shadows.replacement(shifted_leaf, p)
            return _reshard.cross_reshard(
                snap_leaf, dst, dead=dead_pos, replacements=repl,
                spc=self.spc)

        snap_params, snap_opt = self.shadows.snap
        shifted_params, shifted_opt = self.shadows.shifted
        new_params = jax.tree.map(migrate, snap_params, shifted_params)
        new_opt = jax.tree.map(migrate, snap_opt, shifted_opt)
        moved = int(_reshard.pvar_value("reshard_bytes") - bytes0)
        t_reshard = time.perf_counter()
        if trace.enabled:
            trace.instant("ft_reshard", "ft",
                          args={"leaves": leaves, "wire_bytes": moved,
                                "mesh_before": self.n,
                                "mesh_after":
                                    int(np.asarray(new_mesh.devices).size),
                                "epoch_step": epoch})
        # 3. rebind + roll back to the shadow epoch and resume
        old_n = self.n
        self._bind(new_mesh, new_params, new_opt)
        steps_lost = trip_step - epoch
        self.step = epoch
        t_resume = time.perf_counter()
        reads = _ckpt.restore_count() - reads0
        rec = {
            "dead_rank": int(dead_pos[0]), "dead": sorted(dead_pos),
            "kind": verdict["kind"], "verdict": verdict,
            "trip_step": trip_step, "epoch_step": epoch,
            "resume_step": epoch, "steps_lost": steps_lost,
            "budget_steps": self.recovery_budget,
            "mesh_before": old_n, "mesh_after": self.n,
            "survivors": survivor_positions(old_n, dead_pos),
            "leaves": leaves, "wire_bytes": moved, "ckpt_reads": reads,
            "shrink": shrink_info,
            "t_trip_ms": 0.0,
            "t_shrink_ms": round((t_shrink - t0) * 1e3, 3),
            "t_reshard_ms": round((t_reshard - t0) * 1e3, 3),
            "t_resume_ms": round((t_resume - t0) * 1e3, 3),
        }
        with _lock:
            _counts["ft_recoveries"] += 1
            _counts["ft_steps_lost"] += int(steps_lost)
            _recovery_log.append(rec)
            global _last_recovery
            _last_recovery = rec
        self.recoveries.append(rec)
        if trace.enabled:
            trace.decision(
                "ft_recovery", arm="shrink",
                reason=f"{verdict['kind']}:rank{dead_pos[0]}",
                verdict=dict(verdict),
                nbytes=moved, dead_rank=int(dead_pos[0]),
                dead=sorted(dead_pos), survivors=rec["survivors"],
                mesh_before=old_n, mesh_after=self.n,
                steps_lost=steps_lost, resume_step=epoch,
                ckpt_reads=reads, recover_ms=rec["t_resume_ms"])
            trace.instant("ft_resume", "ft",
                          args={"step": epoch, "steps_lost": steps_lost,
                                "mesh": self.n,
                                "recover_ms": rec["t_resume_ms"]})


def run_elastic(cfg, n_steps: int, **kw) -> ElasticTrainer:
    """One-call face: build an :class:`ElasticTrainer` and run it."""
    return ElasticTrainer(cfg, **kw).run(n_steps)
