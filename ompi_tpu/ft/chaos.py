"""Deterministic fault injection — the CI driver for elastic recovery.

Three injectors, all keyed by explicit (step, rank) coordinates so a
run either reproduces a failure bit-for-bit or doesn't inject at all
(no randomness, no wall-clock coupling):

  * kill-at-step    — single-controller: poison every float shard
                      resident on the victim mesh position (fail-stop:
                      bytes on a dead device are GONE, including its
                      shadow copies) and raise ``ProcFailedError``.
                      Threaded: the victim rank calls ``maybe_die`` and
                      goes silent via ``ft.simulate_failure``.
  * delayed-send    — wrap a rank's transport send with a fixed delay
                      toward (optionally) one destination: watchdog /
                      detector latency-tolerance testing.
  * dropped-revoke  — swallow the first N revoke frames arriving at a
                      rank: exercises the reliable re-flood property
                      (delivery reaches all survivors if any survivor
                      delivers).

Every injection appends an attribution record to ``log`` so tests can
assert exactly what fired where.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..p2p import AM_FT
from .ulfm import ProcFailedError, simulate_failure


def poison_position(tree, mesh, pos: int):
    """Fail-stop a mesh position's resident float shards: every byte it
    held becomes NaN (a dead device's memory is unreadable — any path
    that still consumes it must fail loudly, which is what makes the
    probe's zero-dead-reads assertion real)."""
    devs = list(np.asarray(mesh.devices).flat)
    dev = devs[int(pos)]

    def one(leaf):
        if not isinstance(leaf, jax.Array):
            return leaf
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        if dev not in getattr(leaf.sharding, "device_set", ()):
            return leaf
        datas = []
        hit = False
        for sh in leaf.addressable_shards:
            d = sh.data
            if sh.device == dev:
                d = jnp.full_like(d, jnp.nan)
                hit = True
            datas.append(d)
        if not hit:
            return leaf
        return jax.make_array_from_single_device_arrays(
            leaf.shape, leaf.sharding, datas)

    return jax.tree.map(one, tree)


class ChaosMonkey:
    """Holds the injection schedule; one instance drives one scenario."""

    def __init__(self) -> None:
        self._kills: List[tuple] = []      # (step, rank)
        self.log: List[Dict[str, Any]] = []

    # -- kill-at-step -------------------------------------------------------

    def kill_at_step(self, rank: int, step: int) -> "ChaosMonkey":
        self._kills.append((int(step), int(rank)))
        return self

    def on_step(self, trainer, step: int) -> None:
        """Single-controller hook, called by ElasticTrainer at the top
        of every step."""
        for entry in list(self._kills):
            s, r = entry
            if s == int(step):
                self._kills.remove(entry)
                self.kill_now(trainer, r)

    def kill_now(self, trainer, rank: int) -> None:
        """Fail-stop mesh position ``rank``: poison its resident shards
        across ALL live trees (params, opt state, shadow snapshot AND
        shifted shadows — a dead device loses everything it held), then
        raise the failure signal the elastic loop recovers from."""
        mesh = trainer.mesh
        trainer.params = poison_position(trainer.params, mesh, rank)
        trainer.opt_state = poison_position(trainer.opt_state, mesh, rank)
        sh = getattr(trainer, "shadows", None)
        if sh is not None and sh.snap is not None:
            sh.snap = poison_position(sh.snap, mesh, rank)
            sh.shifted = poison_position(sh.shifted, mesh, rank)
        self.log.append({"kind": "kill", "rank": int(rank),
                         "step": int(trainer.step)})
        raise ProcFailedError(
            int(rank), f"chaos: injected kill of mesh position {rank} "
                       f"at step {trainer.step}")

    def maybe_die(self, ctx, step: int) -> bool:
        """Threaded victim hook: when a kill is scheduled for this
        rank/step, go silent (fail-stop) and report True so the rank
        body can park itself."""
        for entry in list(self._kills):
            s, r = entry
            if s == int(step) and r == int(ctx.rank):
                self._kills.remove(entry)
                self.log.append({"kind": "kill", "rank": int(ctx.rank),
                                 "step": int(step)})
                simulate_failure(ctx)
                return True
        return False

    # -- delayed-send -------------------------------------------------------

    def delay_sends(self, ctx, delay_s: float,
                    dst: Optional[int] = None) -> None:
        """Slow this rank's python-side transport sends by ``delay_s``
        (toward ``dst`` only, when given).  Wraps every transport, so
        both ``layer.send`` control frames (heartbeats, revoke, agree —
        the latency this injector exists to stress) and python-path
        payload sends are covered; payloads riding the native shm
        engine's C fragment path are NOT delayed."""
        chaos = self

        for t in ctx.layer.transports:
            def wrapped(to, tag, header, payload=b"", _inner=t.send):
                if dst is None or int(to) == int(dst):
                    chaos.log.append({"kind": "delayed_send",
                                      "rank": int(ctx.rank),
                                      "dst": int(to),
                                      "delay_s": float(delay_s)})
                    time.sleep(delay_s)
                return _inner(to, tag, header, payload)

            t.send = wrapped

    # -- dropped-revoke -----------------------------------------------------

    def drop_revokes(self, ctx, count: int = 1) -> Dict[str, int]:
        """Swallow the first ``count`` revoke frames arriving at this
        rank.  Returns the live drop-budget dict (``state["left"]``
        reaches 0 once the drops fired) so tests can assert the re-flood
        actually had to route around the loss."""
        state = {"left": int(count)}
        chaos = self

        for t in ctx.layer.transports:
            inner = t.dispatch.get(AM_FT)
            if inner is None:
                continue

            def wrapped(src, h, payload, _inner=inner):
                if h.get("k") == "revoke" and state["left"] > 0:
                    state["left"] -= 1
                    chaos.log.append({"kind": "dropped_revoke",
                                      "rank": int(ctx.rank),
                                      "src": int(src)})
                    return
                _inner(src, h, payload)

            t.dispatch[AM_FT] = wrapped
        return state
