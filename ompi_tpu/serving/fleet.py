"""Serving fleet — goodput-routed replicas + prefill/decode split.

The layer above ``ServingEngine``: one serving config where replica
count (R) and tp degree are the ONLY knobs.  ``ServingFleet`` carves
R disjoint tp-meshes out of the device list (``make_mesh`` over device
subsets — the GSPMD "same application code from 8 to 6 000 chips"
shape), builds one engine per replica against a SHARED spc counter
pool, and admits one Poisson stream through a deterministic
goodput-weighted front-end router (``scheduler.FleetRouter``).

Two topologies over the same chips:

* **colocated** (``prefill_replicas=0``) — every replica runs its own
  continuous-batching loop, prefill and decode serialized on the same
  engine: a long prompt's prefill-bucket call blocks every in-flight
  sequence on that replica for its full duration (the head-of-line ITL
  spike the fleet's p99 ITL shows).
* **disaggregated** — the first ``prefill_replicas`` replicas ONLY
  prefill; the rest ONLY decode.  A finished prompt's KV pages migrate
  prefill→decode through :func:`ServingFleet.migrate`: a KV-page
  migration IS a source-mesh→dest-mesh transition, so it rides
  ``parallel.reshard.cross_reshard`` unchanged — a 2×tp bridge mesh
  over the union of both replicas' devices, the real pages on the
  prefill half and a zero half resident on the decode devices, dest
  spec replicated over ``fleet`` so the plan emits exactly tp
  cross-device pieces (prefill j → decode j, wire == page payload
  bytes) plus tp zero-wire local pieces.  The move inherits the whole
  reshard contract for free: ``reshard_peak_factor`` peak bound
  (peak == 4·shard == the 2.0× default bound exactly), ONE audited
  ``decide:reshard`` event, per-pair ``traffic.note_reshard_step``
  attribution (fleet-wide edge-sum == wire-pvar conservation), and the
  ``reshard_*`` pvars.  On top of that the fleet charges ``simdcn``
  for the hop whenever the bridge's ``fleet`` axis classifies as DCN
  (``topo_sim_dcn_axes=fleet`` makes the cross-replica topology
  CI-drivable on 8 CPU devices) and emits a ``serve:migrate`` span +
  the fleet ledger row (``serving.note_migration``).

Time is the same virtual-clock model the single-replica scheduler
uses, with one clock per replica on a common global axis: the prefill
replica works ahead on its own timeline, and a migrated sequence joins
the decode batch only once the decode clock reaches the handoff time —
so the decode loop NEVER idles through a prefill, which is exactly the
p99-ITL win of the split.  Prefill capacity is modeled per
prefill↔decode pairing (decode replica i prefills on prefill replica
``i % n_prefill``'s lane).

The ``hot_replica`` sentry (p99-ITL skew vs the fleet median, episode
semantics) publishes on the PR 17 policy bus; the pre-verified
``route_weight`` action (policy/engine builtin) shifts admission
weight through ``serving.apply_route_weight`` with one audited
``decide:fleet_route`` naming its verdict — the router reads the bias
on every assignment.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import importlib

from .. import serving, trace
from ..core import var as _var
from ..parallel import simdcn

# The ``parallel`` package re-exports the ``reshard`` *function*, which
# shadows the module attribute of the same name — import the module
# explicitly (same trick as ft/elastic.py).
_reshard = importlib.import_module("ompi_tpu.parallel.reshard")
from ..parallel.collectives import DeviceComm
from ..parallel.hierarchy import classify_axes
from ..parallel.mesh import make_mesh
from . import requests as _requests
from .engine import ServingEngine
from .scheduler import (ContinuousBatchingScheduler, FleetRouter,
                        Request, _Active)


def _j_page_import_build():
    from functools import partial

    @partial(jax.jit, donate_argnums=(0,))
    def _imp(pool, pages, idx):
        return pool.at[:, idx].set(pages)
    return _imp


_j_page_import = _j_page_import_build()


def _percentile(vals: List[float], q: float) -> float:
    if not vals:
        return 0.0
    s = sorted(vals)
    k = min(int(round(q * (len(s) - 1))), len(s) - 1)
    return s[k]


class _Replica:
    """One fleet member: its mesh/DeviceComm/engine plus the role and
    the prefill-lane clock the disaggregated scheduler advances."""

    def __init__(self, idx: int, role: str, devices: List,
                 dc: DeviceComm, engine: ServingEngine) -> None:
        self.idx = idx
        self.role = role                   # "serve" | "prefill" | "decode"
        self.devices = devices
        self.dc = dc
        self.engine = engine
        self.prefills = 0
        self.prefill_s = 0.0
        self.clock = 0.0                   # prefill-lane virtual time


class _ReplicaScheduler(ContinuousBatchingScheduler):
    """The base continuous loop plus per-replica ITL attribution."""

    def __init__(self, replica: _Replica, requests: List[Request],
                 **kw: Any) -> None:
        super().__init__(replica.engine, requests, **kw)
        self.replica = replica
        self.rank = replica.idx            # request-plane lane
        self.itl: List[float] = []
        self._last_t: Dict[Any, float] = {}

    def _on_token(self, st: _Active) -> None:
        rid = st.req.rid
        last = self._last_t.get(rid)
        if last is not None:
            self.itl.append(self.clock - last)
        self._last_t[rid] = self.clock


class _DisaggScheduler(_ReplicaScheduler):
    """Decode-replica loop with prefill+migration on a separate lane.

    The prefill replica runs on its own virtual clock (it may work
    AHEAD of the decode clock — it is a different machine), gated only
    by request arrival and decode-cache admission backpressure.  A
    prefilled sequence's pages migrate immediately (reserving the
    decode slot), then the sequence joins the decode batch once the
    decode clock reaches the handoff time — decode steps for other
    in-flight sequences keep running throughout, so prefill duration
    never lands in their inter-token gaps."""

    def __init__(self, fleet: "ServingFleet", pre: _Replica,
                 dec: _Replica, requests: List[Request],
                 **kw: Any) -> None:
        super().__init__(dec, requests, **kw)
        self.fleet = fleet
        self.pre = pre
        self.ready: List[Tuple[float, Request, int, int]] = []

    def _admissible(self) -> bool:
        return False                       # admission goes via the pump

    def _pump_prefill(self) -> None:
        pre, dec = self.pre, self.replica
        pcache = pre.engine.cache
        while self.pending:
            req = self.pending[0]
            if req.arrival > max(self.clock, pre.clock):
                break
            if not dec.engine.cache.can_admit(len(req.prompt),
                                              req.max_new):
                break                      # decode-cache backpressure
            self.pending.pop(0)
            pre.clock = max(pre.clock, req.arrival)
            if serving.enabled:
                serving.note_admit(req.rid, len(req.prompt),
                                   req.max_new, req.arrival, pre.clock)
            if _requests.enabled:
                _requests.note_admit(req.rid, req.arrival, pre.clock,
                                     len(req.prompt), req.max_new,
                                     replica=dec.idx, rank=pre.idx)
            pslot = pcache.admit(len(req.prompt), req.max_new)
            t0 = time.perf_counter()
            first, _ = pre.engine.prefill(pslot, req.prompt,
                                          rid=req.rid)
            pdur = time.perf_counter() - t0
            pre.clock += pdur
            pre.prefills += 1
            pre.prefill_s += pdur
            if serving.enabled:
                serving.note_prefill(pdur, len(req.prompt))
                serving.note_token(req.rid, pre.clock)
            if _requests.enabled:
                _requests.note_stage(req.rid, "prefill",
                                     pre.clock - pdur, pre.clock,
                                     rank=pre.idx)
                _requests.note_token(req.rid, pre.clock, rank=pre.idx)
            self._last_t[req.rid] = pre.clock
            eos = (req.eos_id if req.eos_id is not None else self.eos_id)
            if (eos is not None and first == eos) or req.max_new <= 1:
                # done at the first token: nothing to migrate
                pcache.release(pslot)
                reason = ("eos" if eos is not None and first == eos
                          else "max_new")
                self.results[req.rid] = {
                    "rid": req.rid, "tokens": [first], "reason": reason,
                    "finished_at": pre.clock}
                if serving.enabled:
                    serving.note_evict(req.rid, reason, pre.clock)
                if _requests.enabled:
                    _requests.note_finish(req.rid, pre.clock, reason)
                continue
            t0 = time.perf_counter()
            dslot = self.fleet.migrate(pre, dec, pslot,
                                       len(req.prompt), req.max_new,
                                       rid=req.rid)
            mdur = time.perf_counter() - t0
            pre.clock += mdur
            pcache.release(pslot)
            if _requests.enabled:
                last = _reshard.report()["last"] or {}
                _requests.note_stage(
                    req.rid, "migrate", pre.clock - mdur, pre.clock,
                    rank=pre.idx, src=pre.idx, dst=dec.idx,
                    wire_bytes=int(last.get("wire_bytes", 0)),
                    link="decide:reshard")
            self.ready.append((pre.clock, req, dslot, first))

    def _join_ready(self) -> None:
        rest = []
        for t, req, dslot, first in self.ready:
            if t <= self.clock:
                self.active[dslot] = _Active(req=req, slot=dslot,
                                             tokens=[first], last=first)
                if _requests.enabled:
                    _requests.note_stage(req.rid, "join", t, self.clock,
                                         rank=self.replica.idx)
            else:
                rest.append((t, req, dslot, first))
        self.ready = rest

    def run(self, max_steps: int = 100000) -> Dict[str, Any]:
        while self.pending or self.ready or self.active:
            self._pump_prefill()
            self._join_ready()
            if not self.active:
                if self.ready:
                    # idle: jump the decode clock to the next handoff
                    self.clock = max(self.clock,
                                     min(t for t, *_ in self.ready))
                elif self.pending:
                    self.clock = max(self.clock,
                                     self.pending[0].arrival)
                else:
                    break
                continue
            self._step()
            if self.decode_steps >= max_steps:
                raise RuntimeError(f"fleet scheduler exceeded "
                                   f"{max_steps} decode steps without "
                                   "draining")
        return self.summary()


class ServingFleet:
    """R data-parallel serving replicas over disjoint tp-meshes.

    ``params`` arrive in the train layout ONCE (host or replicated);
    each replica shards them onto its own submesh and converts to the
    decode layout at engine init.  ``prefill_replicas=0`` is the
    colocated topology; ``prefill_replicas=k`` dedicates the first k
    replicas to prefill and the rest to decode."""

    def __init__(self, params: Dict, cfg, *, replicas: int = 1,
                 tp: int = 8, prefill_replicas: int = 0,
                 devices: Optional[List] = None, n_pages: int = 96,
                 page_size: int = 8, max_seqs: int = 8,
                 spc=None, router: Optional[FleetRouter] = None,
                 layout: str = "train") -> None:
        from ..models import transformer as tfm
        devs = list(devices) if devices is not None else \
            list(jax.devices())
        need = int(replicas) * int(tp)
        if len(devs) < need:
            raise ValueError(f"ServingFleet: {replicas} replicas × "
                             f"tp={tp} needs {need} devices, have "
                             f"{len(devs)}")
        if prefill_replicas < 0 or prefill_replicas >= replicas and \
                prefill_replicas > 0:
            raise ValueError(
                f"ServingFleet: prefill_replicas={prefill_replicas} "
                f"must leave at least one decode replica of {replicas}")
        self.cfg = cfg
        self.tp = int(tp)
        self.spc = spc
        self.mode = ("disaggregated" if prefill_replicas
                     else "colocated")
        self.replicas: List[_Replica] = []
        for r in range(int(replicas)):
            sub = devs[r * tp:(r + 1) * tp]
            mesh = make_mesh({"tp": tp}, devices=sub)
            dc = DeviceComm(mesh, "tp")
            dc.spc = spc
            sharded = (tfm.shard_params(params, mesh, cfg)
                       if layout == "train" else params)
            eng = ServingEngine(dc, sharded, cfg, n_pages=n_pages,
                                page_size=page_size, max_seqs=max_seqs,
                                layout=layout)
            role = ("prefill" if r < prefill_replicas
                    else ("decode" if prefill_replicas else "serve"))
            self.replicas.append(_Replica(r, role, sub, dc, eng))
        self.prefill_ids = list(range(prefill_replicas))
        self.serve_ids = list(range(prefill_replicas, int(replicas)))
        self.router = router if router is not None else \
            FleetRouter(len(self.serve_ids))
        self._bridges: Dict[Tuple[int, int], Any] = {}
        self._hot: Dict[int, bool] = {}
        serving.set_fleet_replicas(int(replicas))
        for rep in self.replicas:
            serving.update_replica(rep.idx, {"role": rep.role})

    # -- KV-page migration (the cross_reshard hop) -------------------------

    def _bridge(self, src: _Replica, dst: _Replica):
        key = (src.idx, dst.idx)
        m = self._bridges.get(key)
        if m is None:
            m = make_mesh({"fleet": 2, "tp": self.tp},
                          devices=src.devices + dst.devices)
            self._bridges[key] = m
        return m

    def migrate(self, src: _Replica, dst: _Replica, src_slot: int,
                prompt_len: int, max_new: int,
                rid: Any = None) -> int:
        """Hand ``src_slot``'s KV pages from ``src`` to ``dst``;
        returns the dest slot (admitted here, pages scattered through
        a donated write, ``seq_lens`` carried over).  Page values are
        moved bitwise — whole pages, dest pages fully overwritten."""
        t0 = time.perf_counter()
        try:
            return self._migrate(src, dst, src_slot, prompt_len,
                                 max_new, rid, t0)
        except BaseException:
            if trace.enabled:
                trace.record_span("serve:migrate", "serve", t0,
                                  time.perf_counter(),
                                  args={"rid": rid, "src": src.idx,
                                        "dst": dst.idx,
                                        "status": "error"})
            raise

    def _migrate(self, src: _Replica, dst: _Replica, src_slot: int,
                 prompt_len: int, max_new: int, rid: Any,
                 t0: float) -> int:
        scache, dcache = src.engine.cache, dst.engine.cache
        if (scache.page_size, scache.heads_local, scache.head_dim,
                scache.n_layers) != (dcache.page_size,
                                     dcache.heads_local,
                                     dcache.head_dim, dcache.n_layers):
            raise ValueError("ServingFleet.migrate: prefill/decode "
                             "cache geometries differ")
        pages = list(scache._slot_pages[src_slot])
        npg = len(pages)
        L, pg = scache.n_layers, scache.page_size
        hl, hd = scache.heads_local, scache.head_dim
        seq_len = int(scache.seq_lens[src_slot])
        dst_slot = dcache.admit(prompt_len, max_new)
        dpages = list(dcache._slot_pages[dst_slot])
        if len(dpages) != npg:
            dcache.release(dst_slot)
            raise RuntimeError(f"ServingFleet.migrate: page count "
                               f"mismatch ({npg} src vs {len(dpages)} "
                               "dst)")
        idx = jnp.asarray(pages, jnp.int32)
        bridge = self._bridge(src, dst)
        rows = 2 * L * npg                 # k then v, layer-major
        shape = (2, self.tp, rows, pg, hl, hd)
        src_sh = NamedSharding(bridge, P("fleet", "tp"))
        kmaps = [{s.device: s.data for s in pool.addressable_shards}
                 for pool in scache.k]
        vmaps = [{s.device: s.data for s in pool.addressable_shards}
                 for pool in scache.v]
        src_devs = set(src.devices)
        blocks = []
        for dev, _r in src_sh.devices_indices_map(shape).items():
            if dev in src_devs:
                parts = [jnp.take(kmaps[l][dev], idx, axis=1)
                         for l in range(L)]
                parts += [jnp.take(vmaps[l][dev], idx, axis=1)
                          for l in range(L)]
                blk = jnp.concatenate(parts, axis=1)
                blk = blk.reshape(1, 1, rows, pg, hl, hd)
            else:
                # the zero half: resident on the decode device, so its
                # piece is a zero-wire local copy in the cross plan
                blk = jax.device_put(
                    jnp.zeros((1, 1, rows, pg, hl, hd), scache.dtype),
                    dev)
            blocks.append(blk)
        x = jax.make_array_from_single_device_arrays(shape, src_sh,
                                                     blocks)
        dst_sh = NamedSharding(dst.dc.mesh, P(None, "tp"))
        out = _reshard.cross_reshard(x, dst_sh, spc=self.spc)
        last = _reshard.report()["last"] or {}
        wire = int(last.get("wire_bytes", 0))
        # cross_reshard audits wire/traffic on the bridge mesh; the
        # fleet additionally charges the simulated DCN hop when the
        # bridge's fleet axis classifies as DCN
        if wire and simdcn.us_per_mib() > 0 and \
                classify_axes(bridge).get("fleet") == "dcn":
            simdcn.charge(wire)
        payload = out[0]                   # (tp, rows, pg, hl, hd)
        didx = jnp.asarray(dpages, jnp.int32)
        for l in range(L):
            dcache.k[l] = _j_page_import(
                dcache.k[l], payload[:, l * npg:(l + 1) * npg], didx)
            dcache.v[l] = _j_page_import(
                dcache.v[l], payload[:, (L + l) * npg:
                                     (L + l + 1) * npg], didx)
        dcache.seq_lens[dst_slot] = seq_len
        t1 = time.perf_counter()
        if serving.enabled:
            serving.note_migration(rid, src.idx, dst.idx, npg, wire,
                                   int(last.get("peak_bytes", 0)),
                                   int(last.get("bound_bytes", 0)),
                                   t1 - t0)
        if trace.enabled:
            trace.record_span("serve:migrate", "serve", t0, t1,
                              args={"rid": rid, "src": src.idx,
                                    "dst": dst.idx, "pages": npg,
                                    "wire_bytes": wire,
                                    "seq_len": seq_len})
        return dst_slot

    # -- the fleet run -----------------------------------------------------

    def run(self, requests: List[Request], *,
            eos_id: Optional[int] = None,
            spec_k: int = 0) -> Dict[str, Any]:
        """Admit one request stream across the fleet: the router
        assigns every request (in arrival order) to a serving/decode
        replica under the current effective weights, each replica
        drains its share on its own virtual clock (replicas are
        concurrent machines — fleet makespan is the MAX replica clock,
        not the sum), then the per-replica rows feed the fleet ledger,
        the router's live goodput/ITL weights, and the hot_replica
        sentry."""
        serving.set_fleet_replicas(len(self.replicas))
        for rep in self.replicas:
            # each run() replays an independent stream whose arrivals
            # restart near t=0: the prefill lanes' virtual clocks (and
            # their busy accounting) restart with it, like the decode
            # schedulers' do
            rep.clock = 0.0
            rep.prefills = 0
            rep.prefill_s = 0.0
        reqs = sorted(requests, key=lambda r: r.arrival)
        buckets: Dict[int, List[Request]] = {i: [] for i in
                                             self.serve_ids}
        for req in reqs:
            pick = self.serve_ids[self.router.assign(req.rid)]
            buckets[pick].append(req)
        scheds: List[Tuple[int, _ReplicaScheduler]] = []
        for i, t in enumerate(self.serve_ids):
            dec = self.replicas[t]
            if self.mode == "disaggregated":
                pre = self.replicas[
                    self.prefill_ids[i % len(self.prefill_ids)]]
                s: _ReplicaScheduler = _DisaggScheduler(
                    self, pre, dec, buckets[t], eos_id=eos_id)
            else:
                s = _ReplicaScheduler(dec, buckets[t], eos_id=eos_id,
                                      spec_k=spec_k)
            scheds.append((t, s))
        results: Dict[Any, Dict[str, Any]] = {}
        itl_all: List[float] = []
        per_replica: List[Dict[str, Any]] = []
        total_tokens = 0
        total_steps = 0
        clock = 0.0
        for i, (t, s) in enumerate(scheds):
            out = s.run()
            results.update(out["results"])
            itl_all.extend(s.itl)
            total_tokens += out["tokens"]
            total_steps += out["decode_steps"]
            clock = max(clock, out["clock_s"])
            p99 = 1e3 * _percentile(s.itl, 0.99)
            row = {
                "replica": t, "role": self.replicas[t].role,
                "requests": len(buckets[t]),
                "tokens": out["tokens"],
                "decode_steps": out["decode_steps"],
                "clock_s": round(out["clock_s"], 6),
                "tokens_per_s": round(out["tokens_per_s"], 2),
                "occupancy": round(
                    s.occ_sum / max(s.decode_steps, 1), 4),
                "itl_p50_ms": round(1e3 * _percentile(s.itl, 0.50), 3),
                "itl_p99_ms": round(p99, 3),
            }
            per_replica.append(row)
            serving.update_replica(t, row)
            # live reweighting: goodput per unit tail latency
            self.router.update(i, out["tokens_per_s"], max(p99, 1e-3))
        for p in self.prefill_ids:
            pre = self.replicas[p]
            clock = max(clock, pre.clock)
            row = {"replica": p, "role": "prefill",
                   "prefills": pre.prefills,
                   "prefill_s": round(pre.prefill_s, 6),
                   "clock_s": round(pre.clock, 6)}
            per_replica.append(row)
            serving.update_replica(p, row)
        self.check_hot_replicas(step=total_steps)
        itl = sorted(itl_all)
        return {
            "mode": self.mode,
            "replicas": len(self.replicas),
            "tp": self.tp,
            "clock_s": clock,
            "completed": len(results),
            "tokens": total_tokens,
            "decode_steps": total_steps,
            "tokens_per_s": (total_tokens / clock) if clock else 0.0,
            "itl": {"count": len(itl),
                    "p50_ms": 1e3 * _percentile(itl, 0.50),
                    "p99_ms": 1e3 * _percentile(itl, 0.99)},
            "per_replica": per_replica,
            "results": results,
        }

    # -- the hot_replica sentry --------------------------------------------

    def check_hot_replicas(self, step: int = 0) -> List[Any]:
        """p99-ITL skew vs the fleet (lower) median across serving
        replicas.  Episode semantics: one ``policy_verdict`` per
        excursion, re-armed once the skew recovers below 90% of the
        threshold — the builtin ``fleet_hot_replica`` rule answers
        with the pre-verified ``route_weight`` action."""
        from .. import policy
        rep = serving.fleet_report()
        rows = [r for r in rep["replica_rows"]
                if r.get("role") != "prefill"
                and r.get("itl_p99_ms") is not None]
        if len(rows) < 2:
            return []
        p99s = sorted(float(r["itl_p99_ms"]) for r in rows)
        med = max(p99s[(len(p99s) - 1) // 2], 1e-9)
        thr = float(_var.get("serve_fleet_hot_skew", 1.75))
        out = []
        for r in rows:
            i = int(r["replica"])
            skew = float(r["itl_p99_ms"]) / med
            if skew >= thr and not self._hot.get(i):
                self._hot[i] = True
                out.append(policy.publish(
                    "serve", "hot_replica", "warn",
                    {"replica": i,
                     "itl_p99_ms": float(r["itl_p99_ms"]),
                     "median_p99_ms": med,
                     "skew": round(skew, 3),
                     "tokens_per_s": r.get("tokens_per_s")},
                    step=step))
            elif skew < 0.9 * thr:
                self._hot[i] = False
        return out
