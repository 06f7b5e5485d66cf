"""coll/adapt analog: event-driven collectives with dynamic segmentation.

≙ ompi/mca/coll/adapt (coll_adapt_bcast.c:1, coll_adapt_ireduce.c): the
reference's adapt component progresses a segmented tree through COMPLETION
CALLBACKS — a segment forwards the moment it arrives, no round barrier —
and picks segmentation dynamically. The nbc Schedule engine here
(coll/nbc.py) is round-synchronous by design (a round starts when the
previous round fully completes), so adapt is its event-driven sibling:

  * chain (pipeline) topology in rank order from the root — the
    bandwidth-optimal shape for large messages (the same regime the
    reference routes to adapt);
  * every rank posts the next segment's receive IMMEDIATELY and forwards
    each received segment to its child from the receive's completion
    callback — receive(k+1) overlaps forward(k) at every hop;
  * the ROOT adapts segment size to observed completion latency: a
    segment's send-to-completion time below the low-water mark means
    per-message overhead dominates (segments double, up to max); above
    the high-water mark the pipe is saturated and finer overlap pays
    (segments halve, down to min). Receivers discover sizes from
    status.count — no size pre-agreement, which is what makes the
    segmentation free to adapt mid-message.

Selection: registered as coll component ``adapt`` at priority 5 (below
nbc), so the stock dispatch is unchanged; raise ``coll_adapt_priority``
to let its ibcast/ireduce win selection, or call
``ibcast_adapt``/``ireduce_adapt`` directly.

Status (round-4 measurement, BASELINE.md "Adaptive collectives", the
DCN stand-in): on every fabric this box can express — shm+CMA, and
tcp-only 4-rank (the DCN stand-in) at 1/4/16 MB — whole-message
binomial beats adapt by ~1.2-1.6×, because event-driven overlap needs
CONCURRENT cores and this host has one: segment completion callbacks
serialize, leaving only their per-segment overhead. The component is
therefore demoted to a correctness-complete, measurement-pending
implementation: its claimed habitat (multi-host DCN, a core per rank,
per-hop bandwidth dominating) does not exist on this hardware, and the
default priority keeps it unselected until a fabric where it measures a
win is available.
"""

from __future__ import annotations

import time
from typing import List, Optional

import numpy as np

from ..core import var as _var
from ..core.component import Component, component
from ..op import SUM, Op, reduce_local
from ..p2p.request import Request
from .framework import CollModule

_var.register("coll", "adapt", "priority", 5, type=int, level=4,
              help="Selection priority of the event-driven adapt "
                   "collectives (default 5 = available but not selected; "
                   "raise above 40 to outrank the round-based nbc "
                   "schedules for ibcast/ireduce).")
_var.register("coll", "adapt", "seg_min", 64 * 1024, type=int, level=4,
              help="Adaptive segmentation floor (bytes).")
_var.register("coll", "adapt", "seg_max", 1 << 20, type=int, level=4,
              help="Adaptive segmentation ceiling (bytes).")

_ADAPT_TAG_BASE = -1200     # own reserved band (nbc uses -200..-999)
_ADAPT_TAG_SPAN = 200


def _tag(comm) -> int:
    seq = getattr(comm, "_adapt_seq", 0)
    comm._adapt_seq = seq + 1
    return _ADAPT_TAG_BASE - (seq % _ADAPT_TAG_SPAN)


class _AdaptBcast:
    """One in-flight adaptive bcast instance (engine-registered)."""

    # completion-latency water marks for the segment controller: below
    # LO the per-message overhead dominates → coarser; above HI the pipe
    # is backed up → finer (more overlap). Seconds.
    T_LO = 0.0008
    T_HI = 0.008

    def __init__(self, comm, buf: np.ndarray, root: int, tag: int) -> None:
        self.comm = comm
        self.buf = buf.reshape(-1).view(np.uint8)
        self.total = self.buf.nbytes
        self.req = Request()
        self.tag = tag
        n, me = comm.size, comm.rank
        pos = (me - root) % n               # chain position (root = 0)
        self.child = (pos + 1 + root) % n if pos < n - 1 else None
        self.parent = (pos - 1 + root) % n if pos > 0 else None
        self.is_root = pos == 0
        self.seg = int(_var.get("coll_adapt_seg_min", 64 * 1024))
        self.seg_max = int(_var.get("coll_adapt_seg_max", 1 << 20))
        self.seg_min = self.seg
        self.sent = 0                       # root: bytes handed to child
        self.received = 0
        self.forwarded = 0
        self._send_reqs: List[Request] = []
        self._recv_req: Optional[Request] = None
        self._t_send = 0.0
        self.segments_sent = 0

    def start(self) -> Request:
        if self.comm.size == 1 or self.total == 0:
            self.req.complete()
            return self.req
        self.comm.ctx.engine.register(self._progress)
        if self.is_root:
            self._push()
        else:
            self._post_recv()
        return self.req

    # -- root: adaptive segment pump ----------------------------------------

    def _push(self) -> None:
        """Keep ≤2 segments in flight; adapt size from completion times."""
        while self.sent < self.total and len(self._send_reqs) < 2:
            n = min(self.seg, self.total - self.sent)
            view = self.buf[self.sent:self.sent + n]
            r = self.comm.isend(view, self.child, self.tag)
            self._send_reqs.append((r, time.perf_counter()))
            self.sent += n
            self.segments_sent += 1

    def _root_progress(self) -> int:
        done = [(r, t0) for r, t0 in self._send_reqs if r.done]
        for r, t0 in done:
            self._send_reqs.remove((r, t0))
            dt = time.perf_counter() - t0
            # the adaptive controller (the component's namesake): latency
            # per segment tells whether overhead or saturation dominates
            if dt < self.T_LO and self.seg < self.seg_max:
                self.seg = min(self.seg * 2, self.seg_max)
            elif dt > self.T_HI and self.seg > self.seg_min:
                self.seg = max(self.seg // 2, self.seg_min)
        self._push()
        if self.sent >= self.total and not self._send_reqs:
            self._finish()
        return len(done)

    # -- non-root: receive → forward event chain -----------------------------

    def _post_recv(self) -> None:
        view = self.buf[self.received:]     # capacity: whatever arrives
        self._recv_req = self.comm.irecv(view, self.parent, self.tag)

    def _other_progress(self) -> int:
        n = 0
        r = self._recv_req
        if r is not None and r.done:
            n = 1
            got = r.status.count
            seg_start = self.received
            self.received += got
            # forward THIS segment before waiting for the next — the
            # event-driven overlap the round-based schedules cannot do
            if self.child is not None and got:
                sr = self.comm.isend(
                    self.buf[seg_start:seg_start + got], self.child,
                    self.tag)
                self._send_reqs.append((sr, 0.0))
                self.forwarded += got
            if self.received < self.total:
                self._post_recv()
            else:
                self._recv_req = None
        self._send_reqs = [e for e in self._send_reqs if not e[0].done]
        if self._recv_req is None and not self._send_reqs:
            self._finish()
        return n

    def _progress(self) -> int:
        if self.req.done:
            return 0
        return self._root_progress() if self.is_root \
            else self._other_progress()

    def _finish(self) -> None:
        self.comm.ctx.engine.unregister(self._progress)
        self.req.complete()


class _AdaptReduce:
    """Event-driven chain reduce toward the root: each hop combines the
    incoming partial with its local contribution segment-by-segment and
    forwards the running partial — segment k forwards while k+1 is still
    inbound (≙ coll_adapt_ireduce.c's callback-progressed tree)."""

    def __init__(self, comm, send: np.ndarray, recv: Optional[np.ndarray],
                 op: Op, root: int, tag: int) -> None:
        if not op.commutative:
            # the chain combines far-end-first (and rotated for root != 0)
            # — only commutative ops reduce correctly that way (the same
            # guard nbc's recursive-doubling schedules apply)
            raise ValueError(
                "adapt ireduce requires a commutative op (use the "
                "in-order tuned/nbc algorithms for non-commutative ops)")
        self.comm = comm
        self.op = op
        self.tag = tag
        contrib = np.ascontiguousarray(send)
        self.elem = contrib.dtype
        n, me = comm.size, comm.rank
        pos = (me - root) % n
        # chain runs from the far end toward the root: my SOURCE is the
        # next rank out, my SINK is the next rank in
        self.src = (pos + 1 + root) % n if pos < n - 1 else None
        self.dst = (pos - 1 + root) % n if pos > 0 else None
        self.is_root = pos == 0
        # accumulator starts as my contribution (root may write into recv)
        if self.is_root and recv is not None:
            self.acc = np.asarray(recv).reshape(-1)
            np.copyto(self.acc, contrib.reshape(-1))
        else:
            self.acc = contrib.reshape(-1).copy()
        self.nelems = self.acc.size
        self.received = 0                  # elements combined from src
        self.forwarded = 0                 # elements shipped to dst
        self.req = Request()
        self.req.result = None             # type: ignore[attr-defined]
        self._send_reqs: List[Request] = []
        self._recv_req: Optional[Request] = None
        self._recv_view: Optional[np.ndarray] = None
        self.seg_elems = max(int(_var.get("coll_adapt_seg_min",
                                          64 * 1024))
                             // self.elem.itemsize, 1)

    def start(self) -> Request:
        if self.comm.size == 1 or self.nelems == 0:
            self.req.result = self.acc     # type: ignore[attr-defined]
            self.req.complete()
            return self.req
        self.comm.ctx.engine.register(self._progress)
        if self.src is not None:
            self._post_recv()
        else:
            self._forward()                # chain tail starts the flow
        return self.req

    def _post_recv(self) -> None:
        n = min(self.seg_elems, self.nelems - self.received)
        self._recv_view = np.empty(n, self.elem)
        self._recv_req = self.comm.irecv(self._recv_view, self.src,
                                         self.tag)

    def _forward(self) -> None:
        """Ship every fully-combined segment not yet forwarded."""
        ready = self.received if self.src is not None else self.nelems
        while self.dst is not None and self.forwarded < ready:
            n = min(self.seg_elems, ready - self.forwarded)
            sr = self.comm.isend(
                self.acc[self.forwarded:self.forwarded + n], self.dst,
                self.tag)
            self._send_reqs.append(sr)
            self.forwarded += n

    def _progress(self) -> int:
        if self.req.done:
            return 0
        n = 0
        r = self._recv_req
        if r is not None and r.done:
            n = 1
            got = self._recv_view
            view = self.acc[self.received:self.received + got.size]
            reduce_local(self.op, got, view)
            self.received += got.size
            self._forward()                # event-driven: combine → ship
            if self.received < self.nelems:
                self._post_recv()
            else:
                self._recv_req = None
        self._send_reqs = [s for s in self._send_reqs if not s.done]
        if self._recv_req is None and not self._send_reqs and \
                (self.dst is None or self.forwarded >= self.nelems):
            self.comm.ctx.engine.unregister(self._progress)
            if self.is_root:
                self.req.result = self.acc  # type: ignore[attr-defined]
            self.req.complete()
        return n


def ibcast_adapt(comm, buf, root: int = 0) -> Request:
    """Event-driven adaptive-segmentation broadcast (returns a request)."""
    return _AdaptBcast(comm, np.asarray(buf), root, _tag(comm)).start()


def ireduce_adapt(comm, sendbuf, recvbuf=None, op: Op = SUM,
                  root: int = 0) -> Request:
    """Event-driven segmented chain reduce (returns a request; the root's
    ``request.result`` carries the reduction)."""
    return _AdaptReduce(comm, np.asarray(sendbuf), recvbuf, op, root,
                        _tag(comm)).start()


class AdaptModule(CollModule):
    """ibcast/ireduce via the event-driven engine (wins selection only
    when coll_adapt_priority is raised above the nbc schedules)."""

    def ibcast(self, comm, buf, root: int = 0):
        return ibcast_adapt(comm, buf, root)

    def ireduce(self, comm, sendbuf, recvbuf=None, op: Op = SUM,
                root: int = 0):
        return ireduce_adapt(comm, sendbuf, recvbuf, op, root)


@component("coll", "adapt", priority=5)
class AdaptColl(Component):
    name = "adapt"

    def query(self, comm):
        return int(_var.get("coll_adapt_priority", 5)), AdaptModule()
