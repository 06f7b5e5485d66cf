"""Shared-memory transport over native SPSC rings (≙ opal/mca/btl/sm).

The reference's fastest intra-node byte transport is the shared-memory BTL:
per-peer mmap'd segments with lock-free "fast box" mailboxes
(btl_sm_fbox.h:31-35). Here the ring machinery is native C++
(native/shmbox.cpp) and this component owns the lifecycle:

  * at init each rank *creates* one directed ring per peer for its inbound
    side (peer→me) and publishes its host identity through the modex;
  * senders lazily open the (me→peer) ring after the startup fence;
  * per-channel FIFO gives the non-overtaking order p2p relies on;
  * a full ring parks frames on a pending queue flushed from progress() —
    ordering is preserved because new sends append behind pending ones.

Selection: priority 50 — above tcp (10) for same-host peers, below self
(100) for loopback. ``open()`` disqualifies the component when the native
library can't be built, the same way reference components disqualify
themselves in query (e.g. no /dev/shm → btl/sm out).
"""

from __future__ import annotations

import ctypes
import os
import socket
from collections import deque
from typing import Any, Dict, Optional

from .. import native
from ..core import var as _var
from ..core.component import component
from . import transport as T
from . import wire

_var.register("transport", "shm", "ring_size", 1 << 22, type=int, level=4,
              help="Bytes per directed shared-memory ring channel. 4 MiB "
                   "default: the fragment path then moves 1 MiB chunks "
                   "with few drain handoffs (BASELINE.md, host p2p path).")


def _host_key() -> str:
    """Shared-memory host identity: hostname ALONE merges distinct
    containers/VMs that default to the same name (e.g. 'localhost'), so
    qualify with the kernel boot id — equal only for processes under one
    kernel, i.e. exactly the processes that can share /dev/shm."""
    try:
        with open("/proc/sys/kernel/random/boot_id") as fh:
            boot = fh.read().strip()
    except OSError:
        boot = ""
    return f"{socket.gethostname()}#{boot}"


def _chan_name(job: str, src: int, dst: int) -> bytes:
    safe = "".join(c for c in str(job) if c.isalnum())[-24:]
    return f"/otpu_{safe}_{src}to{dst}".encode()


def _bell_name(job: str, rank: int) -> bytes:
    safe = "".join(c for c in str(job) if c.isalnum())[-24:]
    return f"/otpu_{safe}_bell{rank}".encode()


@component("transport", "shm", priority=50)
class ShmTransport(T.Transport):
    name = "shm"
    bandwidth = 100          # striping weight (measured ~3 GB/s class)

    def __init__(self) -> None:
        super().__init__()
        self.rank = -1
        self.size = 0
        self._bootstrap = None
        self._lib = None
        self._rx: Dict[int, int] = {}        # peer → handle (peer→me ring)
        self._tx: Dict[int, int] = {}        # peer → handle (me→peer ring)
        self._pending: Dict[int, deque] = {}  # peer → frames awaiting space
        self._hosts: Dict[int, Optional[str]] = {}
        self._ring = int(_var.get("transport_shm_ring_size", 1 << 22))
        self._bell = -1
        self._tx_bells: Dict[int, int] = {}
        # cap fragments so one frame can never exceed half a ring
        self.max_send_size = min(self.max_send_size, self._ring // 4)
        # reusable rx frame buffer sized to the ring: payloads are capped at
        # max_send_size but pickled control headers (osc/ft dict headers)
        # are unbounded, and any frame the writer accepted fits the ring —
        # so ring-sized is the provably-sufficient choice
        self._rxbuf = (ctypes.c_uint8 * self._ring)()
        # cast: a raw ctypes-array view carries format '<B', which
        # memoryview refuses to index/slice-read; 'B' is the plain bytes view
        self._rxview = memoryview(self._rxbuf).cast("B")
        self._rxbody = ctypes.c_uint32(0)
        # native-engine adoption (p2p/pmlx.py): when set, the C++ mx engine
        # owns this transport's rings — send() routes frames through the
        # engine's per-peer FIFO and progress() defers to mx_progress
        self._mx = None                       # (lib, engine handle)
        self._mx_tx_wired: set = set()

    def open(self) -> bool:
        return native.available()

    def init_job(self, bootstrap) -> None:
        self._lib = native.load()
        self.rank, self.size = bootstrap.rank, bootstrap.size
        self._bootstrap = bootstrap
        bootstrap.put("transport_shm_host", _host_key())
        for peer in range(self.size):
            if peer == self.rank:
                continue
            h = self._lib.shmbox_attach(
                _chan_name(bootstrap.job_id, peer, self.rank), self._ring, 1)
            if h < 0:
                # a create-attach can only fail for environmental reasons
                # (/dev/shm exhausted, name collision) — failing init is the
                # clean outcome; silently skipping would let senders crash
                # later and would falsify the ring-ready key's guarantee
                raise RuntimeError(
                    f"shm transport: cannot create rx ring from rank {peer}")
            self._rx[peer] = h
        # our doorbell: senders post it after writing into an empty ring so
        # an idle_wait()-blocked receiver wakes in µs, not a scheduler
        # quantum (≙ mpi_yield_when_idle for oversubscribed hosts)
        self._bell = self._lib.doorbell_open(
            _bell_name(bootstrap.job_id, self.rank), 1)
        # published AFTER the rx rings exist: dynamic spawn waits on this
        # key before letting anyone send to us (ring creator = receiver)
        bootstrap.put("transport_shm_rings", True)

    def add_peers(self, new_size: int) -> None:
        """Dynamic spawn grew the global rank space: create+attach rx rings
        for the new peers (the receiver is the ring creator, so this must
        run before a new peer's first send to us — dpm.spawn sequences it
        via the ready key)."""
        for peer in range(self.size, new_size):
            h = self._lib.shmbox_attach(
                _chan_name(self._bootstrap.job_id, peer, self.rank),
                self._ring, 1)
            if h < 0:
                raise RuntimeError(
                    f"shm transport: cannot create rx ring from rank {peer}")
            self._rx[peer] = h
            if self._mx is not None:
                self._mx[0].mx_add_rx(self._mx[1], peer, h)
        self.size = max(self.size, new_size)

    def reachable(self, peer: int) -> bool:
        if peer == self.rank or not (0 <= peer < self.size):
            return False
        host = self._hosts.get(peer, False)
        if host is False:
            try:
                host = self._bootstrap.get(peer, "transport_shm_host")
            except Exception:
                host = None
            self._hosts[peer] = host
        return host == _host_key()

    # -- tx -----------------------------------------------------------------

    def _tx_handle(self, peer: int) -> int:
        h = self._tx.get(peer)
        if h is None:
            h = self._lib.shmbox_attach(
                _chan_name(self._bootstrap.job_id, self.rank, peer), 0, 0)
            if h < 0:
                raise RuntimeError(
                    f"shm transport: cannot open channel to rank {peer}")
            self._tx[peer] = h
        return h

    def _try_write(self, peer: int, hdr: bytes, payload) -> bool:
        h = self._tx_handle(peer)
        # bytes pass straight through the c_char_p prototypes (zero copy);
        # other buffer shapes (memoryview/ndarray slices) convert once
        if not isinstance(payload, bytes):
            payload = bytes(payload)
        rc = self._lib.shmbox_write(h, hdr, len(hdr), payload, len(payload))
        if rc == -2:
            raise ValueError(
                f"frame of {len(hdr)}+{len(payload)} bytes exceeds shm ring "
                f"capacity {self._ring} (raise transport_shm_ring_size)")
        if rc == 1:      # ring was empty → peer may be blocked on its bell
            bell = self._tx_bells.get(peer)
            if bell is None:
                bell = self._lib.doorbell_open(
                    _bell_name(self._bootstrap.job_id, peer), 0)
                self._tx_bells[peer] = bell
            self._lib.doorbell_post(bell)
        return rc >= 0

    def adopt_mx(self, lib, eng: int) -> None:
        """Hand the rings to the native engine: rx rings registered for
        C++ draining; tx rings wired lazily at first send."""
        self._mx = (lib, eng)
        for peer, h in self._rx.items():
            lib.mx_add_rx(eng, peer, h)

    def _mx_wire_tx(self, peer: int) -> None:
        lib, eng = self._mx
        h = self._tx_handle(peer)
        bell = self._tx_bells.get(peer)
        if bell is None:
            bell = self._lib.doorbell_open(
                _bell_name(self._bootstrap.job_id, peer), 0)
            self._tx_bells[peer] = bell
        lib.mx_set_peer_tx(eng, peer, h, bell)
        self._mx_tx_wired.add(peer)

    def send(self, peer: int, tag: int, header: Dict[str, Any],
             payload: bytes) -> None:
        hdr = wire.encode(tag, header)
        if self._mx is not None:
            if peer not in self._mx_tx_wired:
                self._mx_wire_tx(peer)
            if not isinstance(payload, bytes):
                payload = bytes(payload)
            rc = self._mx[0].mx_tx(self._mx[1], peer, hdr, len(hdr),
                                   payload, len(payload))
            if rc == -2:
                raise ValueError(
                    f"frame of {len(hdr)}+{len(payload)} bytes exceeds shm "
                    f"ring capacity {self._ring} (raise "
                    f"transport_shm_ring_size)")
            if rc == -3:
                raise RuntimeError(
                    f"shm ring to rank {peer} is dead (handle closed)")
            return
        q = self._pending.get(peer)
        if q:
            q.append((hdr, payload))    # keep FIFO behind parked frames
            return
        if not self._try_write(peer, hdr, payload):
            self._pending.setdefault(peer, deque()).append((hdr, payload))

    # -- rx / progress ------------------------------------------------------

    def progress(self) -> int:
        if self._mx is not None:
            return 0        # the native pml's drain loop owns the rings
        n = 0
        for peer, q in list(self._pending.items()):
            while q:
                hdr, payload = q[0]
                if not self._try_write(peer, hdr, payload):
                    break
                q.popleft()
                n += 1
        rxbuf, rxview, body = self._rxbuf, self._rxview, self._rxbody
        read_frame = self._lib.shmbox_read_frame
        cap = len(rxbuf)
        for peer, h in self._rx.items():
            while True:
                # single-call pop into the reusable buffer (no peek
                # round-trip, no per-frame allocation)
                hlen = read_frame(h, rxbuf, cap, body)
                if hlen == -2:
                    # frame larger than rxbuf: tail did NOT advance, so
                    # breaking would re-hit it forever — a protocol bug
                    # (writers cap frames at max_send_size, headers at the
                    # rxbuf slack) must be loud, not a silent wedge
                    raise RuntimeError(
                        f"shm rx frame from rank {peer} exceeds the "
                        f"{cap}-byte frame buffer (protocol bug: writer "
                        f"must respect max_send_size)")
                if hlen < 0:
                    break
                total = body.value
                tag, header = wire.decode(rxview[:hlen])
                # the payload must outlive the reusable buffer (matching
                # may park it on the unexpected queue) → one owned copy
                self.deliver(peer, tag, header, rxview[hlen:total].tobytes())
                n += 1
        return n

    def pending_count(self, exclude: frozenset = frozenset()) -> int:
        if self._mx is not None:
            lib, eng = self._mx
            if not exclude:
                return lib.mx_pending_tx(eng, -1)
            return sum(lib.mx_pending_tx_peer(eng, p)
                       for p in self._mx_tx_wired if p not in exclude)
        return sum(len(q) for p, q in self._pending.items()
                   if p not in exclude)

    def _has_parked(self) -> bool:
        if self._mx is not None:
            return self._mx[0].mx_pending_tx(self._mx[1], -1) > 0
        return any(self._pending.values())

    def idle_wait(self, timeout: float) -> None:
        """Block until a sender rings our doorbell (or timeout) — called by
        the progress engine when a wait loop goes idle."""
        if self._has_parked():
            # Our own parked frames need progress, not sleep — but the
            # peer needs the core to drain its ring, so cede it instead of
            # hot-spinning (the caller's loop re-enters progress right away).
            import time
            time.sleep(0)
            return
        if self._bell < 0:      # no doorbell: plain sleep beats a hot spin
            import time
            time.sleep(timeout)
            return
        self._lib.doorbell_wait(self._bell, int(timeout * 1e6))

    def finalize(self) -> None:
        for h in list(self._tx.values()) + list(self._rx.values()):
            self._lib.shmbox_close(h)
        self._tx.clear()
        self._rx.clear()
        for bell in self._tx_bells.values():
            self._lib.doorbell_close(bell, None)
        self._tx_bells.clear()
        if self._bell >= 0:
            self._lib.doorbell_close(
                self._bell, _bell_name(self._bootstrap.job_id, self.rank))
            self._bell = -1
