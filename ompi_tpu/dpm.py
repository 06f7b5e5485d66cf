"""Dynamic process management: spawn / open_port / connect / accept.

≙ ompi/dpm/dpm.c — the MPI-2 dynamic-process chapter, built on the control
plane the way the reference builds on PMIx:

  * ``spawn``: the parent communicator collectively launches ``maxprocs``
    new processes. The coordinator reserves a block of new GLOBAL ranks in
    its own fence group (Coordinator GROW ≙ PMIx_Spawn's slot request), the
    root fork/execs the children with the standard env contract plus
    WORLD_BASE/WORLD_SIZE (children get their OWN COMM_WORLD — MPI
    semantics), every parent widens its transports to the grown rank space,
    and both sides assemble the same intercommunicator; children reach it
    via :func:`get_parent`.
  * ``open_port``/``connect``/``accept``: client/server rendezvous WITHIN a
    running global rank space (two disjoint communicators of the same job
    or of a parent+spawned-job family), carried over control-plane events —
    the reference's ports are PMIx-published strings the same way
    (dpm.c MPI_Open_port). Cross-launcher connects (two independent tpurun
    invocations) are out of scope: their rank spaces collide by
    construction, exactly why the reference needs a PMIx server mesh there.

Sequencing guarantee for shm: ring creators are receivers, so children may
only send to parents after every parent ran ``add_peers``; spawn's root
publishes the ``dpm_ready`` key after the parent-side barrier, and
``get_parent`` blocks on it before returning.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import Optional, Sequence

import numpy as np

from .comm import Communicator, Group
from .control.launch import TPU_PROCESS_VARS

_SPAWN_CID_BASE = 1 << 44        # intercomm cids for spawn, out of all ranges
_PORT_CID_BASE = 1 << 45         # intercomm cids for connect/accept


def spawn(comm: Communicator, command: Sequence[str], maxprocs: int,
          root: int = 0, env_extra: Optional[dict] = None,
          info=None) -> Communicator:
    """MPI_Comm_spawn: collective over ``comm``; returns the parent side of
    the parent↔children intercommunicator. Honored MPI_Info hints: ``wdir``
    (children's working directory), ``path`` (prepended to the child's
    PATH); others are advisory."""
    ctx = comm.ctx
    if comm.rank == root:
        base, gid = ctx.bootstrap.grow(maxprocs)
        meta = np.array([base, gid], np.int64)
    else:
        meta = np.zeros(2, np.int64)
    meta = np.asarray(comm.coll.bcast(comm, meta, root=root))
    base, gid = int(meta[0]), int(meta[1])
    total = base + maxprocs
    children = list(range(base, base + maxprocs))

    ok = np.zeros(1, np.int64)
    if comm.rank == root:
        try:
            cmd = list(command)
            if cmd[0].endswith(".py"):
                cmd = [sys.executable] + cmd
            coord = ctx.bootstrap.coord_address
            for i, child in enumerate(children):
                env = dict(os.environ)
                # chip and CPU binding do NOT inherit: the children are a
                # new job placement the caller controls via env_extra
                # (≙ the MPI_Info keys of MPI_Comm_spawn) — inheriting the
                # parent's cpuset would pile every child onto one core
                for k in TPU_PROCESS_VARS:
                    env.pop(k, None)
                env.pop("OMPI_TPU_BIND_CPUS", None)
                if env_extra:
                    env.update(env_extra)
                env.update({
                    "OMPI_TPU_RANK": str(child),
                    "OMPI_TPU_SIZE": str(total),
                    "OMPI_TPU_COORD": f"{coord[0]}:{coord[1]}",
                    "OMPI_TPU_JOB": ctx.bootstrap.job_id,
                    "OMPI_TPU_LOCAL_RANK": str(i),
                    "OMPI_TPU_NUM_LOCAL": str(maxprocs),
                    "OMPI_TPU_WORLD_BASE": str(base),
                    "OMPI_TPU_WORLD_SIZE": str(maxprocs),
                    "OMPI_TPU_SPAWN_GROUP": str(gid),
                    "OMPI_TPU_PARENT_RANKS": ",".join(
                        map(str, comm.group.world_ranks)),
                    "OMPI_TPU_PARENT_ROOT": str(
                        comm.group.world_of_rank(root)),
                    "OMPI_TPU_PARENT_CID": str(_SPAWN_CID_BASE | gid),
                })
                wdir = info.get("wdir") if info is not None else None
                if info is not None and info.get("path"):
                    env["PATH"] = (info.get("path") + os.pathsep
                                   + env.get("PATH", ""))
                subprocess.Popen(cmd, env=env, cwd=wdir)
            # children's ring-ready keys appear once their shm rx rings
            # exist; waiting here closes the add_peers/first-send race
            # (only the shm transport publishes them)
            if any(t.name == "shm" for t in ctx.layer.transports):
                for child in children:
                    ctx.bootstrap.get(child, "transport_shm_rings",
                                      timeout=60.0)
            ok[0] = 1
        except Exception as exc:   # surface collectively, not a hang
            ok[0] = 0
            err = exc
    ok = np.asarray(comm.coll.bcast(comm, ok, root=root))
    if not int(ok[0]):
        if comm.rank == root:
            raise RuntimeError(f"spawn failed to launch: {err!r}") from err
        raise RuntimeError("spawn failed to launch (see root rank)")
    comm.coll.barrier(comm)
    ctx.layer.add_peers(total)       # every parent can now serve children
    comm.coll.barrier(comm)
    if comm.rank == root:
        ctx.bootstrap.put(f"dpm_ready:{gid}", True)   # children may send
    return comm._inherit(Communicator(
        ctx, Group(list(comm.group.world_ranks)), _SPAWN_CID_BASE | gid,
        f"{comm.name}.spawn{gid}", remote_group=Group(children),
        local_comm=comm))


def get_parent(ctx) -> Optional[Communicator]:
    """MPI_Comm_get_parent: on a spawned child, the child side of the spawn
    intercommunicator (None in a non-spawned process). Blocks until the
    parents finished widening their transports."""
    ranks = os.environ.get("OMPI_TPU_PARENT_RANKS")
    if not ranks:
        return None
    gid = int(os.environ.get("OMPI_TPU_SPAWN_GROUP", "0"))
    parents = [int(r) for r in ranks.split(",")]
    spawn_root = int(os.environ.get("OMPI_TPU_PARENT_ROOT", parents[0]))
    ctx.bootstrap.get(spawn_root, f"dpm_ready:{gid}", timeout=60.0)
    world = ctx.comm_world
    return Communicator(
        ctx, Group(list(world.group.world_ranks)),
        int(os.environ["OMPI_TPU_PARENT_CID"]),
        "parent", remote_group=Group(parents), local_comm=world)


# -- port-based client/server (MPI_Open_port / connect / accept) ------------

def open_port(ctx) -> str:
    """MPI_Open_port: a name the accept side publishes and the connect side
    dials."""
    seq = getattr(ctx, "_dpm_port_seq", 0)
    ctx._dpm_port_seq = seq + 1
    return f"ompi-tpu-port:{ctx.rank}:{seq}"


def accept(port: str, comm: Communicator, root: int = 0,
           timeout: float = 60.0) -> Communicator:
    """MPI_Comm_accept: collective over ``comm``; pairs with one connect()
    on the same port name."""
    return _rendezvous(port, comm, root, timeout, accepting=True)


def connect(port: str, comm: Communicator, root: int = 0,
            timeout: float = 60.0) -> Communicator:
    """MPI_Comm_connect."""
    return _rendezvous(port, comm, root, timeout, accepting=False)


def _rendezvous(port: str, comm: Communicator, root: int, timeout: float,
                accepting: bool) -> Communicator:
    """Both sides' roots exchange (group, cid proposal) via control-plane
    events keyed by the port name; everyone else learns via local bcast.
    cid = max(both proposals) | PORT base — identical on every rank of both
    communicators without a global collective (the comm.py intercomm
    discipline)."""
    ctx = comm.ctx
    me_root = comm.rank == root
    props = np.asarray(comm.coll.allgather(
        comm, np.array([comm._cid_counter], np.int64)))
    my_prop = int(props.max())
    if me_root:
        kind = "acc" if accepting else "con"
        ctx.bootstrap.publish_event({
            "dpm": kind, "port": port, "prop": my_prop,
            "ranks": list(comm.group.world_ranks)})
        other = _wait_event(ctx, port, "con" if accepting else "acc",
                            timeout)
        payload = np.array([other["prop"], len(other["ranks"])]
                           + list(other["ranks"]), np.int64)
    else:
        payload = None
    n = np.zeros(1, np.int64)
    if me_root:
        n[0] = len(payload)
    n = np.asarray(comm.coll.bcast(comm, n, root=root))
    if payload is None:
        payload = np.zeros(int(n[0]), np.int64)
    payload = np.asarray(comm.coll.bcast(comm, payload, root=root))
    remote_prop, rn = int(payload[0]), int(payload[1])
    remote = [int(x) for x in payload[2:2 + rn]]
    cid = _PORT_CID_BASE | max(my_prop, remote_prop)
    with comm._lock:
        comm._cid_counter = max(comm._cid_counter,
                                max(my_prop, remote_prop) + 1)
    return comm._inherit(Communicator(
        ctx, Group(list(comm.group.world_ranks)), cid,
        f"{comm.name}.{'accept' if accepting else 'connect'}",
        remote_group=Group(remote), local_comm=comm))


def _wait_event(ctx, port: str, kind: str, timeout: float) -> dict:
    """Drain control-plane events until the matching port event arrives;
    unrelated events are re-queued for their real consumers."""
    stash = getattr(ctx, "_dpm_events", None)
    if stash is None:
        stash = ctx._dpm_events = []
    deadline = time.monotonic() + timeout
    while True:
        for i, ev in enumerate(stash):
            if ev.get("dpm") == kind and ev.get("port") == port:
                return stash.pop(i)
        for ev in ctx.poll_events():
            if ev.get("dpm"):
                stash.append(ev)
            else:
                # not ours (e.g. the detector's proc_failed events): back
                # onto the context's event backlog so the next
                # ctx.poll_events() caller still sees it
                ctx.push_event(ev)
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"dpm: no peer arrived on port {port!r} within {timeout}s")
        ctx.engine.progress()
        time.sleep(0.002)
