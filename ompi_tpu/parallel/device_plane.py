"""Multi-process device plane — rank-per-chip wiring (north star).

The reference's process model is one OS process per rank, bound to its
device by the launcher (PRRTE binding, ompi/runtime/ompi_rte.c:536). JAX's
single-controller mode (one process owns the whole mesh) is the opposite;
the north star (BASELINE.json) requires the MPI model: every tpurun rank is
its own process owning its own chip(s), and device collectives run across
processes over ICI.

This module bridges the two control planes: the ompi_tpu bootstrap (modex/
fence — our PMIx) elects and distributes the JAX coordination-service
address, then ``jax.distributed.initialize`` wires PJRT's cross-process
runtime. After ``init_device_plane(ctx)``:

  * ``jax.devices()`` spans every rank's chips (local + proxies);
  * a ``Mesh`` over them with ``DeviceComm.from_local``/``to_local`` gives
    MPI-shaped device collectives where each rank contributes its own rows
    — the multi-process analog of the single-controller ``from_ranks``;
  * compiled collectives execute as one SPMD program per rank, riding ICI
    on TPU pods (gloo on CPU hosts — the test fabric).

Chip pinning is the launcher's job (tpurun --chips-per-rank sets libtpu's
per-process chip variables per rank; --device-plane cpu gives the
1-device-per-process CPU fabric for tests), mirroring how PRRTE owns
binding.
"""

from __future__ import annotations

import os
import socket
from typing import Optional

_initialized = False


def _pick_port() -> int:
    # TOCTOU caveat: the port is free when probed, bound by the JAX
    # coordination service shortly after — another process could snipe it
    # in between (rare; manifests as a failed initialize and a failed job,
    # which the launcher surfaces). jax.distributed offers no bind-to-0 +
    # report-back path, so a probe is the practical option.
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def init_device_plane(ctx, coordinator: Optional[str] = None,
                      timeout_s: int = 60) -> None:
    """Wire JAX's multi-process runtime from the bootstrap control plane.

    Must run before the first JAX backend use in this process (the same
    constraint jax.distributed.initialize documents). Idempotent per
    process. Rank 0 hosts the coordination service; its address travels
    through the modex (≙ how PMIx distributes wire-up info at
    instance.c:529-596).
    """
    global _initialized
    if _initialized:
        return
    import jax

    # device-plane identity is WORLD-relative: a spawned child job elects
    # its own coordinator (its lowest world rank) and numbers processes by
    # world position, so process_id ∈ [0, num_processes) holds even though
    # global ranks start at WORLD_BASE
    members = list(getattr(ctx, "world_ranks", range(ctx.size)))
    pos = members.index(ctx.rank)
    if coordinator is None:
        if pos == 0:
            host = os.environ.get("OMPI_TPU_COORD", "127.0.0.1:0"
                                  ).rpartition(":")[0] or "127.0.0.1"
            coordinator = f"{host}:{_pick_port()}"
            ctx.bootstrap.put("jax_coordinator", coordinator)
        else:
            coordinator = str(ctx.bootstrap.get(members[0],
                                                "jax_coordinator",
                                                timeout=timeout_s))
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=ctx.size,
        process_id=pos,
        initialization_timeout=timeout_s,
    )
    _initialized = True


def device_plane_active() -> bool:
    return _initialized
