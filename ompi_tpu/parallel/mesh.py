"""Device mesh construction and topology mapping.

The TPU-native answer to the reference's process/topology layer: where Open
MPI wires COMM_WORLD onto hosts/NICs via PRRTE + hwloc (SURVEY.md §3.4), a
TPU job wires its ranks onto a slice's chips via a named-axis
``jax.sharding.Mesh``. Axis names carry the parallelism intent (dp/fsdp/tp/
sp/pp/ep), and axis *order* encodes the ICI-vs-DCN hierarchy the same way
coll/han splits intra-node vs inter-node communicators
(ompi/mca/coll/han/coll_han_subcomms.c): the innermost axes should map onto
ICI neighbors, the outermost onto DCN (process) boundaries.

``jax.make_mesh`` already performs topology-aware device ordering on TPU;
these helpers add the job-level conventions (standard axis names, hierarchy
classification, per-axis subcommunicator views).
"""

from __future__ import annotations

from typing import Dict, FrozenSet, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import var as _var

# conventional axis names, outer→inner (DCN-most → ICI-most)
STANDARD_AXES = ("dp", "fsdp", "pp", "ep", "sp", "tp")

# the simulated DCN plane: a single-process CPU test mesh has no real
# slice boundaries, so the two-tier decision layer (hier arm, plane-keyed
# rules, per-plane traffic rollup) would be untestable before multi-slice
# hardware.  Naming axes here force-classifies them as 'dcn' everywhere
# the topology is consulted (classify_axes, traffic/planes.plane_fn); the
# companion delay shim (parallel/simdcn) charges wall-clock per byte that
# crosses the simulated boundary so arm sweeps see a skewed fabric.
_var.register("topo", "sim", "dcn_axes", "", type=str, level=4,
              help="Comma-separated mesh axis names to force-classify as "
                   "DCN (simulated slow plane for single-process test "
                   "meshes; empty = infer from process boundaries).")
_var.register("topo", "sim", "dcn_us_per_mib", 0.0, type=float, level=4,
              help="Simulated-DCN delay shim: host-side microseconds "
                   "charged per MiB that crosses a simulated DCN "
                   "boundary (parallel/simdcn; 0 = shim off).")


def sim_dcn_axes() -> FrozenSet[str]:
    """Axis names the sim-DCN override forces to 'dcn' (empty = off)."""
    raw = str(_var.get("topo_sim_dcn_axes", "") or "")
    return frozenset(a.strip() for a in raw.split(",") if a.strip())


def make_mesh(axes: Dict[str, int],
              devices: Optional[Sequence] = None) -> Mesh:
    """Create a named mesh, e.g. ``make_mesh({"dp": 2, "tp": 4})``.

    Axis sizes must multiply to the device count; pass ``-1`` for at most one
    axis to absorb the remainder (like a reshape). Axes are *Auto* (GSPMD
    infers intermediate shardings from annotations — the classic
    annotate-and-let-XLA-insert-collectives mode); shard_map programs enter
    Manual mode on top of this as usual.
    """
    devs = list(devices) if devices is not None else jax.devices()
    names, sizes = list(axes.keys()), list(axes.values())
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = len(devs) // known
    total = int(np.prod(sizes))
    if total != len(devs):
        raise ValueError(
            f"mesh {dict(zip(names, sizes))} needs {total} devices, "
            f"have {len(devs)}")
    auto = {"axis_types": (jax.sharding.AxisType.Auto,) * len(names)}
    if devices is None:
        return jax.make_mesh(tuple(sizes), tuple(names), **auto)
    return Mesh(np.asarray(devs).reshape(sizes), tuple(names), **auto)


def axis_index_of(mesh: Mesh, axis: str, device) -> int:
    """Which position along `axis` a device occupies."""
    coords = np.argwhere(mesh.devices == device)
    return int(coords[0][mesh.axis_names.index(axis)])


def classify_axes(mesh: Mesh) -> Dict[str, str]:
    """Classify each axis as 'ici' (within a process/slice) or 'dcn'
    (crosses process boundaries) — the han intra/inter split. An axis is
    'dcn' when moving along it changes the process index on ANY line of
    the mesh, not just the first one (the old first-line probe missed
    meshes whose process boundary only shows up at nonzero coordinates
    of the other axes). On CPU test meshes everything is 'ici' unless
    the ``topo_sim_dcn_axes`` override names a simulated slow plane."""
    out = {}
    sim = sim_dcn_axes()
    devs = np.asarray(mesh.devices)
    procs = np.frompyfunc(
        lambda d: int(getattr(d, "process_index", 0)), 1, 1)(
        devs).astype(np.int64)
    for i, name in enumerate(mesh.axis_names):
        if name in sim:
            out[name] = "dcn"
            continue
        moved = np.moveaxis(procs, i, 0)
        out[name] = "dcn" if bool((moved != moved[:1]).any()) else "ici"
    return out


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def sharded(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def shard_leading(mesh: Mesh, axis: str) -> NamedSharding:
    """Shard dim 0 over `axis` — the canonical layout for per-rank blocks."""
    return NamedSharding(mesh, P(axis))
