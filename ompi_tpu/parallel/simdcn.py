"""parallel/simdcn — the simulated-DCN delay shim.

A single-process CPU mesh is one flat ICI plane: every arm sees the same
fabric, so the hierarchical (`hier`) arm — whose entire value is moving
n_inner× fewer bytes over the SLOW plane — can never win a wall-clock
sweep in CI.  This shim makes the simulated slow plane cost something:
when ``topo_sim_dcn_us_per_mib`` is nonzero, every audited device
collective is charged a host-side sleep proportional to the bytes its
geometry moves across a simulated DCN boundary (axes named by
``topo_sim_dcn_axes``, the same override ``classify_axes`` and the
traffic plane's edge classifier honor).

The model is deliberately simple — a bandwidth-proportional penalty with
no contention — because its only job is to order arms the way a real
two-tier fabric would: flat arms pay for their full cross-boundary
share, `hier` pays only for the scattered outer stage, `hier+quant` for
a quarter of that.  The shim sits in coll/xla's audit path (one branch
when disabled) so `coll_tune --device` hier sweeps and the plane-keyed
perf-ledger cells all see the same skew.
"""

from __future__ import annotations

import time
from typing import Dict, Tuple, Union

import numpy as np

from ..core import var as _var
from .mesh import classify_axes, sim_dcn_axes

AxisLike = Union[str, Tuple[str, ...]]

# ring-geometry DCN fraction per (mesh id, axis) — meshes are long-lived
# and few (same bound rationale as traffic/planes._PROC_CACHE)
_FRAC_CACHE: Dict[Tuple[int, AxisLike], float] = {}
_FRAC_CACHE_MAX = 32


def axis_tuple(axis: AxisLike) -> Tuple[str, ...]:
    """Normalize a DeviceComm axis (one name or a tuple) to a tuple."""
    return tuple(axis) if isinstance(axis, (tuple, list)) else (axis,)


def us_per_mib() -> float:
    """Configured shim cost (0.0 = shim off)."""
    try:
        return float(_var.get("topo_sim_dcn_us_per_mib", 0.0))
    except (TypeError, ValueError):
        return 0.0


def ring_dcn_fraction(mesh, axis: AxisLike) -> float:
    """Fraction of the axis ring's hops that cross a DCN boundary.

    The ring runs over the flattened (row-major) product of the named
    axes — the same order a flat collective over a tuple axis uses — and
    a hop crosses DCN when the coordinate changes along any
    DCN-classified axis (real process boundaries or the sim override).
    """
    key = (id(mesh), axis_tuple(axis), tuple(sorted(sim_dcn_axes())))
    got = _FRAC_CACHE.get(key)
    if got is not None:
        return got
    axes = axis_tuple(axis)
    kinds = classify_axes(mesh)
    sizes = [int(mesh.shape[a]) for a in axes]
    n = int(np.prod(sizes))
    if n < 2:
        frac = 0.0
    else:
        dcn_dims = [k for k, a in enumerate(axes) if kinds.get(a) == "dcn"]
        if not dcn_dims:
            frac = 0.0
        else:
            cross = 0
            for i in range(n):
                ci = np.unravel_index(i, sizes)
                cj = np.unravel_index((i + 1) % n, sizes)
                if any(ci[k] != cj[k] for k in dcn_dims):
                    cross += 1
            frac = cross / n
    if len(_FRAC_CACHE) >= _FRAC_CACHE_MAX:
        _FRAC_CACHE.clear()
    _FRAC_CACHE[key] = frac
    return frac


def penalty_us(dcn_bytes: int, us_mib: float = None) -> float:
    """Modeled delay for ``dcn_bytes`` crossing the simulated boundary."""
    us = us_per_mib() if us_mib is None else us_mib
    if us <= 0 or dcn_bytes <= 0:
        return 0.0
    return dcn_bytes / float(1 << 20) * us


def charge(dcn_bytes: int) -> None:
    """Sleep the modeled delay (no-op when the shim is off)."""
    us = penalty_us(int(dcn_bytes))
    if us > 0:
        time.sleep(us * 1e-6)


def clear_cache() -> None:
    """Test helper: the fraction cache keys on mesh identity, but the
    classification behind it moves with the sim vars."""
    _FRAC_CACHE.clear()
