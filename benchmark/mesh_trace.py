"""The collectives of a data- and tensor-parallel step in a profiler trace:
the mesh axes each collective op runs over, the device time of each axis's
collectives, and how much of a chip's window a collective holds alone.

A device op's event name is its HLO text.  Its axes come from the replica
groups there (``replica_groups={{0,2},{1,3}}``, or the iota form
``replica_groups=[2,2]<=[2,2]T(1,0)``) or, for a permute, its
``source_target_pairs``: logical device ids, which index the mesh's
devices in order, so the axes along which a group's members differ are the
op's axes.  Where the text names no group (an async ``-done``, an
all-to-all printed without them), the axes that the driver read from the
compiled step under the instruction's name, or under the name of the
``-start`` that a ``-done`` completes, are used (``records["coll_axes"]``).
"""

from __future__ import annotations

import re

import numpy as np

from benchmark import trace_reduce

_GROUPS_LIST = re.compile(r"replica_groups=\{((?:\{[\d,]*\},?)*)\}")
_GROUPS_IOTA = re.compile(
    r"replica_groups=\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_PAIRS = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")
_DONE_OF = re.compile(r"-done\(.*%([\w.\-]+)\)")


def _ints(s: str) -> list:
    return [int(x) for x in s.split(",") if x]


def groups_of(name: str):
    """Device groups named in one op's HLO text, or None."""
    m = _GROUPS_IOTA.search(name)
    if m:
        ids = np.arange(int(np.prod(_ints(m.group(2))))).reshape(
            _ints(m.group(2)))
        if m.group(3):
            ids = ids.transpose(_ints(m.group(3)))
        return ids.reshape(_ints(m.group(1))).tolist()
    m = _GROUPS_LIST.search(name)
    if m and m.group(1):
        return [_ints(g) for g in re.findall(r"\{([\d,]*)\}", m.group(1))]
    m = _PAIRS.search(name)
    if m:
        return [[int(a), int(b)]
                for a, b in re.findall(r"\{(\d+),(\d+)\}", m.group(1))]
    return None


def op_axes(name: str, records: dict):
    """"tp", "dp", "dp+tp" (mesh order) or None for one collective op."""
    axes, shape = records.get("mesh_axes"), records.get("mesh_shape")
    if not axes:
        return None
    groups = groups_of(name)
    if groups is None:
        # an async "-done" names no group: it is its start's, by name
        known = records.get("coll_axes", {})
        instr = name.split(" = ", 1)[0].lstrip("%")
        start = _DONE_OF.search(name)
        return known.get(instr, known.get(start.group(1)) if start else None)
    varying = set()
    for g in groups:
        coords = np.array(np.unravel_index(np.asarray(g), shape))
        varying |= {i for i in range(len(shape))
                    if len(set(coords[i].tolist())) > 1}
    return "+".join(axes[i] for i in sorted(varying))


def axis_time(tr: dict, records: dict, axis: str) -> float:
    """Device seconds, averaged over devices, of the collective ops inside
    the window that run over ``axis``."""
    return trace_reduce.op_time(
        tr, lambda n: trace_reduce.is_collective(n)
        and op_axes(n, records) == axis)


def _length(ivs) -> float:
    return sum(e - s for s, e in ivs)


def exposed_share(tr: dict):
    """Per chip, the time in the window in which a collective op runs and
    no other op does, over the window; the mean over the chips that ran
    anything (None where none did)."""
    used = [evs for evs in tr["devices"].values() if evs]
    if not used:
        return None
    lo, hi = trace_reduce.window_of(tr)
    shares = []
    for evs in used:
        coll = trace_reduce._union(trace_reduce._clip(
            [(s, e) for s, e, n in evs if trace_reduce.is_collective(n)],
            lo, hi))
        other = trace_reduce._union(trace_reduce._clip(
            [(s, e) for s, e, n in evs
             if not trace_reduce.is_collective(n)], lo, hi))
        both = trace_reduce._union(coll + other)
        shares.append((_length(both) - _length(other)) / (hi - lo))
    return sum(shares) / len(shares)


def ici_share(run: dict, axis: str, ici_bytes_per_s: float):
    """Percent of the ICI peak: the yardstick's bus bytes over ``axis``
    per step (``benchmark.comm_bytes``) times the window's steps, over the
    device time of the collectives over that axis (None where nothing can
    be read)."""
    r = run["records"]
    if axis not in r.get("bus_bytes", {}) or not r.get("steps"):
        return None
    t = axis_time(run["trace"], r, axis)
    if not t:
        return None
    return 100.0 * r["bus_bytes"][axis] * r["steps"] / t / ici_bytes_per_s
