"""The program's own timed regions, for the per-layer metrics that split
its host path (``ompi_tpu.trace.region``; docs/observability.md).

``ompi_tpu.trace.regions()`` holds count, total and self time of every
region that closed while a profiler session recorded, so in a ``--trace 1``
run it covers the measured window, and ``ompi.compile`` counts the backend
compiles and compile-cache loads in it.  A run without a traced window
(no device plane in its trace), or a program without that table, gives
None here, and so does every reader of it.
"""

from __future__ import annotations

COLL_PARTS = ("decide", "audit", "launch")


def table(run):
    if not (run.get("trace") or {}).get("devices"):
        return None
    from ompi_tpu import trace
    read = getattr(trace, "regions", None)
    return read() if read is not None else None


def coll_split_us(run):
    """Microseconds per collective call over every ``ompi.coll.<op>`` call
    of the window: ``hooks`` (the ops' self time: the dispatch wrapper's
    own checks and plane hooks, and the module entry around the three
    parts) and each of ``decide``, ``audit`` and ``launch``; None without
    a call."""
    from ompi_tpu.coll.framework import COLL_FUNCTIONS

    t = table(run)
    if not t:
        return None
    ops = [v for n, v in t.items()
           if n.startswith("ompi.coll.") and n[10:] in COLL_FUNCTIONS]
    calls = sum(v["count"] for v in ops)
    if not calls:
        return None
    out = {"hooks": sum(v["self_s"] for v in ops) / calls * 1e6}
    for part in COLL_PARTS:
        r = t.get(f"ompi.coll.{part}")
        out[part] = r["total_s"] / calls * 1e6 if r else None
    return out


def coll_us(run, part: str):
    split = coll_split_us(run)
    return split[part] if split else None


def ms_per(run, time_of: str, count_of: str, self_time: bool = False,
           plus: tuple = ()):
    """Milliseconds of region ``time_of`` (its self time with
    ``self_time``), plus those of ``plus``, per closing of region
    ``count_of``; None where ``count_of`` never closed."""
    t = table(run)
    if not t or not t.get(count_of, {}).get("count"):
        return None
    key = "self_s" if self_time else "total_s"
    total = sum(t[n][key] for n in (time_of,) + tuple(plus) if n in t)
    return total / t[count_of]["count"] * 1e3


def compiles(run):
    """Backend compiles and compile-cache loads in the window (0 where
    the program keeps the table and none happened)."""
    t = table(run)
    if t is None:
        return None
    return t.get("ompi.compile", {}).get("count", 0)
