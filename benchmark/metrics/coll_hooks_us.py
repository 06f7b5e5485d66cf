"""Microseconds per collective call, over every call of the window, of
the self time of ``ompi.coll.<op>``: the dispatch wrapper's revoked check,
SPC counters and plane gates, and the module entry's buffer checks
(``check_addr``) around decide, audit and launch (program regions)."""

from benchmark import regions


def read(run):
    return regions.coll_us(run, "hooks")
