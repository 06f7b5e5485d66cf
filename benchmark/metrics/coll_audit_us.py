"""Microseconds per collective call, over every call of the window, of
``ompi.coll.audit``: ``XlaModule._audit`` (the wire model, arm and wire
SPC counters, the plane notes) (program regions)."""

from benchmark import regions


def read(run):
    return regions.coll_us(run, "audit")
