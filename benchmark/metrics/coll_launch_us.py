"""Microseconds per collective call, over every call of the window, of
``ompi.coll.launch``: the ``DeviceComm`` entry the native arm calls (the
cache key, the executable lookup, alltoallv's count preparation and the
jitted call's enqueue) (program regions)."""

from benchmark import regions


def read(run):
    return regions.coll_us(run, "launch")
