"""Microseconds per collective call, over every call of the window, of
``ompi.coll.decide``: ``XlaModule._decide`` (per-rank bytes, the hier and
quant gates, ``decide_mode``'s precedence chain) (program regions)."""

from benchmark import regions


def read(run):
    return regions.coll_us(run, "decide")
