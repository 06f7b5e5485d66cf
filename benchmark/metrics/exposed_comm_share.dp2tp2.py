"""Exposed communication: per chip, the share of the measured window in
which a collective op (``trace_reduce.is_collective``, an async pair's
``-start`` and ``-done`` included) runs and no other op does; the mean over
the chips.  Lower is better."""

from benchmark import mesh_trace


def read(run):
    share = mesh_trace.exposed_share(run["trace"])
    return None if share is None else 100.0 * share
