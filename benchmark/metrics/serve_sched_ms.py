"""Milliseconds per decode step of the scheduler's own work: the self time
of ``ompi.serve.step`` and ``ompi.serve.admit``, that is the scheduler's
bookkeeping around the engine's decode steps and prefills, the benchmark's
per-token and per-finish hooks included (program regions)."""

from benchmark import regions


def read(run):
    return regions.ms_per(run, "ompi.serve.step", "ompi.serve.step",
                          self_time=True, plus=("ompi.serve.admit",))
