"""Milliseconds per prefill of ``ompi.engine.prefill.dispatch``: the
host's time from entry into ``ServingEngine.prefill`` until its last
program is enqueued, before the wait on the first token (program
regions)."""

from benchmark import regions


def read(run):
    return regions.ms_per(run, "ompi.engine.prefill.dispatch",
                          "ompi.engine.prefill.dispatch")
