"""Milliseconds per decode step of ``ompi.engine.decode.dispatch``: the
host's time from entry into ``ServingEngine.decode_step`` until its last
program is enqueued, before the wait on the next tokens (program
regions)."""

from benchmark import regions


def read(run):
    return regions.ms_per(run, "ompi.engine.decode.dispatch",
                          "ompi.engine.decode.dispatch")
