"""Share of the ICI peak that the step's tensor-parallel collectives reach:
the bus bytes that the Megatron step must move over ``tp``
(``benchmark.comm_bytes``) times the window's steps, over the device time
of the collective ops whose replica groups are tp pairs
(``benchmark.mesh_trace``), over the ICI peak in ``peaks.py``.  Time spent
in tp collectives the yardstick does not count lowers it."""

from benchmark import mesh_trace, peaks


def read(run):
    return mesh_trace.ici_share(run, "tp",
                                peaks.ici_bytes_per_s(run["device_kind"]))
