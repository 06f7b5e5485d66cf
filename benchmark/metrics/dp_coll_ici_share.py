"""Share of the ICI peak that the step's data-parallel gradient all-reduce
reaches: the bus bytes of every gradient once over ``dp``
(``benchmark.comm_bytes``) times the window's steps, over the device time
of the collective ops whose replica groups are dp pairs
(``benchmark.mesh_trace``), over the ICI peak in ``peaks.py``."""

from benchmark import mesh_trace, peaks


def read(run):
    return mesh_trace.ici_share(run, "dp",
                                peaks.ici_bytes_per_s(run["device_kind"]))
