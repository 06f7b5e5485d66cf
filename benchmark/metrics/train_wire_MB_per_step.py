"""The program's own count of what its compiled step moves between chips
(``step.comm_graph``, ``analysis.commgraph.from_compiled``): wire bytes a
chip sends per step over every mesh axis, in MB (1e6 B), as the driver read
them in set-up."""


def read(run):
    wire = run["records"].get("wire_MB")
    return None if wire is None else float(sum(wire.values()))
