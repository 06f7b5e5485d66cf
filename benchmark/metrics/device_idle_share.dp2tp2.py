"""Share of the measured window in which no op ran on the device, from the
profiler trace, averaged over the cell's chips."""


def read(run):
    return 100.0 * run["reduced"]["idle_share"]
