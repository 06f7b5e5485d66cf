"""Backend compiles and compile-cache loads inside the traced window
(``ompi.compile`` in the program's region table); 0 is the aim."""

from benchmark import regions


def read(run):
    return regions.compiles(run)
