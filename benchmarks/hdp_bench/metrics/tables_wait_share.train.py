"""The driver's ``tables.build`` span time (waiting for the Phi draw and
the z-step tables) over the window, in %."""

from benchmarks.hdp_bench.readers import span_share


def read(run):
    return span_share(run, "tables.build")
