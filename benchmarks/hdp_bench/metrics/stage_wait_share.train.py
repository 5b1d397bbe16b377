"""The driver's ``stage_wait`` span time (waiting for the next staged
block) over the window, in %."""

from benchmarks.hdp_bench.readers import span_share


def read(run):
    return span_share(run, "stage_wait")
