"""The serving engine's ``engine.retire_wait`` span time (the blocking
read of retiring mixtures) over the window, in %."""

from benchmarks.hdp_bench.readers import span_share


def read(run):
    return span_share(run, "engine.retire_wait")
