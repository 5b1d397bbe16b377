"""Least time of one streaming iteration's work (``work.iteration``)
over the measured time per iteration, in %."""

from benchmarks.hdp_bench.readers import least_s


def read(run):
    c = run.counts
    if not c.get("iterations"):
        return None
    return 100.0 * least_s(run, run.work["iteration"]) / (
        c["window_s"] / c["iterations"])
