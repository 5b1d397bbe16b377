"""Least time on the cell's chips of the useful work of the requests
answered in the window (``work.foldin_request``) over the host time of
the window's engine steps, in %."""

from benchmarks.hdp_bench.readers import least_s


def read(run):
    reqs, step_s = run.work.get("requests"), run.counts.get("step_s_in_window")
    if not reqs or not step_s:
        return None
    return 100.0 * sum(least_s(run, w) for w in reqs) / step_s
