"""Least time of the window's hdp_z sweeps (the cell's ``work["hdp_z"]``:
training blocks or fold-in engine steps) over the summed device time of
the ``hdp_z`` kernel events, in %."""

from benchmarks.hdp_bench.readers import kernel_s, least_s


def read(run):
    t = kernel_s(run)
    return None if not t else 100.0 * least_s(run, run.work["hdp_z"]) / t
