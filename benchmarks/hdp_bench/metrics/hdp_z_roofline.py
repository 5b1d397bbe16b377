"""One chip's least time of the window's hdp_z sweeps (the cell's
``work["hdp_z"]``: training blocks or fold-in engine steps) over the
chip-seconds of the ``hdp_z`` kernel events, summed over the cell's
chips, in %: the same work reads the same share on any number of
chips."""

from benchmarks.hdp_bench.readers import kernel_s, least_chip_s


def read(run):
    t = kernel_s(run)
    if not t:
        return None
    return 100.0 * least_chip_s(run, run.work["hdp_z"]) / t
