"""Device time of the block statistics (module ``jit_z_block`` without
the ``hdp_z`` kernel: the ``delta_n`` scatter, the document histogram)
per streaming iteration, in ms."""

from benchmarks.hdp_bench.attribute import module_ms_per_iter


def read(run):
    return module_ms_per_iter(run, "jit_z_block", other=True)
