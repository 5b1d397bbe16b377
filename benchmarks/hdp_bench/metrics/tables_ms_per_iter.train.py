"""Device time of the Phi draw and the z-step tables (module
``jit_phi_tables``) per streaming iteration, in ms."""

from benchmarks.hdp_bench.attribute import module_ms_per_iter


def read(run):
    return module_ms_per_iter(run, "jit_phi_tables")
