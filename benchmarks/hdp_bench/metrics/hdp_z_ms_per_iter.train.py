"""Device time of the ``hdp_z`` kernel per streaming iteration, in ms:
chip-milliseconds summed over the cell's chips."""

from benchmarks.hdp_bench.readers import kernel_s


def read(run):
    t = kernel_s(run)
    its = run.counts.get("iterations")
    return None if t is None or not its else 1e3 * t / its
