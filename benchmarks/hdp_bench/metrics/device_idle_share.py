"""Share of the window in which no operation ran on a chip, in %: the
mean over the cell's chips."""

from benchmarks.hdp_bench.readers import idle_share as read  # noqa: F401
