"""Share of the window in which no operation ran on the device, in %."""

from benchmarks.hdp_bench.readers import idle_share as read  # noqa: F401
