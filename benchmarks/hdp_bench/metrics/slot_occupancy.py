"""Useful share of the slot sweeps of the window's engine steps: requests
answered x burnin over engine steps x slots, in %."""

from benchmarks.hdp_bench.readers import occupancy as read  # noqa: F401
