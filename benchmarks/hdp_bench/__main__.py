"""``python -m benchmarks.hdp_bench``: the benchmark's entry point."""

from benchmarks.hdp_bench.run import main

main()
