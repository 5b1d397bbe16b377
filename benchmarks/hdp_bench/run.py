#!/usr/bin/env python3
"""Entry point of the HDP benchmark; see ``harness.py``.

  python3 benchmarks/hdp_bench/run.py --workload <cell> --seed <n> \
      --seconds <s> --trace <0|1>

Run from the root of a checkout (``python -m benchmarks.hdp_bench`` is
the same). ``setup_s`` counts from the start of this process.
"""

import os
import sys
import time

T_ENTRY = time.perf_counter()


def process_age_s() -> float:
    """Seconds since this process started (0 where /proc is missing)."""
    try:
        with open("/proc/self/stat") as f:
            start = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(up - start / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


def main():
    t0 = T_ENTRY - process_age_s()
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path[:0] = [root, os.path.join(root, "src")]
    from benchmarks.hdp_bench.harness import main as run

    sys.exit(run(t0=t0))


if __name__ == "__main__":
    main()
