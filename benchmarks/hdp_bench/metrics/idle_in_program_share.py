"""Device-idle time of the window under a ``repro.*`` program span on the
thread that holds the window, over the window, in %: the mean over the
cell's chips. The log gets the idle seconds per innermost span, the ten
largest."""

import sys

from benchmarks.hdp_bench.attribute import of


def read(run):
    a = of(run)
    if a is None or not a["program_spans"]:
        return None
    top = sorted(a["idle_by_span"].items(), key=lambda kv: -kv[1])[:10]
    print(f"hdp_bench: device idle {a['idle_s']:.6f} s of the "
          f"{a['window_s']:.3f} s window; by innermost program span (s): "
          f"{[[n, t] for n, t in top]}; under no program span "
          f"{a['idle_s'] - a['idle_in_program_s']:.6f} s",
          file=sys.stderr, flush=True)
    return 100.0 * a["idle_in_program_s"] / a["window_s"]
