#!/usr/bin/env python3
"""The control of ``correct``: each cell run with the program's own
lower-precision table layout switched on.

The configurations state float32 word tables. The program has a compact
layout (``compact_tables`` / ``compact=True``: bfloat16 phi values and
alias probabilities, int16 topic ids) that a later change could be
tempted to make the default. Under ``compact()`` the training cells
build their sampler with ``ShardedHDP(compact_tables=True)`` (which also
turns the kernel-prologue alias build off) and the serving cells build a
compact snapshot; everything else runs as in a benchmark run, so the
checks have to come out failing.

  python3 benchmarks/hdp_bench/control.py --workload <cell> \
      --seeds 1 2 3 --seconds 2 [--as-configured]

prints one line per seed with every number compared and its limit.
``--as-configured`` runs the program as the configuration states
instead: the readings of sound runs on many seeds, in one process and
with a short window. It runs on the chip, like the benchmark; the tests
run the control on the CPU at a small size.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import time


@contextlib.contextmanager
def compact():
    """Switch the program's compact table layout on for the duration."""
    import repro.launch.train as TR
    import repro.serve.snapshot as SN
    from repro.core.sharded import ShardedHDP

    build_sampler, build_snapshot = TR.build_hdp_sampler, SN.build_snapshot

    def sampler(*a, **kw):
        corpus, sh = build_sampler(*a, **kw)
        return corpus, ShardedHDP(sh.mesh, sh.cfg, compact_tables=True)

    TR.build_hdp_sampler = sampler
    SN.build_snapshot = functools.partial(build_snapshot, compact=True)
    try:
        yield
    finally:
        TR.build_hdp_sampler, SN.build_snapshot = build_sampler, \
            build_snapshot


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--as-configured", action="store_true")
    args = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path[:0] = [root, os.path.join(root, "src")]
    import jax
    from benchmarks.hdp_bench import harness, peaks
    from benchmarks.hdp_bench.bench import Bench

    harness.configure_jax()
    cell = Bench.load().cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit("control: runs on the chip")
    kind = peaks.for_kind(devices[0].device_kind)
    layout = contextlib.nullcontext if args.as_configured else compact
    for seed in args.seeds:
        with layout():
            res = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                                   trace=False, t0=time.perf_counter(),
                                   peaks=kind, devices=devices[:cell.chips])
        print(json.dumps({"control": not args.as_configured,
                          "workload": args.workload, "seed": seed,
                          "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)


if __name__ == "__main__":
    main()
