#!/usr/bin/env python3
"""Sweep of offered rates for a serving cell, to find its knee once.

  python3 benchmarks/hdp_bench/knee.py --workload serve.pubmed.poisson \
      --rates 100 200 400 --seconds 15 --seed 1

Runs the cell's traffic at each rate in one process (the set-up compiles
once) and prints, per rate, the completed rate, p50 and p95 latency and
how late the sender ran. The knee is the highest rate whose completed
rate keeps up with the offered rate without a growing backlog; the
cells' traffic files carry fixed rates chosen from it.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import sys
import time


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path[:0] = [root, os.path.join(root, "src")]
    import jax
    from benchmarks.hdp_bench import harness, peaks, readers, serve_cell
    from benchmarks.hdp_bench.bench import Bench

    harness.configure_jax()
    cell = Bench.load().cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit("knee: runs on the chip")
    kind = peaks.for_kind(devices[0].device_kind)
    base = dict(cell.traffic)
    for rate in args.rates:
        cell.traffic = dict(base, rate_docs_per_s=rate)
        log = io.StringIO()
        out = serve_cell.run(cell, seed=args.seed, seconds=args.seconds,
                             t0=time.perf_counter(), log=log)
        c = out.counts
        print(json.dumps({
            "rate": rate, "done_per_s": c["completed_in_window"]
            / c["window_s"], "p50_ms": c["p50_ms"], "p95_ms": c["p95_ms"],
            "unanswered": out.failed, "send_late_p95_ms":
            c["send_late_p95_ms"], "occupancy_pct": readers.occupancy(
                harness.RunData(cell=cell, peaks=kind, outcome=out))}),
            flush=True)


if __name__ == "__main__":
    main()
