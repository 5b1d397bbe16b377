"""§Perf hillclimb driver for the hdp-pubmed cell (paper-representative).

Runs the paper-faithful baseline and the beyond-paper variants through
the dry-run, recording the roofline terms of each. Results feed
EXPERIMENTS.md §Perf.

  PYTHONPATH=src python -m benchmarks.perf_hdp --out perf_hdp.json
"""
import argparse
import json
import os
import time

VARIANTS = [
    # (label, kwargs)
    ("baseline: paper-faithful dense Phi + (V,K) alias tables (f32)",
     dict(z_impl="sparse", gather_tables=True, phi_dtype="f32")),
    ("H2: bf16 Phi broadcast",
     dict(z_impl="sparse", gather_tables=True, phi_dtype="bf16")),
    ("H3: local table rebuild (gather Phi only)",
     dict(z_impl="sparse", gather_tables=False, phi_dtype="f32")),
    ("H3+H2: local rebuild + bf16 Phi",
     dict(z_impl="sparse", gather_tables=False, phi_dtype="bf16")),
    ("H1: word-sparse packed tables (pallas kernel, W=128)",
     dict(z_impl="pallas", gather_tables=True, phi_dtype="f32", bucket=128)),
    ("H1+H4: word-sparse + compact bf16/int16 tables",
     dict(z_impl="pallas", gather_tables=True, phi_dtype="f32", bucket=128,
          compact_tables=True)),
]


def _reset_peak_rss() -> bool:
    """Reset the kernel's peak-RSS watermark (Linux: writing "5" to
    /proc/self/clear_refs clears VmHWM), so each config's record is its
    OWN peak instead of inheriting earlier configs' highs. Returns False
    where unsupported (non-Linux / restricted procfs) — the fallback is
    the old process-lifetime semantics."""
    try:
        with open("/proc/self/clear_refs", "w") as f:
            f.write("5")
        return True
    except OSError:
        return False


def _peak_rss_mb() -> float:
    """Peak resident set size in MB since the last ``_reset_peak_rss``
    (Linux VmHWM), falling back to process-lifetime ru_maxrss (KB on
    Linux, bytes on macOS) where /proc is unavailable."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return round(int(line.split()[1]) / 1024, 1)  # KB
    except OSError:
        pass
    import resource
    import sys

    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    div = 1024 ** 2 if sys.platform == "darwin" else 1024
    return round(rss / div, 1)


def stream_bench(args):
    """Streaming-pipeline throughput: tokens/s and per-block wall time as
    a function of block size, on a synthetic corpus several blocks deep.
    Measures the minibatch driver itself (prefetch + per-block z-sweep +
    statistic merge), not the dry-run roofline. Records peak RSS next to
    tokens/s so the RAM/disk z-store overhead stays tracked
    (``--z-store disk`` keeps only in-flight z slabs host-resident)."""
    import jax
    import numpy as np

    from repro import obs
    from repro.core import hdp as H
    from repro.core.sharded import ShardedHDP
    from repro.core.streaming import StreamingHDP
    from repro.data.stream import ShardedCorpusStore
    from repro.data.synthetic import paper_corpus
    from repro.launch.mesh import make_host_mesh

    rng = np.random.default_rng(0)
    corpus = paper_corpus("ap", rng, scale=args.scale, max_len=128)
    n_dev = len(jax.devices())
    devices = args.devices
    if devices is None:
        import os
        devices = int(os.environ.get("REPRO_STREAM_DEVICES", "1") or "1")
    # lane mode keeps the primary mesh on ONE device (the lane threads
    # place the sweeps across devices themselves) so the measured chain
    # is bitwise-identical to the single-device records; a multi-device
    # primary mesh would sample a mesh-shaped chain instead.
    if devices > 1:
        from repro import compat
        mesh = compat.single_device_mesh()
        mesh_data = 1
    else:
        mesh = make_host_mesh()
        mesh_data = n_dev // mesh.shape["model"]
    v_pad = ((corpus.V + mesh.shape["model"] - 1)
             // mesh.shape["model"]) * mesh.shape["model"]
    results = []
    for block_docs in args.block_docs:
        store = ShardedCorpusStore.from_corpus(
            corpus, block_docs,
            doc_multiple=int(np.lcm(mesh_data, devices))
        )
        # bucket must hold a document's active topics (min(K, L) —
        # enforced at sampler construction since the delta-stats PR).
        bucket = min(args.topics, 128)
        if args.ppu_budget < 0:  # auto: corpus tokens always bound nnz(n)
            budget = 1 << max(int(store.num_tokens) - 1, 1).bit_length()
        else:
            budget = args.ppu_budget or None
        cfg = H.HDPConfig(K=args.topics, V=v_pad, bucket=bucket,
                          z_impl=args.z_impl, hist_cap=128,
                          ppu_nnz_budget=budget,
                          alias_in_kernel=args.alias_in_kernel)
        stream = StreamingHDP(ShardedHDP(mesh, cfg), store,
                              z_store=args.z_store, z_pack=args.z_pack,
                              block_sparse_tables=args.block_sparse_tables,
                              n_devices=devices)
        state = stream.init_state(jax.random.key(0))
        state = stream.iteration(state)  # compile + warm cache
        _reset_peak_rss()  # per-config peak, not inherited highs
        bytes0 = state.z_blocks.bytes_written
        rd0 = state.z_blocks.bytes_read
        dr0 = stream.delta_reduce_bytes
        t0 = time.perf_counter()
        for _ in range(args.iters):
            state = stream.iteration(state)
        dt = time.perf_counter() - t0
        wb_bytes = state.z_blocks.bytes_written - bytes0
        rd_bytes = state.z_blocks.bytes_read - rd0
        dr_bytes = stream.delta_reduce_bytes - dr0
        obs_on_rate = None
        if args.obs_overhead and not obs.metrics_on():
            # Same run, same chain: attach a throwaway metrics sink and
            # re-time, so obs_overhead_pct measures PR 7's "within
            # noise" claim instead of asserting it. One warm iteration
            # first — the diagnostics reductions compile on their first
            # metrics-on pass and compile time is not overhead. Skipped
            # when the user already attached a sink (--metrics): the
            # off-path would not exist to compare against.
            import os
            import tempfile

            with tempfile.TemporaryDirectory() as td:
                obs.enable_metrics(os.path.join(td, "metrics.jsonl"))
                state = stream.iteration(state)  # compile diagnostics
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    state = stream.iteration(state)
                dt_on = time.perf_counter() - t0
                obs.disable_metrics()
            obs_on_rate = store.num_tokens * args.iters / dt_on
        rec = {
            "mode": "streaming", "z_impl": args.z_impl,
            "z_store": state.z_blocks.kind,
            "z_dtype": state.z_blocks.dtype.name,
            "n_devices": stream.n_devices,
            "mesh": "x".join(str(s) for s in mesh.devices.shape),
            "block_docs": store.block_docs, "blocks": store.num_blocks,
            "tokens": store.num_tokens, "iters": args.iters,
            "ppu_budget": budget or 0,
            "alias_in_kernel": args.alias_in_kernel,
            "block_sparse_tables": stream.block_sparse_tables,
            "sec_per_iter": round(dt / args.iters, 3),
            "sec_per_block": round(
                dt / (args.iters * store.num_blocks), 4),
            "tokens_per_s": round(
                store.num_tokens * args.iters / dt, 1),
            "writeback_mb_per_iter": round(
                wb_bytes / args.iters / 2 ** 20, 3),
            "zstore_read_mb_per_iter": round(
                rd_bytes / args.iters / 2 ** 20, 3),
            # packed delta_n exchange volume of the lane merge (0.0 on a
            # single device — no exchange exists); deterministic at a
            # fixed seed, so check_bench hard-gates it like the other
            # byte keys.
            "delta_reduce_mb_per_iter": round(
                dr_bytes / args.iters / 2 ** 20, 3),
            "peak_rss_mb": _peak_rss_mb(),
            "resident_z_slabs_hwm": int(state.z_blocks.high_water),
        }
        if obs_on_rate is not None:
            rec["tokens_per_s_obs_on"] = round(obs_on_rate, 1)
            rec["obs_overhead_pct"] = round(
                (1 - obs_on_rate / rec["tokens_per_s"]) * 100, 2)
        if args.phases:
            # one serialized, phase-attributed iteration (bitwise the
            # same chain; tokens_per_s above stays the overlapped number)
            state, timers = stream.iteration_profiled(state)
            frac = timers.fractions()
            rec["phases_s"] = timers.summary()
            rec["phase_frac"] = frac
            rec["tables_pct"] = round(sum(
                v for k, v in frac.items() if k.startswith("tables")), 3)
        print(f"block_docs={store.block_docs} [{rec['z_store']}/"
              f"{rec['z_dtype']}/d{rec['n_devices']}]: "
              f"{rec['tokens_per_s']:,} tok/s "
              f"({rec['sec_per_block']}s/block, "
              f"wb {rec['writeback_mb_per_iter']} MB/iter, "
              f"peak RSS {rec['peak_rss_mb']} MB)", flush=True)
        if obs_on_rate is not None:
            print(f"  obs-on: {rec['tokens_per_s_obs_on']:,} tok/s "
                  f"(overhead {rec['obs_overhead_pct']}%)", flush=True)
        results.append(rec)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)


def serve_bench(args):
    """Serving-path throughput: fold-in docs/s and latency percentiles of
    the continuous-batching engine (serve/engine.py) across slot counts,
    plus held-out fold-in perplexity of the snapshot — the repo's
    model-quality number, recorded alongside the perf numbers."""
    import jax
    import numpy as np

    from repro.launch import serve_hdp as SH
    from repro.serve import eval as EV
    from repro.serve.engine import ServeEngine

    targs = argparse.Namespace(
        seed=0, eval_docs=16, train_docs=args.train_docs,
        train_iters=args.train_iters, topics=args.topics,
        vocab=args.vocab, compact=False, export=None,
    )
    snap, heldout = SH.train_tiny_snapshot(targs)
    rng = np.random.default_rng(1)
    docs = [rng.integers(0, snap.V, size=int(n)).astype(np.int32)
            for n in rng.integers(8, 48, size=args.requests)]
    perplexity = EV.heldout_perplexity(
        snap, heldout[0], heldout[1], jax.random.key(2),
        burnin=args.burnin, impl=args.z_impl,
    )
    results = []
    for slots in args.slots:
        engine = ServeEngine(
            snap, slots=slots, burnin=args.burnin, impl=args.z_impl,
            buckets=(32, 64), base_key=jax.random.key(0),
        )
        for doc in docs:
            engine.submit(doc)
        engine.run()
        rec = {
            "mode": "serve", "impl": args.z_impl, "slots": slots,
            "burnin": args.burnin, "requests": args.requests,
            "K": snap.K, "V": snap.V, "W": snap.W,
            "heldout_perplexity": round(perplexity, 3),
            **engine.stats.summary(),
        }
        print(f"slots={slots}: {rec['docs_per_s']} docs/s "
              f"(p95 {rec['p95_latency_ms']}ms)", flush=True)
        results.append(rec)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)


def serve_fleet_bench(args):
    """Fleet scaling: aggregate docs/s of the replicated serving fleet
    across worker counts on the default synthetic config (one trained
    snapshot, pinned). On CPU, workers are threads whose XLA sweeps
    release the GIL, so docs/s should scale near-linearly up to the core
    count; the committed BENCH_hdp.json records the trajectory and
    check_bench flags >20% regressions warn-only in CI."""
    import jax
    import numpy as np

    from repro.launch import serve_hdp as SH
    from repro.serve.fleet import ServeFleet

    targs = argparse.Namespace(
        seed=0, eval_docs=16, train_docs=args.train_docs,
        train_iters=args.train_iters, topics=args.topics,
        vocab=args.vocab, compact=False, export=None,
    )
    snap, _ = SH.train_tiny_snapshot(targs)
    rng = np.random.default_rng(1)
    docs = [rng.integers(0, snap.V, size=int(n)).astype(np.int32)
            for n in rng.integers(8, 48, size=args.requests)]
    results = []
    for workers in args.workers:
        with ServeFleet(
            snap, workers=workers, slots=args.fleet_slots,
            burnin=args.burnin, impl=args.z_impl, buckets=(32, 64),
            base_key=jax.random.key(0),
        ) as fleet:
            for doc in docs:  # warm-up: compile + first admissions
                fleet.submit(doc)
            fleet.run()
            # percentiles must describe the timed pass only — warm-up
            # completions include XLA compile time.
            fleet.router.reset_latencies()
            t0 = time.perf_counter()
            for i, doc in enumerate(docs):
                fleet.submit(doc, seed=10_000 + i)
            fleet.run()
            wall = time.perf_counter() - t0
            s = fleet.stats_summary()
        rec = {
            "mode": "serve_fleet", "impl": args.z_impl,
            "workers": workers, "slots": args.fleet_slots,
            "burnin": args.burnin, "requests": args.requests,
            "K": snap.K, "V": snap.V, "W": snap.W,
            "docs_per_s": round(args.requests / wall, 2),
            "p50_latency_ms": s["p50_latency_ms"],
            "p95_latency_ms": s["p95_latency_ms"],
        }
        print(f"workers={workers}: {rec['docs_per_s']} docs/s "
              f"(p95 {rec['p95_latency_ms']}ms)", flush=True)
        results.append(rec)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="hdp-pubmed")
    ap.add_argument("--out", default=None,
                    help="stats JSON path (default: BENCH_hdp.json for "
                         "--stream — the committed trajectory baseline — "
                         "and a mode-suffixed file otherwise, so serve/"
                         "dry-run runs never clobber the baseline)")
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--stream", action="store_true",
                    help="benchmark the streaming minibatch driver")
    ap.add_argument("--serve", action="store_true",
                    help="benchmark the fold-in serving engine")
    ap.add_argument("--serve-fleet", action="store_true",
                    help="benchmark replicated-fleet docs/s scaling "
                         "across --workers counts")
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--topics", type=int, default=100)
    ap.add_argument("--z-impl", default="sparse")
    ap.add_argument("--z-store", default=None, choices=["ram", "disk"],
                    help="z-slab backend for --stream (default: "
                         "$REPRO_Z_STORE or ram); 'disk' keeps only "
                         "in-flight slabs host-resident")
    ap.add_argument("--z-pack", default=None, choices=["auto", "off"],
                    help="bit-pack z slabs for --stream (default: "
                         "$REPRO_Z_PACK or auto); 'off' pins int32 — "
                         "the packed-vs-int32 byte-volume baseline")
    ap.add_argument("--obs-overhead", action="store_true",
                    help="for --stream: re-time each config with a "
                         "throwaway metrics sink attached and record "
                         "tokens_per_s_obs_on / obs_overhead_pct "
                         "(check_bench warns above 3%%)")
    ap.add_argument("--phases", action="store_true",
                    help="attach a per-phase breakdown (one serialized "
                         "profiled iteration per record, incl. the "
                         "tables.h2d/build/gather split and tables_pct; "
                         "tokens_per_s stays the overlapped measurement)")
    ap.add_argument("--ppu-budget", type=int, default=-1,
                    help="doubly-sparse budgeted PPU draw for --stream: "
                         "-1 auto (corpus tokens — an always-valid "
                         "nnz(n) bound), 0 dense draw, >0 explicit")
    ap.add_argument("--alias-in-kernel", default="auto",
                    choices=["auto", "on", "off"],
                    help="build term-(a) alias tables in the pallas "
                         "kernel prologue instead of the epilogue-fused "
                         "table build (pallas impl only)")
    ap.add_argument("--block-sparse-tables", default="auto",
                    choices=["auto", "on", "off"],
                    help="build alias tables only for vocab rows present "
                         "in the corpus (auto: when coverage < 50%%)")
    ap.add_argument("--block-docs", type=int, nargs="+",
                    default=[64, 256, 1024])
    ap.add_argument("--devices", type=int, default=None,
                    help="data-parallel sweep lanes for --stream "
                         "(default: $REPRO_STREAM_DEVICES or 1); >1 "
                         "splits each block's rows across that many jax "
                         "devices with the sparse packed delta_n merge "
                         "(CPU CI: REPRO_HOST_DEVICES=N ./run.sh ...)")
    # serving-mode knobs (CPU-sized defaults so CI can run them)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--burnin", type=int, default=8)
    ap.add_argument("--slots", type=int, nargs="+", default=[4, 16])
    ap.add_argument("--workers", type=int, nargs="+", default=[1, 2, 4],
                    help="fleet worker counts (--serve-fleet)")
    ap.add_argument("--fleet-slots", type=int, default=32,
                    help="slots per fleet worker (--serve-fleet); wide "
                         "batches amortize per-step dispatch")
    ap.add_argument("--train-docs", type=int, default=64)
    ap.add_argument("--train-iters", type=int, default=15)
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record a Chrome trace of pipeline/serve spans")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="append metrics-registry snapshots (JSONL)")
    args = ap.parse_args()
    if args.out is None:
        args.out = ("BENCH_hdp.json" if args.stream else
                    "BENCH_hdp_serve.json" if args.serve else
                    "BENCH_hdp_fleet.json" if args.serve_fleet else
                    "BENCH_hdp_dryrun.json")
    if not (args.stream or args.serve or args.serve_fleet):
        # the dry-run lowers production meshes on 512 placeholder host
        # devices; the flag must be in place before jax starts a backend.
        os.environ["XLA_FLAGS"] = " ".join(filter(None, [
            os.environ.get("XLA_FLAGS", ""),
            "--xla_force_host_platform_device_count=512",
        ]))
    from repro import obs
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    obs.setup(trace=args.trace, metrics_path=args.metrics)
    try:
        if args.serve_fleet:
            return serve_fleet_bench(args)
        if args.serve:
            return serve_bench(args)
        if args.stream:
            return stream_bench(args)
        return dryrun_bench(args)
    finally:
        obs.finalize()


def dryrun_bench(args):
    from repro.launch.dryrun import hdp_cell

    multi = args.mesh == "multi"
    results = []
    for label, kw in VARIANTS:
        t0 = time.perf_counter()
        try:
            rec = hdp_cell(args.cell, multi, **kw)
            rec["variant"] = label
        except Exception as e:
            rec = {"variant": label, "status": "error", "error": str(e)}
        rec["wall_s"] = round(time.perf_counter() - t0, 1)
        coll = sum(rec.get("collectives", {}).values())
        print(f"{label}: {rec.get('status')} coll={coll/1e6:.0f}MB "
              f"({rec['wall_s']}s)", flush=True)
        results.append(rec)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
