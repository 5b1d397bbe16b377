"""HDP topic-inference serving driver: snapshot -> engine/fleet -> stats.

Loads (or, with --smoke/--train-iters, trains and exports) a frozen
``ModelSnapshot``, runs a query workload through the continuous-batching
engine — or, with ``--workers``, through a replicated ``ServeFleet`` —
and reports docs/s, latency percentiles, and held-out fold-in perplexity
as JSON — the serving counterpart of launch/train.py.

  # end-to-end from nothing (tiny model, 16 queries):
  PYTHONPATH=src python -m repro.launch.serve_hdp --smoke

  # the same through a 2-worker fleet (the CI fleet smoke):
  PYTHONPATH=src python -m repro.launch.serve_hdp --smoke --workers 2

  # serve an exported snapshot against a synthetic AP-like workload:
  PYTHONPATH=src python -m repro.launch.serve_hdp \
      --snapshot /tmp/snap --corpus ap --scale 0.01 --requests 256 \
      --slots 32 --burnin 16 --impl sparse

  # serve the latest version of a snapshot registry with hot-swap on
  # publish and 3-sample posterior ensembling:
  PYTHONPATH=src python -m repro.launch.serve_hdp \
      --registry /tmp/hdp_reg --workers 4 --watch-registry --ensemble 3
"""

from __future__ import annotations

import argparse
import json
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve import eval as EV
from repro.serve import snapshot as SNAP
from repro.serve.engine import DEFAULT_BUCKETS, ServeEngine


def train_tiny_snapshot(args):
    """Fit a small model on a planted-topic corpus and export it —
    the from-scratch path for --smoke and CI. A quarter of the corpus is
    held out of training and returned as the perplexity eval batch
    (held-out docs must come from the modeled distribution for the
    metric to mean anything)."""
    from repro.core import hdp as H
    from repro.data.synthetic import planted_topics_corpus

    rng = np.random.default_rng(args.seed)
    n_eval = max(args.eval_docs, 1)
    corpus, _ = planted_topics_corpus(
        rng, D=args.train_docs + n_eval, V=args.vocab, K_true=3,
        doc_len=(10, 24)
    )
    cfg = H.HDPConfig(K=args.topics, V=corpus.V, bucket=args.topics,
                      z_impl="sparse", hist_cap=64)
    tokens = jnp.asarray(corpus.tokens[:args.train_docs])
    mask = jnp.asarray(corpus.mask[:args.train_docs])
    state = H.init_state(jax.random.key(args.seed), tokens, mask, cfg)
    step = jax.jit(lambda s: H.gibbs_iteration(s, tokens, mask, cfg))
    for _ in range(args.train_iters):
        state = step(state)
    snap = SNAP.snapshot_from_state(state, cfg, compact=args.compact)
    if args.export:
        SNAP.save(args.export, snap)
        print(f"exported snapshot (it={int(snap.it)}) to {args.export}")
    heldout = (corpus.tokens[args.train_docs:], corpus.mask[args.train_docs:])
    return snap, heldout


def make_workload(args, snap: SNAP.ModelSnapshot, heldout):
    """Variable-length query documents + a held-out eval batch. Queries
    come from a corpus replica (--corpus) or are synthetic; the eval
    batch prefers genuinely held-out docs (from-scratch training path or
    --corpus tail), falling back to synthetic ones (a loaded snapshot
    with a synthetic workload — throughput-only, perplexity is then a
    number against noise)."""
    rng = np.random.default_rng(args.seed + 1)
    n_eval = max(args.eval_docs, 1)
    if args.corpus:
        from repro.data.synthetic import paper_corpus

        corpus = paper_corpus(args.corpus, rng, scale=args.scale,
                              max_len=max(DEFAULT_BUCKETS))
        docs = [corpus.tokens[i][corpus.mask[i]] % snap.V
                for i in range(min(args.requests, corpus.num_docs))]
        if heldout is None and corpus.num_docs > args.requests:
            tail = slice(args.requests, args.requests + n_eval)
            heldout = (corpus.tokens[tail] % snap.V, corpus.mask[tail])
    else:
        lengths = rng.integers(args.min_len, args.max_len + 1,
                               size=args.requests)
        docs = [rng.integers(0, snap.V, size=int(n)).astype(np.int32)
                for n in lengths]
    if heldout is not None:
        ev_tokens, ev_mask = heldout
    else:
        # uniform-random eval docs: perplexity becomes a score against
        # noise (harmless for throughput runs; flagged in the output)
        elen = max(args.max_len, 16)
        ev_tokens = np.zeros((n_eval, elen), np.int32)
        ev_mask = np.zeros((n_eval, elen), bool)
        for i in range(n_eval):
            n = int(rng.integers(8, elen + 1))
            ev_tokens[i, :n] = rng.integers(0, snap.V, size=n)
            ev_mask[i, :n] = True
    return docs, np.asarray(ev_tokens), np.asarray(ev_mask), heldout is None


def _serve_fleet(args, snap, docs):
    """Route the workload through a replicated ServeFleet. Serves from
    --registry when given (publishing a freshly trained snapshot into it
    first), else from the pinned snapshot."""
    from repro.serve.fleet import ServeFleet
    from repro.serve.registry import SnapshotRegistry

    source = snap
    if args.registry:
        reg = SnapshotRegistry(args.registry)
        if args.smoke or args.train_iters:
            v = reg.publish(snap)
            print(f"published trained snapshot as v{v} in {args.registry}")
        source = reg
    with ServeFleet(
        source, workers=args.workers, slots=args.slots, burnin=args.burnin,
        impl=args.impl, buckets=tuple(args.buckets),
        base_key=jax.random.key(args.seed), ensemble=args.ensemble,
        watch_registry=args.watch_registry, slo_ms=args.slo_ms,
    ) as fleet:
        rids = [fleet.submit(doc) for doc in docs]
        mixtures = fleet.run()
        stats = fleet.stats_summary()
    return rids, mixtures, stats


def serve(args) -> dict:
    heldout = None
    if args.snapshot and not args.smoke and not args.train_iters:
        snap = SNAP.load(args.snapshot)
    elif args.registry and not args.smoke and not args.train_iters:
        from repro.serve.registry import SnapshotRegistry

        snap = SnapshotRegistry(args.registry).load()
    else:
        snap, heldout = train_tiny_snapshot(args)
    print(f"snapshot: K={snap.K} V={snap.V} W={snap.W} "
          f"compact={snap.compact} ({snap.nbytes()/1e6:.2f} MB)")

    docs, ev_tokens, ev_mask, ev_synth = make_workload(args, snap, heldout)
    if args.workers:
        rids, mixtures, fleet_stats = _serve_fleet(args, snap, docs)
    else:
        engine = ServeEngine(
            snap, slots=args.slots, burnin=args.burnin, impl=args.impl,
            buckets=tuple(args.buckets), base_key=jax.random.key(args.seed),
        )
        rids = [engine.submit(doc) for doc in docs]
        mixtures = engine.run()
        fleet_stats = None

    # every accepted request must come back as a valid mixture
    assert len(mixtures) == len(rids), (len(mixtures), len(rids))
    for rid in rids:
        th = mixtures[rid]
        assert th.shape == (snap.K,) and np.all(th >= 0), rid
        assert abs(float(th.sum()) - 1.0) < 1e-4, rid

    t0 = time.time()
    perplexity = EV.heldout_perplexity(
        snap, ev_tokens, ev_mask, jax.random.key(args.seed + 2),
        burnin=args.burnin, impl=args.impl,
    )
    eval_s = time.time() - t0

    out = {
        "mode": "serve_hdp",
        "impl": args.impl,
        "snapshot": {"K": snap.K, "V": snap.V, "W": snap.W,
                     "compact": snap.compact, "it": int(snap.it),
                     "mbytes": round(snap.nbytes() / 1e6, 3)},
        "requests": len(rids),
        "burnin": args.burnin,
        "slots": args.slots,
        **(fleet_stats if fleet_stats is not None
           else engine.stats.summary()),
        "heldout_perplexity": round(perplexity, 3),
        # True when no genuinely held-out docs were available and the
        # eval batch is uniform noise — the perplexity is then only a
        # smoke number, not a model-quality metric.
        "eval_synthetic": ev_synth,
        "eval_docs": ev_tokens.shape[0],
        "eval_s": round(eval_s, 2),
        "sample_mixture_top3": sorted(
            np.asarray(mixtures[rids[0]]).tolist(), reverse=True
        )[:3],
    }
    print(json.dumps(out, indent=1))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--snapshot", default=None,
                    help="snapshot dir to load (serve/snapshot.py)")
    ap.add_argument("--export", default=None,
                    help="export the freshly trained snapshot here")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny end-to-end run: train, export, serve, eval")
    ap.add_argument("--impl", default="sparse",
                    choices=["dense", "sparse", "pallas"])
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--burnin", type=int, default=8)
    ap.add_argument("--buckets", type=int, nargs="+",
                    default=list(DEFAULT_BUCKETS))
    ap.add_argument("--workers", type=int, default=0,
                    help="serve through a replicated fleet of N engine "
                         "workers (0 = single engine)")
    ap.add_argument("--ensemble", type=int, default=1,
                    help="fan each request out to the E newest registry "
                         "versions and average mixtures (needs --registry)")
    ap.add_argument("--registry", default=None,
                    help="snapshot registry dir to serve from (latest "
                         "version; freshly trained snapshots are "
                         "published into it)")
    ap.add_argument("--watch-registry", action="store_true",
                    help="hot-swap fleet workers onto newly published "
                         "registry versions between engine steps")
    ap.add_argument("--corpus", default=None,
                    help="ap|cgcbib|neurips|pubmed synthetic query workload")
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--min-len", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=48)
    ap.add_argument("--eval-docs", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compact", action="store_true",
                    help="bf16/int16 snapshot tables")
    # training knobs for --smoke / from-scratch export
    ap.add_argument("--train-iters", type=int, default=0)
    ap.add_argument("--train-docs", type=int, default=64)
    ap.add_argument("--topics", type=int, default=16)
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record a Chrome trace (Perfetto-loadable) of "
                         "per-request serve spans to PATH")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="append metrics-registry snapshots (JSONL) to "
                         "PATH")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="end-to-end latency SLO threshold: classify "
                         "completions into per-bucket ok/miss counters "
                         "(fleet mode)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.smoke and not args.train_iters:
        args.train_iters = 20
    if not args.snapshot and not args.registry and not args.train_iters:
        ap.error("need --snapshot, --registry, --smoke, or --train-iters")
    if (args.watch_registry or args.ensemble > 1) and not args.workers:
        ap.error("--watch-registry/--ensemble serve through the fleet: "
                 "pass --workers N")
    if (args.watch_registry or args.ensemble > 1) and not args.registry:
        ap.error("--watch-registry/--ensemble need --registry")
    if args.slo_ms is not None and not args.workers:
        ap.error("--slo-ms is accounted by the fleet router: pass "
                 "--workers N")
    from repro import obs
    obs.setup(trace=args.trace, metrics_path=args.metrics)
    try:
        serve(args)
        obs.flush_metrics(force=True)
    finally:
        obs.finalize()


if __name__ == "__main__":
    main()
