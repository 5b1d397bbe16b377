"""End-to-end training driver (LM architectures and the HDP sampler).

Examples (CPU-sized):
  PYTHONPATH=src python -m repro.launch.train --arch starcoder2-3b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt /tmp/ck
  PYTHONPATH=src python -m repro.launch.train --hdp ap --scale 0.02 --iters 200

On a real cluster the same driver runs under the production mesh; the
mesh shape is inferred from the available devices (elastic.remesh).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.lm_data import SyntheticLMStream, batches
from repro.launch import mesh as MESH
from repro.train.optimizer import AdamWConfig
from repro.train.trainer import Trainer, init_train_state, make_train_step


def train_lm(args):
    from repro.models import lm as LM

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.batch and args.seq:
        pass
    mesh = MESH.make_host_mesh() if args.mesh is None else None
    rules = MESH.train_rules(mesh)

    stream = SyntheticLMStream(
        cfg.vocab_size, args.batch, args.seq,
        prefix_len=cfg.prefix_len, d_model=cfg.d_model,
    )
    opt = AdamWConfig(lr=args.lr, warmup=20)
    step_fn_pure = make_train_step(cfg, opt)

    with mesh:
        from repro.launch.dryrun import abstract_train_state

        shapes, axes = abstract_train_state(cfg)
        state_sh = jax.tree.map(
            lambda _: None, shapes, is_leaf=lambda x: False
        )
        psh = MESH.shardings_for_tree(shapes.params, axes, rules, mesh)
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.train.trainer import TrainState

        state_sh = TrainState(
            psh,
            MESH.shardings_for_tree(shapes.mu, axes, rules, mesh),
            MESH.shardings_for_tree(shapes.nu, axes, rules, mesh),
            NamedSharding(mesh, P()),
        )
        step_fn = jax.jit(step_fn_pure, donate_argnums=(0,),
                          in_shardings=(state_sh, None),
                          out_shardings=(state_sh, None))

        trainer = Trainer(
            cfg, opt, step_fn, checkpoint_dir=args.ckpt,
            checkpoint_every=args.ckpt_every, step_deadline_s=args.deadline,
        )
        state = trainer.restore_or_init(jax.random.key(args.seed))
        state = jax.device_put(state, state_sh)
        t0 = time.time()
        start = int(state.step)
        data = ({k: jnp.asarray(v) for k, v in b.items()}
                for b in batches(stream, args.steps, start=start))
        state, history = trainer.run(state, data, log_every=args.log_every)
        dt = time.time() - t0

    tokens = args.steps * args.batch * args.seq
    print(json.dumps({
        "arch": cfg.name, "steps": args.steps,
        "final_loss": history[-1]["loss"] if history else None,
        "first_loss": history[0]["loss"] if history else None,
        "tokens_per_s": round(tokens / dt, 1),
        "deadline_breaches": trainer.deadline_breaches,
        "history": history,
    }, indent=1))
    return state, history


def _stream_devices(args):
    """Lane count for the streaming driver: --devices, else
    $REPRO_STREAM_DEVICES, else 1."""
    if args.devices is not None:
        return args.devices
    return int(os.environ.get("REPRO_STREAM_DEVICES", "1") or "1")


def build_hdp_sampler(corpus, *, topics: int, bucket=None,
                      z_impl: str = "sparse"):
    """``(corpus, ShardedHDP)`` on the explicit single-device mesh.

    The model, the key schedule and the non-sweep ops live on
    ``jax.devices()[0]`` however many devices the host has: a mesh over
    every visible device would fold per-shard keys into the chain and
    sample a mesh-shaped chain instead of the canonical one. Data
    parallelism is the streaming lane mode (``build_streaming``), which
    keeps the chain bitwise-identical at every lane count.
    """
    from repro import compat
    from repro.core import hdp as H
    from repro.core.sharded import ShardedHDP
    from repro.data.corpus import shard_balanced

    mesh = compat.single_device_mesh()
    corpus = shard_balanced(corpus, 1)
    # auto bucket: the sparse z-step needs bucket >= min(K, L) (enforced
    # at sampler construction since the delta-stats PR).
    if bucket is None:
        bucket = min(topics, corpus.max_len)
    cfg = H.HDPConfig(K=topics, V=corpus.V, bucket=bucket, z_impl=z_impl,
                      hist_cap=min(corpus.max_len, 256))
    return corpus, ShardedHDP(mesh, cfg)


def build_streaming(corpus, sh, *, block_docs: int, devices: int = 1,
                    z_store=None, z_dir=None, z_pack=None):
    """The minibatch driver over ``corpus`` for sampler ``sh``, with
    ``devices`` data-parallel sweep lanes (blocks pad to a doc count the
    lanes divide evenly)."""
    from repro.core.streaming import StreamingHDP
    from repro.data.stream import ShardedCorpusStore

    store = ShardedCorpusStore.from_corpus(corpus, block_docs,
                                           doc_multiple=devices)
    return StreamingHDP(sh, store, z_store=z_store, z_dir=z_dir,
                        z_pack=z_pack, n_devices=devices)


def train_hdp_streaming(args, corpus, sh):
    """Minibatch path: corpus swept block-by-block in bounded device
    memory, resumable mid-epoch (block cursor + RNG in the checkpoint).
    With --z-store disk, z slabs are out-of-core too (bounded host
    memory): they live as per-block version files rooted at --z-dir
    (default: the checkpoint dir, which makes saves near-free)."""
    stream = build_streaming(corpus, sh, block_docs=args.block_docs,
                             devices=_stream_devices(args),
                             z_store=args.z_store,
                             z_dir=args.z_dir or args.ckpt,
                             z_pack=args.z_pack)
    store = stream.store
    state, resume_kw = (None, {})
    if args.ckpt:
        state, resume_kw = stream.restore(args.ckpt)
        if state is not None:
            print(f"restored streaming state: iteration {int(state.it)}, "
                  f"block cursor {resume_kw.get('start_block', 0)}")
    if state is None:
        state = stream.init_state(jax.random.key(args.seed))
    print(f"streaming: {store.num_blocks} blocks x {store.block_docs} docs "
          f"(corpus {store.num_docs} docs, {store.num_tokens} tokens), "
          f"z slabs in {state.z_blocks.kind} as {state.z_blocks.dtype}")

    history = []
    t0 = time.time()
    for i in range(args.iters):
        state = stream.iteration(
            state, ckpt_dir=args.ckpt,
            ckpt_every_blocks=args.ckpt_every_blocks, **resume_kw,
        )
        resume_kw = {}
        if (i + 1) % args.log_every == 0:
            history.append({
                "iter": int(state.it),
                "active_topics": int(jnp.sum(jnp.sum(state.n, 1) > 0)),
                "flag_tokens": int(state.n[-1].sum()),
            })
            print(history[-1], flush=True)
        if args.ckpt and (i + 1) % args.ckpt_every == 0:
            stream.save(args.ckpt, state)
    dt = time.time() - t0
    print(json.dumps({
        "corpus": args.hdp, "tokens": store.num_tokens, "mode": "streaming",
        "blocks": store.num_blocks, "iters": args.iters,
        "z_store": state.z_blocks.kind,
        "z_dtype": state.z_blocks.dtype.name,
        "sec_per_iter": round(dt / args.iters, 3),
        "tokens_per_s": round(store.num_tokens * args.iters / dt, 1),
    }))
    return state, history


def train_hdp(args):
    from repro.core import hdp as H
    from repro.data.synthetic import paper_corpus
    from repro.train import checkpoint as CKPT

    rng = np.random.default_rng(args.seed)
    corpus = paper_corpus(args.hdp, rng, scale=args.scale, max_len=args.max_len)
    corpus, sh = build_hdp_sampler(corpus, topics=args.topics,
                                   bucket=args.bucket, z_impl=args.z_impl)
    cfg = sh.cfg
    if args.stream:
        return train_hdp_streaming(args, corpus, sh)
    tokens = jax.device_put(jnp.asarray(corpus.tokens), sh.corpus_shardings()[0])
    mask = jax.device_put(jnp.asarray(corpus.mask), sh.corpus_shardings()[1])

    state = None
    if args.ckpt:
        step = CKPT.latest_step(args.ckpt)
        if step is not None:
            template = jax.eval_shape(
                lambda: sh.init_state(jax.random.key(args.seed), tokens, mask)
            )
            state = CKPT.restore(args.ckpt, step, template,
                                 sh.state_shardings())
            print(f"restored HDP state at iteration {step}")
    if state is None:
        state = sh.init_state(jax.random.key(args.seed), tokens, mask)

    step_fn = sh.jit_iteration()
    history = []
    t0 = time.time()
    for i in range(args.iters):
        state = step_fn(state, tokens, mask)
        if (i + 1) % args.log_every == 0:
            ll = float(H.log_marginal_likelihood(state, tokens, mask, cfg))
            history.append({
                "iter": int(state.it), "log_lik": ll,
                "active_topics": int(H.active_topics(state)),
                "flag_tokens": int(H.flag_topic_tokens(state)),
            })
            print(history[-1], flush=True)
        if args.ckpt and (i + 1) % args.ckpt_every == 0:
            CKPT.save(args.ckpt, int(state.it), state)
    dt = time.time() - t0
    print(json.dumps({
        "corpus": args.hdp, "tokens": corpus.num_tokens,
        "iters": args.iters, "sec_per_iter": round(dt / args.iters, 3),
        "tokens_per_s": round(corpus.num_tokens * args.iters / dt, 1),
    }))
    return state, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--hdp", default=None, help="ap|cgcbib|neurips|pubmed")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--topics", type=int, default=100)
    ap.add_argument("--bucket", type=int, default=None,
                    help="sparse z-step active-topic bucket; default "
                         "min(topics, max doc length)")
    ap.add_argument("--z-impl", default="sparse")
    ap.add_argument("--stream", action="store_true",
                    help="sweep the corpus in fixed-shape blocks (bounded "
                         "device memory; required beyond-device-memory runs)")
    ap.add_argument("--block-docs", type=int, default=4096,
                    help="documents per streaming block")
    ap.add_argument("--devices", type=int, default=None,
                    help="data-parallel sweep lanes (streaming only): "
                         "split each block's rows across this many "
                         "devices; the chain stays bitwise-identical to "
                         "--devices 1. Default: $REPRO_STREAM_DEVICES "
                         "or 1. On CPU, expose host devices with "
                         "REPRO_HOST_DEVICES=N ./run.sh ...")
    ap.add_argument("--z-store", default=None, choices=["ram", "disk"],
                    help="z-slab backend (streaming only): 'ram' keeps "
                         "all slabs host-resident, 'disk' keeps only "
                         "in-flight slabs (out-of-core; >RAM corpora). "
                         "Default: $REPRO_Z_STORE or ram")
    ap.add_argument("--z-pack", default=None, choices=["auto", "off"],
                    help="bit-pack z slabs to the narrowest dtype that "
                         "holds [0, K) (streaming only; cuts H2D/D2H and "
                         "disk bytes up to 4x, bitwise-identical chain). "
                         "Default: $REPRO_Z_PACK or auto")
    ap.add_argument("--z-dir", default=None,
                    help="disk z-store root (default: --ckpt dir when "
                         "set, making checkpoint saves near-free, else "
                         "a temp dir)")
    ap.add_argument("--ckpt-every-blocks", type=int, default=None,
                    help="mid-epoch checkpoint cadence (streaming only)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--deadline", type=float, default=None)
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record a Chrome trace (Perfetto-loadable) of "
                         "the run's pipeline spans to PATH")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="append metrics-registry snapshots (JSONL) to "
                         "PATH; also enables per-iteration model-health "
                         "gauges (K*, delta_n sparsity)")
    ap.add_argument("--metrics-every", type=float, default=None,
                    help="periodic metrics flush cadence in seconds "
                         "(default: iteration boundaries only)")
    args = ap.parse_args()
    from repro import obs
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    obs.setup(trace=args.trace, metrics_path=args.metrics,
              metrics_every_s=args.metrics_every)
    try:
        if args.hdp:
            train_hdp(args)
        else:
            train_lm(args)
    finally:
        obs.finalize()


if __name__ == "__main__":
    main()
