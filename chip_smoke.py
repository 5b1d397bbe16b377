#!/usr/bin/env python3
"""Bring-up check of the HDP trainer and fold-in server on one TPU chip.

One process, the normal entry points, the full width of the AP paper
configuration (D=2,206 documents, V=7,074, K=1000 topics, rows of 512
tokens, table width W=128), with the compiled Pallas z-step:

  train  5 streaming iterations (blocks of 512 documents, the explicit
         single-device mesh, ``z_impl=pallas``); sec/iter and tokens/s
         over iterations 3-5 are printed as information only
  check  n equals a from-scratch integer recount of z; on 64 documents,
         from random topics and with the same uniforms, the compiled
         kernel's z equals the ``hdp_z_ref`` oracle's bitwise, both for
         the per-word epilogue tables that training and serving sweep on
         and for the kernel-prologue alias build (``alias_in_kernel=
         "on"``); on a mismatch it prints the fraction and compares the
         alias rows the chip builds with the oracle's before failing;
         its m equals a recount of its z. The
         joint log-likelihood of every iteration is printed as
         information
  serve  a snapshot exported from the trained state folds in 16
         held-out AP documents through ``ServeEngine`` with the pallas
         and the sparse impl: every mixture row lies on the simplex and
         the two impls agree bitwise

With ``--chips 4`` it runs only the four-chip phase: lane training with
``n_devices=4`` against ``n_devices=1`` on the same single-device primary
mesh (bitwise: model arrays, chain key, every z slab), and a 4-worker
``ServeFleet`` against one engine, request by request.

  python chip_smoke.py [--seed 0] [--chips 4]

The last line of stdout is ``{"ok": true, "device": {...}}``. Without a
TPU the script exits 2 before doing anything and prints no such line;
any failed check exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

K_TOPICS = 1000
BUCKET = 128       # table width W: one 128-lane register row
BLOCK_DOCS = 512
MAX_LEN = 512
N_HELDOUT = 16
SCALE = 1.0        # fraction of the AP corpus's D and N
CHECK_DOCS = 64


def check(ok: bool, what: str):
    if not ok:
        print(f"chip_smoke: FAILED: {what}", flush=True)
        sys.exit(1)
    print(f"  ok: {what}", flush=True)


def ap_corpus(seed: int):
    import numpy as np
    from repro.data.synthetic import paper_corpus

    return paper_corpus("ap", np.random.default_rng(seed), scale=SCALE,
                        max_len=MAX_LEN)


def make_stream(corpus, devices: int):
    from repro.launch.train import build_hdp_sampler, build_streaming

    corpus, sh = build_hdp_sampler(corpus, topics=K_TOPICS, bucket=BUCKET,
                                   z_impl="pallas")
    return build_streaming(corpus, sh, block_docs=BLOCK_DOCS,
                           devices=devices)


def full_z(stream, state):
    """(D, L) int32 assignments in corpus row order."""
    z = state.z_blocks.materialize().astype("int32")
    return z.reshape(-1, z.shape[-1])[: stream.store.num_docs]


def recount(stream, z):
    """n[k, v] and the (D, K) doc-topic counts from z, in numpy ints."""
    import numpy as np

    cfg, store = stream.cfg, stream.store
    mask = np.asarray(store.mask)
    tokens = np.asarray(store.tokens)
    n = np.zeros((cfg.K, cfg.V), np.int64)
    np.add.at(n, (z[mask], tokens[mask]), 1)
    rows = np.nonzero(mask)[0]
    m = np.zeros((store.num_docs, cfg.K), np.int32)
    np.add.at(m, (rows, z[mask]), 1)
    return n, m


def joint_loglik(stream, state, m):
    import jax.numpy as jnp
    from repro.core import hdp as H
    from repro.obs.diagnostics import make_joint_loglik_fn

    cfg = stream.cfg
    dh = H.d_histogram(jnp.asarray(m), cfg.hist_cap)
    return float(make_joint_loglik_fn(cfg)(state.n, dh, state.psi))


def train(corpus, seed: int, devices: int, iters: int, after=None):
    """Streaming training through the launch/train.py construction;
    returns (stream, state, per-iteration wall seconds). ``after(it,
    stream, state)`` runs outside the timed part of every iteration."""
    import jax

    stream = make_stream(corpus, devices)
    state = stream.init_state(jax.random.key(seed))
    walls = []
    for it in range(1, iters + 1):
        t0 = time.perf_counter()
        state = stream.iteration(state)
        jax.block_until_ready((state.n, state.phi, state.psi))
        walls.append(time.perf_counter() - t0)
        if after is not None:
            after(it, stream, state)
    return stream, state, walls


def kernel_vs_oracle(stream, state, seed: int):
    """The first CHECK_DOCS documents of block 0 through the compiled
    kernel and through the oracle, same tables and uniforms (documents
    sweep independently, so a slice of the block is a fair sample; the
    oracle's vmapped per-token alias build is slow at a whole block).
    The sweep starts from uniformly random topics so that most tokens
    move and both branches of the draw are exercised.
    Returns {path: (z mismatch fraction over live tokens, fraction the
    sweep moved, kernel m == recount of its z, words of the mismatched
    tokens)} and the prologue's (apsi, vals, ids) for ``alias_probe``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import hdp as H
    from repro.kernels.hdp_z import ops as zops
    from repro.kernels.hdp_z.hdp_z import hdp_z_pallas
    from repro.kernels.hdp_z.ref import hdp_z_ref, hdp_z_ref_prologue

    cfg = stream.cfg
    blk = stream.store.block(0)
    tokens = jnp.asarray(blk.tokens[:CHECK_DOCS])
    mask = jnp.asarray(blk.mask[:CHECK_DOCS])
    k_z, k_u = jax.random.split(jax.random.key(seed + 7))
    z0 = jax.random.randint(k_z, tokens.shape, 0, cfg.K, jnp.int32)
    u = jax.random.uniform(k_u, tokens.shape + (3,))
    phi = jnp.asarray(state.phi, jnp.float32)
    psi = jnp.asarray(state.psi)
    live = np.asarray(mask)

    vals, ids = zops.build_word_sparse_supports(phi, BUCKET)
    apsi = jnp.float32(cfg.alpha) * psi
    q_a, fpack, ipack = zops.build_word_sparse_tables(phi, psi, cfg.alpha,
                                                      BUCKET)
    ref_pro = jax.jit(hdp_z_ref_prologue, static_argnames=("kk",))
    ref_epi = jax.jit(hdp_z_ref, static_argnames=("kk",))
    runs = {
        "prologue": (
            hdp_z_pallas(tokens, mask, z0, u, apsi, vals, ids, kk=cfg.K,
                         in_kernel=True),
            ref_pro(tokens, mask, z0, u, apsi, vals, ids, kk=cfg.K)),
        "epilogue": (
            hdp_z_pallas(tokens, mask, z0, u, q_a, fpack, ipack, kk=cfg.K),
            ref_epi(tokens, mask, z0, u, q_a, fpack, ipack, kk=cfg.K)),
    }
    out = {}
    for path, (got, want) in runs.items():
        zk, mk = (np.asarray(a) for a in got)
        zr = np.asarray(want[0])
        bad = (zk != zr) & live
        n_live = live.sum()
        moved = float(((zk != np.asarray(z0)) & live).sum() / n_live)
        m_ok = np.array_equal(
            mk, np.asarray(H.doc_topic_counts(jnp.asarray(zk), mask, cfg.K)))
        words = np.unique(np.asarray(tokens)[bad])
        out[path] = (float(bad.sum() / n_live), moved, bool(m_ok), words)
    return out, (apsi, vals, ids)


def alias_rows_compiled(wa):
    """(prob, alias, row total) of each (W,) row of ``wa`` (rows a
    multiple of 8) from ``alias_build_row_onehot`` and ``prefix_sum``
    inside a compiled Pallas kernel, as the hdp_z prologue builds them."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from repro.core.alias import alias_build_row_onehot, last_lane, prefix_sum

    n, w = wa.shape

    def kern(wa_ref, p_ref, a_ref, q_ref):
        for i in range(8):
            row = wa_ref[i:i + 1, :]
            p, a = alias_build_row_onehot(row, pltpu.roll)
            p_ref[i:i + 1, :] = p
            a_ref[i:i + 1, :] = a
            q_ref[i:i + 1, :] = jnp.broadcast_to(
                last_lane(prefix_sum(row, pltpu.roll)), (1, w))

    spec = pl.BlockSpec((8, w), lambda g: (g, 0))
    return pl.pallas_call(
        kern, grid=(n // 8,), in_specs=[spec], out_specs=[spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((n, w), jnp.float32),
                   jax.ShapeDtypeStruct((n, w), jnp.int32),
                   jax.ShapeDtypeStruct((n, w), jnp.float32)])(wa)


def alias_probe(apsi, vals, ids, words):
    """For the given words, the alias row and row total the compiled
    kernel builds against the same functions in XLA. Returns (rows whose
    prob differs, rows whose alias differs, rows whose total differs,
    rows probed)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.alias import alias_build_row_onehot, prefix_sum

    words = jnp.asarray(np.resize(words, -(-len(words) // 8) * 8))
    wa = vals[words] * apsi[ids[words]]          # (n, W), as the kernel
    pk, ak, qk = jax.jit(alias_rows_compiled)(wa)
    px, ax = jax.jit(jax.vmap(alias_build_row_onehot))(wa)
    qx = jax.jit(lambda x: prefix_sum(x)[:, -1])(wa)
    pk, ak, qk, px, ax, qx = (np.asarray(x) for x in (pk, ak, qk, px, ax, qx))
    return (int((pk != px).any(1).sum()), int((ak != ax).any(1).sum()),
            int((qk[:, 0] != qx).sum()), wa.shape[0])


def serve(snap, docs, seed: int, impl: str):
    import jax
    import numpy as np
    from repro.serve.engine import ServeEngine

    eng = ServeEngine(snap, slots=8, burnin=8, impl=impl,
                      base_key=jax.random.key(seed))
    rids = [eng.submit(d, seed=i) for i, d in enumerate(docs)]
    t0 = time.perf_counter()
    got = eng.run()
    wall = time.perf_counter() - t0
    return np.stack([got[r] for r in rids]), wall


def heldout_docs(seed: int):
    held = ap_corpus(seed + 1)
    return [held.tokens[i][held.mask[i]] for i in range(N_HELDOUT)]


def on_simplex(mix) -> bool:
    import numpy as np

    return bool(np.all(mix >= 0) and np.all(np.abs(mix.sum(1) - 1) < 1e-5))


def one_chip(seed: int):
    import numpy as np

    print(f"[train] AP corpus, K={K_TOPICS}, W={BUCKET}, "
          f"block_docs={BLOCK_DOCS}, z_impl=pallas (compiled)", flush=True)
    lls = []

    def loglik(it, stream, state):
        _, m = recount(stream, full_z(stream, state))
        lls.append(joint_loglik(stream, state, m))

    stream, state, walls = train(ap_corpus(seed), seed, devices=1, iters=5,
                                 after=loglik)
    store = stream.store
    print(f"  corpus: D={store.num_docs} V={stream.cfg.V} "
          f"N={store.num_tokens} tokens, {store.num_blocks} blocks; "
          f"alias build in kernel: {stream.sh.alias_in_kernel}")
    steady = walls[2:]
    sec = sum(steady) / len(steady)
    print(f"  iteration wall s: {walls}")
    print(f"  info only: {sec} s/iter, {store.num_tokens / sec} tokens/s "
          "over iterations 3-5 (not a benchmark)", flush=True)
    print(f"  joint log-likelihood by iteration (information): {lls}")

    print("[check] training", flush=True)
    z = full_z(stream, state)
    n_re, _ = recount(stream, z)
    check(np.array_equal(n_re, np.asarray(state.n)),
          "n equals a from-scratch integer recount of z after iteration 5")
    check(all(np.isfinite(lls)), "log-likelihood finite at every iteration")

    res, supports = kernel_vs_oracle(stream, state, seed)
    for path, (frac, moved, m_ok, words) in res.items():
        print(f"  kernel vs oracle on {CHECK_DOCS} documents, {path}: z "
              f"mismatch fraction {frac} of live tokens (the sweep moved "
              f"{moved}); m == recount {m_ok}")
        if frac:
            print(f"  {path}: mismatched tokens fall on {len(words)} words:"
                  f" {words[:16].tolist()}")
    if res["prologue"][0]:
        diff = alias_probe(*supports, res["prologue"][3])
        print(f"  alias rows built in a compiled kernel vs XLA, on those "
              f"words: prob differs in {diff[0]}, alias in {diff[1]}, row "
              f"total in {diff[2]} of {diff[3]} rows")
    for path, (frac, _, m_ok, _) in res.items():
        check(m_ok, f"kernel m equals an integer recount of its z ({path})")
        check(frac == 0.0, f"compiled kernel == oracle bitwise ({path})")

    print(f"[serve] {N_HELDOUT} held-out AP documents, snapshot W={BUCKET}",
          flush=True)
    with tempfile.TemporaryDirectory() as tmp:
        snap = stream.export_snapshot(tmp, state, w=BUCKET)
    docs = heldout_docs(seed)
    mix = {}
    for impl in ("pallas", "sparse"):
        mix[impl], wall = serve(snap, docs, seed, impl)
        print(f"  impl={impl}: {len(docs)} requests in {wall} s "
              "(compile included)", flush=True)
        check(on_simplex(mix[impl]), f"every {impl} mixture row on the simplex")
    diff = float(np.max(np.abs(mix["pallas"] - mix["sparse"])))
    print(f"  max |pallas - sparse| = {diff}")
    check(np.array_equal(mix["pallas"], mix["sparse"]),
          "pallas and sparse fold-in agree bitwise on every request")


def four_chips(seed: int):
    import jax
    import numpy as np
    from repro.serve.fleet import ServeFleet

    devs = jax.devices()
    check(len(devs) >= 4, f"four devices visible (found {len(devs)})")
    iters = 2
    print(f"[lanes] {iters} streaming iterations, n_devices=1 vs 4",
          flush=True)
    runs = {}
    for n in (1, 4):
        stream, state, walls = train(ap_corpus(seed), seed, devices=n,
                                     iters=iters)
        print(f"  n_devices={n}: iteration wall s {walls}", flush=True)
        runs[n] = (stream, state)
    s4, st4 = runs[4]
    s1, st1 = runs[1]
    check(len({d.id for d in s4._lane_devices}) == 4,
          "the four sweep lanes sit on four distinct devices")
    for name in ("n", "phi", "varphi", "psi", "l", "it"):
        check(np.array_equal(np.asarray(getattr(st1, name)),
                             np.asarray(getattr(st4, name))),
              f"state.{name} bitwise equal, n_devices=4 vs 1")
    check(np.array_equal(np.asarray(jax.random.key_data(st1.key)),
                         np.asarray(jax.random.key_data(st4.key))),
          "chain key equal, n_devices=4 vs 1")
    check(all(np.array_equal(st1.z_blocks.peek(b), st4.z_blocks.peek(b))
              for b in range(s1.store.num_blocks)),
          "every z slab bitwise equal, n_devices=4 vs 1")

    print("[fleet] 4 workers on 4 devices vs one engine", flush=True)
    with tempfile.TemporaryDirectory() as tmp:
        snap = s1.export_snapshot(tmp, st1, w=BUCKET)
    docs = heldout_docs(seed) * 2
    one, _ = serve(snap, docs, seed, "pallas")
    # two slots a worker, so the 32 requests cannot all sit on one
    with ServeFleet(snap, workers=4, slots=2, burnin=8, impl="pallas",
                    base_key=jax.random.key(seed)) as fleet:
        check(len({w.device.id for w in fleet.workers}) == 4,
              "the four fleet workers sit on four distinct devices")
        rids = [fleet.submit(d, seed=i) for i, d in enumerate(docs)]
        got = fleet.run()
        per_worker = [w.completed for w in fleet.workers]
    print(f"  requests completed per worker: {per_worker}")
    check(min(per_worker) > 0, "every fleet worker served requests")
    fleet_mix = np.stack([got[r] for r in rids])
    check(np.array_equal(fleet_mix, one),
          f"4-worker fleet == one engine on all {len(docs)} requests")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip lane and fleet phase")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); this check runs only on the chip",
              file=sys.stderr)
        sys.exit(2)

    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    print(f"device: {dev.device_kind} x{len(jax.devices())}, "
          f"jax {jax.__version__}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(f"total wall s: {time.perf_counter() - t0}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
