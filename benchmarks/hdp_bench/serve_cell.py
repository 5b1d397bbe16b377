"""Serving cells: open-loop fold-in requests through ``ServeEngine``.

Set-up draws the planted model from the seed, counts n from a planted
training corpus, draws the snapshot weights from n (the Poisson
Polya-urn draw, in the benchmark's own code) and psi from the planted
topic sizes, and builds the snapshot with the program's
``serve/snapshot.py`` ``build_snapshot``. The queries are held-out
documents of the same planted model on a seed stream of their own; the
send times are a Poisson process at the traffic's fixed rate, the same
for every seed, as is the multiset of query lengths (``gen.open_loop``). Set-up
warms every bucket's programs with requests outside the measured set.

The window sends each request at its time (``ServeEngine.submit``),
drives ``step`` and collects mixtures from ``drain_completed``, one
thread. A request's latency runs from its scheduled send time to the
``drain_completed`` that hands its mixture back, so a stall delays every
request behind it. After ``seconds`` nothing more is due; the requests
sent are drained and timed, for at most ``drain_s`` more.

``correct`` holds the run to the plain reference (``reference.py``):

  unanswered             requests due in the window that never came back
                         (limit 0)
  mixture_mismatch_share share of a sample of the answered requests (drawn
                         from the seed, the longest request in it) whose
                         mixture differs in any topic from the reference
                         fold-in of that request; the limit is the
                         traffic's ``mixture_mismatch_share_limit``, set
                         from sound runs and from the control (PERF.md): a
                         token whose uniform lands within float32 rounding
                         of a topic boundary may go either way
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.hdp_bench import gen, reference as R, work
from benchmarks.hdp_bench.harness import (Outcome, Phases, Window,
                                          memory_peak, note)

WARM_SEED0 = 1 << 30   # request ids of the warm-up, apart from the window's


def snapshot_weights(cfg: dict, seed: int):
    """(phi (K, V) f32, psi (K,) f32) drawn from the planted state, and
    the planted topics."""
    import jax
    import jax.numpy as jnp

    k, v = cfg["K"], cfg["V"]
    topics = gen.planted_topics(seed, cfg["corpus"], v)
    docs = gen.draw_docs(gen.rng_for(seed, gen.TRAIN_DOCS), topics,
                         cfg["D"], cfg["corpus"])
    # tokens padded to a multiple of 2^18, so that one program serves
    # every seed's corpus
    size = -(-docs.words.size // (1 << 18)) * (1 << 18)
    live = np.zeros((1, size), bool)
    live[0, :docs.words.size] = True
    n = R.recount(jnp.asarray(np.resize(docs.topics, (1, size))),
                  jnp.asarray(np.resize(docs.words, (1, size))),
                  jnp.asarray(live), k=k, v=v)
    key = jax.random.key(gen.jax_seed(seed))
    _, phi = R.phi_step(key, n, beta=cfg["beta"])
    sizes = np.bincount(docs.topics, minlength=k).astype(np.float64)
    psi = sizes + cfg["gamma"] / k
    return phi, jnp.asarray((psi / psi.sum()).astype(np.float32)), topics


def bucket_of(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def run(cell, *, seed: int, seconds: float, t0: float, trace_dir=None,
        log=None) -> Outcome:
    import jax
    import jax.numpy as jnp
    from repro.serve.engine import ServeEngine
    from repro.serve.snapshot import build_snapshot

    cfg, traffic = cell.config, cell.traffic
    srv = cfg["serve"]
    k, w, burnin = cfg["K"], cfg["W"], srv["burnin"]
    buckets = tuple(sorted(srv["buckets"]))
    phase = Phases()
    with note("setup"):
        phi, psi, topics = snapshot_weights(cfg, seed)
        phase("weights")
        snap = build_snapshot(phi, psi, cfg["alpha"], w=w)
        jax.block_until_ready(snap)
        phase("snapshot")
        sched, lengths = gen.open_loop(seed, traffic["rate_docs_per_s"],
                                       seconds, cfg["corpus"])
        queries = gen.draw_docs(gen.rng_for(seed, gen.QUERY_DOCS), topics,
                                len(sched), cfg["corpus"], lengths)
        docs = [queries.doc(i) for i in range(len(sched))]
        phase("queries")
        base_key = jax.random.key(gen.jax_seed(seed))
        engine = ServeEngine(snap, slots=srv["slots"], burnin=burnin,
                             impl="pallas", buckets=buckets,
                             base_key=base_key)
        longest = max(docs, key=len)
        for j, b in enumerate(buckets):
            engine.submit(np.resize(longest, b), seed=WARM_SEED0 + j)
        engine.run()
        phase("warm-up")
    setup_s = time.perf_counter() - t0
    steps0 = engine.stats.steps
    done_at, theta = {}, {}
    late, step_s = [], 0.0
    n = len(sched)
    i = 0
    with Window(trace_dir) as win:
        start = win.t0
        window_s = None
        while True:
            now = time.perf_counter() - start
            with note("submit"):
                while i < n and sched[i] <= now:
                    late.append(time.perf_counter() - start - sched[i])
                    engine.submit(docs[i], seed=i)
                    i += 1
            if window_s is None and now >= seconds:
                window_s = win.close()
                steps_window = engine.stats.steps - steps0
            if now >= seconds + traffic["drain_s"]:
                break
            if engine.in_flight():
                t = time.perf_counter()
                with note("engine_step"):
                    engine.step()
                    got = engine.drain_completed()
                step_s += (time.perf_counter() - t) if window_s is None else 0
                t = time.perf_counter() - start
                for rid, th in got.items():
                    done_at[rid] = t
                    theta[rid] = th
            elif i < n:
                with note("wait"):
                    time.sleep(max(0.0, sched[i] - now))
            elif window_s is not None:
                break
            else:
                with note("wait"):
                    time.sleep(max(0.0, seconds - now))
        if window_s is None:
            window_s = win.close()
            steps_window = engine.stats.steps - steps0
    peak = memory_peak(jax.devices()[:cell.chips])
    spans, span_args = win.spans() if trace_dir else ({}, [])
    reduced = win.reduce() if trace_dir else None
    del engine, snap

    lat = np.array([done_at[r] - sched[r] if r in done_at
                    else seconds + traffic["drain_s"] - sched[r]
                    for r in range(n)])
    in_window = [r for r in range(n) if r in done_at and done_at[r] <= seconds]
    unanswered = n - len(done_at)

    # the reference on a sample of the answered requests
    t_ref = time.perf_counter()
    rng = gen.rng_for(seed, gen.CHECK)
    answered = sorted(done_at)
    take = min(traffic["check_requests"], len(answered))
    sample = set(rng.choice(answered, take, replace=False).tolist()) \
        if take else set()
    if answered:
        sample.add(max(answered, key=lambda r: len(docs[r])))
    tabs = R.word_tables(phi, psi, cfg["alpha"], w=w, by_topic=True)
    worst, differ = 0.0, 0
    by_bucket: dict = {}
    for r in sorted(sample):
        by_bucket.setdefault(bucket_of(len(docs[r]), buckets), []).append(r)
    for b, rids in by_bucket.items():
        # one batch shape per bucket whatever the sample, so the
        # reference compiles once and then comes from the cache
        rows = traffic["check_requests"] + 1
        tok = np.zeros((rows, b), np.int32)
        msk = np.zeros((rows, b), bool)
        seeds = np.zeros((rows,), np.int32)
        seeds[:len(rids)] = rids
        for j, r in enumerate(rids):
            d = docs[r][:b]
            tok[j, :d.size] = d
            msk[j, :d.size] = True
        ref = R.foldin(jnp.asarray(tok), jnp.asarray(msk),
                       jnp.asarray(seeds), base_key,
                       *tabs, psi, jnp.float32(cfg["alpha"]),
                       burnin=burnin, kk=k)
        got = np.stack([theta[r] for r in rids])
        gap = np.abs(got - np.asarray(ref)[:len(rids)]).max(1)
        worst = max(worst, float(gap.max()))
        differ += int((gap > 0).sum())
    ref_s = time.perf_counter() - t_ref
    checks = [("unanswered", unanswered, 0),
              ("mixture_mismatch_share", differ / max(len(sample), 1),
               traffic["mixture_mismatch_share_limit"])]

    lengths = np.minimum(lengths, buckets[-1])
    # hdp_z work of the window's engine steps: the live tokens of the
    # requests answered in the window, ``burnin`` sweeps each, and every
    # slot position of the engine steps the program's spans record
    steps = [a for name, a in span_args if name == "engine_step"]
    kernel_work = work.hdp_z(
        live=int(lengths[in_window].sum()) * burnin if in_window else 0,
        positions=sum(srv["slots"] * int(a.get("bucket", 0))
                      for a in steps),
        rows=srv["slots"] * len(steps), k=k, w=w, prologue=False)
    req_work = [work.foldin_request(tokens=int(lengths[r]),
                                    bucket=bucket_of(len(docs[r]), buckets),
                                    k=k, w=w, sweeps=burnin)
                for r in in_window]
    late = np.array(late) if late else np.zeros(1)
    counts = {"requests": n, "completed_in_window": len(in_window),
              "window_s": window_s, "steps_in_window": steps_window,
              "slots": srv["slots"], "burnin": burnin,
              "step_s_in_window": step_s,
              "live_tokens_in_window": int(lengths[in_window].sum())
              if in_window else 0,
              "p50_ms": float(np.percentile(lat, 50) * 1e3),
              "p95_ms": float(np.percentile(lat, 95) * 1e3),
              "send_late_p95_ms": float(np.percentile(late, 95) * 1e3),
              "send_late_max_ms": float(late.max() * 1e3),
              "compiles_in_window": win.compiles, "setup_s": setup_s,
              "checked_requests": len(sample),
              "mixture_max_abs_diff": worst}
    if log is not None:
        print(f"serve: {n} requests at {traffic['rate_docs_per_s']} docs/s "
              f"offered; {len(in_window)} done in the {window_s:.3f} s "
              f"window; p50 {counts['p50_ms']:.2f} ms p95 "
              f"{counts['p95_ms']:.2f} ms; send lateness p95 "
              f"{counts['send_late_p95_ms']:.3f} ms max "
              f"{counts['send_late_max_ms']:.3f} ms; set-up {setup_s:.3f} s ("
              f"{phase}; before: {setup_s - sum(t for _, t in phase.done):.2f}"
              f" s); reference {ref_s:.2f} s over {len(sample)} requests, "
              f"largest mixture difference {worst}; compiles in window "
              f"{win.compiles}", file=log, flush=True)
    return Outcome(
        e2e={"serve_p95_ms": counts["p95_ms"],
             "serve_docs_per_s": len(in_window) / window_s,
             "setup_s": setup_s},
        checks=checks, attempted=n, failed=unanswered,
        memory_peak_bytes=peak, counts=counts,
        work={"requests": req_work, "hdp_z": kernel_work}, spans=spans, span_args=span_args,
        trace=reduced)
