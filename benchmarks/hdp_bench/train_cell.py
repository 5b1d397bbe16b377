"""Training cells: streaming Gibbs iterations over a planted corpus.

Set-up draws the corpus from the seed, builds the sampler through the
program's own entry points (``launch/train.py`` ``build_hdp_sampler`` and
``build_streaming``), starts the chain from the planted assignments with
n counted from them, and runs iteration 1 through
``StreamingHDP.iteration``, which compiles every program the window runs.
The window then runs whole iterations of that same object until
``seconds`` have passed.

``correct`` holds the run to the plain reference (``reference.py``),
computed after the window, on two iterations: iteration 1 (``.it1``),
from the start state the benchmark drew, and one more iteration of the
same object after the window (``.chk``), from the state the window left,
through the same call and the same compiled programs. For each:

  varphi_mismatch   cells where the Poisson Polya-urn draw differs from
                    the reference's (Phi/tables layer)
  z_mismatch_share  share of the corpus's live tokens whose topic after
                    the sweep differs from the reference sweep over the
                    reference's tables (z-step kernel)
  n_recount_mismatch  cells where n after the iteration differs from a
                    recount of its z (delta/merge layer)
  l_mismatch        topics where l differs from the reference's draw
                    from the document histogram of the iteration's z
  psi_max_abs_diff  largest difference of psi from the reference's
                    stick-breaking draw from that l (l and psi layer)

and besides

  n_recount_mismatch.end  the same for the state the window left
  key_mismatch      iterations (the window's last, the check) whose
                    chain key is not the reference's after as many
                    iterations: a window iteration that hands back its
                    state shows here
  lanes_missing     the cell's chips less the number of them on which
                    the program placed its sweep lanes: lanes stacked on
                    one chip show here

The sampler sweeps one lane per chip of the cell (``build_streaming``'s
``devices``). The lanes' chain is bitwise the one-lane chain, so the
reference is the same at every chip count; a lane's delta left out of
the exchange between chips shows in ``n_recount_mismatch``.

The draws, the recounts and the keys are exact (limit 0). The sweep's
limit is the traffic's ``z_mismatch_share_limit`` and psi's its
``psi_max_abs_diff_limit``, set from sound runs and from the control
(PERF.md): a float32 sampler whose uniform lands within rounding of a
boundary between two topics takes either, so the compiled kernel and
the reference may part on a few tokens in a million.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.hdp_bench import gen, reference as R, work
from benchmarks.hdp_bench.harness import (Outcome, Phases, Window,
                                          memory_peak, note)


def planted_corpus(cfg: dict, seed: int):
    """(tokens, planted z, mask) rows of the training corpus."""
    topics = gen.planted_topics(seed, cfg["corpus"], cfg["V"])
    docs = gen.draw_docs(gen.rng_for(seed, gen.TRAIN_DOCS), topics,
                         cfg["D"], cfg["corpus"])
    return gen.pack_rows(docs, cfg["max_len"])


def build(cfg: dict, seed: int, phase=lambda name: None, chips: int = 1):
    """The program's stream over the planted corpus, sweeping ``chips``
    lanes, its start state (``StreamingState`` with the planted z slabs
    and n counted from them; psi proportional to the planted topic sizes
    plus gamma / K), and the planted z as the blocks' padded rows, in the
    store's row order (blocks padded to a row count the lanes divide)."""
    import jax
    import jax.numpy as jnp
    from repro.core.streaming import StreamingState
    from repro.data.corpus import Corpus, shard_balanced
    from repro.data.zstore import make_zslab_store
    from repro.launch.train import build_hdp_sampler, build_streaming

    k, v = cfg["K"], cfg["V"]
    tokens, z, mask = planted_corpus(cfg, seed)
    phase("corpus")
    corpus, sh = build_hdp_sampler(Corpus(tokens, mask, v), topics=k,
                                   bucket=cfg["W"], z_impl="pallas")
    for name in ("alpha", "beta", "gamma", "hist_cap"):
        if getattr(sh.cfg, name) != cfg[name]:
            raise ValueError(f"{name}: the sampler runs {getattr(sh.cfg, name)}"
                             f", the configuration states {cfg[name]}")
    # the sampler reorders rows by length; the same deterministic order
    # applied to the planted z keeps every token with its topic
    z = shard_balanced(Corpus(z, mask, v), 1).tokens
    stream = build_streaming(corpus, sh, block_docs=cfg["block_docs"],
                             devices=chips)
    phase("sampler")
    store = stream.store
    db, nb, ll = store.block_docs, store.num_blocks, store.max_len
    zpad = np.zeros((nb * db, ll), np.int32)
    zpad[:z.shape[0]] = z
    slabs = make_zslab_store("ram", nb, (db, ll), dtype=stream.z_dtype)
    for b in range(nb):
        slabs.write(b, zpad[b * db:(b + 1) * db])
    sizes = np.bincount(z[corpus.mask], minlength=k).astype(np.float64)
    psi = sizes + cfg["gamma"] / k
    psi = (psi / psi.sum()).astype(np.float32)
    shard = sh.state_shardings()
    tokens, mask = padded(store)
    n = jax.device_put(R.recount(jnp.asarray(zpad), jnp.asarray(tokens),
                                 jnp.asarray(mask), k=k, v=v), shard.n)
    state = StreamingState(
        n=n, phi=n, varphi=n, psi=jax.device_put(jnp.asarray(psi), shard.psi),
        l=jax.device_put(jnp.zeros((k,), jnp.int32), shard.l),
        key=jax.random.key(gen.jax_seed(seed)), it=jnp.int32(0),
        z_blocks=slabs)
    phase("state")
    return stream, state, zpad


def padded(store):
    """(tokens, mask) of every block laid end to end: the same shape for
    every seed, so programs over them compile once."""
    nb, db, ll = store.num_blocks, store.block_docs, store.max_len
    tokens = np.zeros((nb * db, ll), np.int32)
    mask = np.zeros((nb * db, ll), bool)
    tokens[:store.num_docs] = store.tokens
    mask[:store.num_docs] = store.mask
    return tokens, mask


def _ready(state):
    import jax

    jax.block_until_ready((state.n, state.phi, state.psi, state.l))


def _taken(state, z=None) -> dict:
    """What a check reads of a state: z as host rows, the rest on the
    device."""
    z = state.z_blocks.materialize() if z is None else z
    return {"z": z.reshape(-1, z.shape[-1]), "n": state.n,
            "varphi": state.varphi, "psi": state.psi, "l": state.l,
            "key": state.key}


def lanes_missing(stream, devices) -> int:
    """``devices`` less the number of them that hold a sweep lane of
    ``stream`` (one lane on the sampler's device when it sweeps one).
    The lanes' jitted sweeps run where their inputs are placed, on these
    devices; ``StreamingHDP`` keeps them in ``_lane_devices``."""
    return len(devices) - len(set(devices) & set(stream._lane_devices))


def _key_bad(a, b) -> int:
    import jax

    return int(not (jax.random.key_data(a) == jax.random.key_data(b)).all())


def compare(cfg: dict, block: tuple, tokens, mask, before: dict,
            after: dict, log=None, tag="") -> list:
    """The checks of one iteration that took ``before`` to ``after``,
    against the reference started from ``before``."""
    import jax.numpy as jnp

    k, v, w = cfg["K"], cfg["V"], cfg["W"]
    db, ll = block
    _, k_phi, k_u, k_l, k_psi = R.iteration_keys(before["key"])
    varphi, phi = R.phi_step(k_phi, before["n"], beta=cfg["beta"])
    varphi_bad = int(jnp.sum(varphi != after["varphi"]))
    del varphi
    tabs = R.word_tables(phi, jnp.asarray(before["psi"]), cfg["alpha"],
                         w=w)
    del phi
    z_bad, where = 0, []
    for b in range(tokens.shape[0] // db):
        rows = slice(b * db, (b + 1) * db)
        u = R.block_uniforms(k_u, b, (db, ll))
        zr, _ = R.sweep(jnp.asarray(tokens[rows]), jnp.asarray(mask[rows]),
                        jnp.asarray(before["z"][rows]), u, *tabs, kk=k)
        bad = (np.asarray(zr) != after["z"][rows]) & mask[rows]
        z_bad += int(bad.sum())
        for r, c in zip(*np.nonzero(bad)):
            if len(where) < 8:
                where.append((b, int(r), int(c), int(tokens[rows][r, c])))
    del tabs
    z_d, tok_d, mask_d = (jnp.asarray(after["z"]), jnp.asarray(tokens),
                          jnp.asarray(mask))
    n_bad = int(jnp.sum(R.recount(z_d, tok_d, mask_d, k=k, v=v)
                        != after["n"]))
    dh = R.doc_histogram(z_d, mask_d, k=k, cap=cfg["hist_cap"])
    l, psi = R.tail_step(k_l, k_psi, dh, jnp.asarray(before["psi"]),
                         alpha=cfg["alpha"], gamma=cfg["gamma"])
    l_bad = int(jnp.sum(l != after["l"]))
    psi_gap = float(jnp.max(jnp.abs(psi - after["psi"])))
    if log is not None and where:
        print(f"train: z mismatches {tag} (block, row, position, word): "
              f"{where}", file=log)
    return [(f"varphi_mismatch.{tag}", varphi_bad, 0),
            (f"z_mismatch_share.{tag}", z_bad / int(mask.sum()),
             cfg["z_mismatch_share_limit"]),
            (f"n_recount_mismatch.{tag}", n_bad, 0),
            (f"l_mismatch.{tag}", l_bad, 0),
            (f"psi_max_abs_diff.{tag}", psi_gap,
             cfg["psi_max_abs_diff_limit"])]


def run(cell, *, seed: int, seconds: float, t0: float, trace_dir=None,
        log=None) -> Outcome:
    import jax
    import jax.numpy as jnp

    cfg = cell.config
    k, v, w = cfg["K"], cfg["V"], cfg["W"]
    phase = Phases()
    with note("setup"):
        stream, state, z0 = build(cfg, seed, phase, chips=cell.chips)
        store = stream.store
        before1 = _taken(state, z0)
        with note("iteration"):
            state = stream.iteration(state)
            _ready(state)
        phase("iteration 1")
        after1 = _taken(state)
    setup_s = time.perf_counter() - t0
    walls = []
    with Window(trace_dir) as win:
        while True:
            t = time.perf_counter()
            with note("iteration"):
                state = stream.iteration(state)
                _ready(state)
            walls.append(time.perf_counter() - t)
            if time.perf_counter() - win.t0 >= seconds:
                break
        window_s = win.close()
    devices = jax.devices()[:cell.chips]
    peak = memory_peak(devices)
    missing = lanes_missing(stream, devices)
    spans, span_args = win.spans() if trace_dir else ({}, [])
    reduced = win.reduce() if trace_dir else None
    # the check iteration: the same object and call, after the window
    t_chk = time.perf_counter()
    end = _taken(state)
    state = stream.iteration(state)
    _ready(state)
    after_chk = _taken(state)
    chk_s = time.perf_counter() - t_chk
    live = store.num_tokens
    its = len(walls)
    prologue = bool(stream.sh.alias_in_kernel)
    db, nb, ll = store.block_docs, store.num_blocks, store.max_len
    del state, stream

    # the reference, after the window and with the program's state gone
    t_ref = time.perf_counter()
    tokens, mask = padded(store)
    limits = dict(cfg, **cell.traffic)
    checks = compare(limits, (db, ll), tokens, mask, before1, after1,
                     log, "it1")
    checks += compare(limits, (db, ll), tokens, mask, end, after_chk,
                      log, "chk")
    n_end_bad = int(jnp.sum(R.recount(
        jnp.asarray(end["z"]), jnp.asarray(tokens), jnp.asarray(mask),
        k=k, v=v) != end["n"]))
    key_end = R.key_chain(before1["key"], 1 + its)
    key_bad = (_key_bad(end["key"], key_end)
               + _key_bad(after_chk["key"], R.key_chain(key_end, 1)))
    checks += [("n_recount_mismatch.end", n_end_bad, 0),
               ("key_mismatch", key_bad, 0),
               ("lanes_missing", missing, 0)]
    ref_s = time.perf_counter() - t_ref
    counts = {"iterations": its, "window_s": window_s, "walls_s": walls,
              "live_tokens": live, "positions": nb * db * ll,
              "rows": nb * db, "compiles_in_window": win.compiles,
              "setup_s": setup_s, "prologue": prologue}
    if log is not None:
        print(f"train: {live} live tokens in {nb} blocks of {db} x {ll}; "
              f"{its} iterations in {window_s:.3f} s; "
              f"set-up {setup_s:.3f} s ({phase}; before: "
              f"{setup_s - sum(t for _, t in phase.done):.2f} s); check "
              f"iteration {chk_s:.2f} s; reference {ref_s:.2f} s; compiles "
              f"in window {win.compiles}", file=log, flush=True)
    it_work = work.iteration(live=live, k=k, v=v, w=w)
    z_work = work.hdp_z(live=live * its, positions=nb * db * ll * its,
                        rows=nb * db * its, k=k, w=w, prologue=prologue)
    return Outcome(
        e2e={"train_tokens_per_s": its * live / window_s,
             "setup_s": setup_s},
        checks=checks, attempted=its, failed=0, memory_peak_bytes=peak,
        counts=counts, work={"iteration": it_work, "hdp_z": z_work},
        spans=spans, span_args=span_args, trace=reduced)
