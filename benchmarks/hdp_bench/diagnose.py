#!/usr/bin/env python3
"""Where a training cell's ``z_mismatch_share.it1`` comes from, per seed.

``correct`` compares the program's iteration 1 with the reference sweep
over the reference's own tables. This splits that one number:

  tables         per table plane (q_a, vals, aprob, ids, aalias): the
                 live words whose row differs between the program's
                 build and the reference's, and the tokens on them
  vs_ref         the check's own number: the program's iteration 1
                 (``StreamingHDP.iteration``, the timed path's call)
                 against the reference sweep over the reference tables
  vs_ref_on_program_tables   the same sweep over the program's tables:
                 what is left is the kernel's own parting
  vs_ref_counts_alias        over the reference tables with the alias
                 partition taken by counting comparisons (as the
                 program's one-hot build takes it) instead of binary
                 searches over the cumulative lines
  twice          with ``--twice``: tokens on which a second build and
                 iteration of the same seed part from the first
  first_words    the words at the first parting token of each row that
                 parts, most frequent first: (rows, word, its tokens)

  python3 benchmarks/hdp_bench/diagnose.py --config hdp-pubmed \
      --seeds 1317388283 2 3 [--twice]

``--config`` names a configuration file (one chip); ``--workload`` a
training cell of ``BENCHMARK.json``.

One JSON line per seed on standard output. It runs where JAX's default
device is, like the benchmark; on the CPU the kernel is interpreted.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from types import SimpleNamespace


def alias_row_counts(p):
    """The reference's index-ordered alias partition of one row with
    each binary search replaced by the count it stands for: over a
    nondecreasing line both give the same table; over a line that dips
    by an ulp they may part."""
    import jax.numpy as jnp

    from benchmarks.hdp_bench import reference as R

    k = p.shape[0]
    p = jnp.where((p > 0) & (p < jnp.inf), p, 0.0)
    total = R._last(R.prefix_sum(p))
    q = jnp.where(total > 0, p / jnp.maximum(total, 1e-30) * k,
                  jnp.ones_like(p))
    pos = jnp.arange(k, dtype=jnp.int32)
    small = q < 1.0
    large = ~small
    ns = jnp.sum(small.astype(jnp.int32))
    nl = k - ns
    rank_s = jnp.cumsum(small.astype(jnp.int32)) - 1
    rank_l = jnp.cumsum(large.astype(jnp.int32)) - 1
    d = jnp.where(small, 1.0 - q, 0.0)
    u = jnp.where(large, q - 1.0, 0.0)
    dcum = R.prefix_sum(d)
    ucum = R.prefix_sum(u)
    dprev = dcum - d

    def large_at(rank):     # position of the large of each given rank
        hit = large[None, :] & (rank_l[None, :] == rank[:, None])
        return jnp.sum(jnp.where(hit, pos[None, :], 0), axis=1)

    # [i, j]: entry i of the row against entry j
    r = jnp.sum(large[None, :] & (ucum[None, :] < dprev[:, None]), axis=1)
    alias_small = jnp.where(small & (r < nl), large_at(r), pos)
    mstar = jnp.sum(small[None, :] & (dcum[None, :] <= ucum[:, None]),
                    axis=1)
    demoted = large & (mstar < ns)
    s_at = jnp.sum(jnp.where(small[None, :]
                             & (rank_s[None, :] == mstar[:, None]),
                             dcum[None, :], 0.0), axis=1)
    has_next = demoted & (rank_l + 1 < nl)
    prob = jnp.where(small, q, jnp.where(demoted, 1.0 + ucum - s_at, 1.0))
    alias = jnp.where(small, alias_small,
                      jnp.where(has_next, large_at(rank_l + 1), pos))
    return (jnp.clip(prob, 0.0, 1.0).astype(jnp.float32),
            alias.astype(jnp.int32))


def alias_counts(wa, step: int = 2048):
    """``alias_row_counts`` over the (V, W) rows, ``step`` rows at a
    time so the (W, W) comparisons stay small."""
    import jax
    import jax.numpy as jnp

    n, w = wa.shape
    step = min(step, n)
    blocks = jnp.pad(wa, ((0, -n % step), (0, 0))).reshape(-1, step, w)
    p, a = jax.jit(lambda b: jax.lax.map(jax.vmap(alias_row_counts), b))(
        blocks)
    return p.reshape(-1, w)[:n], a.reshape(-1, w)[:n]


def _words_bad(a, b, live, counts) -> list:
    import numpy as np

    bad = (a != b).reshape(a.shape[0], -1).any(axis=1) & live
    w = np.flatnonzero(bad)
    top = sorted(((int(counts[x]), int(x)) for x in w), reverse=True)[:4]
    return [int(bad.sum()), int(counts[w].sum()), top]


def _iteration_z(cfg, seed, chips):
    from benchmarks.hdp_bench import train_cell

    stream, state, zpad = train_cell.build(cfg, seed, chips=chips)
    state = stream.iteration(state)
    return state.z_blocks.materialize().reshape(-1, zpad.shape[1])


def diagnose(cell, seed: int, twice: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.hdp_bench import reference as R, train_cell

    cfg = cell.config
    k, v, w = cfg["K"], cfg["V"], cfg["W"]
    t = time.perf_counter()
    stream, state, zpad = train_cell.build(cfg, seed, chips=cell.chips)
    _, k_phi, k_u, _, _ = R.iteration_keys(state.key)
    q_a, fpack, ipack = stream._phi_fn(state.n, state.psi, k_phi)[2]
    if fpack.ndim != 3 or fpack.shape[1] != 2:
        raise SystemExit("diagnose: the program builds no packed tables "
                         "(kernel-prologue build)")
    prog = tuple(np.asarray(a) for a in (q_a, fpack[:, 0], fpack[:, 1],
                                         ipack[:, 0], ipack[:, 1]))
    del q_a, fpack, ipack
    _, phi = R.phi_step(k_phi, state.n, beta=cfg["beta"])
    ref = tuple(np.asarray(a) for a in R.word_tables(
        phi, state.psi, cfg["alpha"], w=w))
    del phi
    wa = jnp.asarray(ref[1]) * (jnp.float32(cfg["alpha"])
                                * state.psi)[jnp.asarray(ref[3])]
    cp, ca = alias_counts(wa)
    counted = (ref[0], ref[1], np.asarray(cp), ref[3], np.asarray(ca))
    del wa, cp, ca
    tokens, mask = train_cell.padded(stream.store)
    db, ll = stream.store.block_docs, stream.store.max_len
    counts = np.bincount(tokens[mask], minlength=v)
    live = counts > 0
    planes = ("q_a", "vals", "aprob", "ids", "aalias")
    res = {"seed": seed, "tables": {
        n: _words_bad(a, b, live, counts)
        for n, a, b in zip(planes, prog, ref)},
        "counts_alias_vs_ref": {
        n: _words_bad(a, b, live, counts)
        for n, a, b in zip(planes[2:], counted[2:], ref[2:])}}
    state = stream.iteration(state)
    jax.block_until_ready(state.n)
    zi = state.z_blocks.materialize().reshape(-1, zpad.shape[1])
    del state, stream
    bad = {"ref": 0, "prog": 0, "counted": 0}
    firsts = []
    for b in range(tokens.shape[0] // db):
        rows = slice(b * db, (b + 1) * db)
        u = R.block_uniforms(k_u, b, (db, ll))
        args = (jnp.asarray(tokens[rows]), jnp.asarray(mask[rows]),
                jnp.asarray(zpad[rows]), u)
        for tag, tabs in (("ref", ref), ("prog", prog),
                          ("counted", counted)):
            zr = np.asarray(R.sweep(*args, *map(jnp.asarray, tabs),
                                    kk=k)[0])
            d = (zr != zi[rows]) & mask[rows]
            bad[tag] += int(d.sum())
            if tag == "ref":
                for r in np.flatnonzero(d.any(1)):
                    firsts.append(int(tokens[rows][r, np.argmax(d[r])]))
    n_live = int(mask.sum())
    res["vs_ref"] = bad["ref"] / n_live
    res["vs_ref_on_program_tables"] = bad["prog"] / n_live
    res["vs_ref_counts_alias"] = bad["counted"] / n_live
    fw = np.bincount(np.asarray(firsts, np.int64), minlength=v)
    res["rows_parting"] = len(firsts)
    res["first_words"] = [(int(fw[x]), int(x), int(counts[x]))
                          for x in np.argsort(-fw)[:6] if fw[x]]
    if twice:
        res["twice"] = int(((_iteration_z(cfg, seed, cell.chips) != zi)
                            & mask).sum())
    res["s"] = round(time.perf_counter() - t, 1)
    return res


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    which = ap.add_mutually_exclusive_group(required=True)
    which.add_argument("--workload")
    which.add_argument("--config")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--twice", action="store_true")
    args = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path[:0] = [root, os.path.join(root, "src")]
    from benchmarks.hdp_bench import harness
    from benchmarks.hdp_bench.bench import Bench

    harness.configure_jax()
    bench = Bench.load()
    if args.config:
        cell = SimpleNamespace(config=bench.data("configs", args.config),
                               chips=1)
    else:
        cell = bench.cell(args.workload)
        if cell.traffic["kind"] != "train":
            sys.exit("diagnose: a training cell")
    for seed in args.seeds:
        print(json.dumps(diagnose(cell, seed, args.twice)), flush=True)


if __name__ == "__main__":
    main()
