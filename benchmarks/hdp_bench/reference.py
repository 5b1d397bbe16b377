"""Plain reference of the HDP Gibbs step and of fold-in, for ``correct``.

It imports nothing of the program and takes nothing the program made:
it starts from the state and the snapshot weights the benchmark drew
from the seed, and draws its own randomness from the same keys. The
arithmetic is copied from the program's documented contracts, so that a
sound run agrees with it exactly:

  * keys: an iteration splits the chain key into
    ``(key, k_phi, k_u, k_l, k_psi)``; block b of the sweep draws its
    (DB, L, 3) uniforms from ``fold_in(k_ub, 0)`` with ``k_ub = k_u`` for
    b == 0 and ``fold_in(k_u, b)`` after;
  * Phi-step: ``varphi ~ Poisson(n + beta)`` from ``fold_in(k_phi, 0)``,
    ``phi = varphi / max(row sum, 1)``;
  * word tables: per word the top-W topics of phi (by value for
    training, sorted by topic id for serving snapshots), weights
    ``wa = vals * alpha * psi[ids]``, the row total ``q_a`` and Walker's
    alias partition of ``wa`` in the index-ordered form
    (``repro.core.alias._alias_build_row_flat``); row totals and
    cumulative lines are the log-step prefix sum, which fixes the order
    of additions;
  * z-step (Section 2.5 of the paper): for each token in document order,
    remove it from m, then ``t = u1 * (q_a + q_b)`` with
    ``q_b = sum(vals * m[ids])``; t < q_b draws from the document term
    by inverse CDF, else the global term by the alias draw with u2, u3;
  * l and psi (Sections 2.4 and 2.6): the document histogram
    ``d[k, p]`` counts rows whose topic k holds p tokens (capped at
    ``hist_cap``); l is the binomial-trick draw from ``k_l`` and psi the
    truncated stick-breaking draw from ``k_psi``;
  * fold-in: request ``seed`` folds into ``base_key``; sweep s consumes
    ``uniform(fold_in(doc_key, s), (L, 3))``; z starts from one alias
    draw per token; the mixture is ``(m + alpha psi) / sum``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def prefix_sum(x):
    """Inclusive prefix sum along the last axis by log-step doubling."""
    n = x.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    s = 1
    while s < n:
        x = x + jnp.where(lane >= s, jnp.roll(x, s, x.ndim - 1),
                          jnp.zeros_like(x))
        s *= 2
    return x


def _last(x):
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.sum(jnp.where(lane == x.shape[-1] - 1, x, jnp.zeros_like(x)),
                   axis=-1, keepdims=True)


def _alias_row(p):
    """Walker alias table (prob, alias) of one weight row, index-ordered
    pairing of smalls and larges by their cumulative deficit/surplus."""
    k = p.shape[0]
    p = jnp.where((p > 0) & (p < jnp.inf), p, 0.0)
    total = _last(prefix_sum(p))
    q = jnp.where(total > 0, p / jnp.maximum(total, 1e-30) * k,
                  jnp.ones_like(p))
    pos = jnp.arange(k, dtype=jnp.int32)
    small = q < 1.0
    large = ~small
    cs = jnp.cumsum(small.astype(jnp.int32))
    cl = jnp.cumsum(large.astype(jnp.int32))
    ns = cs[-1]
    nl = k - ns
    rank_l = cl - 1
    d = jnp.where(small, 1.0 - q, 0.0)
    u = jnp.where(large, q - 1.0, 0.0)
    dcum = prefix_sum(d)
    ucum = prefix_sum(u)
    dprev = dcum - d
    t1 = jnp.searchsorted(ucum, dprev, side="left").astype(jnp.int32)
    r = jnp.where(t1 > 0, cl[jnp.maximum(t1 - 1, 0)], 0)
    has_donor = small & (r < nl)
    jstar = jnp.searchsorted(cl, r, side="right").astype(jnp.int32)
    alias_small = jnp.where(has_donor, jnp.minimum(jstar, k - 1), pos)
    t2 = jnp.searchsorted(dcum, ucum, side="right").astype(jnp.int32)
    mstar = jnp.where(t2 > 0, cs[jnp.maximum(t2 - 1, 0)], 0)
    demoted = large & (mstar < ns)
    p2 = jnp.minimum(jnp.searchsorted(cs, mstar, side="right"), k - 1)
    resid = 1.0 + ucum - dcum[p2]
    has_next = demoted & (rank_l + 1 < nl)
    next_l = jnp.minimum(
        jnp.searchsorted(cl, rank_l + 1, side="right"), k - 1
    ).astype(jnp.int32)
    prob = jnp.where(small, q, jnp.where(demoted, resid, 1.0))
    alias = jnp.where(small, alias_small, jnp.where(has_next, next_l, pos))
    return (jnp.clip(prob, 0.0, 1.0).astype(jnp.float32),
            alias.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("w", "by_topic", "dtype"))
def word_tables(phi, psi, alpha, *, w, by_topic=False, dtype=jnp.float32):
    """(q_a, vals, aprob, ids, aalias) of the top-W topics of each word.
    ``dtype`` below float32 rounds vals and aprob, as a lower-precision
    table layout would."""
    vals, ids = jax.lax.top_k(phi.T.astype(jnp.float32), w)
    if by_topic:
        perm = jnp.argsort(ids, axis=-1)
        vals = jnp.take_along_axis(vals, perm, axis=-1)
        ids = jnp.take_along_axis(ids, perm, axis=-1)
    ids = ids.astype(jnp.int32)
    wa = vals * (jnp.float32(alpha) * psi)[ids]
    q_a = prefix_sum(wa)[:, -1]
    aprob, aalias = jax.vmap(_alias_row)(wa)
    vals = vals.astype(dtype).astype(jnp.float32)
    aprob = aprob.astype(dtype).astype(jnp.float32)
    return q_a, vals, aprob, ids, aalias


@functools.partial(jax.jit, static_argnames=("kk",))
def sweep(tokens, mask, z, u, q_a, vals, aprob, ids, aalias, *, kk):
    """One z-sweep of a (D, L) batch: ``(z_new, m)``."""
    w = vals.shape[-1]

    def doc(tok_d, msk_d, z_d, u_d):
        m = jnp.zeros((kk,), jnp.int32).at[jnp.where(msk_d, z_d, 0)].add(
            msk_d.astype(jnp.int32))

        def body(i, carry):
            z_d, m = carry
            v = tok_d[i]
            live = msk_d[i]
            z_old = z_d[i]
            m = m.at[z_old].add(-jnp.where(live, 1, 0))
            vv, ii = vals[v], ids[v]
            wb = vv * m[ii].astype(jnp.float32)
            c = prefix_sum(wb)
            qb = c[-1]
            qa = q_a[v]
            tot = qa + qb
            t = u_d[i, 0] * tot
            k_doc = ii[jnp.minimum(jnp.sum((c < t).astype(jnp.int32)),
                                   w - 1)]
            slot = jnp.minimum((u_d[i, 1] * w).astype(jnp.int32), w - 1)
            slot = jnp.where(u_d[i, 2] < aprob[v][slot], slot,
                             aalias[v][slot])
            k_glob = ii[slot]
            k_new = jnp.where((t < qb) | (qa <= 0.0), k_doc, k_glob)
            k_new = jnp.where(live & (tot > 0), k_new, z_old).astype(
                jnp.int32)
            m = m.at[k_new].add(jnp.where(live, 1, 0))
            return z_d.at[i].set(k_new), m

        return jax.lax.fori_loop(0, tok_d.shape[0], body, (z_d, m))

    return jax.vmap(doc)(tokens, mask, z, u)


@functools.partial(jax.jit, static_argnames=("beta",))
def phi_step(k_phi, n, *, beta):
    """(varphi, phi) of the Poisson Polya-urn draw from n."""
    varphi = jax.random.poisson(jax.random.fold_in(k_phi, 0),
                                n.astype(jnp.float32) + beta,
                                dtype=jnp.int32)
    row = jnp.sum(varphi, axis=1).astype(jnp.float32)
    return varphi, varphi.astype(jnp.float32) / jnp.maximum(row[:, None],
                                                            1.0)


@jax.jit
def iteration_keys(key):
    """(next key, k_phi, k_u, k_l, k_psi)."""
    return tuple(jax.random.split(key, 5))


@functools.partial(jax.jit, static_argnames=("shape",))
def _uniforms(k_ub, *, shape):
    return jax.random.uniform(jax.random.fold_in(k_ub, 0), shape + (3,),
                              jnp.float32)


def block_uniforms(k_u, b: int, shape: tuple):
    """The (DB, L, 3) uniforms of block ``b`` of an iteration's sweep."""
    return _uniforms(k_u if b == 0 else jax.random.fold_in(k_u, b),
                     shape=shape)


@functools.partial(jax.jit, static_argnames=("k", "cap"))
def doc_histogram(z, mask, *, k, cap):
    """d[k, p]: rows of ``z`` in which topic k holds p live tokens, for
    p in 1..cap (more than cap counts at cap)."""
    m = jax.vmap(lambda zr, mr: jnp.zeros((k,), jnp.int32).at[zr].add(
        mr.astype(jnp.int32)))(z, mask)
    p = jnp.clip(m, 0, cap)
    topic = jnp.broadcast_to(jnp.arange(k)[None, :], m.shape)
    return jnp.zeros((k, cap + 1), jnp.int32).at[
        topic.reshape(-1), p.reshape(-1)].add((m > 0).astype(
            jnp.int32).reshape(-1))


@functools.partial(jax.jit, static_argnames=("alpha", "gamma"))
def tail_step(k_l, k_psi, dh, psi, *, alpha, gamma):
    """(l, psi') of the binomial-trick draw of l from the document
    histogram and the stick-breaking draw of psi from l."""
    kk, cols = dh.shape
    d_geq = jnp.cumsum(dh[:, ::-1], axis=1)[:, ::-1]
    j = jnp.arange(cols, dtype=jnp.float32)
    rate = psi[:, None] * jnp.float32(alpha)
    p_j = jnp.clip(rate / (rate + jnp.maximum(j[None, :] - 1.0, 0.0)),
                   0.0, 1.0)
    draws = jax.random.binomial(k_l, d_geq.astype(jnp.float32), p_j)
    draws = jnp.where(jnp.arange(cols)[None, :] >= 1, draws, 0.0)
    l = jnp.sum(draws, axis=1).astype(jnp.int32)
    lf = l.astype(jnp.float32)
    tail = jnp.cumsum(lf[::-1])[::-1] - lf
    sigma = jax.random.beta(k_psi, 1.0 + lf, jnp.float32(gamma) + tail)
    sigma = jnp.clip(sigma, 1e-30, 1.0 - 1e-7).at[kk - 1].set(1.0)
    last = jnp.arange(kk) == kk - 1
    log1m = jnp.where(last, 0.0, jnp.log1p(-sigma))
    cum = jnp.concatenate([jnp.zeros((1,)), jnp.cumsum(log1m)[:-1]])
    out = sigma * jnp.exp(cum)
    return l, out / jnp.sum(out)


def key_chain(key, steps: int):
    """The chain key after ``steps`` iterations."""
    for _ in range(steps):
        key = iteration_keys(key)[0]
    return key


@functools.partial(jax.jit, static_argnames=("k", "v"))
def recount(z, tokens, mask, *, k, v):
    """Topic-word counts n[k, v] of the assignments."""
    return jnp.zeros((k, v), jnp.int32).at[
        jnp.where(mask, z, 0).reshape(-1),
        jnp.where(mask, tokens, 0).reshape(-1)].add(
        mask.astype(jnp.int32).reshape(-1))


def foldin_uniforms(base_key, seeds, sweep_ids, length):
    def one(seed, s):
        doc_key = jax.random.fold_in(base_key, seed)
        return jax.random.uniform(jax.random.fold_in(doc_key, s),
                                  (length, 3))
    return jax.vmap(one)(seeds, sweep_ids)


@functools.partial(jax.jit, static_argnames=("burnin", "kk"))
def foldin(tokens, mask, seeds, base_key, q_a, vals, aprob, ids, aalias,
           psi, alpha, *, burnin, kk):
    """(D, K) topic mixtures of a (D, L) batch of query documents."""
    w = vals.shape[-1]
    length = tokens.shape[1]
    u0 = foldin_uniforms(base_key, seeds, jnp.zeros_like(seeds), length)
    slot = jnp.minimum((u0[..., 1] * w).astype(jnp.int32), w - 1)
    keep = u0[..., 2] < jnp.take_along_axis(aprob[tokens], slot[..., None],
                                            -1)[..., 0]
    slot = jnp.where(keep, slot, jnp.take_along_axis(
        aalias[tokens], slot[..., None], -1)[..., 0])
    z = jnp.take_along_axis(ids[tokens], slot[..., None], -1)[..., 0]
    z = jnp.where(mask, z, 0).astype(jnp.int32)
    def one(s, carry):
        u = foldin_uniforms(base_key, seeds, jnp.full_like(seeds, s), length)
        return sweep(tokens, mask, carry[0], u, q_a, vals, aprob, ids,
                     aalias, kk=kk)

    m = jnp.zeros(tokens.shape[:1] + (kk,), jnp.int32)
    z, m = jax.lax.fori_loop(1, burnin + 1, one, (z, m))
    theta = m.astype(jnp.float32) + alpha * psi[None, :]
    return theta / jnp.sum(theta, axis=1, keepdims=True)
