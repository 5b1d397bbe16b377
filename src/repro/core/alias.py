"""Walker alias tables (Walker 1977; Vose 1991), vectorized for TPU.

The paper folds the token-independent term (a) of the z full conditional,
``phi[k, v] * alpha * Psi[k]``, into one alias table per word type v,
rebuilt once per Gibbs iteration (Section 2.5).  Because Phi and Psi are
*fixed* during the z-step under partial collapsing, the table is exact and
no Metropolis-Hastings correction is required (unlike Li et al. 2014).

Construction is a *sort-free* prefix-sum partition of the small/large
entries (``_alias_build_row_flat``): the sequential Vose pairing is
recovered in closed form from cumulative small deficits D and cumulative
large surpluses U taken in **index order** — small i's donor is the
first large whose running surplus covers D before i, and large j demotes
at the first small whose running deficit exceeds U[j] (``searchsorted``
both ways on rank-compacted lines). The pairing identity is order-free:
whenever a large demotes, the deficit it absorbs from the next large
re-synchronizes the consumed-surplus line with the original-smalls
deficit line (conservation), so *any* fixed processing order yields a
valid table — index order costs two cumsums and two binary searches
where the previous revision also paid a full ascending ``argsort`` per
row (the single most expensive op of the build on CPU/TPU alike).

``alias_build_row_onehot`` is the same pairing expressed with only
comparisons, selects, one-hot reductions and ``prefix_sum`` — no sort,
gather, scatter or ``searchsorted`` primitives — so it lowers inside a
Pallas TPU kernel. It is the builder the hdp_z kernel prologue
(``alias_in_kernel="on"``) runs per token in VMEM and, batched over
rows by ``alias_build_onehot``, the builder of the word-sparse (V, W)
tables. It is bitwise-equal to ``_alias_build_row_flat`` wherever the
cumulative lines are nondecreasing: both take those lines from
``prefix_sum`` (one order of additions on every backend), binary search
on a nondecreasing line equals its comparison count, and one-hot gathers
select values without arithmetic on them. ``prefix_sum`` adds each lane
in its own tree, so a line can dip by an ulp, and a search over a
dipping line can part from the count. Seen: where the total deficit
exceeds the total surplus by rounding, the last large keeps 1 in one
build and 1 - 2e-6 in the other (about 0.2% of random rows; aliases
equal in every row tried).

Bitwise note (conformance rationale): the flat partition realizes a
*different but equally valid* pairing than the retired value-sorted
builds (kept below as ``_alias_build_row_psum`` / ``_alias_build_row_scan``
oracles), so tables are NOT bitwise-identical across build generations —
only the reconstructed pmfs agree to fp accuracy. Every conformance
surface in this repo is *relative* (dense/sparse/pallas z-steps against
shared tables, streaming against monolithic, engine against direct
fold-in) and is unaffected; there are no stored golden tables.
tests/test_alias.py pins flat-vs-sorted pmf equivalence and
flat-vs-onehot bitwise equality.

Sampling is deterministic given two uniforms: ``slot = floor(u1 * K)``,
then ``select(u2 < prob[slot], slot, alias[slot])`` — two gathers and a
select, O(1) per draw.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def prefix_sum(x: jax.Array, roll=jnp.roll) -> jax.Array:
    """Inclusive prefix sum along the last axis, with one fixed order of
    additions: log2(n) doubling steps, ``x += shift(x, s)`` for
    s = 1, 2, 4, ... with zeros shifted in (Hillis and Steele 1986).

    Every row total and cumulative line of the hdp_z z-step map goes
    through this helper — in XLA (``roll=jnp.roll``) and inside the
    kernel (``roll=pltpu.roll``) — so the kernel, its oracle and the
    word-sparse table builders add the same floats in the same order on
    every backend.
    ``jnp.cumsum`` gives no such promise: XLA rewrites it into blocked
    scans whose shape depends on the length and the platform. Lane l
    depends only on lanes <= l, so padding past the end changes no
    earlier lane.
    """
    n = x.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    s = 1
    while s < n:
        x = x + jnp.where(lane >= s, roll(x, s, x.ndim - 1),
                          jnp.zeros_like(x))
        s *= 2
    return x


def last_lane(x: jax.Array) -> jax.Array:
    """``x[..., -1:]`` as a one-hot lane reduction (one selected value
    plus exact zeros), which lowers inside a Pallas TPU kernel."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.sum(jnp.where(lane == x.shape[-1] - 1, x, jnp.zeros_like(x)),
                   axis=-1, keepdims=True)


def _normalized(p: jax.Array, cumsum=prefix_sum) -> jax.Array:
    """q = p / mean(p): the alias construction's working scale, where
    "small" entries sit below 1.

    Guards: non-finite and negative weights are clamped to zero *before*
    normalizing (a single Inf used to give total=inf and silently zero
    the whole row with a NaN at the Inf entry — the resulting table
    sampled garbage without tripping any error), and all-zero rows
    (e.g. padded vocab entries, or rows that were entirely non-finite)
    fall back to uniform. A positive total divides as it is, however
    small (a floor on it would shrink every q of a row whose total lies
    below the floor); weights the backend flushes to zero count as zero.
    Kernel-safe: comparisons and selects only; the total is the last
    lane of ``cumsum``.
    """
    p = jnp.where((p > 0) & (p < jnp.inf), p, 0.0)
    total = last_lane(cumsum(p))
    return jnp.where(
        total > 0, p / jnp.where(total > 0, total, 1.0) * p.shape[-1],
        jnp.ones_like(p),
    )


def _alias_build_row_flat(
    p: jax.Array, cumsum=prefix_sum
) -> tuple[jax.Array, jax.Array]:
    """Build one alias table from an unnormalized weight vector ``p`` (K,)
    via the sort-free, index-ordered prefix-sum partition.

    Returns (prob, alias): prob[j] is the probability that slot j keeps
    its own index, alias[j] the donor index otherwise.

    Smalls (q < 1) are consumed in index order against larges consumed in
    index order. With S/U the masked cumulative deficit/surplus lines:

      * small i's donor is the first large (by index) whose cumulative
        surplus covers S[i] - d[i] — found by ``searchsorted`` on the
        rank-compacted surplus line (side='left', matching the retired
        sorted build's convention);
      * large j demotes at the first small whose cumulative deficit
        strictly exceeds U[j] (side='right'), with residual prob
        1 + U[j] - S[that small] and alias the next large by index;
      * no demoting small => the large keeps prob 1; no covering large
        (total deficit exceeding total surplus by fp residue) => the
        small keeps its own slot.

    Validity does not depend on processing order: when large j demotes,
    the deficit it absorbs from large j+1 is exactly S[m*] - U[j], which
    re-synchronizes the consumed-surplus line with the original-smalls
    deficit line — the same telescoping identity the value-sorted build
    relied on, holding for any fixed order. Dropping the per-row
    ``argsort`` removes the most expensive op of the batched build.
    """
    k = p.shape[0]
    q = _normalized(p, cumsum)
    pos = jnp.arange(k, dtype=jnp.int32)
    small = q < 1.0
    large = ~small
    cs = jnp.cumsum(small.astype(jnp.int32))    # 1-based count of smalls
    cl = jnp.cumsum(large.astype(jnp.int32))    # 1-based count of larges
    ns = cs[-1]
    nl = k - ns
    rank_l = cl - 1

    d = jnp.where(small, 1.0 - q, 0.0)
    u = jnp.where(large, q - 1.0, 0.0)
    dcum = cumsum(d)            # S: plateaus at larges
    ucum = cumsum(u)            # U: plateaus at smalls

    # Both monotone lines are searched at *full length*; the count of
    # larges (resp. smalls) inside the located prefix converts a
    # position on the padded line into a rank, and an integer search on
    # the cumulative-count line converts a rank back into a position.
    # All scatter-free: cumsum + searchsorted + gathers only.

    # smalls: donor = first large whose running surplus covers D-before.
    dprev = dcum - d
    t1 = jnp.searchsorted(ucum, dprev, side="left").astype(jnp.int32)
    r = jnp.where(t1 > 0, cl[jnp.maximum(t1 - 1, 0)], 0)   # donor rank
    has_donor = small & (r < nl)
    jstar = jnp.searchsorted(cl, r, side="right").astype(jnp.int32)
    alias_small = jnp.where(has_donor, jnp.minimum(jstar, k - 1), pos)

    # larges: demoting small = first with cumulative deficit > U[j].
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
    prob = jnp.clip(prob, 0.0, 1.0)
    return prob.astype(jnp.float32), alias.astype(jnp.int32)


def alias_build_row_onehot(
    p: jax.Array, roll=jnp.roll
) -> tuple[jax.Array, jax.Array]:
    """``_alias_build_row_flat`` re-expressed with Pallas-lowerable ops
    only: comparisons, selects, 2-D iotas, lane rolls, transposes and
    one-hot reductions — no sort, gather, scatter or ``searchsorted``.

    ``p`` is one row, (K,) or (1, K); the outputs keep its shape. The
    pairwise (K, K) comparisons put the index being counted on sublanes
    and the row entry on lanes, so every count is a reduction over axis
    0 that comes out as a (1, K) row again; ``col(x)`` broadcasts a row
    down the columns by a transpose. Inside the hdp_z kernel ``roll`` is
    ``pltpu.roll``.

    This is the builder the hdp_z kernel prologue runs per token over
    the word's W-wide support row, and the oracle side of the
    ``alias_in_kernel`` conformance tests. Bitwise-equal to
    ``_alias_build_row_flat`` where the cumulative lines are
    nondecreasing (module docstring): both take those lines from
    ``prefix_sum``, a binary search on a nondecreasing line returns
    exactly its comparison count, and one-hot reductions (one selected
    value and exact zeros) reproduce gathers bit for bit. O(K^2)
    comparisons per row — meant for the kernel's small W, not for the
    batched (V, K) build.
    """
    shape = p.shape
    k = shape[-1]
    p = p.reshape(1, k)
    q = _normalized(p, functools.partial(prefix_sum, roll=roll))
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    small = q < 1.0
    large = ~small
    smi = small.astype(jnp.int32)
    lgi = 1 - smi
    ns = jnp.sum(smi)
    nl = k - ns

    d = jnp.where(small, 1.0 - q, 0.0)
    u = jnp.where(large, q - 1.0, 0.0)
    dcum = prefix_sum(d, roll)
    ucum = prefix_sum(u, roll)
    rank_s = prefix_sum(smi, roll) - 1
    rank_l = prefix_sum(lgi, roll) - 1

    def row(x):   # row(x)[j, i] = x[i]
        return jnp.broadcast_to(x, (k, k))

    def col(x):   # col(x)[j, i] = x[j]
        return jnp.transpose(row(x))

    large_c = col(lgi) > 0
    small_c = col(smi) > 0
    rank_l_c = col(rank_l)
    pos_c = col(pos)
    dcum_c = col(dcum)

    def count(b):
        return jnp.sum(b.astype(jnp.int32), axis=0, keepdims=True)

    def pick(b, x):
        return jnp.sum(jnp.where(b, x, jnp.zeros_like(x)), axis=0,
                       keepdims=True)

    # smalls: r = |{larges j : U[j] < dprev}| == searchsorted(side='left')
    dprev = dcum - d
    r = count(large_c & (col(ucum) < row(dprev)))
    has_donor = small & (r < nl)
    alias_small = jnp.where(
        has_donor, pick(large_c & (rank_l_c == row(r)), pos_c), pos)

    # larges: mstar = |{smalls m : S[m] <= U[j]}| == side='right'
    mstar = count(small_c & (dcum_c <= row(ucum)))
    demoted = large & (mstar < ns)
    s_at = pick(small_c & (col(rank_s) == row(mstar)), dcum_c)
    resid = 1.0 + ucum - s_at
    has_next = demoted & (rank_l + 1 < nl)
    next_l = pick(large_c & (rank_l_c == row(rank_l + 1)), pos_c)

    prob = jnp.where(small, q, jnp.where(demoted, resid, 1.0))
    alias = jnp.where(small, alias_small, jnp.where(has_next, next_l, pos))
    prob = jnp.clip(prob, 0.0, 1.0)
    return (prob.astype(jnp.float32).reshape(shape),
            alias.astype(jnp.int32).reshape(shape))


def _alias_build_row_psum(p: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Retired value-sorted prefix-sum partition build, kept as an oracle
    for the sort-free ``_alias_build_row_flat`` (pmf equivalence tests).

    Returns (prob, alias): prob[j] is the probability that slot j keeps
    its own index, alias[j] the donor index otherwise.

    After the ascending sort, positions [0, nS) are small (q < 1) and
    [nS, K) are large; larges are consumed from the top down, exactly as
    the sequential two-stack scan did. The scan's pairing is then a
    closed form in two monotone prefix sums — D[m] (cumulative small
    deficits 1-q) and U[j] (cumulative large surpluses q-1, descending
    consumption order) — because demoted-large residual deficits
    telescope: by the time large j has demoted, the sorted smalls it and
    its predecessors absorbed carry total deficit exactly U[j]. Hence

      * small m's donor is the first large j with U[j] >= D[m-1]
        (the large active when m is consumed);
      * large j demotes at the first small m* with D[m*] > U[j]
        (strict: a large drained to exactly 1.0 stays large), with
        residual prob 1 + U[j] - D[m*] and alias the next large down;
      * no such m* => the large keeps prob 1; no such j (total deficit
        exceeding total surplus by fp residue) => the small keeps its
        own slot, as in the sequential scan.
    """
    k = p.shape[0]
    q = _normalized(p)
    order = jnp.argsort(q)
    qs = q[order]                                   # ascending
    pos = jnp.arange(k, dtype=jnp.int32)
    small = qs < 1.0
    ns = jnp.sum(small.astype(jnp.int32))
    nl = k - ns

    d = jnp.where(small, 1.0 - qs, 0.0)
    dcum = jnp.cumsum(d)                            # D[m], increasing on smalls
    dprev = dcum - d                                # D[m-1] (0 at m = 0)
    # larges in consumption order: descending sorted position k-1-j.
    u = jnp.where(pos < nl, qs[::-1] - 1.0, 0.0)
    ucum = jnp.cumsum(u)                            # U[j], nondecreasing
    upad = jnp.where(pos < nl, ucum, jnp.inf)       # stays sorted past nl

    # smalls: donor = first large whose running surplus covers D[m-1].
    j_small = jnp.searchsorted(upad, dprev, side="left").astype(jnp.int32)
    has_donor = small & (j_small < nl)
    alias_small = jnp.where(has_donor, k - 1 - j_small, pos)

    # larges: demoting small = first m with D[m] > U[j] (strict).
    dpad = jnp.where(small, dcum, jnp.inf)          # stays sorted past ns
    j_of_pos = k - 1 - pos                          # consumption index
    u_here = ucum[j_of_pos]
    mstar = jnp.searchsorted(dpad, u_here, side="right").astype(jnp.int32)
    demoted = (~small) & (mstar < ns)
    resid = 1.0 + u_here - dcum[jnp.minimum(mstar, k - 1)]
    has_next = demoted & (pos - 1 >= ns)            # next large down exists

    prob_sorted = jnp.where(small, qs, jnp.where(demoted, resid, 1.0))
    alias_sorted = jnp.where(
        small, alias_small, jnp.where(has_next, pos - 1, pos)
    )
    prob_sorted = jnp.clip(prob_sorted, 0.0, 1.0)

    # Un-sort back to original topic indices.
    inv = jnp.zeros((k,), dtype=jnp.int32).at[order].set(pos)
    prob = prob_sorted[inv]
    alias = order[alias_sorted[inv]]
    return prob.astype(jnp.float32), alias.astype(jnp.int32)


def _alias_build_row_scan(p: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Reference sequential construction: the two-stack Vose algorithm as
    a ``lax.scan`` of K O(1) steps. Retired from the production path by
    the prefix-sum partition above (same pairing in exact arithmetic,
    O(log K) depth instead of K sequential steps); kept as the oracle the
    equivalence test pins the prefix-sum build against.
    """
    k = p.shape[0]
    q = _normalized(p)

    # Sort ascending; positions [0, boundary) are "small" (q < 1).
    order = jnp.argsort(q)
    q_sorted = q[order]

    def step(carry, _):
        q_cur, alias_cur, small_ptr, fifo, fifo_head, fifo_tail, g_ptr = carry

        fifo_nonempty = fifo_head < fifo_tail
        # Next small: prefer demoted-large FIFO entries, else sorted smalls.
        sorted_small_ok = (
            (~fifo_nonempty) & (small_ptr < g_ptr) & (q_cur[small_ptr] < 1.0)
        )
        s_pos = jnp.where(fifo_nonempty, fifo[fifo_head % k], small_ptr)
        have_small = fifo_nonempty | sorted_small_ok
        # Current large is at g_ptr (top of the sorted-descending large run).
        g_pos = g_ptr
        g_valid = (g_pos >= 0) & (q_cur[g_pos] >= 1.0)
        do_pair = have_small & g_valid & (s_pos != g_pos)

        qs = q_cur[s_pos]
        qg = q_cur[g_pos]
        new_qg = qg - (1.0 - qs)

        # Guarded one-element scatters (write back the old value when the
        # step is a no-op) instead of `where(do_pair, arr.at[..], arr)`
        # full-array selects: the latter copies the whole (K,) row — and
        # under the vmap over word types the whole (V, K) table — every
        # scan step, turning the build into O(V*K^2). The scatter form is
        # O(V) per step (O(V*K) total) and bitwise-identical.
        alias_next = alias_cur.at[s_pos].set(
            jnp.where(do_pair, g_pos, alias_cur[s_pos])
        )
        q_next = q_cur.at[g_pos].set(jnp.where(do_pair, new_qg, qg))

        small_ptr_next = jnp.where(
            do_pair & ~fifo_nonempty, small_ptr + 1, small_ptr
        )
        fifo_head_next = jnp.where(do_pair & fifo_nonempty, fifo_head + 1, fifo_head)

        # If the large dropped below 1 it becomes small: demote and move g.
        demote = do_pair & (new_qg < 1.0)
        fifo_next = fifo.at[fifo_tail % k].set(
            jnp.where(demote, g_pos, fifo[fifo_tail % k])
        )
        fifo_tail_next = jnp.where(demote, fifo_tail + 1, fifo_tail)
        g_ptr_next = jnp.where(demote, g_ptr - 1, g_ptr)

        return (
            q_next,
            alias_next,
            small_ptr_next,
            fifo_next,
            fifo_head_next,
            fifo_tail_next,
            g_ptr_next,
        ), None

    alias0 = jnp.arange(k, dtype=jnp.int32)
    fifo0 = jnp.zeros((k,), dtype=jnp.int32)
    carry0 = (
        q_sorted,
        alias0,
        jnp.int32(0),
        fifo0,
        jnp.int32(0),
        jnp.int32(0),
        jnp.int32(k - 1),
    )
    (q_fin, alias_sorted, *_), _ = jax.lax.scan(step, carry0, None, length=k)

    # Any residue (fp error / unresolved) keeps its own slot.
    prob_sorted = jnp.clip(q_fin, 0.0, 1.0)

    # Un-sort back to original topic indices.
    inv = jnp.zeros((k,), dtype=jnp.int32).at[order].set(
        jnp.arange(k, dtype=jnp.int32)
    )
    prob = prob_sorted[inv]
    alias = order[alias_sorted[inv]]
    return prob.astype(jnp.float32), alias.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("cumsum",))
def alias_build(
    p: jax.Array, cumsum=prefix_sum
) -> tuple[jax.Array, jax.Array]:
    """Vectorized alias build (sort-free index-ordered partition).

    p: (..., K) unnormalized weights — one table per leading index.
    Returns (prob, alias) with the same leading shape.

    ``cumsum`` makes the row total and the cumulative lines. The default
    ``prefix_sum`` adds in the order the hdp_z kernel does; the dense
    (V, K) tables, which nothing compiled has to match, pass
    ``jnp.cumsum`` (one pass per line instead of log2(K)). The
    word-sparse (V, W) tables use ``alias_build_onehot``.
    """
    flat = p.reshape((-1, p.shape[-1]))
    prob, alias = jax.vmap(
        functools.partial(_alias_build_row_flat, cumsum=cumsum))(flat)
    return prob.reshape(p.shape), alias.reshape(p.shape)


# rows per step of alias_build_onehot: (rows, W, W) comparison blocks of
# 134 MB at W=128, bounding the build's temporaries.
_ONEHOT_ROWS_PER_STEP = 2048


@jax.jit
def alias_build_onehot(p: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Batched ``alias_build_row_onehot``: the alias build of the
    word-sparse (V, W) tables, one row per word type.

    O(W^2) comparisons and reductions per row in place of
    ``alias_build``'s binary searches and per-lane gathers: on a TPU v5e
    at V=90,112, W=128 this takes 35 ms against 4.2 s for the vmapped
    ``searchsorted`` form (PERF.md). It is the builder the hdp_z kernel
    prologue runs per token, so tables built here equal that build
    bitwise, and ``alias_build``'s wherever the cumulative lines are
    nondecreasing (module docstring). Rows go
    ``_ONEHOT_ROWS_PER_STEP`` at a time through ``lax.map``.
    """
    k = p.shape[-1]
    flat = p.reshape((-1, k))
    n = flat.shape[0]
    step = min(_ONEHOT_ROWS_PER_STEP, max(n, 1))
    blocks = jnp.pad(flat, ((0, -n % step), (0, 0))).reshape(-1, step, k)
    prob, alias = jax.lax.map(jax.vmap(alias_build_row_onehot), blocks)
    return (prob.reshape(-1, k)[:n].reshape(p.shape),
            alias.reshape(-1, k)[:n].reshape(p.shape))


@functools.partial(jax.jit)
def alias_build_sorted(p: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Vectorized alias build via the retired value-sorted prefix-sum
    partition — the oracle the sort-free production build is tested
    against (pmf equivalence; pairings differ by construction)."""
    flat = p.reshape((-1, p.shape[-1]))
    prob, alias = jax.vmap(_alias_build_row_psum)(flat)
    return prob.reshape(p.shape), alias.reshape(p.shape)


@functools.partial(jax.jit)
def alias_build_scan(p: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Vectorized alias build via the reference sequential scan (for
    equivalence tests and as a fallback; production uses alias_build)."""
    flat = p.reshape((-1, p.shape[-1]))
    prob, alias = jax.vmap(_alias_build_row_scan)(flat)
    return prob.reshape(p.shape), alias.reshape(p.shape)


def alias_sample(
    prob: jax.Array, alias: jax.Array, u1: jax.Array, u2: jax.Array
) -> jax.Array:
    """Draw indices from alias tables, deterministically given uniforms.

    prob/alias: (K,) single table, u1/u2 broadcastable uniforms in [0,1).
    """
    k = prob.shape[-1]
    slot = jnp.minimum((u1 * k).astype(jnp.int32), k - 1)
    keep = u2 < prob[slot]
    return jnp.where(keep, slot, alias[slot]).astype(jnp.int32)


def alias_build_np(p: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Reference numpy Vose construction (oracle for tests)."""
    p = np.asarray(p, dtype=np.float64)
    k = p.shape[0]
    total = p.sum()
    if total <= 0:
        q = np.ones(k)
    else:
        q = p / total * k
    prob = np.zeros(k)
    alias = np.arange(k, dtype=np.int64)
    small = [i for i in range(k) if q[i] < 1.0]
    large = [i for i in range(k) if q[i] >= 1.0]
    q = q.copy()
    while small and large:
        s = small.pop()
        g = large.pop()
        prob[s] = q[s]
        alias[s] = g
        q[g] = q[g] - (1.0 - q[s])
        if q[g] < 1.0:
            small.append(g)
        else:
            large.append(g)
    for g in large:
        prob[g] = 1.0
    for s in small:  # fp residue
        prob[s] = 1.0
    return prob.astype(np.float32), alias.astype(np.int32)


def alias_sample_np(prob, alias, u1, u2):
    k = prob.shape[0]
    slot = min(int(u1 * k), k - 1)
    return int(slot if u2 < prob[slot] else alias[slot])
