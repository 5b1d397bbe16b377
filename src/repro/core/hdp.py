"""The paper's contribution: doubly sparse partially collapsed Gibbs
sampling for the HDP topic model (Terenin, Magnusson & Jonsson, EMNLP 2020).

State layout (fixed shapes; padding via ``mask``):
  tokens : (D, L) int32   word types, padded docs (mask == 0 on padding)
  z      : (D, L) int32   topic indicators
  n      : (K, V) int32   topic-word sufficient statistic
  phi    : (K, V) f32     topic-word probabilities (PPU-normalized)
  varphi : (K, V) int32   integer PPU counts (sparsity pattern of Phi)
  psi    : (K,)   f32     global topic distribution (FGEM-truncated)
  l      : (K,)   int32   global-draw sufficient statistic

One Gibbs iteration = Algorithm 2 of the paper:
  1. Phi-step  : phi_k ~ PPU(n_k + beta)            (parallel over topics)
  2. z-step    : z_{i,d} ~ phi[k,v] (alpha Psi_k + m_dk^-i)
                                                    (parallel over documents,
                                                     sequential within a doc)
  3. l-step    : binomial trick                     (parallel over topics)
  4. Psi-step  : FGEM stick-breaking posterior, sigma_{K*} = 1

Three z-step implementations share one signature AND one return
contract — a sweep *emits* its sufficient statistics:

    z_step_*(...) -> (z_new, m)

where ``m`` is the (D, K) per-document topic histogram of ``z_new``,
read straight out of the sweep carry (the sweep maintains it anyway for
the document term), bitwise-equal to ``doc_topic_counts(z_new, mask, K)``
by construction. Drivers then update the topic-word statistic by exact
integer *delta* scatters (``delta_n``) over the changed tokens instead
of a from-zero ``count_n`` recount: ``n + delta_n(z_old, z_new, ...)``
is bitwise-identical to ``count_n(z_new, ...)`` in integer arithmetic,
and after burn-in — when most tokens keep their topic — the delta is
the sparsest statistic the sampler has (the update-sparsity analogue of
the paper's "use every available source of sparsity").

  * ``dense``  — O(K) per token inverse-CDF; the semantics oracle and the
                 MXU-friendly baseline at small K.
  * ``sparse`` — the paper's doubly sparse scheme: per-word alias tables
                 for the global term (a) and a bucketed active-topic list
                 for the document term (b). Pure JAX, fixed bucket.
  * ``pallas`` — the Pallas TPU kernel (kernels/hdp_z) with dynamic
                 trip-count inner loops: true O(min(K_d, K_v)) work.

All z-step randomness is consumed from an explicit uniforms tensor
(D, L, 3), so every implementation is deterministic given the key and can
be cross-checked (DESIGN.md section 7).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.alias import alias_build, alias_sample
from repro.core.polya_urn import (
    dirichlet_sample, ppu_sample, ppu_sample_budgeted)
from repro.core.stick import gem_prior_sample, sample_l, sample_psi


class HDPConfig(NamedTuple):
    K: int = 1000            # K* truncation (incl. flag topic)
    V: int = 1000            # vocabulary size
    alpha: float = 0.1       # document DP concentration
    beta: float = 0.01       # topic-word Dirichlet/PPU concentration
    gamma: float = 1.0       # GEM concentration
    bucket: int = 64         # active-topic bucket for sparse z-step
    z_impl: str = "sparse"   # dense | sparse | pallas
    exact_phi: bool = False  # Algorithm 1: exact Dirichlet Phi instead of PPU
    hist_cap: int = 256      # P: per-(doc,topic) count cap for the l histogram
    unroll_z: bool = False   # unroll the in-document sweep (cost probes)
    alias_in_kernel: str = "auto"  # pallas only: build term-(a) alias
    #                          rows inside the z kernel, once per live
    #                          token (auto|on|off). auto = off: per-word
    #                          tables built once per iteration are faster
    #                          on the chip (kernels/hdp_z/ops.py
    #                          resolve_alias_in_kernel)
    ppu_nnz_budget: int | None = None  # doubly-sparse PPU Phi draw over
    #                          at most this many non-zero n cells (must
    #                          bound nnz(n); corpus token count always
    #                          does). None = dense draw. Static: changing
    #                          it retraces, and streaming-vs-monolithic
    #                          bitwise equality needs equal budgets.


class HDPState(NamedTuple):
    z: jax.Array
    n: jax.Array
    phi: jax.Array
    varphi: jax.Array
    psi: jax.Array
    l: jax.Array
    key: jax.Array
    it: jax.Array


# --------------------------------------------------------------------------
# sufficient statistics
# --------------------------------------------------------------------------

def count_n(z: jax.Array, tokens: jax.Array, mask: jax.Array, k: int, v: int) -> jax.Array:
    """Topic-word counts n[k, v] from assignments (scatter-add)."""
    zz = jnp.where(mask, z, 0)
    tt = jnp.where(mask, tokens, 0)
    upd = mask.astype(jnp.int32)
    return jnp.zeros((k, v), jnp.int32).at[zz.reshape(-1), tt.reshape(-1)].add(
        upd.reshape(-1)
    )


def delta_n(
    z_old: jax.Array, z_new: jax.Array, tokens: jax.Array, mask: jax.Array,
    k: int, v: int,
) -> jax.Array:
    """Exact integer update to the topic-word statistic from one sweep.

    Scatters +1 at (z_new, token) and -1 at (z_old, token) for every
    *changed* live token; unchanged and masked tokens contribute exact
    zeros. Because n is integer-valued, ``count_n(z_old) + delta`` is
    bitwise-equal to ``count_n(z_new)`` — no recount, no fresh (K, V)
    histogram of the untouched majority of tokens.
    """
    ch = (mask & (z_new != z_old)).astype(jnp.int32)
    zo = jnp.where(mask, z_old, 0).reshape(-1)
    zn = jnp.where(mask, z_new, 0).reshape(-1)
    tt = jnp.where(mask, tokens, 0).reshape(-1)
    chf = ch.reshape(-1)
    return (
        jnp.zeros((k, v), jnp.int32)
        .at[zn, tt].add(chf)
        .at[zo, tt].add(-chf)
    )


def doc_topic_counts(z: jax.Array, mask: jax.Array, k: int) -> jax.Array:
    """Per-document topic histogram m: (D, K) from (D, L) assignments."""
    zz = jnp.where(mask, z, 0)
    upd = mask.astype(jnp.int32)

    def one(zd, ud):
        return jnp.zeros((k,), jnp.int32).at[zd].add(ud)

    return jax.vmap(one)(zz, upd)


def d_histogram(m: jax.Array, hist_cap: int) -> jax.Array:
    """d[k, p] = #docs with m_{d,k} == p, for p in 1..P (paper Section 2.6)."""
    d_docs, k = m.shape
    p = jnp.clip(m, 0, hist_cap)  # cap: docs beyond cap pool at P (conservative)
    valid = (m > 0).astype(jnp.int32)
    hist = jnp.zeros((k, hist_cap + 1), jnp.int32)
    kidx = jnp.broadcast_to(jnp.arange(k)[None, :], m.shape)
    return hist.at[kidx.reshape(-1), p.reshape(-1)].add(valid.reshape(-1))


# --------------------------------------------------------------------------
# z-step: dense oracle
# --------------------------------------------------------------------------

def _sample_invcdf(w: jax.Array, u: jax.Array) -> jax.Array:
    """Inverse-CDF draw from unnormalized weights (deterministic given u)."""
    c = jnp.cumsum(w)
    t = u * c[-1]
    idx = jnp.searchsorted(c, t, side="right")
    return jnp.minimum(idx, w.shape[0] - 1).astype(jnp.int32)


def _sweep(body, length: int, init, unroll: bool):
    """fori_loop, optionally trace-time unrolled (XLA cost_analysis does
    not multiply while-loop bodies by trip count — the dry-run cost
    probes lower tiny unrolled variants; see launch/dryrun.py)."""
    if unroll:
        carry = init
        for i in range(length):
            carry = body(i, carry)
        return carry
    return jax.lax.fori_loop(0, length, body, init)


def z_step_dense(
    tokens: jax.Array, mask: jax.Array, z: jax.Array, phi: jax.Array,
    psi: jax.Array, alpha: float, uniforms: jax.Array,
    unroll: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """O(K)-per-token Gibbs sweep; the semantics oracle for all z-steps.

    Returns ``(z_new, m)`` with m the (D, K) final per-doc histogram
    emitted from the sweep carry (see module docstring).
    """
    k = phi.shape[0]
    apsi = alpha * psi  # (K,)

    def doc_sweep(tok_d, msk_d, z_d, u_d):
        m = jnp.zeros((k,), jnp.int32).at[jnp.where(msk_d, z_d, 0)].add(
            msk_d.astype(jnp.int32)
        )

        def body(i, carry):
            z_d, m = carry
            v = tok_d[i]
            zi = z_d[i]
            live = msk_d[i]
            m = m.at[zi].add(-live.astype(jnp.int32))
            w = phi[:, v] * (apsi + m.astype(jnp.float32))
            k_new = _sample_invcdf(w, u_d[i, 0])
            # zero total mass (word absent from every PPU topic): keep.
            k_new = jnp.where(live & (jnp.sum(w) > 0), k_new, zi)
            m = m.at[k_new].add(live.astype(jnp.int32))
            return z_d.at[i].set(k_new), m

        return _sweep(body, tok_d.shape[0], (z_d, m), unroll)

    return jax.vmap(doc_sweep)(tokens, mask, z, uniforms)


# --------------------------------------------------------------------------
# z-step: doubly sparse (paper Section 2.5), pure JAX with fixed bucket
# --------------------------------------------------------------------------

def build_alias_tables(
    phi: jax.Array, psi: jax.Array, alpha: float
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-word-type alias tables for term (a) = phi[k,v] alpha Psi_k.

    Returns (q_a (V,), prob (V,K), alias (V,K)). Rebuilt once per
    iteration; exact because Phi, Psi are fixed during the z-step.
    """
    wa = (phi * (alpha * psi)[:, None]).T  # (V, K)
    q_a = jnp.sum(wa, axis=1)  # (V,)
    prob, alias = alias_build(wa, cumsum=jnp.cumsum)
    return q_a, prob, alias


def z_step_sparse(
    tokens: jax.Array, mask: jax.Array, z: jax.Array, phi: jax.Array,
    psi: jax.Array, alpha: float, uniforms: jax.Array, bucket: int,
) -> tuple[jax.Array, jax.Array]:
    """Doubly sparse z-step: alias tables (term a) + active-topic bucket
    (term b), with swap-remove compaction so the bucket holds exactly the
    topics with m_{d,k} > 0. Requires bucket >= min(K, L)."""
    q_a, aprob, aalias = build_alias_tables(phi, psi, alpha)
    return z_step_sparse_tables(
        tokens, mask, z, phi, alpha, uniforms, bucket, q_a, aprob, aalias
    )


def z_step_sparse_tables(
    tokens: jax.Array, mask: jax.Array, z: jax.Array, phi: jax.Array,
    alpha: float, uniforms: jax.Array, bucket: int,
    q_a: jax.Array, aprob: jax.Array, aalias: jax.Array,
    unroll: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Sparse z-step with pre-built alias tables (sharded path builds the
    tables model-parallel and gathers them; see core/sharded.py).

    The fixed-size active-topic bucket silently drops term-(b) mass once
    a document activates more than ``bucket`` topics (``can_insert``
    fails while m keeps counting), so samplers must be constructed with
    ``bucket >= min(K, L)`` — ``validate_bucket`` enforces this where the
    corpus geometry is known (init_state / StreamingHDP).
    """
    k = phi.shape[0]

    def doc_sweep(tok_d, msk_d, z_d, u_d):
        m = jnp.zeros((k,), jnp.int32).at[jnp.where(msk_d, z_d, 0)].add(
            msk_d.astype(jnp.int32)
        )
        ids0 = jnp.nonzero(m, size=bucket, fill_value=0)[0].astype(jnp.int32)
        cnt0 = jnp.minimum(jnp.sum(m > 0), bucket).astype(jnp.int32)

        def body(i, carry):
            z_d, m, ids, cnt = carry
            v = tok_d[i]
            zi = z_d[i]
            live = msk_d[i]

            # --- decrement current assignment (m^{-i}) -------------------
            m = m.at[zi].add(-live.astype(jnp.int32))
            removed = live & (m[zi] == 0)
            # swap-remove zi from the active list
            slot = jnp.argmax((ids == zi) & (jnp.arange(bucket) < cnt))
            last = jnp.maximum(cnt - 1, 0)
            ids = jnp.where(
                removed, ids.at[slot].set(ids[last]).at[last].set(zi), ids
            )
            cnt = jnp.where(removed, cnt - 1, cnt)

            # --- term (b): doc-sparse mass over active bucket ------------
            lane = jnp.arange(bucket)
            active = lane < cnt
            mb = jnp.where(active, m[ids], 0).astype(jnp.float32)
            wb = jnp.where(active, phi[ids, v], 0.0) * mb
            q_b = jnp.sum(wb)
            tot = q_a[v] + q_b
            t = u_d[i, 0] * tot

            # --- choose branch -------------------------------------------
            k_doc = ids[_sample_invcdf(wb, jnp.clip(t / jnp.maximum(q_b, 1e-30), 0.0, 1.0))]
            k_glob = alias_sample(aprob[v], aalias[v], u_d[i, 1], u_d[i, 2])
            doc_branch = (t < q_b) | (q_a[v] <= 0)
            k_new = jnp.where(doc_branch, k_doc, k_glob)
            # zero total mass: keep the current assignment.
            k_new = jnp.where(live & (tot > 0), k_new, zi).astype(jnp.int32)

            # --- increment + insert into active list ----------------------
            was_zero = live & (m[k_new] == 0)
            m = m.at[k_new].add(live.astype(jnp.int32))
            can_insert = was_zero & (cnt < bucket)
            ids = jnp.where(can_insert, ids.at[cnt].set(k_new), ids)
            cnt = jnp.where(can_insert, cnt + 1, cnt)
            return z_d.at[i].set(k_new), m, ids, cnt

        z_d, m, *_ = _sweep(body, tok_d.shape[0], (z_d, m, ids0, cnt0), unroll)
        return z_d, m

    return jax.vmap(doc_sweep)(tokens, mask, z, uniforms)


# --------------------------------------------------------------------------
# full Gibbs iteration (Algorithm 2; Algorithm 1 when exact_phi)
# --------------------------------------------------------------------------

def validate_bucket(cfg: HDPConfig, max_len: int) -> None:
    """Reject sparse-z-step configs whose bucket can overflow.

    A document with L live tokens can hold at most min(K, L) distinct
    active topics; if ``bucket`` is smaller, ``z_step_sparse_tables``
    silently drops term-(b) mass on overflow (the active list rejects
    the insert while m keeps counting), biasing the sampler. Raise at
    sampler construction — where the corpus geometry is first known —
    instead of sampling from the wrong distribution.
    """
    if cfg.z_impl != "sparse":
        return
    need = min(cfg.K, max_len)
    if cfg.bucket < need:
        raise ValueError(
            f"HDPConfig.bucket={cfg.bucket} cannot hold a document's "
            f"active topics: with K={cfg.K} and max document length "
            f"{max_len}, a document can activate up to min(K, L)={need} "
            f"topics, and the sparse z-step silently drops term-(b) mass "
            f"beyond the bucket. Raise bucket to >= {need} (or use "
            f"z_impl='dense'/'pallas')."
        )


def init_state(
    key: jax.Array, tokens: jax.Array, mask: jax.Array, cfg: HDPConfig
) -> HDPState:
    """Initialize with a single topic (paper Section 3, following Teh)."""
    validate_bucket(cfg, tokens.shape[1])
    kp, kd = jax.random.split(key)
    z = jnp.zeros_like(tokens)
    n = count_n(z, tokens, mask, cfg.K, cfg.V)
    if cfg.ppu_nnz_budget is not None:
        phi, varphi = ppu_sample_budgeted(
            kp, n, cfg.beta, cfg.ppu_nnz_budget)
    else:
        phi, varphi = ppu_sample(kp, n, cfg.beta)
    psi = gem_prior_sample(kd, cfg.K, cfg.gamma)
    return HDPState(
        z=z, n=n, phi=phi, varphi=varphi, psi=psi,
        l=jnp.zeros((cfg.K,), jnp.int32), key=key, it=jnp.int32(0),
    )


def _z_step(cfg: HDPConfig, tokens, mask, z, phi, psi, uniforms):
    """Dispatch to the configured z-step.

    Returns ``(z_new, m)``: the new assignments and their (D, K)
    per-document histogram from the sweep carry.
    """
    if cfg.z_impl == "dense":
        return z_step_dense(tokens, mask, z, phi, psi, cfg.alpha,
                            uniforms, unroll=cfg.unroll_z)
    if cfg.z_impl == "sparse":
        q_a, aprob, aalias = build_alias_tables(phi, psi, cfg.alpha)
        return z_step_sparse_tables(
            tokens, mask, z, phi, cfg.alpha, uniforms, cfg.bucket,
            q_a, aprob, aalias, unroll=cfg.unroll_z,
        )
    if cfg.z_impl == "pallas":
        from repro.kernels.hdp_z import ops as zops

        return zops.z_step_pallas(
            tokens, mask, z, phi, psi, cfg.alpha, uniforms, cfg.bucket,
            alias_in_kernel=cfg.alias_in_kernel,
        )
    raise ValueError(f"unknown z_impl {cfg.z_impl!r}")


def gibbs_iteration(
    state: HDPState, tokens: jax.Array, mask: jax.Array, cfg: HDPConfig
) -> HDPState:
    key, k_phi, k_u, k_l, k_psi = jax.random.split(state.key, 5)

    # 1. Phi-step (parallel over topics)
    if cfg.exact_phi:
        phi = dirichlet_sample(k_phi, state.n, cfg.beta)
        varphi = state.varphi
    elif cfg.ppu_nnz_budget is not None:
        phi, varphi = ppu_sample_budgeted(
            k_phi, state.n, cfg.beta, cfg.ppu_nnz_budget)
    else:
        phi, varphi = ppu_sample(k_phi, state.n, cfg.beta)

    # 2. z-step (parallel over documents); the sweep emits its per-doc
    #    histogram m, and n advances by the exact integer delta over
    #    changed tokens — no from-zero recount (see module docstring).
    uniforms = jax.random.uniform(k_u, tokens.shape + (3,), jnp.float32)
    z, m = _z_step(cfg, tokens, mask, state.z, phi, state.psi, uniforms)
    n = state.n + delta_n(state.z, z, tokens, mask, cfg.K, cfg.V)
    dh = d_histogram(m, cfg.hist_cap)

    # 3. l-step (binomial trick; parallel over topics, constant in D/N)
    l = sample_l(k_l, dh, state.psi, cfg.alpha)

    # 4. Psi-step (FGEM stick-breaking, flag topic at K*-1)
    psi = sample_psi(k_psi, l, cfg.gamma)

    return HDPState(
        z=z, n=n, phi=phi, varphi=varphi, psi=psi, l=l,
        key=key, it=state.it + 1,
    )


# --------------------------------------------------------------------------
# diagnostics (paper Figure 1 metrics)
# --------------------------------------------------------------------------

def log_marginal_likelihood(
    state: HDPState, tokens: jax.Array, mask: jax.Array, cfg: HDPConfig
) -> jax.Array:
    """log p(w, z | Phi, Psi): token term + Polya-sequence term per doc."""
    tokens = jnp.asarray(tokens)
    mask = jnp.asarray(mask)
    phi_full = jnp.asarray(state.phi)
    zz = jnp.where(mask, jnp.asarray(state.z), 0)
    tt = jnp.where(mask, tokens, 0)
    tok_ll = jnp.sum(
        jnp.where(mask, jnp.log(jnp.maximum(phi_full[zz, tt], 1e-30)), 0.0)
    )
    apsi = cfg.alpha * jnp.asarray(state.psi)
    k = cfg.K

    def doc_ll(z_d, msk_d):
        m0 = jnp.zeros((k,), jnp.float32)

        def body(i, carry):
            ll, m, cnt = carry
            zi = z_d[i]
            live = msk_d[i]
            num = apsi[zi] + m[zi]
            den = cfg.alpha + cnt
            ll = ll + jnp.where(live, jnp.log(num / den), 0.0)
            m = m.at[zi].add(jnp.where(live, 1.0, 0.0))
            cnt = cnt + jnp.where(live, 1.0, 0.0)
            return ll, m, cnt

        ll, _, _ = jax.lax.fori_loop(
            0, z_d.shape[0], body, (jnp.float32(0.0), m0, jnp.float32(0.0))
        )
        return ll

    return tok_ll + jnp.sum(jax.vmap(doc_ll)(zz, mask))


def posterior_predictive_ll(
    state: HDPState, tokens: jax.Array, mask: jax.Array, cfg: HDPConfig
) -> jax.Array:
    """Token log-likelihood under posterior-mean parameters.

    phi_mean ∝ n + beta, theta_mean ∝ m + alpha psi. Deterministic given
    the state (unlike the complete-data LL, which resamples Phi each
    iteration and is very noisy) — the stable convergence diagnostic used
    by the test-suite."""
    phi_mean = (state.n + cfg.beta) / jnp.sum(
        state.n + cfg.beta, axis=1, keepdims=True
    )
    m = doc_topic_counts(state.z, mask, cfg.K).astype(jnp.float32)
    theta = m + cfg.alpha * state.psi
    theta = theta / jnp.sum(theta, axis=1, keepdims=True)  # (D, K)
    probs = jnp.einsum("dk,kv->dv", theta, phi_mean)  # (D, V)
    tt = jnp.where(mask, tokens, 0)
    tok_p = jnp.take_along_axis(probs, tt.astype(jnp.int32), axis=1)
    return jnp.sum(jnp.where(mask, jnp.log(jnp.maximum(tok_p, 1e-30)), 0.0))


def active_topics(state: HDPState) -> jax.Array:
    """Number of topics with at least one token assigned."""
    return jnp.sum(jnp.sum(state.n, axis=1) > 0)


def flag_topic_tokens(state: HDPState) -> jax.Array:
    """Tokens at the flag topic K* (should stay 0 if K* is large enough)."""
    return jnp.sum(state.n[-1])


def topic_sizes(state: HDPState) -> jax.Array:
    return jnp.sum(state.n, axis=1)
