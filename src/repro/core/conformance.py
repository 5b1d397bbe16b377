"""z-step conformance contract: one canonical uniform->topic map, three
execution strategies, bitwise-equal results.

The production z-steps in core/hdp.py are *law*-equivalent (same full
conditional) but consume the shared (D, L, 3) uniforms through different
maps — dense inverse-CDF vs alias tables — so their sampled z differ
bitwise and can only be cross-checked distributionally (slow, weak
tests). This module pins down a single canonical map — the paper's
doubly-sparse decomposition over word-sparse tables — and implements it
with three different execution strategies:

  * ``dense``  — O(K) per token: the document term is accumulated over a
                 dense ascending-topic K-vector (scatter of the table);
  * ``sparse`` — O(W) per token: pure-jnp gathers over the (V, W) table
                 slots (the kernel's jnp oracle);
  * ``pallas`` — the hdp_z Pallas kernel: compiled on a TPU, in
                 interpret mode elsewhere (``resolve_interpret``).

Bitwise agreement relies on tables built with ``order="topic"``: slots
sorted by ascending topic id, so every left-to-right partial sum over
table slots equals the same sum over the dense K-vector exactly (the
interleaved absent-topic slots contribute exactly 0.0, and IEEE addition
of 0.0 is the identity). The tables must cover each word's full topic
support (W >= max_column_nnz(phi)); builders assert this in tests.

Equality of the three strategies given shared tables + uniforms is the
repo's strongest correctness check on the z-step: any divergence in
masking, decrement/increment ordering, branch selection, or alias
mechanics shows up as a hard bit mismatch instead of a statistical blur
(tests/test_z_conformance.py).

All strategies follow the repo-wide z-step return contract
``(z_new, m)`` (core/hdp.py): the (D, K) per-document histogram comes
out of the sweep carry and must itself agree bitwise across strategies
(and with ``doc_topic_counts(z_new)``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.hdp_z import ops as zops
from repro.kernels.hdp_z.hdp_z import hdp_z_pallas
from repro.kernels.hdp_z.ref import hdp_z_ref


def build_tables(phi: jax.Array, psi: jax.Array, alpha: float, w: int):
    """Canonical (topic-ordered) word-sparse tables shared by all
    strategies: (q_a (V,), fpack (V,2,W), ipack (V,2,W))."""
    return zops.build_word_sparse_tables(phi, psi, alpha, w, order="topic")


def z_step_dense_tables(
    tokens: jax.Array, mask: jax.Array, z: jax.Array, uniforms: jax.Array,
    q_a: jax.Array, fpack: jax.Array, ipack: jax.Array, *, kk: int,
) -> tuple[jax.Array, jax.Array]:
    """Dense execution of the canonical map.

    The document term is a dense (K,) accumulation in ascending topic
    order — the same arithmetic the table slots perform, with the
    absent topics contributing exact zeros — so the sampled topic is
    bitwise-identical to the table-slot strategies. The global (alias)
    term is structural — slot width W is part of the map — and is read
    from the shared table.
    """
    w = fpack.shape[-1]

    def doc_sweep(tok_d, msk_d, z_d, u_d):
        m = jnp.zeros((kk,), jnp.int32).at[jnp.where(msk_d, z_d, 0)].add(
            msk_d.astype(jnp.int32)
        )

        def body(i, carry):
            z_d, m = carry
            v = tok_d[i]
            live = msk_d[i]
            z_old = z_d[i]
            m = m.at[z_old].add(-jnp.where(live, 1, 0))

            vals = fpack[v, 0, :].astype(jnp.float32)
            ids = ipack[v, 0, :].astype(jnp.int32)
            # dense (K,) expansion: ids are distinct per word (top_k), so
            # scatter-set places each slot's phi value at its topic.
            phi_v = jnp.zeros((kk,), jnp.float32).at[ids].set(vals)
            wb = phi_v * m.astype(jnp.float32)  # (K,) ascending topic order
            qb = jnp.sum(wb)
            qa = q_a[v]
            tot = qa + qb

            u1, u2, u3 = u_d[i, 0], u_d[i, 1], u_d[i, 2]
            t = u1 * tot

            # document term: inverse CDF over the dense ascending sweep
            c = jnp.cumsum(wb)
            k_doc = jnp.minimum(
                jnp.sum((c < t).astype(jnp.int32)), kk - 1
            )

            # global term: the shared W-slot alias structure
            aprob = fpack[v, 1, :].astype(jnp.float32)
            aalias = ipack[v, 1, :].astype(jnp.int32)
            slot_a = jnp.minimum((u2 * w).astype(jnp.int32), w - 1)
            keep = u3 < aprob[slot_a]
            slot_a = jnp.where(keep, slot_a, aalias[slot_a])
            k_glob = ids[slot_a]

            doc_branch = (t < qb) | (qa <= 0.0)
            k_new = jnp.where(doc_branch, k_doc, k_glob)
            k_new = jnp.where(live & (tot > 0), k_new, z_old).astype(jnp.int32)

            m = m.at[k_new].add(jnp.where(live, 1, 0))
            return z_d.at[i].set(k_new), m

        return jax.lax.fori_loop(0, tok_d.shape[0], body, (z_d, m))

    return jax.vmap(doc_sweep)(tokens, mask, z, uniforms)


def z_step_conformant(
    impl: str,
    tokens: jax.Array, mask: jax.Array, z: jax.Array, uniforms: jax.Array,
    q_a: jax.Array, fpack: jax.Array, ipack: jax.Array, *, kk: int,
) -> tuple[jax.Array, jax.Array]:
    """Run the canonical z-step via the chosen execution strategy;
    returns ``(z_new, m)``."""
    if impl == "dense":
        return z_step_dense_tables(
            tokens, mask, z, uniforms, q_a, fpack, ipack, kk=kk
        )
    if impl == "sparse":
        return hdp_z_ref(
            tokens, mask, z, uniforms, q_a, fpack, ipack, kk=kk
        )
    if impl == "pallas":
        return hdp_z_pallas(
            tokens, mask, z, uniforms, q_a, fpack, ipack, kk=kk,
        )
    raise ValueError(f"unknown conformance impl {impl!r}")
