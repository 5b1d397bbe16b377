"""Pure-jnp oracle for the hdp_z Pallas kernel.

Identical math over the identical word-sparse tables consuming the
identical uniforms — tests assert *bitwise* equality of the sampled z
(and of the emitted per-doc histogram m) against the kernel in
interpret mode. Row totals and the term-(b) cumulative line come from
``core.alias.prefix_sum``, the same order of additions the compiled
kernel uses, so the oracle is exact on the chip as well. Like every z-step, returns ``(z_new, m)`` with m the
(D, K) sweep-carry histogram of z_new.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.alias import alias_build_row_onehot, prefix_sum


def hdp_z_ref(
    tokens: jax.Array,    # (D, L) int32
    mask: jax.Array,      # (D, L) bool
    z: jax.Array,         # (D, L) int32
    uniforms: jax.Array,  # (D, L, 3) f32
    q_a: jax.Array,       # (V,) f32
    fpack: jax.Array,     # (V, 2, W) f32
    ipack: jax.Array,     # (V, 2, W) int32
    *,
    kk: int,
    emit_delta: bool = False,
) -> tuple[jax.Array, ...]:
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
            aprob = fpack[v, 1, :].astype(jnp.float32)
            ids = ipack[v, 0, :].astype(jnp.int32)
            aalias = ipack[v, 1, :].astype(jnp.int32)

            mb = m[ids].astype(jnp.float32)
            wb = vals * mb
            c = prefix_sum(wb)
            qb = c[-1]
            qa = q_a[v]
            tot = qa + qb

            u1, u2, u3 = u_d[i, 0], u_d[i, 1], u_d[i, 2]
            t = u1 * tot

            slot_b = jnp.minimum(jnp.sum((c < t).astype(jnp.int32)), w - 1)
            k_doc = ids[slot_b]

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

    z_new, m = jax.vmap(doc_sweep)(tokens, mask, z, uniforms)
    if not emit_delta:
        return z_new, m
    # delta_n over changed live tokens, inlined (same scatter as
    # core/hdp.py delta_n — bitwise-equal by integer commutativity).
    vv = q_a.shape[0]
    ch = (mask & (z_new != z)).astype(jnp.int32).reshape(-1)
    zo = jnp.where(mask, z, 0).reshape(-1)
    zn = jnp.where(mask, z_new, 0).reshape(-1)
    tt = jnp.where(mask, tokens, 0).reshape(-1)
    dn = (
        jnp.zeros((kk, vv), jnp.int32)
        .at[zn, tt].add(ch)
        .at[zo, tt].add(-ch)
    )
    return z_new, m, dn


def hdp_z_ref_prologue(
    tokens: jax.Array,    # (D, L) int32
    mask: jax.Array,      # (D, L) bool
    z: jax.Array,         # (D, L) int32
    uniforms: jax.Array,  # (D, L, 3) f32
    apsi: jax.Array,      # (K,) f32 — alpha * psi
    vals_all: jax.Array,  # (V, W) f32 — raw support values
    ids_all: jax.Array,   # (V, W) int32 — raw support topic ids
    *,
    kk: int,
    emit_delta: bool = False,
) -> tuple[jax.Array, ...]:
    """Oracle for the kernel-prologue alias build (``in_kernel=True``).

    Mirrors the kernel's per-token math: DMA'd raw (W,) supports,
    wa = vals * apsi[ids], q_a = sum(wa), alias row via the same
    ``alias_build_row_onehot`` the kernel lowers — tests assert bitwise
    equality against the kernel in interpret mode.
    """
    w = vals_all.shape[-1]

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

            vals = vals_all[v].astype(jnp.float32)
            ids = ids_all[v].astype(jnp.int32)
            wa = vals * apsi[ids]
            qa = prefix_sum(wa)[-1]
            aprob, aalias = alias_build_row_onehot(wa)

            mb = m[ids].astype(jnp.float32)
            wb = vals * mb
            c = prefix_sum(wb)
            qb = c[-1]
            tot = qa + qb

            u1, u2, u3 = u_d[i, 0], u_d[i, 1], u_d[i, 2]
            t = u1 * tot

            slot_b = jnp.minimum(jnp.sum((c < t).astype(jnp.int32)), w - 1)
            k_doc = ids[slot_b]

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

    z_new, m = jax.vmap(doc_sweep)(tokens, mask, z, uniforms)
    if not emit_delta:
        return z_new, m
    vv = vals_all.shape[0]
    ch = (mask & (z_new != z)).astype(jnp.int32).reshape(-1)
    zo = jnp.where(mask, z, 0).reshape(-1)
    zn = jnp.where(mask, z_new, 0).reshape(-1)
    tt = jnp.where(mask, tokens, 0).reshape(-1)
    dn = (
        jnp.zeros((kk, vv), jnp.int32)
        .at[zn, tt].add(ch)
        .at[zo, tt].add(-ch)
    )
    return z_new, m, dn
