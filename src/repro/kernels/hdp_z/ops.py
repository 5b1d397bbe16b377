"""jit'd wrappers + table builders for the hdp_z kernel.

``build_word_sparse_tables`` converts a (K, V) Phi into the kernel's
word-sparse layout: per word type, the top-W topics by phi value (== the
non-zero set when W >= max column nnz, which the PPU draw makes small),
the per-word alias table over those W slots, and the term-(a) mass q_a.

In the sharded sampler the tables are built model-parallel on vocab
shards and all-gathered — (V, W) tables instead of the paper's dense
(K, V) Phi broadcast, a W/K communication saving (EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.core.alias import alias_build_onehot, prefix_sum
from repro.core.hdp import delta_n
from repro.kernels.hdp_z.hdp_z import hdp_z_pallas, resolve_interpret
from repro.kernels.hdp_z.ref import hdp_z_ref, hdp_z_ref_prologue

_FALSY = ("0", "false", "no", "off", "")


def resolve_alias_in_kernel(
    explicit: str | bool | None = "auto", *, interpret: bool,
    compact: bool = False,
) -> bool:
    """Resolve whether the alias partition is built in the kernel prologue.

    Precedence: an explicit ``"on"``/``"off"`` (or bool) wins; else the
    ``REPRO_ALIAS_IN_KERNEL`` env var; else ``"auto"`` = off on every
    backend: the kernel sweeps on per-word ``(q_a, fpack, ipack)``
    tables built once per iteration. The prologue rebuilds a word's
    alias partition (O(W^2) compares) for every live token instead of
    once per word, which costs more than the (V, 2, W) tables it saves:
    on one v5e chip a live token took 2.5-2.7 us in the kernel with the
    prologue and 1.6-1.7 us on per-word tables, whose build adds about
    0.1 s an iteration at V=90k (PERF.md). ``interpret`` no longer
    changes the result; it stays in the signature for the existing
    callers.

    The prologue consumes raw f32 supports, so it composes with
    ``compact=False`` only: an explicit ``"on"`` with compact tables
    raises; env resolution silently degrades to the epilogue.
    """
    if isinstance(explicit, bool):
        on = explicit
        if on and compact:
            raise ValueError("alias_in_kernel='on' requires compact=False "
                             "(the prologue reads raw f32 supports)")
        return on and not compact
    if explicit not in (None, "auto", "on", "off"):
        raise ValueError(f"unknown alias_in_kernel mode {explicit!r}")
    if explicit == "on":
        if compact:
            raise ValueError("alias_in_kernel='on' requires compact=False "
                             "(the prologue reads raw f32 supports)")
        return True
    if explicit == "off":
        return False
    env = os.environ.get("REPRO_ALIAS_IN_KERNEL")
    if env is not None:
        return (env.strip().lower() not in _FALSY) and not compact
    return False


def _word_supports(pt: jax.Array, w: int, order: str):
    """Per-word top-W supports of a (V, K) phi-transpose: (vals, ids).

    Row-independent (top_k / argsort / gathers act per row), so a build
    over any gathered subset of rows is bitwise-equal to the same rows of
    the full build — the invariant the block-sparse path relies on.
    """
    w = min(w, pt.shape[-1])
    vals, idx = jax.lax.top_k(pt, w)
    if order == "topic":
        perm = jnp.argsort(idx, axis=-1)
        vals = jnp.take_along_axis(vals, perm, axis=-1)
        idx = jnp.take_along_axis(idx, perm, axis=-1)
    elif order != "value":
        raise ValueError(f"unknown table order {order!r}")
    return vals.astype(jnp.float32), idx.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("w", "order"))
def build_word_sparse_supports(
    phi: jax.Array, w: int, order: str = "value"
) -> tuple[jax.Array, jax.Array]:
    """Raw word-sparse supports for the kernel-prologue alias build.

    Returns ``(vals (V, W) f32, ids (V, W) int32)`` — the top-W phi
    values and topic ids per word, *without* the alias epilogue: the
    prologue reconstructs ``wa = vals * (alpha * psi)[ids]``, ``q_a``,
    and the alias partition per token in VMEM, so only half the table
    bytes (no aprob/aalias planes, no q_a) ever touch HBM.
    """
    return _word_supports(phi.T, w, order)


@functools.partial(jax.jit, static_argnames=("w", "compact", "order"))
def build_word_sparse_tables(
    phi: jax.Array, psi: jax.Array, alpha: float, w: int,
    compact: bool = False, order: str = "value",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (q_a (V,), fpack (V,2,W), ipack (V,2,W)).

    Exact when every word appears in <= W topics; otherwise the smallest
    phi entries beyond W are dropped (checked by ``max_column_nnz``).

    ``compact=True`` packs fpack in bf16 and ipack in int16 (valid for
    K* <= 32768, enforced), halving the table broadcast — the §Perf "compact tables"
    variant. bf16 phi values only perturb sampling weights ~1e-3
    relatively, within the PPU approximation's own error.

    ``order`` fixes the slot order within each word's table: "value"
    (top_k order, the production default) or "topic" (ascending topic
    id). Topic order makes every left-to-right partial sum over the
    table bitwise-equal to the same sum over a dense ascending-topic
    sweep (zero slots add exactly 0.0), which is what the z-step
    conformance contract (core/conformance.py) relies on.
    """
    if compact and phi.shape[0] > 2**15:
        # int16 topic ids (0..K-1) would silently wrap past 32767,
        # aliasing high topics onto low ones — refuse at trace time
        # (K is static). K == 32768 is the last legal size.
        raise ValueError(
            f"compact int16 topic ids need K <= 32768, got K={phi.shape[0]}"
        )
    vals, ids = _word_supports(phi.T, w, order)
    wa = vals * (jnp.float32(alpha) * psi)[ids]
    q_a = prefix_sum(wa)[..., -1]
    aprob, aalias = alias_build_onehot(wa)
    if compact:
        fpack = jnp.stack(
            [vals.astype(jnp.bfloat16), aprob.astype(jnp.bfloat16)], axis=1
        )
        ipack = jnp.stack(
            [ids.astype(jnp.int16), aalias.astype(jnp.int16)], axis=1
        )
    else:
        fpack = jnp.stack([vals.astype(jnp.float32), aprob], axis=1)
        ipack = jnp.stack([ids, aalias.astype(jnp.int32)], axis=1)
    return q_a.astype(jnp.float32), fpack, ipack


@functools.partial(
    jax.jit, static_argnames=("w", "cap", "compact", "order")
)
def build_word_sparse_tables_masked(
    phi: jax.Array, psi: jax.Array, alpha: float, w: int,
    u_mask: jax.Array, cap: int,
    compact: bool = False, order: str = "value",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Block-sparse ``build_word_sparse_tables``: only vocab rows flagged
    in ``u_mask`` (V,) bool are built; the rest stay zero.

    ``cap`` (static) must bound the number of flagged rows — rows are
    compacted via a fixed-size ``jnp.nonzero`` gather, built as a
    (cap, ...) subset, and scattered back into zero-initialized full
    (V, ...) outputs. Fill slots alias row 0 (so row 0 gets a real
    table even when unflagged) and scatter duplicate *identical*
    values, so the result is deterministic, and since every
    table op is row-independent (see ``_word_supports``), flagged rows
    are bitwise-equal to the dense build — the sweep only ever gathers
    table rows at token positions, so a sweep over tokens covered by
    ``u_mask`` is bitwise-unchanged. Cost drops from O(V * K) to
    O(cap * K) — the block-sparse tables lever for streamed blocks and
    fold-in request batches that touch a fraction of V.
    """
    if compact and phi.shape[0] > 2**15:
        raise ValueError(
            f"compact int16 topic ids need K <= 32768, got K={phi.shape[0]}"
        )
    v = phi.shape[1]
    cap = min(cap, v)
    (rows,) = jnp.nonzero(u_mask, size=cap, fill_value=0)
    vals, ids = _word_supports(phi.T[rows], w, order)
    wa = vals * (jnp.float32(alpha) * psi)[ids]
    q_a_sub = prefix_sum(wa)[..., -1]
    aprob, aalias = alias_build_onehot(wa)
    if compact:
        fpack_sub = jnp.stack(
            [vals.astype(jnp.bfloat16), aprob.astype(jnp.bfloat16)], axis=1
        )
        ipack_sub = jnp.stack(
            [ids.astype(jnp.int16), aalias.astype(jnp.int16)], axis=1
        )
    else:
        fpack_sub = jnp.stack([vals, aprob], axis=1)
        ipack_sub = jnp.stack([ids, aalias.astype(jnp.int32)], axis=1)
    ww = vals.shape[-1]
    q_a = jnp.zeros((v,), jnp.float32).at[rows].set(
        q_a_sub.astype(jnp.float32))
    fpack = jnp.zeros((v, 2, ww), fpack_sub.dtype).at[rows].set(fpack_sub)
    ipack = jnp.zeros((v, 2, ww), ipack_sub.dtype).at[rows].set(ipack_sub)
    return q_a, fpack, ipack


def max_column_nnz(phi: jax.Array) -> jax.Array:
    """Largest number of topics any single word appears in (for choosing W)."""
    return jnp.max(jnp.sum((phi > 0).astype(jnp.int32), axis=0))


def delta_sparsify(dn: jax.Array, cap: int):
    """Device-side COO extraction of a sweep's integer ``delta_n``: the
    device half of the sparse bit-packed exchange (data/deltawire.py).

    Returns ``(idx, val, nnz)`` with ``idx`` the first ``cap`` flat
    C-order nonzero positions (ascending, zero-padded past ``nnz``),
    ``val`` the deltas at those positions, ``nnz`` the true count.
    ``cap`` must be a static upper bound on nnz — the z-step changes at
    most two cells per resampled token, so ``min(2 * tokens, K * V)``
    always holds — which keeps the D2H copy bounded by ``cap`` entries
    instead of the full (K, V) grid; the host then truncates to ``nnz``
    and dtype-narrows (``deltawire.pack_coo``)."""
    flat = dn.reshape(-1)
    nnz = jnp.count_nonzero(flat)
    (idx,) = jnp.nonzero(flat, size=cap, fill_value=0)
    return idx.astype(jnp.int32), flat[idx], nnz


@functools.partial(
    jax.jit,
    static_argnames=(
        "bucket", "order", "compact", "interpret", "emit_delta", "in_kernel"
    ),
)
def _z_step_pallas_fused(
    tokens, mask, z, phi, psi, alpha, uniforms,
    *, bucket, order, compact, interpret, emit_delta, in_kernel=False,
):
    """Table build + kernel as ONE jitted program: the alias epilogue
    (top_k / argsort / alias partition) lowers on-device right before the
    pallas_call, so there is no host round-trip between building the
    word-sparse tables and sweeping with them.

    With ``in_kernel=True`` the alias epilogue disappears entirely: only
    the raw supports (vals, ids) are materialized, and the kernel builds
    wa / q_a / the alias row per token in VMEM (the kernel-prologue
    path).

    ``emit_delta=True`` appends the (K, V) ``delta_n`` XLA scatter; the
    kernel itself only returns ``(z_new, m)``."""
    if in_kernel:
        vals, ids = build_word_sparse_supports(phi, bucket, order=order)
        apsi = jnp.float32(alpha) * psi
        out = hdp_z_pallas(
            tokens, mask, z, uniforms, apsi, vals, ids,
            kk=phi.shape[0], interpret=interpret, in_kernel=True,
        )
    else:
        q_a, fpack, ipack = build_word_sparse_tables(
            phi, psi, alpha, bucket, compact=compact, order=order
        )
        out = hdp_z_pallas(
            tokens, mask, z, uniforms, q_a, fpack, ipack,
            kk=phi.shape[0], interpret=interpret,
        )
    if not emit_delta:
        return out
    return out + (delta_n(z, out[0], tokens, mask, phi.shape[0],
                          phi.shape[1]),)


def z_step_pallas(
    tokens, mask, z, phi, psi, alpha, uniforms, bucket, *,
    order="value", compact=False, emit_delta=False, alias_in_kernel="auto",
):
    """Drop-in z-step: builds tables then runs the kernel (W = bucket),
    fused into a single jitted dispatch (no host hop between the table
    epilogue and the sweep).

    ``order``/``compact`` select the table variant (see
    ``build_word_sparse_tables``); the kernel runs compiled on a TPU and
    in interpret mode elsewhere (``resolve_interpret``);
    ``alias_in_kernel`` ("auto"/"on"/"off", see
    ``resolve_alias_in_kernel``; "auto" is off) selects the
    kernel-prologue alias build over the epilogue-fused tables. Returns
    ``(z_new, m)`` like every z-step (core/hdp.py docstring), plus the
    (K, V) ``delta_n`` when ``emit_delta=True``."""
    interp = resolve_interpret()
    return _z_step_pallas_fused(
        tokens, mask, z, phi, psi, alpha, uniforms,
        bucket=bucket, order=order, compact=compact,
        interpret=interp, emit_delta=emit_delta,
        in_kernel=resolve_alias_in_kernel(
            alias_in_kernel, interpret=interp, compact=compact
        ),
    )


def z_step_ref(
    tokens, mask, z, phi, psi, alpha, uniforms, bucket, *,
    order="value", compact=False, emit_delta=False, alias_in_kernel="off",
):
    """Same math via the pure-jnp oracle (bitwise-identical to the kernel);
    returns ``(z_new, m)`` (plus ``delta_n`` when ``emit_delta=True``).
    ``alias_in_kernel="on"`` mirrors the kernel-prologue path (per-token
    alias build from raw supports) instead of the table epilogue."""
    if resolve_alias_in_kernel(
        alias_in_kernel, interpret=True, compact=compact
    ):
        vals, ids = build_word_sparse_supports(phi, bucket, order=order)
        apsi = jnp.float32(alpha) * psi
        return hdp_z_ref_prologue(
            tokens, mask, z, uniforms, apsi, vals, ids, kk=phi.shape[0],
            emit_delta=emit_delta,
        )
    q_a, fpack, ipack = build_word_sparse_tables(
        phi, psi, alpha, bucket, compact=compact, order=order
    )
    return hdp_z_ref(
        tokens, mask, z, uniforms, q_a, fpack, ipack, kk=phi.shape[0],
        emit_delta=emit_delta,
    )
