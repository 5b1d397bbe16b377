"""Pallas TPU kernel for the doubly sparse HDP z-step (paper Section 2.5).

TPU-native layout (DESIGN.md section 3): Phi is stored *word-sparse* —
for each word type v, the W topics with varphi_{k,v} > 0:

  fpack (V, 2, W) f32  : [vals, alias_prob]   vals = phi[ids, v]
  ipack (V, 2, W) i32  : [ids,  alias_idx]    alias_idx indexes SLOTS
  q_a   (V,)      f32  : sum_k phi[k,v] alpha psi_k   (term-a mass)

Per token the kernel DMAs the word's rows from HBM (2*(4+4)*W bytes; at
W=128 that is 2 KiB vs 2*K*8 = 16 KiB for dense-K tables) and keeps the
per-document topic histogram m resident in VMEM as one (R, 128) int32
tile (R = 8 per 1024 topics). Term (b) is vals * m[ids] over the W
lanes, the gather done as R in-register lane gathers; term (a) is an
O(1) alias draw over the W slots. This is the TPU translation of the
paper's "iterate over whichever of m / Phi has fewer non-zeros": the
word's non-zero list bounds the work and the traffic, the document's
non-zeros enter through the dense-in-VMEM m gather.

Memory placement follows what Mosaic lowers:

  * tokens, mask, z and the uniforms are per-token scalars, so their
    (DB, L) blocks live in SMEM, and so does the z output block; the
    word id read there is the row index of the per-token DMAs;
  * vector values are 2-D (1, W) rows; a value at a dynamic lane (the
    drawn slot, q_a[v] inside its 128-lane row) is a one-hot lane
    reduction, which selects without arithmetic;
  * the term-(b) cumulative line and every row total use
    ``core.alias.prefix_sum`` with ``pltpu.roll``, the same additions in
    the same order as the oracle in ref.py;
  * dead (masked) tokens skip the body and keep their z.

The kernel consumes three externally supplied uniforms per token, so the
pure-jnp oracle in ref.py must match it exactly (tests assert bitwise
equality of the sampled z in interpret mode).

Grid: one program per block of DB documents; within a program the sweep
is sequential over each document's tokens (Gibbs order within documents,
parallel across documents — exactly the parallelism the paper licenses).
The document axis is padded up to a multiple of ``doc_block`` with
all-False mask rows (pad rows sweep to nothing and emit zero
histograms), so the grid never degenerates to one-document programs
when D is prime or coprime with the block size.

Outputs follow the repo-wide z-step contract: ``(z_new, m)`` where m is
the (D, K) per-document topic histogram of z_new, written from the
kernel's VMEM-resident sweep carry after each document's sweep.

The kernel does not accumulate the (K, V) update to the topic-word
statistic: a (K, V) int32 block resident in VMEM is 28 MB at the AP cell
(K=1000, V=7168) and 360 MB at PubMed width, beyond what the chip's VMEM
holds. Callers take it from the XLA scatter ``core.hdp.delta_n`` after
the sweep, the same way at every width.

With ``in_kernel=True`` (the kernel-prologue alias build, gated by
``HDPConfig.alias_in_kernel``) the packed-table inputs are replaced by
raw supports — vals (V, W) f32, ids (V, W) i32 — plus apsi = alpha*psi
(K,) resident in VMEM in the q_a slot. Per token the kernel DMAs the
two raw (W,) rows (half the packed-table bytes), rebuilds
``wa = vals * apsi[ids]``, ``q_a = sum(wa)``, and the alias partition
via ``core.alias.alias_build_row_onehot`` (the Pallas-safe one-hot twin
of ``alias_build``, no scatters). The (V, 2, W) alias-table
materialization to HBM never happens, but the build runs once per live
token instead of once per word: five (W, W) transposes and about a dozen
(W, W) compare-and-reduce passes in the token loop. The packed tables
are the default (``resolve_alias_in_kernel``): on one v5e chip a live
token costs 2.5-2.7 us with the prologue build and 1.6-1.7 us on packed
tables, whose build, once per word, adds about 0.1 s an iteration at
V=90k (PERF.md).

Compiled (not interpret) mode needs W to be a multiple of 128: the
lane gathers work on whole 128-lane registers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.alias import alias_build_row_onehot, last_lane, prefix_sum

LANES = 128


def resolve_interpret(explicit: bool | None = None) -> bool:
    """Pallas execution mode: compiled on a TPU backend, interpret mode
    (the CPU conformance path) everywhere else.

    An explicit ``True`` pins interpret mode for tests off the chip; it
    is refused on a TPU, where the training and serving paths always
    run the compiled kernel. Called at trace time: the result is a
    static argument of the jitted kernel wrapper.
    """
    on_tpu = jax.default_backend() == "tpu"
    if explicit is None:
        return not on_tpu
    if explicit and on_tpu:
        raise ValueError("interpret mode is the CPU conformance path; on a "
                         "TPU the hdp_z kernel always runs compiled")
    return bool(explicit)


def _roll(x, s, axis):
    return pltpu.roll(x, s, axis)


def _pick(x, lane, j):
    """``x[0, j]`` for a traced scalar j: a one-hot lane reduction."""
    return jnp.sum(jnp.where(lane == j, x, jnp.zeros_like(x)))


def _gather(tab, ids):
    """``tab.reshape(-1)[ids]`` for a (R, 128) topic table (R % 8 == 0)
    and a (1, W) row of topic ids, as one in-register lane gather per
    128-lane chunk of ids followed by a one-hot row select."""
    r = tab.shape[0]
    w = ids.shape[1]
    chunk = min(w, LANES)
    row = jax.lax.broadcasted_iota(jnp.int32, (r, chunk), 0)
    out = []
    for c0 in range(0, w, chunk):
        idc = jnp.broadcast_to(ids[:, c0:c0 + chunk], (r, chunk))
        g = jnp.take_along_axis(tab, idc & (LANES - 1), axis=1)
        hit = jnp.right_shift(idc, 7) == row
        out.append(jnp.sum(jnp.where(hit, g, jnp.zeros_like(g)), axis=0,
                           keepdims=True))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


def _z_kernel(
    tok_ref,      # (DB, L) int32 SMEM
    msk_ref,      # (DB, L) int32 SMEM
    z_ref,        # (DB, L) int32 SMEM
    u_ref,        # (DB, 3L) f32 SMEM — token i's uniforms at 3i, 3i+1, 3i+2
    side_ref,     # q_a (Vq, 1, 128) f32 HBM — in_kernel: apsi (R, 128) VMEM
    f_hbm,        # fpack (V, 2, W) f32 HBM — in_kernel: vals (V, 1, W)
    i_hbm,        # ipack (V, 2, W) i32 HBM — in_kernel: ids (V, 1, W)
    zo_ref,       # (DB, L) int32 SMEM out
    mo_ref,       # (DB, R, 128) int32 VMEM out — final histograms
    m_ref,        # (R, 128) int32 VMEM — per-document histogram
    frow,         # (2 | 1, W) f32 VMEM
    irow,         # (2 | 1, W) int32 VMEM
    qrow,         # (1, 128) f32 VMEM — the 128-lane row holding q_a[v]
    sem,          # DMA semaphores (3,)
    *,
    ww: int,
    ll: int,
    db: int,
    in_kernel: bool,
):
    r = m_ref.shape[0]
    kidx = (jax.lax.broadcasted_iota(jnp.int32, (r, LANES), 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, (r, LANES), 1))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, ww), 1)
    lane128 = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    zero = jnp.zeros((r, LANES), jnp.int32)

    def doc_body(d, _):
        # ---- build m from the incoming assignments ----------------------
        def hist(i, m):
            hit = (kidx == z_ref[d, i]) & (msk_ref[d, i] != 0)
            return m + jnp.where(hit, 1, 0)

        m_ref[...] = jax.lax.fori_loop(0, ll, hist, zero)

        # ---- sequential Gibbs sweep over the document -------------------
        def tok_body(i, _):
            z_old = z_ref[d, i]
            zo_ref[d, i] = z_old

            @pl.when(msk_ref[d, i] != 0)
            def _live():
                v = tok_ref[d, i]
                # m^{-i}: remove the current assignment
                m = m_ref[...] - jnp.where(kidx == z_old, 1, 0)

                # DMA this word's rows HBM -> VMEM
                copies = [
                    pltpu.make_async_copy(f_hbm.at[v], frow, sem.at[0]),
                    pltpu.make_async_copy(i_hbm.at[v], irow, sem.at[1]),
                ]
                if not in_kernel:
                    copies.append(pltpu.make_async_copy(
                        side_ref.at[jnp.right_shift(v, 7)], qrow, sem.at[2]))
                for cp in copies:
                    cp.start()
                for cp in copies:
                    cp.wait()

                if in_kernel:
                    # prologue mode: raw (W,) supports arrive; wa / q_a
                    # and the alias partition are built here, in VMEM,
                    # from phi values and apsi = alpha * psi — the
                    # (V, 2, W) table round-trip never happens.
                    vals = frow[...]
                    ids = irow[...]
                    wa = vals * _gather(side_ref[...], ids)
                    qa = jnp.sum(last_lane(prefix_sum(wa, _roll)))
                    aprob, aalias = alias_build_row_onehot(wa, _roll)
                else:
                    vals = frow[0:1, :]    # (1, W) phi values
                    aprob = frow[1:2, :]   # (1, W) alias keep-probability
                    ids = irow[0:1, :]     # (1, W) topic ids
                    aalias = irow[1:2, :]  # (1, W) alias donor slots
                    qa = _pick(qrow[...], lane128, v & (LANES - 1))

                # term (b): doc mass over the word's non-zero topics
                wb = vals * _gather(m, ids).astype(jnp.float32)
                c = prefix_sum(wb, _roll)
                qb = _pick(c, lane, ww - 1)
                tot = qa + qb

                u1 = u_ref[d, 3 * i]
                u2 = u_ref[d, 3 * i + 1]
                u3 = u_ref[d, 3 * i + 2]
                t = u1 * tot

                # doc branch: inverse CDF over wb
                slot_b = jnp.minimum(
                    jnp.sum((c < t).astype(jnp.int32)), ww - 1)
                k_doc = _pick(ids, lane, slot_b)

                # global branch: O(1) alias draw over W slots
                slot_a = jnp.minimum((u2 * ww).astype(jnp.int32), ww - 1)
                keep = u3 < _pick(aprob, lane, slot_a)
                slot_a = jnp.where(keep, slot_a, _pick(aalias, lane, slot_a))
                k_glob = _pick(ids, lane, slot_a)

                k_new = jnp.where((t < qb) | (qa <= 0.0), k_doc, k_glob)
                k_new = jnp.where(tot > 0, k_new, z_old)
                m_ref[...] = m + jnp.where(kidx == k_new, 1, 0)
                zo_ref[d, i] = k_new

            return 0

        jax.lax.fori_loop(0, ll, tok_body, 0)
        # emit the sweep-carry histogram: m_out[d] == hist(z_out[d]).
        mo_ref[d] = m_ref[...]
        return 0

    jax.lax.fori_loop(0, db, doc_body, 0)


@functools.partial(
    jax.jit,
    static_argnames=("kk", "doc_block", "interpret", "in_kernel"),
)
def hdp_z_pallas(
    tokens: jax.Array,   # (D, L) int32
    mask: jax.Array,     # (D, L) bool
    z: jax.Array,        # (D, L) int32
    uniforms: jax.Array,  # (D, L, 3) f32
    q_a: jax.Array,      # (V,) f32   — in_kernel=True: apsi (K,) f32
    fpack: jax.Array,    # (V, 2, W) f32 — in_kernel=True: vals (V, W) f32
    ipack: jax.Array,    # (V, 2, W) i32 — in_kernel=True: ids (V, W) i32
    *,
    kk: int,
    doc_block: int = 8,
    interpret: bool | None = None,
    in_kernel: bool = False,
) -> tuple[jax.Array, jax.Array]:
    interpret = resolve_interpret(interpret)
    d, l = tokens.shape
    v, w = fpack.shape[0], fpack.shape[-1]
    if not interpret and w % LANES:
        raise ValueError(f"the compiled hdp_z kernel needs a table width W "
                         f"that is a multiple of {LANES}, got W={w}")
    db = min(doc_block, d)
    # Pad the document axis up to a multiple of db with all-False mask
    # rows instead of shrinking db to a divisor of D (which collapsed to
    # db=1 whenever D was prime). Pad rows sweep to nothing and are
    # sliced off below.
    d_pad = ((d + db - 1) // db) * db
    pad = ((0, d_pad - d), (0, 0))
    tok_p = jnp.pad(tokens, pad)
    msk_p = jnp.pad(mask, pad).astype(jnp.int32)
    z_p = jnp.pad(z, pad)
    u_p = jnp.pad(uniforms, pad + ((0, 0),)).reshape(d_pad, 3 * l)
    # the (K,) histogram as a (R, 128) tile, R a multiple of 8 sublanes
    r = -(-kk // (8 * LANES)) * 8

    if in_kernel:
        side = jnp.pad(q_a.astype(jnp.float32),
                       (0, r * LANES - kk)).reshape(r, LANES)
        side_spec = pl.BlockSpec((r, LANES), lambda g: (0, 0))
        f_in = fpack.astype(jnp.float32).reshape(v, 1, w)
        i_in = ipack.astype(jnp.int32).reshape(v, 1, w)
        rows = 1
    else:
        vq = -(-v // LANES)
        side = jnp.pad(q_a.astype(jnp.float32),
                       (0, vq * LANES - v)).reshape(vq, 1, LANES)
        side_spec = pl.BlockSpec(memory_space=pl.ANY)
        # compact (bf16 / int16) tables widen exactly
        f_in = fpack.astype(jnp.float32)
        i_in = ipack.astype(jnp.int32)
        rows = 2

    def smem(width):
        return pl.BlockSpec((db, width), lambda g: (g, 0),
                            memory_space=pltpu.SMEM)

    z_out, m_out = pl.pallas_call(
        functools.partial(_z_kernel, ww=w, ll=l, db=db, in_kernel=in_kernel),
        grid=(d_pad // db,),
        in_specs=[
            smem(l),      # tokens
            smem(l),      # mask
            smem(l),      # z
            smem(3 * l),  # uniforms
            side_spec,    # q_a (HBM) / apsi (VMEM resident)
            pl.BlockSpec(memory_space=pl.ANY),  # fpack / vals (HBM)
            pl.BlockSpec(memory_space=pl.ANY),  # ipack / ids (HBM)
        ],
        out_specs=[
            smem(l),
            pl.BlockSpec((db, r, LANES), lambda g: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d_pad, l), jnp.int32),
            jax.ShapeDtypeStruct((d_pad, r, LANES), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((r, LANES), jnp.int32),
            pltpu.VMEM((rows, w), jnp.float32),
            pltpu.VMEM((rows, w), jnp.int32),
            pltpu.VMEM((1, LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((3,)),
        ],
        interpret=interpret,
        name="hdp_z",
    )(tok_p, msk_p, z_p, u_p, side, f_in, i_in)
    return z_out[:d], m_out.reshape(d_pad, r * LANES)[:d, :kk]
