"""Data-parallel HDP Gibbs iteration on a (pod, data, model) mesh.

Mapping of the paper's parallelism (DESIGN.md section 4):

  * documents  -> sharded over EVERY mesh axis (the z-step is
                  embarrassingly parallel over documents; parallelism
                  scales with D, the paper's key scalability claim);
  * n, Phi     -> vocabulary-sharded over the `model` axis, replicated
                  over (pod, data). The PPU Phi-step and alias-table
                  build are `model`-parallel over vocab shards;
  * Psi, l     -> replicated; their samplers are O(K*) and use identical
                  keys on every device (deterministic replication).

Collective schedule per iteration (the roofline terms in EXPERIMENTS.md
are derived from exactly these):

  1. psum(row sums)                       [model]        K * 4B
  2. all_gather(phi_shard)                [model]        K*V*4B / dev
  3. all_gather(q_a, alias prob/idx)      [model]        ~2 K*V / dev
  4. local z-step (emits z', per-doc m)   none
  5. psum_scatter(delta_n local)          [model]        K*V*4B
  6. psum(delta_n vshard)                 [pod, data]    K*V/M * 4B
  7. psum(d_hist from emitted m)          [all]          K*(P+1)*4B

Steps 5-7 reduce *update deltas*, not recounts: the z-sweep emits its
per-document histogram m straight from the sweep carry, and the
topic-word statistic advances by ``n += delta_n(z_old, z_new)`` — an
exact integer scatter over changed tokens only (core/hdp.py). The wire
bytes of 5-6 are unchanged (dense (K, V) int32 either way), but the
from-zero count_n scatter of every token and the separate
doc_topic_counts pass are gone from the per-block hot path.

Baseline = paper-faithful replicated-Phi pattern (MALLET shared memory ->
all_gather). The config flags `gather_tables` / `phi_dtype` select the
beyond-paper optimized variants measured in EXPERIMENTS.md §Perf.

The iteration is decomposed into three mesh-local sub-steps —
``_phi_tables`` (1-3), ``_z_sweep`` (4), ``_block_stats`` (5-7a) — plus
a replicated tail (7b: l-step + Psi-step). The monolithic
``iteration_fn`` composes all of them inside one shard_map; the
streaming driver (core/streaming.py) shard_maps them separately so the
Phi-step runs once per Gibbs iteration while the z-sweep and the
statistics merge run once per corpus block.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat
from repro.core import hdp as H
from repro.core.alias import alias_build
from repro.core.stick import sample_l, sample_psi


class ShardedHDP:
    """Mesh-aware HDP sampler. All state arrays keep *global* shapes;
    NamedShardings describe placement, shard_map makes collectives
    explicit."""

    def __init__(
        self,
        mesh: Mesh,
        cfg: H.HDPConfig,
        *,
        doc_axes: Sequence[str] | None = None,
        model_axis: str = "model",
        gather_tables: bool = True,
        phi_dtype: jnp.dtype = jnp.float32,
        compact_tables: bool = False,
    ):
        self.mesh = mesh
        self.cfg = cfg
        self.model_axis = model_axis
        axis_names = list(mesh.axis_names)
        if doc_axes is None:
            doc_axes = tuple(axis_names)  # shard docs over every axis
        self.doc_axes = tuple(doc_axes)
        self.repl_axes = tuple(a for a in axis_names if a != model_axis)
        self.gather_tables = gather_tables
        self.phi_dtype = phi_dtype
        self.compact_tables = compact_tables
        if cfg.V % mesh.shape[model_axis]:
            raise ValueError(
                f"V={cfg.V} must divide model axis {mesh.shape[model_axis]}"
            )
        if cfg.z_impl not in ("dense", "sparse", "pallas"):
            raise ValueError(f"unknown z_impl {cfg.z_impl!r}")
        # kernel-prologue alias build: resolved once (static for every
        # jitted sub-step). Only meaningful for the pallas impl.
        self.alias_in_kernel = False
        if cfg.z_impl == "pallas":
            from repro.kernels.hdp_z import ops as zops

            self.alias_in_kernel = zops.resolve_alias_in_kernel(
                cfg.alias_in_kernel,
                interpret=zops.resolve_interpret(),
                compact=compact_tables,
            )

    # -- sharding specs ---------------------------------------------------
    def specs(self) -> dict[str, P]:
        da = self.doc_axes if len(self.doc_axes) > 1 else self.doc_axes[0]
        return dict(
            z=P(da, None),
            tokens=P(da, None),
            mask=P(da, None),
            n=P(None, self.model_axis),
            phi=P(None, self.model_axis),
            varphi=P(None, self.model_axis),
            psi=P(),
            l=P(),
            key=P(),
            it=P(),
        )

    def state_shardings(self) -> H.HDPState:
        s = self.specs()
        ns = lambda p: NamedSharding(self.mesh, p)
        return H.HDPState(
            z=ns(s["z"]), n=ns(s["n"]), phi=ns(s["phi"]),
            varphi=ns(s["varphi"]), psi=ns(s["psi"]), l=ns(s["l"]),
            key=ns(s["key"]), it=ns(s["it"]),
        )

    def corpus_shardings(self):
        s = self.specs()
        return (
            NamedSharding(self.mesh, s["tokens"]),
            NamedSharding(self.mesh, s["mask"]),
        )

    # -- mesh-local sub-steps ---------------------------------------------
    # Each of these runs INSIDE a shard_map region (collectives explicit).

    def _ppu_shard(self, n_shard, k_phi, midx):
        """Step 1: PPU draw on the local vocab shard (model-parallel).
        Same key within a model column -> replicated over (pod, data).

        With ``cfg.ppu_nnz_budget`` set, the draw is the doubly-sparse
        budgeted decomposition (core/polya_urn.py): Poisson(beta)
        background for every cell + Poisson(n) over a fixed-size gather
        of non-zeros. Exact in distribution; a *different* stream than
        the dense draw, so all bitwise chains keep budget=None.
        """
        cfg = self.cfg
        kk = jax.random.fold_in(k_phi, midx)
        if cfg.ppu_nnz_budget is not None:
            from repro.core.polya_urn import ppu_counts_budgeted

            return ppu_counts_budgeted(
                kk, n_shard, cfg.beta, cfg.ppu_nnz_budget
            )
        return jax.random.poisson(
            kk, n_shard.astype(jnp.float32) + cfg.beta, dtype=jnp.int32
        )

    def _phi_tables(self, n_shard, psi, k_phi, u_mask_shard=None, *,
                    mask_cap=None):
        """Steps 1-3: PPU Phi-step on the vocab shard + z-step operand
        build/gather. Returns (phi_shard, varphi_shard, ztables) where
        ztables is the impl-specific tuple of replicated z-step operands.

        ``u_mask_shard`` ((V/M,) bool, vocab-sharded) + ``mask_cap``
        (static bound on flagged rows per shard) switch the table build
        to block-sparse: alias tables are constructed only for flagged
        vocab rows (bitwise-equal on those rows; a sweep touching only
        flagged words is bitwise-unchanged). Ignored where it cannot
        help: the dense impl (no tables), the kernel-prologue path (no
        epilogue to shrink), and gather_tables=False.
        """
        maxis = self.model_axis
        midx = jax.lax.axis_index(maxis)

        # 1. Phi-step: PPU on the local vocab shard (model-parallel).
        with jax.named_scope("ppu_draw"):
            varphi_shard = self._ppu_shard(n_shard, k_phi, midx)
            row_local = jnp.sum(varphi_shard, axis=1).astype(jnp.float32)
            row = jax.lax.psum(row_local, maxis)  # (K,)
            phi_shard = (
                varphi_shard.astype(jnp.float32)
                / jnp.maximum(row[:, None], 1.0)
            ).astype(self.phi_dtype)

        # 2./3. Replicate the z-step operands.
        with jax.named_scope("tables"):
            ztables = self._z_tables(phi_shard, psi, u_mask_shard, mask_cap)
        return phi_shard, varphi_shard, ztables

    def _z_tables(self, phi_shard, psi, u_mask_shard, mask_cap):
        """Steps 2-3: the impl-specific tuple of replicated z-step
        operands, built from the vocab shard of Phi."""
        cfg = self.cfg
        maxis = self.model_axis
        if cfg.z_impl == "pallas":
            from repro.kernels.hdp_z import ops as zops

            if self.alias_in_kernel:
                # Kernel-prologue path: only the raw supports (vals,
                # ids) are built and gathered — half the table wire
                # bytes, no alias epilogue anywhere. The kernel
                # rebuilds wa/q_a/alias rows in VMEM from apsi.
                vals_s, ids_s = zops.build_word_sparse_supports(
                    phi_shard.astype(jnp.float32), cfg.bucket
                )
                vals = jax.lax.all_gather(vals_s, maxis, axis=0, tiled=True)
                ids = jax.lax.all_gather(ids_s, maxis, axis=0, tiled=True)
                apsi = jnp.float32(cfg.alpha) * psi
                return (apsi, vals, ids)

            # Word-sparse tables built model-parallel on the vocab shard,
            # then gathered: (V, W) instead of the paper's (K, V) Phi
            # broadcast — a W/K communication saving (§Perf).
            if u_mask_shard is not None:
                q_a_s, fpack_s, ipack_s = zops.build_word_sparse_tables_masked(
                    phi_shard.astype(jnp.float32), psi, cfg.alpha,
                    cfg.bucket, u_mask_shard, mask_cap,
                    compact=self.compact_tables,
                )
            else:
                q_a_s, fpack_s, ipack_s = zops.build_word_sparse_tables(
                    phi_shard.astype(jnp.float32), psi, cfg.alpha,
                    cfg.bucket, compact=self.compact_tables,
                )
            q_a = jax.lax.all_gather(q_a_s, maxis, axis=0, tiled=True)
            fpack = jax.lax.all_gather(fpack_s, maxis, axis=0, tiled=True)
            ipack = jax.lax.all_gather(ipack_s, maxis, axis=0, tiled=True)
            return (q_a, fpack, ipack)

        # keep the gathered Phi in phi_dtype: converting to f32 here lets
        # XLA hoist the convert BEFORE the all-gather, doubling the wire
        # bytes (verified on HLO). The z-step promotes per-op instead.
        phi = jax.lax.all_gather(phi_shard, maxis, axis=1, tiled=True)
        if cfg.z_impl == "dense":
            return (phi,)
        if self.gather_tables:
            wa = (phi_shard.astype(jnp.float32) * (cfg.alpha * psi)[:, None]).T
            if u_mask_shard is not None:
                # block-sparse: alias-partition only flagged rows (the
                # expensive part); wa/q_a stay full-width (cheap VPU
                # work). alias_build is row-independent, so flagged
                # rows are bitwise the dense build.
                (rows,) = jnp.nonzero(
                    u_mask_shard, size=min(mask_cap, wa.shape[0]),
                    fill_value=0,
                )
                p_sub, a_sub = alias_build(wa[rows], cumsum=jnp.cumsum)
                prob_shard = jnp.zeros(wa.shape, jnp.float32).at[rows].set(
                    p_sub)
                alias_shard = jnp.zeros(wa.shape, jnp.int32).at[rows].set(
                    a_sub)
            else:
                prob_shard, alias_shard = alias_build(wa, cumsum=jnp.cumsum)
            qa_shard = jnp.sum(wa, axis=1)
            q_a = jax.lax.all_gather(qa_shard, maxis, axis=0, tiled=True)
            aprob = jax.lax.all_gather(prob_shard, maxis, axis=0, tiled=True)
            aalias = jax.lax.all_gather(alias_shard, maxis, axis=0, tiled=True)
        else:
            # beyond-paper variant: rebuild tables redundantly from the
            # gathered Phi — trades (V,K) fp32+int32 gather for local compute.
            wa = (phi * (cfg.alpha * psi)[:, None]).T
            q_a = jnp.sum(wa, axis=1)
            aprob, aalias = alias_build(wa, cumsum=jnp.cumsum)
        return (phi, q_a, aprob, aalias)

    def _z_sweep(self, ztables, z, tokens, mask, psi, k_u):
        """Step 4: z-step on the local document shard (no communication).
        Returns ``(z_new, m)`` — every impl emits its per-doc
        histogram from the sweep carry.

        ``k_u`` must already be block-specific for streaming; the
        per-device fold happens here so a single-block stream consumes
        randomness bitwise-identically to the monolithic iteration.
        """
        dev_idx = jax.lax.axis_index(tuple(self.mesh.axis_names))
        u = jax.random.uniform(
            jax.random.fold_in(k_u, dev_idx), tokens.shape + (3,), jnp.float32
        )
        return self._z_sweep_u(ztables, z, tokens, mask, psi, u)

    def _z_sweep_u(self, ztables, z, tokens, mask, psi, u):
        """Impl dispatch of the z-step on precomputed per-token uniforms
        ``u`` (tokens.shape + (3,)). No collectives and no PRNG — safe
        under plain jit outside any shard_map (the lane path below
        consumes row slices of a block-global uniform array here)."""
        cfg = self.cfg
        if cfg.z_impl == "pallas":
            from repro.kernels.hdp_z import ops as zops

            # ztables is (q_a, fpack, ipack) — or, on the
            # kernel-prologue path, (apsi, vals, ids) in the same slots.
            q_a, fpack, ipack = ztables
            return zops.hdp_z_pallas(
                tokens, mask, z, u, q_a, fpack, ipack, kk=cfg.K,
                in_kernel=self.alias_in_kernel,
            )
        if cfg.z_impl == "dense":
            (phi,) = ztables
            return H.z_step_dense(tokens, mask, z, phi, psi, cfg.alpha,
                                  u, unroll=cfg.unroll_z)
        phi, q_a, aprob, aalias = ztables
        return H.z_step_sparse_tables(
            tokens, mask, z, phi, cfg.alpha, u, cfg.bucket,
            q_a, aprob, aalias, unroll=cfg.unroll_z,
        )

    def _block_stats(self, z_old, z_new, m, tokens, mask):
        """Steps 5-7a: sufficient-statistic *deltas* for one block.

        Returns (dn_shard, dh) — the vocab-sharded exact integer update
        to the topic-word statistic (``n_next = n + dn``, bitwise-equal
        to a recount) and the fully-reduced document histogram built
        from the sweep-emitted m. Both are pure sums over documents, so
        per-block results merge by addition (exactly: integer
        arithmetic throughout). No count_n / doc_topic_counts recompute
        happens here — the sweep already holds m, and ``delta_n``
        scatters over changed tokens only.
        """
        cfg = self.cfg
        with jax.named_scope("delta_n"):
            dn_local = H.delta_n(z_old, z_new, tokens, mask, cfg.K, cfg.V)
            dn_shard = jax.lax.psum_scatter(
                dn_local, self.model_axis, scatter_dimension=1, tiled=True
            )
            if self.repl_axes:
                dn_shard = jax.lax.psum(dn_shard, self.repl_axes)
        with jax.named_scope("d_histogram"):
            dh = H.d_histogram(m, cfg.hist_cap)
            dh = jax.lax.psum(dh, tuple(self.mesh.axis_names))
        return dn_shard, dh

    # -- the iteration ----------------------------------------------------
    def _local_iteration(self, z, tokens, mask, n_shard, psi, l, key, it):
        cfg = self.cfg
        key, k_phi, k_u, k_l, k_psi = jax.random.split(key, 5)
        phi_shard, varphi_shard, ztables = self._phi_tables(
            n_shard, psi, k_phi
        )
        z_new, m = self._z_sweep(ztables, z, tokens, mask, psi, k_u)
        dn_shard, dh = self._block_stats(z, z_new, m, tokens, mask)
        z = z_new
        n_shard = n_shard + dn_shard

        # 7b. l and Psi: replicated-deterministic (same key everywhere).
        l = sample_l(k_l, dh, psi, cfg.alpha)
        psi = sample_psi(k_psi, l, cfg.gamma)

        return z, n_shard, phi_shard, varphi_shard, psi, l, key, it + 1

    def iteration_fn(self):
        s = self.specs()
        state_in = (
            s["z"], s["tokens"], s["mask"], s["n"], s["psi"], s["l"],
            s["key"], s["it"],
        )
        state_out = (
            s["z"], s["n"], s["phi"], s["varphi"], s["psi"], s["l"],
            s["key"], s["it"],
        )
        fn = compat.shard_map(
            self._local_iteration,
            mesh=self.mesh,
            in_specs=state_in,
            out_specs=state_out,
            check_vma=False,
        )

        def iteration(state: H.HDPState, tokens, mask) -> H.HDPState:
            z, n, phi, varphi, psi, l, key, it = fn(
                state.z, tokens, mask, state.n, state.psi, state.l,
                state.key, state.it,
            )
            return H.HDPState(
                z=z, n=n, phi=phi, varphi=varphi, psi=psi, l=l, key=key, it=it
            )

        return iteration

    def jit_iteration(self):
        ss = self.state_shardings()
        ts, ms = self.corpus_shardings()
        return jax.jit(
            self.iteration_fn(),
            in_shardings=(ss, ts, ms),
            out_shardings=ss,
            donate_argnums=(0,),
        )

    # -- streaming sub-step entry points ----------------------------------
    # shard_map wrappers over the same mesh-local functions, for drivers
    # that sweep the corpus block-by-block (core/streaming.py).

    def _ztable_specs(self):
        if self.cfg.z_impl == "pallas":
            return (P(), P(), P())
        if self.cfg.z_impl == "dense":
            return (P(),)
        return (P(), P(), P(), P())

    def phi_tables_fn(self):
        """(n, psi, k_phi) -> (phi, varphi, ztables); one call/iteration."""
        s = self.specs()

        def phi_tables(n, psi, k_phi):
            return self._phi_tables(n, psi, k_phi)

        return compat.shard_map(
            phi_tables,
            mesh=self.mesh,
            in_specs=(s["n"], s["psi"], s["key"]),
            out_specs=(s["phi"], s["varphi"], self._ztable_specs()),
            check_vma=False,
        )

    def supports_masked_tables(self) -> bool:
        """True when the block-sparse table build can change anything:
        per-word alias tables exist (sparse w/ gather_tables, or pallas
        with the epilogue build) — the dense impl has no tables and the
        kernel-prologue path has no epilogue to shrink."""
        cfg = self.cfg
        if cfg.z_impl == "pallas":
            return not self.alias_in_kernel
        return cfg.z_impl == "sparse" and self.gather_tables

    def phi_tables_masked_fn(self, cap: int):
        """Block-sparse variant of ``phi_tables_fn``:
        (n, psi, k_phi, u_mask) -> (phi, varphi, ztables), with u_mask a
        (V,) bool of vocab rows to build tables for and ``cap`` a static
        per-shard bound on flagged rows (the full flagged count always
        works). Falls back to the dense build where masking cannot help
        (``supports_masked_tables``)."""
        if not self.supports_masked_tables():
            fn = self.phi_tables_fn()
            return lambda n, psi, k_phi, u_mask: fn(n, psi, k_phi)
        s = self.specs()

        def phi_tables(n, psi, k_phi, u_mask):
            return self._phi_tables(n, psi, k_phi, u_mask, mask_cap=cap)

        return compat.shard_map(
            phi_tables,
            mesh=self.mesh,
            in_specs=(s["n"], s["psi"], s["key"], P(self.model_axis)),
            out_specs=(s["phi"], s["varphi"], self._ztable_specs()),
            check_vma=False,
        )

    def z_block_fn(self):
        """(ztables, z_b, tokens_b, mask_b, psi, k_ub) ->
        (z_b', dn_contrib, dh_contrib); one call per corpus block.

        ``dn_contrib`` is the block's exact integer delta to n (not a
        recount): the streaming driver merges it with
        ``n += dn_contrib`` (core/streaming.py)."""
        s = self.specs()

        def z_block(ztables, z, tokens, mask, psi, k_ub):
            z_new, m = self._z_sweep(ztables, z, tokens, mask, psi, k_ub)
            dn_shard, dh = self._block_stats(z, z_new, m, tokens, mask)
            return z_new, dn_shard, dh

        return compat.shard_map(
            z_block,
            mesh=self.mesh,
            in_specs=(
                self._ztable_specs(), s["z"], s["tokens"], s["mask"],
                s["psi"], s["key"],
            ),
            out_specs=(s["z"], s["n"], P()),
            check_vma=False,
        )

    def z_lane_fn(self, n_lanes: int, lane: int, block_docs: int):
        """Single-device lane variant of ``z_block_fn`` for the
        data-parallel streaming driver (core/streaming.py lane mode):
        ``(ztables, z_rows, tokens_rows, mask_rows, psi, k_ub) ->
        (z_rows', dn_full, dh)`` over this lane's ``block_docs //
        n_lanes`` document rows.

        Device-count bitwise invariance: the lane generates the FULL
        block's uniforms from ``fold_in(k_ub, 0)`` — exactly the array
        the single-device sweep draws inside its (1, 1)-mesh shard_map —
        and consumes only its static row slice, so every lane count
        (including 1) samples identical per-token uniforms. XLA pushes
        the static slice through the elementwise threefry lowering, so
        each lane materializes ~its slice, not the whole block.

        No collectives: ``dn_full`` is the lane's whole (K, V) integer
        delta and ``dh`` its unreduced histogram — the driver merges
        them host-side through the packed exchange (data/deltawire.py),
        which is the single-host prototype of the cross-host wire
        protocol. Runs under plain jit; placement follows the committed
        input arrays (the driver stages each lane's rows onto its
        device)."""
        if block_docs % n_lanes:
            raise ValueError(
                f"block_docs={block_docs} not divisible by "
                f"n_lanes={n_lanes}")
        cfg = self.cfg
        rows = block_docs // n_lanes
        lo = lane * rows

        def z_lane(ztables, z, tokens, mask, psi, k_ub):
            u_full = jax.random.uniform(
                jax.random.fold_in(k_ub, 0),
                (block_docs, tokens.shape[1], 3), jnp.float32,
            )
            u = jax.lax.slice_in_dim(u_full, lo, lo + rows, axis=0)
            z_new, m = self._z_sweep_u(ztables, z, tokens, mask, psi, u)
            dn = H.delta_n(z, z_new, tokens, mask, cfg.K, cfg.V)
            dh = H.d_histogram(m, cfg.hist_cap)
            return z_new, dn, dh

        return z_lane

    # -- state construction -------------------------------------------------
    def init_state(self, key, tokens, mask) -> H.HDPState:
        """Single-topic init (paper Section 3) with proper placement."""
        cfg = self.cfg
        state = H.init_state(key, tokens, mask, cfg)
        ss = self.state_shardings()
        return jax.tree.map(jax.device_put, state, ss)
