"""Streaming minibatch Gibbs driver: corpora larger than device memory
(and, with the disk slab store, larger than host memory).

``StreamingHDP`` layers on the mesh-local sub-steps of
``core/sharded.py`` to sweep a ``ShardedCorpusStore`` block-by-block
within each Gibbs iteration:

  * the model state (n, phi, varphi, psi, l) stays device-resident
    across blocks — O(K*V), independent of corpus size;
  * topic indicators z live in a pluggable ``ZSlabStore``
    (data/zstore.py): ``RamZStore`` keeps every (DB, L) slab in one host
    array (the classic layout), ``DiskZStore`` keeps slabs as immutable
    per-block version files on disk with only *in-flight* slabs
    host-resident — at most ``prefetch_depth + writeback_depth + 1`` —
    which removes the last >RAM blocker for the paper's PubMed scale
    (8m documents / 768m tokens on one machine). Both backends are
    bitwise-interchangeable; select with ``z_store="ram"|"disk"`` or the
    ``REPRO_Z_STORE`` env var;
  * the Phi-step (PPU draw + z-step table build/gather) runs ONCE per
    iteration — valid because Phi and Psi are held fixed during the
    z-step, making the block sweep embarrassingly parallel over blocks.
    It is *dispatched* before the prefetcher starts and awaited inside
    the pipeline ("tables.build" span), so the build overlaps block 0's
    corpus read / z read / H2D staging instead of serializing ahead of
    them. With ``block_sparse_tables`` ("auto"|"on"|"off", or the
    ``REPRO_BLOCK_SPARSE_TABLES`` env var) the alias tables are built
    only for vocabulary rows actually present in the corpus
    (``ShardedCorpusStore.vocab_ids``; "auto" enables this below 50%
    vocab coverage); ``HDPConfig.alias_in_kernel="on"`` instead has the
    pallas kernel rebuild each live token's alias row from raw supports
    (the kernel-prologue build, kernels/hdp_z/hdp_z.py), which costs
    more on the chip than building every word's row once here;
  * per-block sufficient statistics merge as *deltas*: the z-sweep
    emits its per-document histogram m from the sweep carry and the
    block's exact integer delta to the topic-word statistic, so the hot
    loop contains no ``count_n`` / ``doc_topic_counts`` recompute —
    ``n`` advances device-resident by ``n += delta_b`` (bitwise-equal
    to a recount; integer arithmetic throughout).

The per-block timeline is fully overlapped, with the driver thread only
*dispatching* work:

    disk  read z slab b+2           (BlockPrefetcher pre-stage thread;
                                     out-of-core backend only)
    H2D   stage block b+1           (BlockPrefetcher stage thread)
    sweep block b                   (device, async dispatch)
    D2H   write back block b-1      (BlockWriteback daemon thread,
                                     through the slab store)

The driver never blocks on a sweep it has dispatched: the swept z block
is handed to the write-back thread, which materializes it (waiting on
the device there) and writes it through the slab store. The only driver
sync points are mid-epoch checkpoint saves (write-back flush) and the
iteration tail.

Randomness contract: each iteration splits the chain key exactly like
the monolithic sampler (key -> k_phi, k_u, k_l, k_psi); block b derives
its z-step uniforms from ``k_u`` for b == 0 and ``fold_in(k_u, b)``
otherwise, so a single-block stream consumes randomness — and therefore
produces states — bitwise-identically to the monolithic
``ShardedHDP.jit_iteration`` (asserted by tests/test_streaming.py).

Checkpoints are resumable mid-epoch, and share storage with the live
state: a save flushes dirty slabs into the per-block ``ZBlockStore``
version files and pins the version vector in the payload manifest. For
a ``DiskZStore`` homed at the checkpoint directory the flush is free —
the live version files ARE the checkpoint files. The payload carries
the block cursor, the partial accumulators, and the pre-split chain
key; resume re-derives the iteration keys and the z-step tables
deterministically and continues from the cursor block without
materializing the full z array (disk backend adopts the pinned version
vector as-is).
"""

from __future__ import annotations

import functools
import os
import queue
import threading
import time
from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import hdp as H
from repro.core.polya_urn import ppu_sample, ppu_sample_budgeted
from repro.core.sharded import ShardedHDP
from repro.core.stick import gem_prior_sample, sample_l, sample_psi
from repro.data import deltawire
from repro.data.stream import (AsyncStage, BlockPrefetcher, BlockWriteback,
                               ShardedCorpusStore)
from repro.data.zstore import (ZBlockStore, ZSlabStore,  # noqa: F401
                               make_zslab_store, pack_dtype_for)
from repro.train import checkpoint as CKPT


def merge_stats(n, dn, dh, dhc):
    """A block's statistic merge: ``n += dn``, ``dh += dhc``."""
    return n + dn, dh + dhc


def split_keys(key):
    """An iteration's keys: (next chain key, k_phi, k_u, k_l, k_psi)."""
    return jax.random.split(key, 5)


def widen_z(z):
    """A packed z slab widened to the sampler's int32, on device."""
    return z.astype(jnp.int32)


class _SweepLane:
    """One device's z-sweep worker for the data-parallel streaming
    driver (lane mode, ``StreamingHDP(n_devices > 1)``).

    A daemon thread owns the lane: per submitted block it runs the
    lane's jitted sweep (``ShardedHDP.z_lane_fn`` — this device's row
    shard with block-global uniforms), the device-side delta
    sparsification, and the on-device narrow for the packed write-back,
    then blocks until the device finishes. The thread is what makes the
    per-device ``sweep.d{d}`` spans land on distinct trace tracks whose
    wall-clock overlap ``check_obs --require-overlap`` asserts, and the
    block wait inside the span is what makes the span measure device
    work, not dispatch.

    The bounded input queue (depth 2) backpressures the driver so at
    most two blocks' row shards are in flight per device. Errors are
    captured and re-raised on the consumer side (``take``); after an
    error, further submissions drain unprocessed, like ``AsyncStage``.
    """

    _DONE = object()

    def __init__(self, d: int, device, fn, sparsify, narrow=None):
        self.d = d
        self.device = device
        self.wall_s = 0.0   # cumulative device-sweep wall (this lane)
        self._fn = fn
        self._sparsify = sparsify
        self._narrow = narrow
        self._in: queue.Queue = queue.Queue(maxsize=2)
        self._out: queue.Queue = queue.Queue()
        self._err: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._worker, daemon=True, name=f"sweep.d{d}"
        )
        self._thread.start()

    def submit(self, b, ztables, z, tokens, mask, psi, k_ub):
        self._in.put((b, ztables, z, tokens, mask, psi, k_ub))

    def take(self, b: int):
        """Next completed block's ``(z_out, idx, val, nnz, dh)``;
        re-raises the lane's error instead if the worker died."""
        got = self._out.get()
        if got[0] == "err":
            raise got[1]
        _, rb, payload = got
        if rb != b:
            raise RuntimeError(
                f"lane d{self.d} produced block {rb}, expected {b}")
        return payload

    def _worker(self):
        tr = obs.tracer()
        while True:
            item = self._in.get()
            if item is self._DONE:
                return
            if self._err is not None:
                continue  # drain post-error submissions
            b, ztables, z, tokens, mask, psi, k_ub = item
            try:
                t0 = time.perf_counter()
                with tr.span(f"sweep.d{self.d}", cat="pipeline", block=b):
                    z_new, dn, dh = self._fn(
                        ztables, z, tokens, mask, psi, k_ub)
                    idx, val, nnz = self._sparsify(dn)
                    if self._narrow is not None:
                        z_new = self._narrow(z_new)
                    jax.block_until_ready((z_new, idx, val, nnz, dh))
                self.wall_s += time.perf_counter() - t0
                self._out.put(("ok", b, (z_new, idx, val, nnz, dh)))
            except BaseException as e:  # surfaced on take()
                self._err = e
                self._out.put(("err", e))

    def close(self):
        if self._thread.is_alive():
            self._in.put(self._DONE)
            self._thread.join(timeout=600)
            if self._thread.is_alive():
                raise RuntimeError(
                    f"sweep lane d{self.d} failed to drain within 600s "
                    "(wedged device?)")


class StreamingState(NamedTuple):
    """Device-resident model state + a handle to the per-block z slabs
    (``ZSlabStore``: host array or out-of-core disk store)."""
    n: jax.Array        # (K, V) int32, vocab-sharded
    phi: jax.Array      # (K, V)
    varphi: jax.Array   # (K, V) int32
    psi: jax.Array      # (K,)
    l: jax.Array        # (K,)
    key: jax.Array      # chain key (pre-split for the NEXT iteration)
    it: jax.Array       # completed Gibbs iterations
    z_blocks: ZSlabStore  # (B, DB, L) int32 slabs behind the store API


class StreamingHDP:
    """Minibatch Gibbs driver over a block store.

    Device memory holds one corpus block (two with prefetch) plus the
    O(K*V) model state, regardless of corpus size; with
    ``z_store="disk"`` host memory holds only the in-flight z slabs as
    well, so neither corpus nor z need fit in RAM.

    ``z_store`` selects the slab backend ("ram" | "disk"; default: the
    ``REPRO_Z_STORE`` env var, else "ram"). ``z_dir`` roots the disk
    backend's version files — point it at the checkpoint directory to
    make saves near-free (live files double as checkpoint files); the
    default is a self-cleaning temp dir. One live run per ``z_dir``.

    ``z_pack`` ("auto" | "off"; default: the ``REPRO_Z_PACK`` env var,
    else "auto") bit-packs the slabs to ``pack_dtype_for(K)`` — uint8
    for K* <= 256, uint16 for K* <= 65536: the H2D staging copy, the D2H
    write-back, and the disk backend's version files all move packed
    bytes (up to 4x less traffic), with exact narrow/widen casts on
    device, so the sampled chain is bitwise-identical to ``"off"``.

    ``n_devices`` (default: the ``REPRO_STREAM_DEVICES`` env var, else
    1) turns on the data-parallel lane mode: each block's document rows
    split evenly across the first ``n_devices`` jax devices, every lane
    runs the z-sweep on its row shard concurrently (its own
    ``_SweepLane`` thread + device), and the per-lane integer deltas
    merge through the sparse bit-packed ``data/deltawire.py`` exchange
    — ``n_run += reduce(pack(delta_d))``, bitwise-equal to the
    single-device sweep because every lane derives its uniforms from
    the same block key (``fold_in(k_ub, 0)``, the value the (1,1)-mesh
    path folds) and slices its row range out of the block-global draw,
    and because the canonical ascending-lane merge order adds the same
    integers. Requires a single-device primary mesh
    (``compat.single_device_mesh()`` — a data axis > 1 would fold
    per-shard keys into the non-sweep ops and sample a mesh-shaped
    chain instead of the canonical one) and
    ``block_docs % n_devices == 0``.
    """

    def __init__(self, sharded: ShardedHDP, store: ShardedCorpusStore, *,
                 prefetch_depth: int = 2, writeback_depth: int = 2,
                 z_store: Union[str, None] = None,
                 z_dir: Optional[str] = None,
                 z_pack: Union[str, None] = None,
                 block_sparse_tables: Union[str, None] = None,
                 n_devices: Union[int, None] = None):
        self.sh = sharded
        self.cfg = sharded.cfg
        self.store = store
        H.validate_bucket(self.cfg, store.max_len)
        self.prefetch_depth = prefetch_depth
        self.writeback_depth = writeback_depth
        if block_sparse_tables is None:
            block_sparse_tables = os.environ.get(
                "REPRO_BLOCK_SPARSE_TABLES", "auto")
        if block_sparse_tables not in ("auto", "on", "off"):
            raise ValueError(
                "block_sparse_tables must be 'auto', 'on' or 'off', got "
                f"{block_sparse_tables!r}"
            )
        if (block_sparse_tables == "on"
                and not sharded.supports_masked_tables()):
            raise ValueError(
                "block_sparse_tables='on' needs per-word alias tables "
                "(sparse impl with gather_tables, or pallas without the "
                "kernel-prologue build) — this configuration has none"
            )
        if z_store is None:
            z_store = os.environ.get("REPRO_Z_STORE", "ram")
        if z_store not in ("ram", "disk"):
            raise ValueError(
                f"z_store must be 'ram' or 'disk', got {z_store!r}"
            )
        self.z_store = z_store
        self.z_dir = z_dir
        if z_pack is None:
            z_pack = os.environ.get("REPRO_Z_PACK", "auto")
        if z_pack not in ("auto", "off"):
            raise ValueError(
                f"z_pack must be 'auto' or 'off', got {z_pack!r}"
            )
        self.z_pack = z_pack
        self.z_dtype = (pack_dtype_for(self.cfg.K) if z_pack == "auto"
                        else np.dtype(np.int32))
        ss = sharded.state_shardings()
        ts, ms = sharded.corpus_shardings()
        self._z_sh, self._n_sh = ss.z, ss.n
        self._repl_sh = ss.psi
        self._ts, self._ms = ts, ms
        # block-sparse tables: only for configs that have per-word alias
        # tables, and (in "auto") only when the corpus leaves a real
        # fraction of the vocabulary untouched — at >= 50% coverage the
        # masked build's gather/scatter overhead buys nothing.
        self._u_mask = None
        enable_mask = (
            sharded.supports_masked_tables()
            and block_sparse_tables != "off"
            and (block_sparse_tables == "on" or store.vocab_coverage < 0.5)
        )
        self.block_sparse_tables = enable_mask
        if enable_mask:
            from jax.sharding import NamedSharding, PartitionSpec

            ids = store.vocab_ids()
            u_mask = np.zeros((self.cfg.V,), bool)
            u_mask[ids] = True
            self._u_mask = jax.device_put(
                jnp.asarray(u_mask),
                NamedSharding(sharded.mesh,
                              PartitionSpec(sharded.model_axis)),
            )
            cap = max(int(ids.size), 1)
            mfn = jax.jit(sharded.phi_tables_masked_fn(cap))
            self._phi_fn = functools.partial(self._masked_phi, mfn)
        else:
            self._phi_fn = jax.jit(sharded.phi_tables_fn())
        # alias rows one table build makes, from shapes alone
        # (train.alias_rows_built): one per vocabulary row, or the masked
        # build's cap per model shard; with the kernel-prologue build,
        # one per live token, since the kernel rebuilds a word's row at
        # every occurrence. The dense impl builds none.
        if sharded.alias_in_kernel:
            self._alias_rows = store.num_tokens
        elif enable_mask:
            m = sharded.mesh.shape[sharded.model_axis]
            self._alias_rows = m * min(cap, self.cfg.V // m)
        else:
            self._alias_rows = 0 if self.cfg.z_impl == "dense" else self.cfg.V
        self._z_fn = jax.jit(sharded.z_block_fn(), donate_argnums=(1,))
        # each program of the iteration is a named function, so its
        # module reads jit_<name> in a profile: one jitted dispatch per
        # block for the statistic merge (the python-level `acc + c` pair
        # it replaces was two uncompiled dispatches on the driver's
        # critical path), one per iteration for the keys and the tail.
        self._merge_fn = jax.jit(merge_stats)
        self._split_fn = jax.jit(split_keys)
        cfg = self.cfg

        def tail_l_psi(dh, psi, k_l, k_psi):
            l = sample_l(k_l, dh, psi, cfg.alpha)
            return l, sample_psi(k_psi, l, cfg.gamma)

        self._tail_fn = jax.jit(tail_l_psi)
        # model-health reductions, dispatched ONLY when a metrics sink
        # is attached (obs.metrics_on()): the disabled path runs the
        # exact same program sequence as an uninstrumented build.
        self._nnz_fn = jax.jit(lambda acc, dn: acc + jnp.count_nonzero(dn))
        self._kstar_fn = jax.jit(lambda n: jnp.sum(jnp.any(n > 0, axis=1)))
        # packed-slab casts, on device: the H2D copy moves packed bytes
        # and widens to the sampler's int32 there; the swept block
        # narrows before the D2H write-back. Exact for values < K.
        self._widen_fn = jax.jit(widen_z)
        _zdt = self.z_dtype

        def narrow_z(z):
            return z.astype(_zdt)

        self._narrow_fn = jax.jit(narrow_z)
        # data-parallel lane mode: row-shard every block over the first
        # n_devices jax devices; the per-lane sweeps are plain per-device
        # jits (no shard_map, no collectives — placement follows the
        # committed inputs), and the delta merge is the host-mediated
        # packed exchange (the cross-host wire-protocol prototype).
        if n_devices is None:
            n_devices = int(
                os.environ.get("REPRO_STREAM_DEVICES", "1") or "1")
        n_devices = int(n_devices)
        avail = jax.devices()
        if not 1 <= n_devices <= len(avail):
            raise ValueError(
                f"n_devices={n_devices} outside [1, {len(avail)}] "
                "available jax devices (CPU CI: set REPRO_HOST_DEVICES=N "
                "so run.sh forces N host-platform devices)"
            )
        self.n_devices = n_devices
        self.delta_reduce_bytes = 0  # cumulative packed-exchange volume
        self._lane_devices = list(avail[:n_devices])
        if n_devices > 1:
            model_size = dict(sharded.mesh.shape)[sharded.model_axis]
            if model_size != 1:
                raise ValueError(
                    "lane mode needs a model axis of size 1 on the "
                    f"primary mesh (got {model_size}): vocab-sharded "
                    "tables would build differently per device count, "
                    "breaking the bitwise device-count invariance — use "
                    "compat.single_device_mesh()"
                )
            mesh_size = int(sharded.mesh.devices.size)
            if mesh_size != 1:
                raise ValueError(
                    "lane mode needs a single-device primary mesh (got "
                    f"{mesh_size} devices): a data axis > 1 runs the "
                    "non-sweep ops under shard_map with per-shard key "
                    "folds, sampling a mesh-shaped chain instead of the "
                    "canonical single-device one — use "
                    "compat.single_device_mesh(); the lanes place their "
                    "own work across devices"
                )
            if store.block_docs % n_devices:
                raise ValueError(
                    f"block_docs={store.block_docs} must divide evenly "
                    f"over n_devices={n_devices} lanes"
                )
            self._lane_rows = store.block_docs // n_devices
            # static nnz cap for the device-side COO extraction: the
            # z-step moves each resampled token between at most two
            # (k, v) cells.
            from repro.kernels.hdp_z import ops as zops

            cap = int(min(2 * self._lane_rows * store.max_len,
                          cfg.K * cfg.V))
            self._sparsify_fn = jax.jit(
                lambda dn: zops.delta_sparsify(dn, cap))
            self._lane_fns = [
                jax.jit(sharded.z_lane_fn(n_devices, d, store.block_docs),
                        donate_argnums=(1,))
                for d in range(n_devices)
            ]
        # foreign-dir checkpoint stores (save dirs that are NOT a disk
        # slab store's home); slab stores track their own dirty stamps.
        self._zstores: dict[str, ZBlockStore] = {}
        # convergence observatory (obs/diagnostics.py), built lazily on
        # the first metrics-on iteration so a metrics-off run never
        # compiles its reductions.
        self._diag = None

    def _masked_phi(self, mfn, n, psi, k_phi):
        """Block-sparse table build: same (n, psi, k_phi) signature as
        the dense ``phi_tables_fn`` so every call site is agnostic."""
        return mfn(n, psi, k_phi, self._u_mask)

    def _make_slab_store(self) -> ZSlabStore:
        return make_zslab_store(
            self.z_store, self.store.num_blocks,
            (self.store.block_docs, self.store.max_len), root=self.z_dir,
            dtype=self.z_dtype,
        )

    def _zstore(self, ckpt_dir: str, slab: ZSlabStore) -> ZBlockStore:
        home = slab.blockstore_for(ckpt_dir)
        if home is not None:
            # a disk slab store homed at the checkpoint dir owns the one
            # ZBlockStore on that dir — drop any foreign handle so two
            # instances never race the version counter.
            self._zstores.pop(ckpt_dir, None)
            return home
        zs = self._zstores.get(ckpt_dir)
        if zs is None:
            zs = self._zstores[ckpt_dir] = ZBlockStore(
                ckpt_dir, self.store.num_blocks
            )
        return zs

    # -- init --------------------------------------------------------------
    def init_state(self, key: jax.Array) -> StreamingState:
        """Single-topic init, bitwise-matching ShardedHDP.init_state on
        the same (concatenated) corpus: z = 0 everywhere, n counted
        blockwise (exact integer merge), Phi/Psi drawn from the same
        subkeys."""
        cfg = self.cfg
        store = self.store
        kp, kd = jax.random.split(key)
        count = jax.jit(
            lambda t, m: H.count_n(jnp.zeros_like(t), t, m, cfg.K, cfg.V)
        )
        n = np.zeros((cfg.K, cfg.V), np.int64)
        for blk in store.blocks():
            n += np.asarray(count(jnp.asarray(blk.tokens),
                                  jnp.asarray(blk.mask)), np.int64)
        n = jnp.asarray(n.astype(np.int32))
        # mirror H.init_state's Phi draw exactly (incl. the budgeted
        # doubly-sparse decomposition) so a streaming chain stays bitwise
        # the monolithic one under every PPU mode.
        if cfg.ppu_nnz_budget is not None:
            phi, varphi = ppu_sample_budgeted(
                kp, n, cfg.beta, cfg.ppu_nnz_budget)
        else:
            phi, varphi = ppu_sample(kp, n, cfg.beta)
        psi = gem_prior_sample(kd, cfg.K, cfg.gamma)
        # a fresh slab store starts as all-zeros content with every slab
        # save-dirty (the store constructor stamps them).
        z_blocks = self._make_slab_store()
        return StreamingState(
            n=jax.device_put(n, self._n_sh),
            phi=jax.device_put(phi, self._n_sh),
            varphi=jax.device_put(varphi, self._n_sh),
            psi=jax.device_put(psi, self._repl_sh),
            l=jax.device_put(jnp.zeros((cfg.K,), jnp.int32), self._repl_sh),
            key=key, it=jnp.int32(0), z_blocks=z_blocks,
        )

    # -- one iteration (optionally partial, for checkpoint/resume) --------
    def _staged_blocks(self, z_store: ZSlabStore, start: int):
        """Two-stage prefetch pipeline: the pre-stage checks the block's
        z slab out of the store (a disk read for the out-of-core
        backend, a view for RAM), the stage thread device_puts and
        releases the host slab. The shared in-flight budget is
        ``prefetch_depth`` slabs."""

        def blocks():
            # corpus reads happen inside the prefetcher's pre thread
            # (the iterator is consumed there); span them so memmap
            # stalls show on that track.
            tr = obs.tracer()
            for b in range(start, self.store.num_blocks):
                with tr.span("corpus_read", cat="pipeline", block=b):
                    blk = self.store.block(b)
                yield blk

        def read_z(blk):
            with obs.tracer().span("z_read", cat="pipeline",
                                   block=blk.index):
                z = z_store.read(blk.index)
            return blk, z

        packed = self.z_dtype != np.int32
        lane_mode = self.n_devices > 1

        def stage(item):
            blk, z = item
            with obs.tracer().span("h2d", cat="pipeline", block=blk.index):
                if lane_mode:
                    # per-device H2D lanes: each device receives only its
                    # row shard (tokens/mask/z), so staging traffic per
                    # device shrinks by the lane count and the sweeps can
                    # start without any cross-device gather.
                    rows = self._lane_rows
                    toks, msks, zs = [], [], []
                    for d, dev in enumerate(self._lane_devices):
                        sl = slice(d * rows, (d + 1) * rows)
                        z_d = jax.device_put(jnp.asarray(z[sl]), dev)
                        if packed:
                            z_d = self._widen_fn(z_d)
                        toks.append(
                            jax.device_put(jnp.asarray(blk.tokens[sl]), dev))
                        msks.append(
                            jax.device_put(jnp.asarray(blk.mask[sl]), dev))
                        zs.append(z_d)
                    out = (blk.index, toks, msks, zs)
                else:
                    # packed slabs cross H2D at their packed width and
                    # widen to the sampler's int32 on device (exact for
                    # values < K).
                    z_dev = jax.device_put(jnp.asarray(z), self._z_sh)
                    if packed:
                        z_dev = self._widen_fn(z_dev)
                    out = (
                        blk.index,
                        jax.device_put(jnp.asarray(blk.tokens), self._ts),
                        jax.device_put(jnp.asarray(blk.mask), self._ms),
                        z_dev,
                    )
                z_store.release(blk.index)  # device copies exist now
            return out

        def drop(item):
            # pre-read slabs discarded on early exit (kill/stop/error)
            # must check back in, or resident accounting leaks.
            z_store.release(item[0].index)

        return BlockPrefetcher(blocks(), stage,
                               depth=self.prefetch_depth, pre=read_z,
                               drop=drop)

    def iteration(
        self, state: StreamingState, *,
        start_block: int = 0, n_run=None, dh_acc=None, ztables=None,
        ckpt_dir: Optional[str] = None,
        ckpt_every_blocks: Optional[int] = None,
        stop_after_blocks: Optional[int] = None,
    ) -> Optional[StreamingState]:
        """One Gibbs iteration = one sweep over all blocks.

        Per block the jitted sweep emits (z', delta_n, dh) and the
        device-resident running statistic advances by
        ``n_run += delta_n`` — no recount anywhere in the loop. Swept z
        blocks are written back through the slab store asynchronously
        (BlockWriteback); the driver thread only dispatches, so block
        b+2's disk z read, block b+1's H2D staging, block b's sweep,
        and block b-1's write-back overlap.

        The keyword arguments exist for mid-epoch resume (start_block,
        the running statistic ``n_run``, the histogram accumulator
        ``dh_acc``, restored from a checkpoint) and for tests that
        simulate a mid-epoch kill (``stop_after_blocks``). Returns the
        advanced state, or None if the sweep was stopped early — the
        in-flight iteration then lives ONLY in the checkpoint (a partial
        save is forced at the stop cursor), because the swept z slabs
        have already been stored while n/psi/key have not.
        ``stop_after_blocks`` therefore requires ``ckpt_dir``.
        """
        cfg = self.cfg
        if stop_after_blocks is not None and not ckpt_dir:
            raise ValueError(
                "stop_after_blocks without ckpt_dir would drop the "
                "partial sweep (z slabs are updated in place)"
            )
        tr = obs.tracer()
        # health reductions (K*, delta sparsity) cost extra device
        # dispatches — run them only when a metrics sink is attached so
        # the silent path stays bitwise-identical to an uninstrumented
        # run.
        health = obs.metrics_on()
        dn_nnz = jnp.zeros((), jnp.int32) if health else None
        key, k_phi, k_u, k_l, k_psi = self._split_fn(state.key)
        built_tables = ztables is None
        if built_tables:
            # async dispatch only: the device builds iteration-t's
            # tables (they depend only on n/psi from t-1, already
            # device-resident) while the prefetcher threads below read
            # and stage block 0 — the serial tables -> stage_wait
            # prologue becomes overlapped work. The wait moves into the
            # "tables.build" span inside the pipeline, where the trace
            # can prove it runs concurrently with corpus_read/z_read/h2d
            # (benchmarks/check_obs.py --require-overlap).
            phi_shard, varphi_shard, ztables = self._phi_fn(
                state.n, state.psi, k_phi
            )
            obs.metrics().counter("train.alias_rebuilds").inc()
            obs.metrics().counter("train.alias_rows_built").inc(
                self._alias_rows)
        else:
            phi_shard, varphi_shard, ztables = ztables
        if n_run is None:
            n_run = state.n  # running statistic: n of the incoming z
        if dh_acc is None:
            dh_acc = jax.device_put(
                jnp.zeros((cfg.K, cfg.hist_cap + 1), jnp.int32),
                self._repl_sh)

        z_store = state.z_blocks
        done = 0
        saved_cursor = -1
        lane_mode = self.n_devices > 1
        lanes: list = []
        reducer = None
        # lane mode hands statistic ownership to the reducer thread: it
        # merges each block's per-lane packed deltas in canonical
        # ascending-lane order and advances n_run/dh_acc; the driver
        # reads them back out of ``hold`` after a flush/close barrier.
        hold = {"n_run": n_run, "dh_acc": dh_acc,
                "dn_nnz": 0 if health else None}
        staged = self._staged_blocks(z_store, start_block)
        writer = BlockWriteback(
            z_store.write, depth=self.writeback_depth,
        )
        try:
            if built_tables:
                with obs.phase("tables.build"):
                    jax.block_until_ready(ztables)
            if lane_mode:
                # every lane holds its own replica of the (small) z-step
                # tables and psi; each block then moves only row shards.
                ztab_lanes = [jax.device_put(ztables, dev)
                              for dev in self._lane_devices]
                psi_lanes = [jax.device_put(state.psi, dev)
                             for dev in self._lane_devices]
                narrow = (None if self.z_dtype == np.int32
                          else self._narrow_fn)
                lanes = [
                    _SweepLane(d, dev, self._lane_fns[d],
                               self._sparsify_fn, narrow)
                    for d, dev in enumerate(self._lane_devices)
                ]
                K, V = cfg.K, cfg.V

                def reduce_block(b):
                    # collect the lanes' sweeps (ascending-lane order —
                    # the canonical merge order the bitwise contract
                    # fixes), pack each lane's COO delta to the
                    # narrowest wire dtypes, and advance the statistic
                    # by ONE device add of the host-merged delta.
                    parts = [lane.take(b) for lane in lanes]
                    with tr.span("delta_reduce", cat="pipeline", block=b):
                        packs, dh_sum, z_parts = [], None, []
                        for z_new, idx, val, nnz, dh in parts:
                            nz = int(nnz)
                            packs.append(deltawire.pack_coo(
                                np.asarray(idx)[:nz],
                                np.asarray(val)[:nz], (K, V)))
                            dh_h = np.asarray(dh)
                            dh_sum = (dh_h if dh_sum is None
                                      else dh_sum + dh_h)
                            z_parts.append(z_new)
                        merged = deltawire.reduce_packed(
                            packs, shape=(K, V))
                        self.delta_reduce_bytes += \
                            deltawire.packed_nbytes(packs)
                        dn_dev = jax.device_put(
                            jnp.asarray(merged), self._n_sh)
                        dh_dev = jax.device_put(
                            jnp.asarray(dh_sum.astype(np.int32)),
                            self._repl_sh)
                        hold["n_run"], hold["dh_acc"] = self._merge_fn(
                            hold["n_run"], dn_dev, hold["dh_acc"], dh_dev)
                        if health:
                            # == the single-device per-block nnz: the
                            # merged host delta IS dn_c's integer values.
                            hold["dn_nnz"] += int(np.count_nonzero(merged))
                    writer.submit(b, z_parts)

                reducer = AsyncStage(reduce_block, depth=2,
                                     name="delta_reduce")
            staged_it = iter(staged)
            while True:
                # the wait for the next staged block is the driver-side
                # pipeline bubble: a long span here means H2D staging
                # (or the disk z read upstream) is not keeping up.
                with obs.phase("stage_wait"):
                    item = next(staged_it, None)
                if item is None:
                    break
                b, tokens_b, mask_b, z_b = item
                # block 0 consumes k_u unchanged => a single-block stream
                # is bitwise the monolithic sampler; later blocks fold
                # their index.
                k_ub = k_u if b == 0 else jax.random.fold_in(k_u, b)
                if lane_mode:
                    # dispatch only: each lane thread runs its row
                    # shard's sweep on its own device; the reducer
                    # thread merges and hands the swept shards to the
                    # write-back. The driver never waits on a device.
                    with obs.phase("sweep_submit", block=b):
                        for d, lane in enumerate(lanes):
                            lane.submit(
                                b, ztab_lanes[d], z_b[d], tokens_b[d],
                                mask_b[d], psi_lanes[d],
                                jax.device_put(k_ub, lane.device))
                        reducer.submit(b)
                else:
                    with obs.phase("sweep", block=b):
                        z_b, dn_c, dh_c = self._z_fn(
                            ztables, z_b, tokens_b, mask_b, state.psi, k_ub
                        )
                        n_run, dh_acc = self._merge_fn(
                            n_run, dn_c, dh_acc, dh_c)
                        if health:
                            dn_nnz = self._nnz_fn(dn_nnz, dn_c)
                    # narrow on device so the write-back D2H moves packed
                    # bytes (the slab store lands them as-is).
                    with obs.phase("wb_submit", block=b):
                        writer.submit(b, z_b if self.z_dtype == np.int32
                                      else self._narrow_fn(z_b))
                done += 1
                cursor = b + 1
                if (ckpt_dir and ckpt_every_blocks
                        and cursor < self.store.num_blocks
                        and cursor % ckpt_every_blocks == 0):
                    with obs.phase("checkpoint", block=b):
                        if lane_mode:
                            reducer.flush()  # statistic current in hold
                            n_run, dh_acc = hold["n_run"], hold["dh_acc"]
                        writer.flush()  # checkpoint reads the stored slabs
                        self._save_partial(
                            ckpt_dir, state, cursor, n_run, dh_acc)
                    saved_cursor = cursor
                if stop_after_blocks is not None and done >= stop_after_blocks:
                    if cursor < self.store.num_blocks:
                        if saved_cursor != cursor:
                            if lane_mode:
                                reducer.flush()
                                n_run, dh_acc = hold["n_run"], hold["dh_acc"]
                            writer.flush()
                            self._save_partial(
                                ckpt_dir, state, cursor, n_run, dh_acc)
                        return None
        finally:
            staged.close()  # unblock the prefetch workers on early exit
            try:
                if lane_mode:
                    try:
                        if reducer is not None:
                            reducer.close()  # drain merges (reads lanes)
                    finally:
                        for lane in lanes:
                            lane.close()
            finally:
                writer.close()  # drain outstanding write-backs
        if lane_mode:
            n_run, dh_acc, dn_nnz = (hold["n_run"], hold["dh_acc"],
                                     hold["dn_nnz"])
        with obs.phase("tail"):
            l, psi = self._tail_fn(dh_acc, state.psi, k_l, k_psi)
        out = StreamingState(
            n=n_run, phi=phi_shard, varphi=varphi_shard, psi=psi, l=l,
            key=key, it=state.it + 1, z_blocks=z_store,
        )
        lane_walls = ([(lane.d, lane.wall_s) for lane in lanes]
                      if lane_mode and health else None)
        self._publish_health(out, dn_nnz, done, dh_acc=dh_acc,
                             lane_walls=lane_walls)
        return out

    def _publish_health(self, state: StreamingState, dn_nnz, blocks_done,
                        dh_acc=None, lane_walls=None):
        """Per-iteration model-health metrics into the global registry.

        Cheap host-side counters/gauges are always maintained; the
        device-derived gauges (live topic count K*, delta_n sparsity —
        the "doubly sparse" quantities the method's speed rests on) and
        the convergence-observatory diagnostics (joint log-likelihood,
        topic lifecycle, ESS/Geweke — obs/diagnostics.py) are only
        computed when ``iteration`` accumulated them, i.e. when a
        metrics sink is attached. All of them are pure reads of the
        state, so the metrics-on chain stays bitwise-identical to the
        metrics-off one (benchmarks/check_health.py gates this). Ends
        with a rate-limited JSONL flush.
        """
        M = obs.metrics()
        store = state.z_blocks
        M.counter("train.iterations").inc()
        M.counter("train.tokens_swept").inc(self.store.num_tokens)
        M.gauge("train.it").set(int(state.it))
        M.gauge("train.zstore_read_mb").set(
            round(store.bytes_read / 2 ** 20, 3))
        M.gauge("train.zstore_written_mb").set(
            round(store.bytes_written / 2 ** 20, 3))
        M.gauge("train.resident_z_slabs_hwm").set(int(store.high_water))
        M.gauge("train.n_devices").set(self.n_devices)
        if self.n_devices > 1:
            M.gauge("train.delta_reduce_mb").set(
                round(self.delta_reduce_bytes / 2 ** 20, 3))
        if lane_walls:
            # per-device sweep wall, as phase counters with a proc label
            # (the dashboard renders them as sweep/d0, sweep/d1, ...
            # device lanes in the phase bar).
            for d, sec in lane_walls:
                M.counter("train.phase_ms", phase="sweep",
                          proc=f"d{d}").inc(round(sec * 1e3, 3))
        if dn_nnz is not None:
            M.gauge("train.k_star").set(int(self._kstar_fn(state.n)))
            denom = max(blocks_done, 1) * self.cfg.K * self.cfg.V
            M.gauge("train.delta_nnz_frac").set(
                round(int(dn_nnz) / denom, 6))
            if dh_acc is not None:
                if self._diag is None:
                    from repro.obs.diagnostics import ConvergenceDiagnostics
                    self._diag = ConvergenceDiagnostics(
                        self.cfg, num_tokens=self.store.num_tokens)
                self._diag.update(M, state.n, dh_acc, state.psi)
        obs.flush_metrics()

    def iteration_profiled(self, state: StreamingState, timers=None):
        """One Gibbs iteration with per-phase wall-time attribution.

        Bitwise-identical to ``iteration()`` — same jitted programs,
        same key schedule, same slab store — but fully serialized: no
        prefetch/write-back threads, and an explicit device sync at
        every phase boundary, so each span of the returned
        ``PhaseTimers`` measures exactly one pipeline phase
        (tables.h2d / tables.build / tables.gather / corpus_read /
        z_read / h2d / sweep / merge / writeback / tail) and the spans
        sum to ~the serialized wall time. The tables sub-split
        attributes the build pipeline: operand transfer, the fused
        PPU+build program, and the gathered-operand sync. Use it to
        answer "which phase dominates?" (benchmarks/roofline_hdp.py);
        use ``iteration()`` for throughput — overlap is the whole point
        there (the overlapped loop only *dispatches* the build and
        absorbs the wait into the pipeline's "tables.build" span while
        block 0 stages concurrently).

        Returns ``(state', timers)``.
        """
        from repro.perf import PhaseTimers

        cfg = self.cfg
        if timers is None:
            timers = PhaseTimers()
        key, k_phi, k_u, k_l, k_psi = self._split_fn(state.key)
        # tables, attributed in three sequential sub-phases: operand H2D
        # (the block-sparse u_mask transfer — cached device-resident, so
        # near-zero after the first iteration; the fused build's other
        # inputs are already device-resident), the fused PPU-draw +
        # table-build program, and the residual sync of the gathered
        # z-step operands (the all-gather tail — identity on one device).
        with timers.phase("tables.h2d"):
            if self._u_mask is not None:
                jax.block_until_ready(self._u_mask)
        with timers.phase("tables.build"):
            phi_shard, varphi_shard, ztables = self._phi_fn(
                state.n, state.psi, k_phi
            )
            jax.block_until_ready((phi_shard, varphi_shard))
        lane_mode = self.n_devices > 1
        with timers.phase("tables.gather"):
            jax.block_until_ready(ztables)
            if lane_mode:
                # lane replica distribution is part of making the tables
                # usable, so it bills to the gather phase.
                ztab_lanes = [jax.device_put(ztables, dev)
                              for dev in self._lane_devices]
                psi_lanes = [jax.device_put(state.psi, dev)
                             for dev in self._lane_devices]
                jax.block_until_ready((ztab_lanes, psi_lanes))
        n_run = state.n
        dh_acc = jax.device_put(
            jnp.zeros((cfg.K, cfg.hist_cap + 1), jnp.int32), self._repl_sh)
        z_store = state.z_blocks
        packed = self.z_dtype != np.int32
        blocks = self.store.blocks()
        while True:
            with timers.phase("corpus_read"):
                blk = next(blocks, None)
            if blk is None:
                break
            b = blk.index
            with timers.phase("z_read"):
                z_host = z_store.read(b)
            with timers.phase("h2d"):
                if lane_mode:
                    rows = self._lane_rows
                    toks, msks, zs = [], [], []
                    for d, dev in enumerate(self._lane_devices):
                        sl = slice(d * rows, (d + 1) * rows)
                        z_d = jax.device_put(jnp.asarray(z_host[sl]), dev)
                        if packed:
                            z_d = self._widen_fn(z_d)
                        toks.append(jax.device_put(
                            jnp.asarray(blk.tokens[sl]), dev))
                        msks.append(jax.device_put(
                            jnp.asarray(blk.mask[sl]), dev))
                        zs.append(z_d)
                    jax.block_until_ready((toks, msks, zs))
                else:
                    tokens_b = jax.device_put(
                        jnp.asarray(blk.tokens), self._ts)
                    mask_b = jax.device_put(jnp.asarray(blk.mask), self._ms)
                    z_b = jax.device_put(jnp.asarray(z_host), self._z_sh)
                    if packed:
                        z_b = self._widen_fn(z_b)
                    jax.block_until_ready((tokens_b, mask_b, z_b))
                z_store.release(b)
            k_ub = k_u if b == 0 else jax.random.fold_in(k_u, b)
            if lane_mode:
                with timers.phase("sweep"):
                    outs = [
                        self._lane_fns[d](
                            ztab_lanes[d], zs[d], toks[d], msks[d],
                            psi_lanes[d],
                            jax.device_put(k_ub, self._lane_devices[d]))
                        for d in range(self.n_devices)
                    ]
                    jax.block_until_ready([o[0] for o in outs])
                with timers.phase("merge"):
                    # the same packed exchange iteration()'s reducer
                    # thread runs: ascending-lane COO pack, host merge,
                    # one device add.
                    packs, dh_sum = [], None
                    for _, dn, dh in outs:
                        idx, val, nnz = self._sparsify_fn(dn)
                        nz = int(nnz)
                        packs.append(deltawire.pack_coo(
                            np.asarray(idx)[:nz], np.asarray(val)[:nz],
                            (cfg.K, cfg.V)))
                        dh_h = np.asarray(dh)
                        dh_sum = dh_h if dh_sum is None else dh_sum + dh_h
                    merged = deltawire.reduce_packed(
                        packs, shape=(cfg.K, cfg.V))
                    self.delta_reduce_bytes += deltawire.packed_nbytes(packs)
                    dn_dev = jax.device_put(jnp.asarray(merged), self._n_sh)
                    dh_dev = jax.device_put(
                        jnp.asarray(dh_sum.astype(np.int32)), self._repl_sh)
                    n_run, dh_acc = self._merge_fn(
                        n_run, dn_dev, dh_acc, dh_dev)
                    jax.block_until_ready(n_run)
                with timers.phase("writeback"):
                    z_store.write(b, np.concatenate(
                        [np.asarray(z if not packed else self._narrow_fn(z))
                         for z, _, _ in outs], axis=0))
            else:
                with timers.phase("sweep"):
                    z_b, dn_c, dh_c = self._z_fn(
                        ztables, z_b, tokens_b, mask_b, state.psi, k_ub
                    )
                    jax.block_until_ready(z_b)
                with timers.phase("merge"):
                    n_run, dh_acc = self._merge_fn(n_run, dn_c, dh_acc, dh_c)
                    jax.block_until_ready(n_run)
                with timers.phase("writeback"):
                    z_store.write(
                        b, np.asarray(z_b if not packed
                                      else self._narrow_fn(z_b)))
        with timers.phase("tail"):
            l, psi = self._tail_fn(dh_acc, state.psi, k_l, k_psi)
            jax.block_until_ready(psi)
        return StreamingState(
            n=n_run, phi=phi_shard, varphi=varphi_shard, psi=psi, l=l,
            key=key, it=state.it + 1, z_blocks=z_store,
        ), timers

    def run(
        self, state: StreamingState, iters: int, *,
        ckpt_dir: Optional[str] = None,
        ckpt_every_iters: Optional[int] = None,
        ckpt_every_blocks: Optional[int] = None,
        registry=None, publish_every_iters: Optional[int] = None,
        publish_w: Optional[int] = None, publish_compact: bool = False,
        publish_keep: Optional[int] = None,
    ) -> StreamingState:
        """Drive ``iters`` Gibbs iterations; optionally checkpoint and
        periodically publish serving snapshots.

        ``registry`` (a ``serve.registry.SnapshotRegistry``) plus
        ``publish_every_iters`` turns a live training run into a fleet
        feed: every N completed iterations the current (Phi, Psi) is
        distilled and atomically published, and fleet workers watching
        the registry hot-swap to it between engine steps. Publishing is
        a posterior-sample export, not a checkpoint — it never perturbs
        the chain (pure read of the state)."""
        if bool(publish_every_iters) != (registry is not None):
            raise ValueError(
                "registry and publish_every_iters go together: passing "
                "only one would silently never publish"
            )
        for _ in range(iters):
            state = self.iteration(
                state, ckpt_dir=ckpt_dir, ckpt_every_blocks=ckpt_every_blocks
            )
            if (ckpt_dir and ckpt_every_iters
                    and int(state.it) % ckpt_every_iters == 0):
                self.save(ckpt_dir, state)
            if (registry is not None and publish_every_iters
                    and int(state.it) % publish_every_iters == 0):
                self.export_snapshot(
                    registry, state, w=publish_w, compact=publish_compact,
                    keep=publish_keep,
                )
        return state

    # -- snapshot export ---------------------------------------------------
    def export_snapshot(self, dest, state: StreamingState, *,
                        w: Optional[int] = None, compact: bool = False,
                        keep: Optional[int] = None):
        """Distill the current model into a serving snapshot
        (serve/snapshot.py): Phi/Psi plus the word-sparse alias tables
        built once, valid for the snapshot's lifetime because serving
        never resamples Phi.

        ``dest`` is either a plain snapshot directory path (single
        artifact, replaced in place) or a ``SnapshotRegistry`` — then the
        snapshot is atomically *published* as a new immutable version
        (``keep`` bounds registry retention), which is the hook
        ``run(publish_every_iters=...)`` drives to feed a serving fleet
        from a live run."""
        from repro.serve import snapshot as SNAP

        snap = SNAP.snapshot_from_state(state, self.cfg, w=w, compact=compact)
        if hasattr(dest, "publish"):
            dest.publish(snap, keep=keep)
        else:
            SNAP.save(dest, snap)
        return snap

    # -- checkpointing ----------------------------------------------------
    # One logical "step" per saved payload: step = it * B + cursor, so
    # mid-epoch checkpoints order correctly between iteration boundaries.
    # z slabs do NOT live in the payload: a save flushes dirty slabs into
    # the per-block ZBlockStore version files (a no-op when the live
    # DiskZStore is homed at the checkpoint dir — its files ARE the
    # checkpoint files) and the payload pins the (B,) version vector +
    # block geometry. GC keeps exactly the union of pinned vectors across
    # retained manifests plus the live store's current versions.

    def _payload(self, state: StreamingState, cursor: int, n_run, dh_acc,
                 z_versions: np.ndarray):
        store = self.store
        return {
            "model": {
                "n": state.n, "phi": state.phi, "varphi": state.varphi,
                "psi": state.psi, "l": state.l, "key": state.key,
                "it": state.it,
            },
            "z_versions": np.asarray(z_versions, np.int64),
            "z_shape": np.asarray(
                [store.num_blocks, store.block_docs, store.max_len], np.int64
            ),
            "cursor": np.int64(cursor),
            # running topic-word statistic at the cursor (state.n + the
            # merged deltas of swept blocks) — the delta-format marker:
            # pre-delta payloads stored partial fresh counts under
            # "n_acc" instead, which restore() refuses mid-epoch.
            "n_run": n_run,
            "dh_acc": dh_acc,
        }

    def _template(self):
        cfg, store = self.cfg, self.store
        return {
            "model": {
                "n": jnp.zeros((cfg.K, cfg.V), jnp.int32),
                "phi": jnp.zeros((cfg.K, cfg.V), jnp.float32),
                "varphi": jnp.zeros((cfg.K, cfg.V), jnp.int32),
                "psi": jnp.zeros((cfg.K,), jnp.float32),
                "l": jnp.zeros((cfg.K,), jnp.int32),
                "key": jax.random.key(0),
                "it": jnp.int32(0),
            },
            "z_versions": np.zeros((store.num_blocks,), np.int64),
            "z_shape": np.zeros((3,), np.int64),
            "cursor": np.int64(0),
            "n_run": jnp.zeros((cfg.K, cfg.V), jnp.int32),
            "dh_acc": jnp.zeros((cfg.K, cfg.hist_cap + 1), jnp.int32),
        }

    def _referenced_z_versions(self, ckpt_dir: str) -> set:
        """(block, version) pairs pinned by any retained checkpoint
        manifest in ``ckpt_dir`` (version -1 = implicit zeros, no
        file)."""
        refs = set()
        for vers in CKPT.arrays_across_steps(ckpt_dir, "z_versions").values():
            refs |= {(b, int(v)) for b, v in enumerate(vers) if int(v) >= 0}
        return refs

    def _save(self, ckpt_dir, state, cursor, n_run, dh_acc) -> str:
        """Incremental save = flush-dirty-slabs + pin manifest: dirty z
        slabs flush into immutable version files first (free when the
        live DiskZStore is homed at ``ckpt_dir``), then the atomic
        payload commit pins the version vector, then GC sweeps versions
        that no retained manifest pins and that are not live state —
        superseded files AND orphans from crashed writers. A crash
        between the first two steps leaves only orphan version files —
        the previous checkpoint stays fully consistent."""
        slab = state.z_blocks
        zbs = self._zstore(ckpt_dir, slab)
        versions, _ = slab.sync_to(zbs)
        step = int(state.it) * self.store.num_blocks + cursor
        path = CKPT.save(ckpt_dir, step,
                         self._payload(state, cursor, n_run, dh_acc, versions))
        referenced = self._referenced_z_versions(ckpt_dir)
        slab.pin_versions(zbs, referenced)
        zbs.gc(referenced | slab.live_versions_in(zbs))
        return path

    def save(self, ckpt_dir: str, state: StreamingState) -> str:
        """Iteration-boundary checkpoint (cursor = 0; n_run/dh_acc are
        dead weight there — restore never reads them at cursor 0)."""
        zero_n = jnp.zeros((self.cfg.K, self.cfg.V), jnp.int32)
        zero_dh = jnp.zeros((self.cfg.K, self.cfg.hist_cap + 1), jnp.int32)
        return self._save(ckpt_dir, state, 0, zero_n, zero_dh)

    def _save_partial(self, ckpt_dir, state, cursor, n_run, dh_acc):
        return self._save(ckpt_dir, state, cursor, n_run, dh_acc)

    def restore(self, ckpt_dir: str):
        """Returns (state, resume_kwargs): pass resume_kwargs to
        ``iteration`` to finish a partially-swept epoch (empty dict when
        the checkpoint is at an iteration boundary).

        The z slabs are NOT materialized into one array: the slab store
        adopts the pinned version vector (free for a DiskZStore homed at
        ``ckpt_dir``; a per-block bounded-memory copy otherwise; the RAM
        backend stacks into its host array as before). Orphan version
        files the pinned manifests do not reference are swept."""
        step = CKPT.latest_step(ckpt_dir)
        if step is None:
            return None, {}
        # legacy format guard: payloads written before the incremental
        # ZBlockStore embed the full z_blocks array and lack z_versions —
        # fail with a migration hint instead of a KeyError mid-restore.
        keys = CKPT.manifest_keys(ckpt_dir, step)
        if "z_versions" not in keys:
            raise ValueError(
                f"checkpoint step_{step} in {ckpt_dir!r} predates the "
                "incremental z-block format (it embeds z_blocks). "
                "Finish that run with the repo revision that wrote it, "
                "save a fresh checkpoint, or restart training."
            )
        template = self._template()
        if "n_run" not in keys:
            # pre-delta payload: "n_acc" held partial *fresh counts*, not
            # the running statistic — a mid-epoch resume would merge it
            # wrongly. Boundary checkpoints (cursor 0) never read it and
            # restore fine.
            if int(CKPT.load_array(ckpt_dir, step, "cursor")) != 0:
                raise ValueError(
                    f"mid-epoch checkpoint step_{step} in {ckpt_dir!r} "
                    "predates the delta-statistics format (its n_acc "
                    "holds partial recounts, not the running n). Finish "
                    "that epoch with the repo revision that wrote it, or "
                    "resume from the last iteration-boundary checkpoint."
                )
            template["n_acc"] = template.pop("n_run")
        payload = CKPT.restore_latest(ckpt_dir, template)
        if payload is None:
            return None, {}
        store = self.store
        want = (store.num_blocks, store.block_docs, store.max_len)
        got = tuple(int(x) for x in np.asarray(payload["z_shape"]))
        if got != want:
            raise ValueError(
                f"checkpoint block geometry {got} does not match the store "
                f"{want} — resume with the block_docs/corpus the checkpoint "
                f"was written with"
            )
        versions = np.asarray(payload["z_versions"], np.int64)
        slab = self._make_slab_store()
        zbs = self._zstore(ckpt_dir, slab)
        slab.load_from(zbs, versions)
        referenced = self._referenced_z_versions(ckpt_dir)
        slab.pin_versions(zbs, referenced)
        zbs.gc(referenced | slab.live_versions_in(zbs))
        m = payload["model"]
        state = StreamingState(
            n=jax.device_put(m["n"], self._n_sh),
            phi=jax.device_put(m["phi"], self._n_sh),
            varphi=jax.device_put(m["varphi"], self._n_sh),
            psi=jax.device_put(m["psi"], self._repl_sh),
            l=jax.device_put(m["l"], self._repl_sh),
            key=m["key"], it=m["it"],
            z_blocks=slab,
        )
        cursor = int(payload["cursor"])
        if cursor == 0:
            return state, {}
        # Mid-epoch: re-derive the current iteration's tables from the
        # pre-split key (deterministic), hand back the running statistic
        # and the histogram partial sum.
        _, k_phi, _, _, _ = self._split_fn(state.key)
        ztables = self._phi_fn(state.n, state.psi, k_phi)
        return state, {
            "start_block": cursor,
            "n_run": jax.device_put(payload["n_run"], self._n_sh),
            "dh_acc": jax.device_put(payload["dh_acc"], self._repl_sh),
            "ztables": ztables,
        }
