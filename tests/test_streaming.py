"""Streaming minibatch pipeline: block store, prefetcher, and the
StreamingHDP driver (equivalence, bounded memory, kill/resume)."""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hdp as H
from repro.core.sharded import ShardedHDP
from repro.core.streaming import StreamingHDP
from repro.data.stream import BlockPrefetcher, ShardedCorpusStore
from repro.data.synthetic import planted_topics_corpus
from repro.launch.mesh import make_host_mesh


def make_setup(rng, D, impl="sparse", V=48, K=12, doc_len=(10, 20)):
    corpus, _ = planted_topics_corpus(rng, D=D, V=V, K_true=3,
                                      doc_len=doc_len)
    mesh = make_host_mesh()
    cfg = H.HDPConfig(K=K, V=V, bucket=K, z_impl=impl, hist_cap=32)
    return corpus, mesh, cfg, ShardedHDP(mesh, cfg)


# -- corpus store -------------------------------------------------------------

def test_store_blocks_partition_corpus(rng):
    corpus, *_ = make_setup(rng, D=37)
    store = ShardedCorpusStore.from_corpus(corpus, block_docs=8)
    assert store.num_blocks == 5
    rows = np.concatenate([b.tokens for b in store.blocks()])
    msk = np.concatenate([b.mask for b in store.blocks()])
    assert rows.shape[0] == 5 * 8  # padded final block
    np.testing.assert_array_equal(rows[:37], corpus.tokens)
    np.testing.assert_array_equal(msk[:37], corpus.mask)
    assert not msk[37:].any()  # padding rows carry no tokens
    assert store.num_tokens == corpus.num_tokens


def test_store_doc_multiple_rounds_block_size(rng):
    corpus, *_ = make_setup(rng, D=20)
    store = ShardedCorpusStore.from_corpus(corpus, block_docs=7,
                                           doc_multiple=4)
    assert store.block_docs == 8


def test_store_save_open_roundtrip(rng):
    corpus, *_ = make_setup(rng, D=16)
    with tempfile.TemporaryDirectory() as d:
        ShardedCorpusStore.from_corpus(corpus, block_docs=4).save(d)
        store = ShardedCorpusStore.open(d)  # memmap-backed
        assert store.num_blocks == 4
        np.testing.assert_array_equal(store.block(1).tokens,
                                      corpus.tokens[4:8])


def test_prefetcher_preserves_order_and_propagates_errors():
    out = list(BlockPrefetcher(iter(range(10)), lambda x: x * x, depth=2))
    assert out == [x * x for x in range(10)]

    def boom(x):
        if x == 3:
            raise RuntimeError("stage failed")
        return x

    with pytest.raises(RuntimeError, match="stage failed"):
        list(BlockPrefetcher(iter(range(10)), boom, depth=2))


def test_prefetcher_pre_stage_order_bound_and_errors():
    """The two-stage (pre -> stage) pipeline preserves order, bounds
    in-flight items to ``depth`` across BOTH stages, and propagates
    errors from either stage."""
    import threading

    in_flight = 0
    peak = 0
    lock = threading.Lock()

    def pre(x):
        nonlocal in_flight, peak
        with lock:
            in_flight += 1
            peak = max(peak, in_flight)
        return x

    def consume():
        out = []
        for item in BlockPrefetcher(iter(range(20)), lambda x: x + 1,
                                    depth=2, pre=pre):
            nonlocal_done()
            out.append(item)
        return out

    def nonlocal_done():
        nonlocal in_flight
        with lock:
            in_flight -= 1

    assert consume() == [x + 1 for x in range(20)]
    # shared budget: at most depth items between pre-start and consumption
    # (+1 slack: the consumer-side decrement runs just after the budget
    # slot frees, so the reader may momentarily overlap it)
    assert peak <= 3, peak

    with pytest.raises(RuntimeError, match="pre failed"):
        def bad_pre(x):
            if x == 5:
                raise RuntimeError("pre failed")
            return x
        list(BlockPrefetcher(iter(range(10)), lambda x: x, depth=2,
                             pre=bad_pre))

    with pytest.raises(RuntimeError, match="stage failed"):
        def bad_stage(x):
            if x == 5:
                raise RuntimeError("stage failed")
            return x
        list(BlockPrefetcher(iter(range(10)), bad_stage, depth=2,
                             pre=lambda x: x))


# -- the tentpole equivalence claim -------------------------------------------

@pytest.mark.parametrize("impl", ["sparse", "dense", "pallas"])
def test_streaming_single_block_bitwise_equals_monolithic(rng, impl):
    """A one-block stream must consume randomness — and produce states —
    bitwise-identically to the monolithic ShardedHDP iteration."""
    corpus, mesh, cfg, sh = make_setup(rng, D=24, impl=impl)
    ts, ms = sh.corpus_shardings()
    tokens = jax.device_put(jnp.asarray(corpus.tokens), ts)
    mask = jax.device_put(jnp.asarray(corpus.mask), ms)
    mono = sh.init_state(jax.random.key(0), tokens, mask)
    step = sh.jit_iteration()

    store = ShardedCorpusStore.from_corpus(corpus, corpus.num_docs)
    assert store.num_blocks == 1
    stream = StreamingHDP(sh, store)
    st = stream.init_state(jax.random.key(0))

    for _ in range(3):
        mono = step(mono, tokens, mask)
        st = stream.iteration(st)

    np.testing.assert_array_equal(np.asarray(mono.z), st.z_blocks[0])
    for f in ("n", "phi", "varphi", "psi", "l"):
        np.testing.assert_array_equal(
            np.asarray(getattr(mono, f)), np.asarray(getattr(st, f)), f
        )
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(mono.key)),
        np.asarray(jax.random.key_data(st.key)),
    )
    assert int(mono.it) == int(st.it) == 3


def test_streaming_multiblock_statistics_consistent(rng):
    """Multi-block sweeps draw different (per-block) uniforms than the
    monolithic sampler, but the merged statistics must stay exact:
    n == count(z), token conservation, psi on the simplex."""
    corpus, mesh, cfg, sh = make_setup(rng, D=40)
    store = ShardedCorpusStore.from_corpus(corpus, block_docs=8)
    stream = StreamingHDP(sh, store)
    st = stream.init_state(jax.random.key(0))
    for _ in range(3):
        st = stream.iteration(st)
    z_all = jnp.asarray(st.z_blocks.materialize().reshape(-1, store.max_len))
    t_all, m_all = [], []
    for blk in store.blocks():
        t_all.append(blk.tokens)
        m_all.append(blk.mask)
    n_re = H.count_n(z_all, jnp.asarray(np.concatenate(t_all)),
                     jnp.asarray(np.concatenate(m_all)), cfg.K, cfg.V)
    np.testing.assert_array_equal(np.asarray(n_re), np.asarray(st.n))
    assert int(np.asarray(st.n).sum()) == corpus.num_tokens
    assert abs(float(st.psi.sum()) - 1.0) < 1e-4


def test_streaming_bounded_device_memory(rng):
    """Corpus 10x the block budget: device-resident bytes stay well under
    the monolithic corpus footprint."""
    corpus, mesh, cfg, sh = make_setup(rng, D=320, doc_len=(30, 60))
    store = ShardedCorpusStore.from_corpus(corpus, block_docs=32)
    assert store.num_blocks >= 10
    stream = StreamingHDP(sh, store)
    st = stream.init_state(jax.random.key(0))
    # monolithic footprint: device tokens + mask + z for the full corpus
    mono_bytes = (corpus.tokens.nbytes + corpus.mask.nbytes
                  + corpus.tokens.nbytes)
    peak = 0
    for _ in range(2):
        st = stream.iteration(st)
        peak = max(peak, sum(a.nbytes for a in jax.live_arrays()))
    assert peak < mono_bytes / 2, (peak, mono_bytes)


def test_streaming_kill_resume_bitwise_deterministic(rng):
    """Mid-epoch kill + restore from the block-cursor checkpoint replays
    to exactly the uninterrupted chain."""
    corpus, mesh, cfg, sh = make_setup(rng, D=40)
    store = ShardedCorpusStore.from_corpus(corpus, block_docs=8)
    stream = StreamingHDP(sh, store)

    a = stream.init_state(jax.random.key(0))
    for _ in range(4):
        a = stream.iteration(a)

    with tempfile.TemporaryDirectory() as d:
        b = stream.init_state(jax.random.key(0))
        for _ in range(2):
            b = stream.iteration(b)
        # killed mid-iteration 3 after 2 of 5 blocks
        r = stream.iteration(b, ckpt_dir=d, ckpt_every_blocks=1,
                             stop_after_blocks=2)
        assert r is None  # sweep did not complete
        b, resume_kw = stream.restore(d)
        assert resume_kw["start_block"] == 2
        b = stream.iteration(b, **resume_kw)
        b = stream.iteration(b)

    for f in ("n", "phi", "varphi", "psi", "l"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)), f
        )
    np.testing.assert_array_equal(a.z_blocks, b.z_blocks)
    assert int(a.it) == int(b.it) == 4


def test_streaming_checkpoints_are_incremental(rng):
    """Mid-epoch saves rewrite ONLY the z slabs touched since the last
    save (per-block version files), never the whole z_blocks array, and
    GC keeps every version a retained checkpoint references."""
    import os

    corpus, mesh, cfg, sh = make_setup(rng, D=40)
    store = ShardedCorpusStore.from_corpus(corpus, block_docs=8)
    stream = StreamingHDP(sh, store)
    st = stream.init_state(jax.random.key(0))
    with tempfile.TemporaryDirectory() as d:
        zdir = os.path.join(d, "zstore")
        stream.save(d, st)
        first = set(os.listdir(zdir))
        assert len(first) == store.num_blocks  # initial save: all slabs
        # sweep 2 of 5 blocks, then a forced partial save
        r = stream.iteration(st, ckpt_dir=d, stop_after_blocks=2)
        assert r is None
        new = set(os.listdir(zdir)) - first
        assert len(new) == 2, new  # ONLY the swept slabs were rewritten
        # every retained manifest's version vector must resolve on disk
        from repro.train import checkpoint as CKPT
        for s in CKPT.all_steps(d):
            vers = np.load(os.path.join(d, f"step_{s}", "z_versions.npy"))
            for b, v in enumerate(vers):
                assert os.path.exists(
                    os.path.join(zdir, f"block_{b}.v{int(v)}.npy")), (s, b)
        # and the restore path reassembles the exact slabs
        st2, kw = stream.restore(d)
        assert kw["start_block"] == 2
        np.testing.assert_array_equal(st2.z_blocks, st.z_blocks)


def test_streaming_restore_rejects_legacy_z_blocks_format(rng):
    """A checkpoint written by the pre-incremental format (full z_blocks
    in the payload) must fail with a migration message, not a KeyError."""
    import os

    corpus, mesh, cfg, sh = make_setup(rng, D=16)
    store = ShardedCorpusStore.from_corpus(corpus, block_docs=8)
    stream = StreamingHDP(sh, store)
    st = stream.init_state(jax.random.key(0))
    from repro.train import checkpoint as CKPT
    with tempfile.TemporaryDirectory() as d:
        CKPT.save(d, 0, {
            "model": {"n": st.n, "phi": st.phi, "varphi": st.varphi,
                      "psi": st.psi, "l": st.l, "key": st.key, "it": st.it},
            "z_blocks": st.z_blocks.materialize(),
            "cursor": np.int64(0),
        })
        with pytest.raises(ValueError, match="predates the incremental"):
            stream.restore(d)


def test_streaming_boundary_checkpoint_roundtrip(rng):
    corpus, mesh, cfg, sh = make_setup(rng, D=24)
    store = ShardedCorpusStore.from_corpus(corpus, block_docs=8)
    stream = StreamingHDP(sh, store)
    st = stream.init_state(jax.random.key(1))
    st = stream.iteration(st)
    with tempfile.TemporaryDirectory() as d:
        stream.save(d, st)
        restored, resume_kw = stream.restore(d)
        assert resume_kw == {}
        for f in ("n", "phi", "varphi", "psi", "l"):
            np.testing.assert_array_equal(
                np.asarray(getattr(st, f)), np.asarray(getattr(restored, f))
            )
        np.testing.assert_array_equal(st.z_blocks, restored.z_blocks)


# -- the pluggable z-slab store (ZSlabStore: ram | disk backends) -------------

def _state_fields_equal(a, b):
    for f in ("n", "phi", "varphi", "psi", "l"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)), f
        )
    np.testing.assert_array_equal(
        a.z_blocks.materialize(), b.z_blocks.materialize()
    )
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(a.key)),
        np.asarray(jax.random.key_data(b.key)),
    )


def test_disk_store_bitwise_equals_ram(rng):
    """The out-of-core backend must produce bitwise-identical chains to
    the resident-array backend (same keys, same slab contents), across
    multi-block iterations."""
    corpus, mesh, cfg, sh = make_setup(rng, D=40)
    store = ShardedCorpusStore.from_corpus(corpus, block_docs=8)
    ram = StreamingHDP(sh, store, z_store="ram")
    disk = StreamingHDP(sh, store, z_store="disk")
    a = ram.init_state(jax.random.key(0))
    b = disk.init_state(jax.random.key(0))
    for _ in range(3):
        a = ram.iteration(a)
        b = disk.iteration(b)
    _state_fields_equal(a, b)


def test_disk_store_bounded_resident_slabs(rng):
    """At most prefetch_depth + writeback_depth + 1 z slabs are ever
    host-resident with the disk backend (store-level high-water mark):
    the prefetch budget covers read-ahead through staging, plus the one
    slab the write-back worker is flushing."""
    corpus, mesh, cfg, sh = make_setup(rng, D=80)
    store = ShardedCorpusStore.from_corpus(corpus, block_docs=8)
    assert store.num_blocks >= 10
    stream = StreamingHDP(sh, store, z_store="disk")
    st = stream.init_state(jax.random.key(0))
    for _ in range(2):
        st = stream.iteration(st)
    bound = stream.prefetch_depth + stream.writeback_depth + 1
    assert 0 < st.z_blocks.high_water <= bound, (
        st.z_blocks.high_water, bound
    )
    assert st.z_blocks.high_water < store.num_blocks  # genuinely out-of-core


def test_disk_home_checkpoint_is_near_free(rng):
    """A DiskZStore homed at the checkpoint directory saves WITHOUT
    copying any slab — the live version files are the checkpoint files;
    the payload just pins the current version vector — and restores by
    adopting the vector."""
    import os

    corpus, mesh, cfg, sh = make_setup(rng, D=40)
    store = ShardedCorpusStore.from_corpus(corpus, block_docs=8)
    with tempfile.TemporaryDirectory() as d:
        stream = StreamingHDP(sh, store, z_store="disk", z_dir=d)
        st = stream.init_state(jax.random.key(0))
        st = stream.iteration(st)
        zdir = os.path.join(d, "zstore")
        before = set(os.listdir(zdir))
        stream.save(d, st)
        assert set(os.listdir(zdir)) == before  # no slab was rewritten
        z_ref = st.z_blocks.materialize()
        restored, kw = stream.restore(d)
        assert kw == {}
        np.testing.assert_array_equal(z_ref, restored.z_blocks.materialize())
        np.testing.assert_array_equal(np.asarray(st.n),
                                      np.asarray(restored.n))
        # the restored chain keeps training from adopted (not copied) slabs
        restored = stream.iteration(restored)


def test_switch_backend_via_checkpoint_bitwise(rng):
    """ram -> save -> restore-as-disk -> iterate must equal the pure-ram
    chain bitwise (and the reverse direction back to ram)."""
    corpus, mesh, cfg, sh = make_setup(rng, D=40)
    store = ShardedCorpusStore.from_corpus(corpus, block_docs=8)
    ram = StreamingHDP(sh, store, z_store="ram")
    ref = ram.init_state(jax.random.key(0))
    for _ in range(4):
        ref = ram.iteration(ref)

    other = ram.init_state(jax.random.key(0))
    other = ram.iteration(other)
    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2:
        ram.save(d1, other)
        disk = StreamingHDP(sh, store, z_store="disk")
        mid, kw = disk.restore(d1)
        assert kw == {}
        mid = disk.iteration(mid)
        disk.save(d2, mid)
        back, kw = ram.restore(d2)
        assert kw == {}
        for _ in range(2):
            back = ram.iteration(back)
    _state_fields_equal(ref, back)


def test_env_var_selects_backend(rng, monkeypatch):
    from repro.data.zstore import DiskZStore, RamZStore

    corpus, mesh, cfg, sh = make_setup(rng, D=16)
    store = ShardedCorpusStore.from_corpus(corpus, block_docs=8)
    monkeypatch.setenv("REPRO_Z_STORE", "disk")
    assert isinstance(StreamingHDP(sh, store).init_state(
        jax.random.key(0)).z_blocks, DiskZStore)
    monkeypatch.setenv("REPRO_Z_STORE", "ram")
    assert isinstance(StreamingHDP(sh, store).init_state(
        jax.random.key(0)).z_blocks, RamZStore)
    with pytest.raises(ValueError, match="ram.*disk|disk.*ram"):
        StreamingHDP(sh, store, z_store="tape")


def test_disk_store_releases_checkouts_on_early_exit(rng):
    """A mid-epoch stop discards pre-read slabs from the prefetch
    pipeline; their checkouts must be released or resident accounting
    leaks (and the documented bound silently degrades)."""
    corpus, mesh, cfg, sh = make_setup(rng, D=80)
    store = ShardedCorpusStore.from_corpus(corpus, block_docs=8)
    stream = StreamingHDP(sh, store, z_store="disk")
    st = stream.init_state(jax.random.key(0))
    with tempfile.TemporaryDirectory() as d:
        r = stream.iteration(st, ckpt_dir=d, stop_after_blocks=2)
        assert r is None
    assert st.z_blocks.resident_slabs == 0, st.z_blocks._resident


def test_disk_store_releases_checkouts_on_worker_exception(rng):
    """A prefetch worker dying mid-iteration (not a clean early exit)
    must also release every in-flight slab checkout: the killed
    pipeline's pre-read slabs drop through the undo hooks, accounting
    returns to zero, and a subsequent iteration still observes the
    documented resident bound."""
    corpus, mesh, cfg, sh = make_setup(rng, D=80)
    store = ShardedCorpusStore.from_corpus(corpus, block_docs=8)
    stream = StreamingHDP(sh, store, z_store="disk")
    st = stream.iteration(stream.init_state(jax.random.key(0)))
    slab = st.z_blocks
    real_read = slab.read
    calls = {"n": 0}

    def dying_read(b):
        calls["n"] += 1
        if calls["n"] == 5:
            raise RuntimeError("injected z-read failure")
        return real_read(b)

    slab.read = dying_read
    try:
        with pytest.raises(RuntimeError, match="injected"):
            stream.iteration(st)
    finally:
        slab.read = real_read
    assert slab.resident_slabs == 0, slab._resident
    # recovery: a fresh full sweep completes inside the bound
    st2 = stream.iteration(st)
    bound = stream.prefetch_depth + stream.writeback_depth + 1
    assert 0 < st2.z_blocks.high_water <= bound, (
        st2.z_blocks.high_water, bound)


def test_disk_read_failure_checks_slab_back_in(rng):
    """DiskZStore.read that fails mid-load (corrupt/missing version
    file) undoes its own checkout — the caller has nothing to
    release."""
    from repro.data.zstore import make_zslab_store

    with tempfile.TemporaryDirectory() as d:
        slab = make_zslab_store("disk", 2, (4, 6), root=d)
        slab.write(1, np.ones((4, 6), np.int32))
        slab._zbs.load_block = lambda *a, **k: (_ for _ in ()).throw(
            OSError("corrupt version file"))
        with pytest.raises(OSError, match="corrupt"):
            slab.read(1)
        assert slab.resident_slabs == 0, slab._resident


def test_async_stage_drop_hook_runs_on_worker_error():
    """AsyncStage releases item side effects through ``drop`` when the
    worker dies: the failing item itself AND everything queued or
    submitted after it."""
    from repro.data.stream import AsyncStage

    done, dropped = [], []

    def fn(x):
        if x == 2:
            raise RuntimeError("worker died")
        done.append(x)

    stage = AsyncStage(fn, depth=2, drop=dropped.append)
    for x in range(5):
        stage.submit(x)
    with pytest.raises(RuntimeError, match="worker died"):
        stage.close()
    assert done == [0, 1]
    assert dropped == [2, 3, 4]


def test_zblockstore_write_block_never_overwrites_foreign_versions(rng):
    """Two store instances on one directory (e.g. two chains
    checkpointing into the same dir): a live write must never reuse —
    and overwrite — a version number the other instance committed."""
    import os

    from repro.data.zstore import ZBlockStore

    with tempfile.TemporaryDirectory() as d:
        a = ZBlockStore(d, 2)
        b = ZBlockStore(d, 2)
        slab_a = np.full((4, 6), 1, np.int32)
        slab_b = np.full((4, 6), 2, np.int32)
        va = a.write_block(0, slab_a, stamp=1)
        vb = b.write_block(0, slab_b, stamp=1)  # b's counter is stale
        assert va != vb
        np.testing.assert_array_equal(a.load_block(0, va), slab_a)
        np.testing.assert_array_equal(b.load_block(0, vb), slab_b)
        assert len(os.listdir(os.path.join(d, "zstore"))) == 2


def test_zblockstore_gc_sweeps_orphan_versions(rng):
    """Forged crash state: a version file written but never referenced
    by any manifest (the writer died between the slab write and the
    payload commit). Both the save path and the restore path must sweep
    it, while every pinned version survives."""
    import os

    corpus, mesh, cfg, sh = make_setup(rng, D=40)
    store = ShardedCorpusStore.from_corpus(corpus, block_docs=8)
    stream = StreamingHDP(sh, store)
    st = stream.init_state(jax.random.key(0))
    st = stream.iteration(st)
    with tempfile.TemporaryDirectory() as d:
        stream.save(d, st)
        zdir = os.path.join(d, "zstore")
        committed = set(os.listdir(zdir))
        # forge the crash: orphan version files no manifest references
        orphans = ["block_0.v99.npy", "block_3.v99.npy"]
        for f in orphans:
            np.save(os.path.join(zdir, f),
                    np.zeros((store.block_docs, store.max_len), np.int32))
        # restore-time sweep (a crashed run that resumes but never saves
        # again must not leak the orphans forever)
        fresh = StreamingHDP(sh, store)  # new driver: no in-memory stamps
        restored, _ = fresh.restore(d)
        assert set(os.listdir(zdir)) == committed
        np.testing.assert_array_equal(st.z_blocks, restored.z_blocks)
        # save-time sweep as well
        for f in orphans:
            np.save(os.path.join(zdir, f),
                    np.zeros((store.block_docs, store.max_len), np.int32))
        st2 = fresh.iteration(restored)
        fresh.save(d, st2)
        names = set(os.listdir(zdir))
        assert not any(f in names for f in orphans)
        # every retained manifest still resolves on disk
        from repro.train import checkpoint as CKPT
        for s, vers in CKPT.arrays_across_steps(d, "z_versions").items():
            for b, v in enumerate(vers):
                assert os.path.exists(
                    os.path.join(zdir, f"block_{b}.v{int(v)}.npy")), (s, b)


def test_restore_pr2_era_checkpoint_format(rng):
    """Compatibility freeze: a checkpoint laid out exactly as the
    incremental-format PRs wrote it (per-block v0 files + z_versions
    vector in the payload) restores bitwise under BOTH backends."""
    import os

    from repro.train import checkpoint as CKPT

    corpus, mesh, cfg, sh = make_setup(rng, D=24)
    store = ShardedCorpusStore.from_corpus(corpus, block_docs=8)
    stream = StreamingHDP(sh, store)
    st = stream.init_state(jax.random.key(3))
    z_forged = np.asarray(
        rng.integers(0, cfg.K, size=(store.num_blocks, store.block_docs,
                                     store.max_len)), np.int32)
    with tempfile.TemporaryDirectory() as d:
        zdir = os.path.join(d, "zstore")
        os.makedirs(zdir)
        for b in range(store.num_blocks):
            np.save(os.path.join(zdir, f"block_{b}.v0.npy"), z_forged[b])
        CKPT.save(d, 0, {
            "model": {"n": st.n, "phi": st.phi, "varphi": st.varphi,
                      "psi": st.psi, "l": st.l, "key": st.key, "it": st.it},
            "z_versions": np.zeros(store.num_blocks, np.int64),
            "z_shape": np.asarray([store.num_blocks, store.block_docs,
                                   store.max_len], np.int64),
            "cursor": np.int64(0),
            "n_run": jnp.zeros((cfg.K, cfg.V), jnp.int32),
            "dh_acc": jnp.zeros((cfg.K, cfg.hist_cap + 1), jnp.int32),
        })
        for backend in ("ram", "disk"):
            drv = StreamingHDP(sh, store, z_store=backend)
            restored, kw = drv.restore(d)
            assert kw == {}
            assert restored.z_blocks.kind == backend
            np.testing.assert_array_equal(
                z_forged, restored.z_blocks.materialize(), backend
            )
            np.testing.assert_array_equal(np.asarray(st.n),
                                          np.asarray(restored.n))


# -- block-sparse tables & budgeted PPU ---------------------------------------

def _chain_equal(a, b, store_a, store_b):
    for f in ("n", "phi", "varphi", "psi", "l", "it"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)), f)
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(a.key)),
        np.asarray(jax.random.key_data(b.key)))
    np.testing.assert_array_equal(store_a.materialize(),
                                  store_b.materialize())


@pytest.mark.parametrize("impl", ["sparse", "pallas"])
@pytest.mark.parametrize("z_store", ["ram", "disk"])
def test_block_sparse_tables_chain_bitwise_equals_dense(rng, impl, z_store):
    """Vocab-masked table construction is a pure cost optimization: the
    sweep only gathers token rows, so the FULL multi-iteration chain —
    z slabs, statistics, chain key — must be bitwise-identical with
    block-sparse tables forced on vs off, per impl and slab backend."""
    corpus, mesh, cfg, sh = make_setup(rng, D=24, impl=impl, V=96)
    store = ShardedCorpusStore.from_corpus(corpus, block_docs=8)
    assert store.vocab_coverage <= 1.0
    states = {}
    for mode in ("off", "on"):
        stream = StreamingHDP(sh, store, z_store=z_store,
                              block_sparse_tables=mode)
        assert stream.block_sparse_tables == (mode == "on")
        st = stream.init_state(jax.random.key(0))
        for _ in range(2):
            st = stream.iteration(st)
        states[mode] = st
    _chain_equal(states["on"], states["off"],
                 states["on"].z_blocks, states["off"].z_blocks)


def test_block_sparse_on_requires_word_tables(rng):
    """The dense z-step has no per-word alias tables to mask — forcing
    block-sparse on there must fail loudly, and "auto" must resolve to
    off rather than crash."""
    corpus, mesh, cfg, sh = make_setup(rng, D=16, impl="dense")
    store = ShardedCorpusStore.from_corpus(corpus, block_docs=8)
    with pytest.raises(ValueError, match="per-word alias tables"):
        StreamingHDP(sh, store, block_sparse_tables="on")
    assert StreamingHDP(sh, store).block_sparse_tables is False


def test_block_sparse_env_var_and_validation(rng, monkeypatch):
    corpus, mesh, cfg, sh = make_setup(rng, D=16)
    store = ShardedCorpusStore.from_corpus(corpus, block_docs=8)
    monkeypatch.setenv("REPRO_BLOCK_SPARSE_TABLES", "on")
    assert StreamingHDP(sh, store).block_sparse_tables is True
    monkeypatch.setenv("REPRO_BLOCK_SPARSE_TABLES", "off")
    assert StreamingHDP(sh, store).block_sparse_tables is False
    with pytest.raises(ValueError, match="block_sparse_tables"):
        StreamingHDP(sh, store, block_sparse_tables="maybe")


def test_pallas_default_builds_word_tables(rng, monkeypatch):
    """Under the compiled kernel's default the pallas sampler sweeps on
    per-word tables, so the block-sparse build is available again and
    "auto" engages it below 50% vocabulary coverage."""
    from repro.kernels.hdp_z import ops as zops

    monkeypatch.delenv("REPRO_ALIAS_IN_KERNEL", raising=False)
    monkeypatch.setattr(zops, "resolve_interpret", lambda: False)
    corpus, mesh, cfg, _ = make_setup(rng, D=8, impl="pallas", V=400)
    sh = ShardedHDP(mesh, cfg)
    assert sh.alias_in_kernel is False
    assert sh.supports_masked_tables()
    store = ShardedCorpusStore.from_corpus(corpus, block_docs=8)
    assert store.vocab_coverage < 0.5
    assert StreamingHDP(sh, store).block_sparse_tables is True
    assert StreamingHDP(sh, store,
                        block_sparse_tables="on").block_sparse_tables


@pytest.mark.parametrize("mode", ["tables", "masked", "prologue"])
def test_alias_rows_built_counter(rng, mode):
    """``train.alias_rows_built`` adds, per table build, the alias rows
    built, read from shapes: V on per-word tables, the block-sparse
    cap when masked, and the live tokens when the kernel rebuilds each
    token's row in its prologue."""
    from repro import obs

    corpus, mesh, cfg, _ = make_setup(rng, D=16, impl="pallas", V=96)
    cfg = cfg._replace(alias_in_kernel="on" if mode == "prologue" else "off")
    sh = ShardedHDP(mesh, cfg)
    store = ShardedCorpusStore.from_corpus(corpus, block_docs=8)
    stream = StreamingHDP(
        sh, store, block_sparse_tables="on" if mode == "masked" else "off")
    want = {"tables": cfg.V, "masked": int(store.vocab_ids().size),
            "prologue": store.num_tokens}[mode]
    assert 0 < want and (mode != "masked" or want < cfg.V)
    rows = obs.metrics().counter("train.alias_rows_built")
    swept = obs.metrics().counter("train.tokens_swept")
    rows0, swept0 = rows.value, swept.value
    st = stream.init_state(jax.random.key(0))
    for _ in range(2):
        st = stream.iteration(st)
    assert rows.value - rows0 == 2 * want
    assert swept.value - swept0 == 2 * store.num_tokens


def test_budgeted_ppu_streaming_bitwise_equals_monolithic(rng):
    """The doubly-sparse budgeted PPU draw is a different uniform stream
    than the dense draw, but it must be the SAME stream on the monolithic
    and streaming sides: a one-block stream with ``ppu_nnz_budget`` set
    stays bitwise-equal to the monolithic sharded iteration (incl. the
    init-state Phi draw, which also goes through the budgeted path)."""
    corpus, _ = planted_topics_corpus(rng, D=24, V=48, K_true=3,
                                      doc_len=(10, 20))
    mesh = make_host_mesh()
    budget = 1 << max(corpus.num_tokens - 1, 1).bit_length()
    cfg = H.HDPConfig(K=12, V=48, bucket=12, z_impl="sparse", hist_cap=32,
                      ppu_nnz_budget=budget)
    sh = ShardedHDP(mesh, cfg)
    ts, ms = sh.corpus_shardings()
    tokens = jax.device_put(jnp.asarray(corpus.tokens), ts)
    mask = jax.device_put(jnp.asarray(corpus.mask), ms)
    mono = sh.init_state(jax.random.key(0), tokens, mask)
    step = sh.jit_iteration()
    store = ShardedCorpusStore.from_corpus(corpus, corpus.num_docs)
    stream = StreamingHDP(sh, store)
    st = stream.init_state(jax.random.key(0))
    for _ in range(3):
        mono = step(mono, tokens, mask)
        st = stream.iteration(st)
    np.testing.assert_array_equal(np.asarray(mono.z), st.z_blocks[0])
    for f in ("n", "phi", "varphi", "psi", "l"):
        np.testing.assert_array_equal(
            np.asarray(getattr(mono, f)), np.asarray(getattr(st, f)), f)
    # sanity: the budgeted draw is genuinely a different uniform stream
    # than the dense draw (same seed, different decomposition), or the
    # budget knob is dead plumbing.
    cfg_d = H.HDPConfig(K=12, V=48, bucket=12, z_impl="sparse", hist_cap=32)
    st_d = StreamingHDP(ShardedHDP(mesh, cfg_d), store).init_state(
        jax.random.key(0))
    st_b = stream.init_state(jax.random.key(0))
    assert (np.asarray(st_d.varphi) != np.asarray(st_b.varphi)).any()
