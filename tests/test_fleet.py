"""Serving fleet: registry publish atomicity, fleet-vs-single-engine
bitwise determinism at any worker count, snapshot hot-swap semantics,
posterior-ensemble aggregation, admission backpressure, and the
streaming-trainer publish hook.

The load-bearing contract: a request's mixture depends only on
(snapshot, base_key, seed, tokens) — never on worker count, dispatch
order, admission timing, or a concurrent registry publish. Every test
here is an instance of that invariant.
"""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hdp as H
from repro.data.synthetic import planted_topics_corpus
from repro.serve import snapshot as SNAP
from repro.serve.engine import ServeEngine
from repro.serve.fleet import ServeFleet
from repro.serve.registry import SnapshotRegistry

K, V = 12, 48
BURNIN = 4
BUCKETS = (16, 32)


@pytest.fixture(scope="module")
def trained():
    """Two posterior samples from one chain (snapshots for hot-swap and
    ensembling) + a held-out query set."""
    rng = np.random.default_rng(0)
    corpus, _ = planted_topics_corpus(rng, D=48, V=V, K_true=3,
                                      doc_len=(10, 20))
    cfg = H.HDPConfig(K=K, V=V, bucket=K, z_impl="sparse", hist_cap=32)
    tokens = jnp.asarray(corpus.tokens[:40])
    mask = jnp.asarray(corpus.mask[:40])
    state = H.init_state(jax.random.key(0), tokens, mask, cfg)
    step = jax.jit(lambda s: H.gibbs_iteration(s, tokens, mask, cfg))
    for _ in range(10):
        state = step(state)
    snap1 = SNAP.snapshot_from_state(state, cfg)
    for _ in range(5):
        state = step(state)
    snap2 = SNAP.snapshot_from_state(state, cfg)
    docs = [corpus.tokens[i][corpus.mask[i]] for i in range(40, 48)]
    return snap1, snap2, docs


BASE_KEY_SEED = 11


def _single_engine(snap, docs, seeds):
    """The single-engine reference the fleet must match bitwise."""
    eng = ServeEngine(snap, slots=3, burnin=BURNIN, impl="sparse",
                      buckets=BUCKETS, base_key=jax.random.key(BASE_KEY_SEED))
    for doc, s in zip(docs, seeds):
        eng.submit(doc, seed=s)
    return eng.run()


def _fleet(source, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("burnin", BURNIN)
    kw.setdefault("impl", "sparse")
    kw.setdefault("buckets", BUCKETS)
    kw.setdefault("base_key", jax.random.key(BASE_KEY_SEED))
    return ServeFleet(source, **kw)


# -- registry -----------------------------------------------------------------

def test_registry_publish_load_roundtrip(trained):
    snap1, snap2, _ = trained
    with tempfile.TemporaryDirectory() as d:
        reg = SnapshotRegistry(d)
        assert reg.latest_version() is None and reg.versions() == []
        with pytest.raises(FileNotFoundError):
            reg.load()
        v1 = reg.publish(snap1)
        v2 = reg.publish(snap2)
        assert (v1, v2) == (1, 2)
        assert reg.versions() == [1, 2] and reg.latest_version() == 2
        got1, got2 = reg.load(1), reg.load()
        np.testing.assert_array_equal(np.asarray(got1.phi),
                                      np.asarray(snap1.phi))
        np.testing.assert_array_equal(np.asarray(got2.phi),
                                      np.asarray(snap2.phi))
        meta = reg.manifest()["versions"]["2"]
        assert meta["K"] == K and meta["V"] == V
        assert meta["it"] == int(snap2.it)


def test_registry_ignores_uncommitted_dirs(trained):
    """Readers trust only the manifest: a crash mid-publish leaves
    orphan dirs that must be invisible — and whose numbers are never
    reused by later publishes."""
    snap1, _, _ = trained
    with tempfile.TemporaryDirectory() as d:
        reg = SnapshotRegistry(d)
        reg.publish(snap1)
        os.makedirs(os.path.join(d, ".tmp-v7"))   # crashed mid-save
        os.makedirs(os.path.join(d, "v9"))        # crashed pre-commit
        assert reg.versions() == [1]
        with pytest.raises(FileNotFoundError):
            reg.load(9)
        assert reg.publish(snap1) == 10  # past every orphan
        assert reg.versions() == [1, 10]


def test_registry_retention(trained):
    snap1, _, _ = trained
    with tempfile.TemporaryDirectory() as d:
        reg = SnapshotRegistry(d)
        for _ in range(4):
            reg.publish(snap1, keep=2)
        assert reg.versions() == [3, 4]
        assert not os.path.exists(os.path.join(d, "v1"))
        reg.load(4)
        with pytest.raises(FileNotFoundError):
            reg.load(1)


def test_registry_latest_versions_for_ensemble(trained):
    snap1, _, _ = trained
    with tempfile.TemporaryDirectory() as d:
        reg = SnapshotRegistry(d)
        reg.publish(snap1)
        reg.publish(snap1)
        assert reg.latest_versions(2) == [1, 2]
        with pytest.raises(ValueError, match="ensemble needs 3"):
            reg.latest_versions(3)


# -- fleet determinism --------------------------------------------------------

@pytest.mark.parametrize("workers", [1, 2, 4])
def test_fleet_matches_single_engine_bitwise(trained, workers):
    """The acceptance criterion: fleet output is bitwise-equal to the
    single continuous-batching engine for every request, per seed, at
    any worker count."""
    snap1, _, docs = trained
    ref = _single_engine(snap1, docs, range(len(docs)))
    with _fleet(snap1, workers=workers) as fl:
        for i, doc in enumerate(docs):
            fl.submit(doc, seed=i)
        out = fl.run(timeout=300)
    assert sorted(out) == sorted(ref)
    for rid in ref:
        np.testing.assert_array_equal(out[rid], ref[rid], rid)


def test_fleet_submission_order_irrelevant(trained):
    snap1, _, docs = trained
    ref = _single_engine(snap1, docs, range(len(docs)))
    with _fleet(snap1, workers=2) as fl:
        for i in reversed(range(len(docs))):
            fl.submit(docs[i], seed=i)
        out = fl.run(timeout=300)
    for rid in ref:
        np.testing.assert_array_equal(out[rid], ref[rid], rid)


# -- hot-swap -----------------------------------------------------------------

def test_fleet_hot_swap_redirects_new_admissions(trained):
    """Before a publish every request serves on v1; after refresh every
    NEW request serves on v2 — and the already-completed v1 mixtures are
    untouched by the publish."""
    snap1, snap2, docs = trained
    n = len(docs)
    ref1 = _single_engine(snap1, docs, range(n))
    ref2 = _single_engine(snap2, docs, range(100, 100 + n))
    with tempfile.TemporaryDirectory() as d:
        reg = SnapshotRegistry(d)
        reg.publish(snap1)
        with _fleet(reg, workers=2, watch_registry=True) as fl:
            for i, doc in enumerate(docs):
                fl.submit(doc, seed=i)
            a = fl.run(timeout=300)
            a_before = {i: a[i].copy() for i in a}
            reg.publish(snap2)
            fl.refresh_registry()
            for i, doc in enumerate(docs):
                fl.submit(doc, seed=100 + i)
            b = fl.run(timeout=300)
            s = fl.stats_summary()
        for i in range(n):
            np.testing.assert_array_equal(a[i], ref1[i], i)
            np.testing.assert_array_equal(a[i], a_before[i], i)
            np.testing.assert_array_equal(b[100 + i], ref2[100 + i], i)
        assert s["completed"] == 2 * n
        # at least one worker actually swapped engines
        assert s["snapshot_swaps"] >= 1


def test_fleet_concurrent_publish_never_corrupts_mixtures(trained):
    """A publish landing WHILE requests are queued/in flight: every
    mixture must still bitwise-match the single-engine result on one of
    the two published snapshots — docs in flight finish on the snapshot
    they started on, queued docs may bind to either side of the swap."""
    snap1, snap2, docs = trained
    reps = 6  # enough work that the publish lands mid-stream
    all_docs = [docs[i % len(docs)] for i in range(reps * len(docs))]
    seeds = list(range(len(all_docs)))
    ref1 = _single_engine(snap1, all_docs, seeds)
    ref2 = _single_engine(snap2, all_docs, seeds)
    with tempfile.TemporaryDirectory() as d:
        reg = SnapshotRegistry(d)
        reg.publish(snap1)
        with _fleet(reg, workers=2, watch_registry=True,
                    poll_registry_s=0.0) as fl:
            for i, doc in enumerate(all_docs):
                fl.submit(doc, seed=i)
                if i == len(all_docs) // 2:
                    reg.publish(snap2)  # no synchronous refresh: racy
            out = fl.run(timeout=300)
    on1 = on2 = 0
    for i in seeds:
        m1 = np.array_equal(out[i], ref1[i])
        m2 = np.array_equal(out[i], ref2[i])
        assert m1 or m2, i
        on1 += m1
        on2 += m2
    # the swap really happened mid-stream (both snapshots served)
    assert on1 >= 1 and on2 >= 1, (on1, on2)


# -- ensemble -----------------------------------------------------------------

def test_fleet_ensemble_is_mean_over_versions(trained):
    """ensemble=E: mixtures averaged over the E newest registry versions
    in ascending version order — deterministic given (version set, seed)
    and equal to averaging the per-version single-engine results."""
    snap1, snap2, docs = trained
    ref1 = _single_engine(snap1, docs, range(len(docs)))
    ref2 = _single_engine(snap2, docs, range(len(docs)))
    with tempfile.TemporaryDirectory() as d:
        reg = SnapshotRegistry(d)
        reg.publish(snap1)
        reg.publish(snap2)
        outs = []
        for workers in (1, 3):
            with _fleet(reg, workers=workers, ensemble=2) as fl:
                for i, doc in enumerate(docs):
                    fl.submit(doc, seed=i)
                outs.append(fl.run(timeout=300))
    for i in range(len(docs)):
        want = np.mean(np.stack([ref1[i], ref2[i]]), axis=0,
                       dtype=np.float32)
        np.testing.assert_array_equal(outs[0][i], want, i)
        np.testing.assert_array_equal(outs[1][i], want, i)
        np.testing.assert_allclose(want.sum(), 1.0, rtol=1e-5)


def test_fleet_ensemble_requires_registry_depth(trained):
    snap1, _, _ = trained
    with pytest.raises(ValueError, match="needs a SnapshotRegistry"):
        ServeFleet(snap1, workers=1, ensemble=2)
    with tempfile.TemporaryDirectory() as d:
        reg = SnapshotRegistry(d)
        reg.publish(snap1)
        with _fleet(reg, workers=1, ensemble=2) as fl:
            with pytest.raises(ValueError, match="ensemble needs 2"):
                fl.submit(np.arange(5, dtype=np.int32), seed=0)


# -- admission router ---------------------------------------------------------

def test_fleet_backpressure_and_stats(trained):
    """max_pending far below the workload: submit must block-and-release
    rather than error or drop, every request completes, and the stats
    roll up per worker."""
    snap1, _, docs = trained
    n = 4 * len(docs)
    ref = _single_engine(snap1, [docs[i % len(docs)] for i in range(n)],
                         range(n))
    with _fleet(snap1, workers=2, max_pending=3) as fl:
        for i in range(n):
            fl.submit(docs[i % len(docs)], seed=i)
        out = fl.run(timeout=300)
        s = fl.stats_summary()
    assert sorted(out) == list(range(n))
    for i in range(n):
        np.testing.assert_array_equal(out[i], ref[i], i)
    assert s["completed"] == n
    assert s["docs_per_s"] > 0
    assert s["p95_latency_ms"] >= s["p50_latency_ms"]
    assert sum(w["completed"] for w in s["per_worker"]) == n
    assert len(s["per_worker"]) == 2
    # engine counters add up over workers: each request's slot is swept
    # burnin times, and every step that retires reads the device once
    assert s["live_slot_sweeps"] == n * BURNIN
    assert s["live_slot_sweeps"] == sum(w["live_slot_sweeps"]
                                        for w in s["per_worker"])
    assert 0 < s["host_syncs"] <= s["steps"]


def test_fleet_ensemble_backpressure_bounded(trained):
    """Worker capacity is `slots` TOTAL across its engines: version-
    pinned ensemble subtasks must not be over-pulled past it into
    unbounded per-version engine queues (that would silently defeat
    max_pending). Exercises the shared-capacity accounting under a tiny
    router bound; results must still be exact."""
    snap1, snap2, docs = trained
    n = 3 * len(docs)
    all_docs = [docs[i % len(docs)] for i in range(n)]
    ref1 = _single_engine(snap1, all_docs, range(n))
    ref2 = _single_engine(snap2, all_docs, range(n))
    with tempfile.TemporaryDirectory() as d:
        reg = SnapshotRegistry(d)
        reg.publish(snap1)
        reg.publish(snap2)
        with _fleet(reg, workers=1, ensemble=2, max_pending=2) as fl:
            for i, doc in enumerate(all_docs):
                fl.submit(doc, seed=i)
                # the single worker holds at most `slots` subtasks; with
                # max_pending=2 queued, total admitted work stays bounded
                assert fl.router.queued() <= 2
                inflight = sum(e.in_flight()
                               for e in fl.workers[0].engines.values())
                assert inflight <= fl.slots + 2, inflight
            out = fl.run(timeout=300)
    for i in range(n):
        want = np.mean(np.stack([ref1[i], ref2[i]]), axis=0,
                       dtype=np.float32)
        np.testing.assert_array_equal(out[i], want, i)


def test_fleet_rejects_duplicate_inflight_seed(trained):
    snap1, _, docs = trained
    with _fleet(snap1, workers=1, max_pending=64) as fl:
        fl.submit(docs[0], seed=5)
        with pytest.raises(ValueError, match="already in flight"):
            fl.submit(docs[1], seed=5)
        out = fl.run(timeout=300)
        assert sorted(out) == [5]
        # drained rid is reusable, like the engine
        fl.submit(docs[1], seed=5)
        assert sorted(fl.run(timeout=300)) == [5]


# -- streaming publish hook ---------------------------------------------------

def test_streaming_run_publishes_to_registry(rng):
    from repro.core.sharded import ShardedHDP
    from repro.core.streaming import StreamingHDP
    from repro.data.stream import ShardedCorpusStore
    from repro.launch.mesh import make_host_mesh

    corpus, _ = planted_topics_corpus(rng, D=16, V=V, K_true=3)
    cfg = H.HDPConfig(K=K, V=V, bucket=K, z_impl="sparse", hist_cap=32)
    stream = StreamingHDP(ShardedHDP(make_host_mesh(), cfg),
                          ShardedCorpusStore.from_corpus(corpus, 8))
    st = stream.init_state(jax.random.key(0))
    with tempfile.TemporaryDirectory() as d:
        reg = SnapshotRegistry(d)
        st = stream.run(st, 4, registry=reg, publish_every_iters=2,
                        publish_keep=2)
        assert reg.versions() == [1, 2]
        newest = reg.load()
        assert int(newest.it) == int(st.it) == 4
        np.testing.assert_array_equal(np.asarray(newest.phi),
                                      np.asarray(st.phi))
        # the published artifact is immediately serveable
        with _fleet(reg, workers=1) as fl:
            fl.submit(corpus.tokens[0][corpus.mask[0]], seed=0)
            out = fl.run(timeout=300)
        np.testing.assert_allclose(out[0].sum(), 1.0, rtol=1e-5)
    with pytest.raises(ValueError, match="go together"):
        stream.run(st, 1, publish_every_iters=1)
    with pytest.raises(ValueError, match="go together"):
        stream.run(st, 1, registry=SnapshotRegistry(tempfile.mkdtemp()))
