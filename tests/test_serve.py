"""Serving subsystem: snapshot roundtrip, fold-in conformance (bitwise
across dense/sparse/pallas), continuous-batching slot invariance, and
held-out perplexity sanity."""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hdp as H
from repro.data.synthetic import planted_topics_corpus
from repro.serve import eval as EV
from repro.serve import foldin as F
from repro.serve import snapshot as SNAP
from repro.serve.engine import ServeEngine

K, V = 12, 48
BURNIN = 4


@pytest.fixture(scope="module")
def trained():
    """A tiny trained model + a held-out query batch (module-scoped:
    training runs once for the whole file)."""
    rng = np.random.default_rng(0)
    corpus, _ = planted_topics_corpus(rng, D=48, V=V, K_true=3,
                                      doc_len=(10, 20))
    cfg = H.HDPConfig(K=K, V=V, bucket=K, z_impl="sparse", hist_cap=32)
    tokens = jnp.asarray(corpus.tokens[:40])
    mask = jnp.asarray(corpus.mask[:40])
    state = H.init_state(jax.random.key(0), tokens, mask, cfg)
    step = jax.jit(lambda s: H.gibbs_iteration(s, tokens, mask, cfg))
    for _ in range(15):
        state = step(state)
    heldout = (corpus.tokens[40:], corpus.mask[40:])
    return state, cfg, heldout


@pytest.fixture(scope="module")
def snap(trained):
    state, cfg, _ = trained
    return SNAP.snapshot_from_state(state, cfg)


# -- snapshot -----------------------------------------------------------------

def test_snapshot_exact_tables_cover_support(snap, trained):
    state, cfg, _ = trained
    from repro.kernels.hdp_z import ops as zops

    assert snap.W >= int(zops.max_column_nnz(state.phi))
    assert snap.K == K and snap.V == V and not snap.compact
    # topic-ordered slots: ids ascending within each word's live slots
    ids = np.asarray(snap.ipack[:, 0, :])
    vals = np.asarray(snap.fpack[:, 0, :])
    live = vals > 0
    for v in range(V):
        lv = ids[v][live[v]]
        assert (np.diff(lv) > 0).all(), v


def test_snapshot_save_load_roundtrip(snap):
    with tempfile.TemporaryDirectory() as d:
        SNAP.save(d, snap)
        s2 = SNAP.load(d)
    for f in snap._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(snap, f)), np.asarray(getattr(s2, f)), f
        )


def test_compact_snapshot_halves_tables(trained):
    state, cfg, _ = trained
    full = SNAP.snapshot_from_state(state, cfg)
    compact = SNAP.snapshot_from_state(state, cfg, compact=True)
    assert compact.compact
    assert compact.nbytes() < 0.6 * full.nbytes()
    with tempfile.TemporaryDirectory() as d:
        SNAP.save(d, compact)
        s2 = SNAP.load(d)
    assert s2.fpack.dtype == jnp.bfloat16 and s2.ipack.dtype == jnp.int16
    np.testing.assert_array_equal(np.asarray(compact.fpack, np.float32),
                                  np.asarray(s2.fpack, np.float32))


def test_streaming_export_snapshot_hook(rng):
    from repro.core.sharded import ShardedHDP
    from repro.core.streaming import StreamingHDP
    from repro.data.stream import ShardedCorpusStore
    from repro.launch.mesh import make_host_mesh

    corpus, _ = planted_topics_corpus(rng, D=16, V=V, K_true=3)
    cfg = H.HDPConfig(K=K, V=V, bucket=K, z_impl="sparse", hist_cap=32)
    stream = StreamingHDP(ShardedHDP(make_host_mesh(), cfg),
                          ShardedCorpusStore.from_corpus(corpus, 8))
    st = stream.init_state(jax.random.key(0))
    st = stream.iteration(st)
    with tempfile.TemporaryDirectory() as d:
        exported = stream.export_snapshot(d, st)
        loaded = SNAP.load(d)
    assert int(loaded.it) == int(st.it) == 1
    np.testing.assert_array_equal(np.asarray(exported.phi),
                                  np.asarray(st.phi))


# -- fold-in ------------------------------------------------------------------

@pytest.mark.parametrize("compact", [False, True])
def test_foldin_impls_bitwise_equal(trained, compact):
    state, cfg, (q_tokens, q_mask) = trained
    s = SNAP.snapshot_from_state(state, cfg, compact=compact)
    seeds = jnp.arange(q_tokens.shape[0], dtype=jnp.int32)
    key = jax.random.key(7)
    out = {
        impl: F.foldin_docs(s, jnp.asarray(q_tokens), jnp.asarray(q_mask),
                            seeds, key, burnin=BURNIN, impl=impl,
                            return_z=True)
        for impl in ("dense", "sparse", "pallas")
    }
    for a, b in (("dense", "sparse"), ("sparse", "pallas")):
        np.testing.assert_array_equal(np.asarray(out[a][1]),
                                      np.asarray(out[b][1]), (a, b))
        np.testing.assert_array_equal(np.asarray(out[a][0]),
                                      np.asarray(out[b][0]), (a, b))
    # and burn-in actually moved assignments off the init
    theta = np.asarray(out["dense"][0])
    assert theta.shape == (q_tokens.shape[0], K)
    np.testing.assert_allclose(theta.sum(1), 1.0, rtol=1e-5)
    assert (theta >= 0).all()


def test_foldin_mixture_tracks_document_topic(snap, trained):
    """Documents folded in twice with different seeds give different z
    (it is sampling), but mixtures concentrate on few topics — the
    doc-sparsity the serving path exploits."""
    _, _, (q_tokens, q_mask) = trained
    key = jax.random.key(3)
    th = np.asarray(F.foldin_docs(
        snap, jnp.asarray(q_tokens), jnp.asarray(q_mask),
        jnp.arange(q_tokens.shape[0], dtype=jnp.int32), key,
        burnin=8, impl="sparse",
    ))
    # top-3 topics carry most of every doc's mass
    top3 = np.sort(th, axis=1)[:, -3:].sum(1)
    assert (top3 > 0.5).all(), top3


# -- engine -------------------------------------------------------------------

def _docs_from(tokens, mask):
    return [tokens[i][mask[i]] for i in range(tokens.shape[0])]


def test_engine_matches_direct_foldin_bitwise(snap, trained):
    _, _, (q_tokens, q_mask) = trained
    key = jax.random.key(11)
    docs = _docs_from(q_tokens, q_mask)
    eng = ServeEngine(snap, slots=3, burnin=BURNIN, impl="sparse",
                      buckets=(16, 32), base_key=key)
    rids = [eng.submit(doc, seed=i) for i, doc in enumerate(docs)]
    out = eng.run()
    assert sorted(out) == sorted(rids)
    for i, doc in enumerate(docs):
        bucket = 16 if len(doc) <= 16 else 32
        t = np.zeros((1, bucket), np.int32)
        m = np.zeros((1, bucket), bool)
        t[0, :len(doc)] = doc
        m[0, :len(doc)] = True
        direct = np.asarray(F.foldin_docs(
            snap, jnp.asarray(t), jnp.asarray(m),
            jnp.asarray([i], jnp.int32), key, burnin=BURNIN, impl="sparse",
        ))[0]
        np.testing.assert_array_equal(out[i], direct, i)


def test_engine_mixture_independent_of_batching(snap, trained):
    """Same documents through radically different packings — single slot
    (pure sequential) vs many slots, submission order reversed — must
    give bitwise-identical mixtures per document."""
    _, _, (q_tokens, q_mask) = trained
    key = jax.random.key(13)
    docs = _docs_from(q_tokens, q_mask)

    def run(slots, order):
        eng = ServeEngine(snap, slots=slots, burnin=BURNIN, impl="sparse",
                          buckets=(16, 32), base_key=key)
        for i in order:
            eng.submit(docs[i], seed=i)
        return eng.run()

    a = run(1, range(len(docs)))
    b = run(5, reversed(range(len(docs))))
    assert sorted(a) == sorted(b)
    for rid in a:
        np.testing.assert_array_equal(a[rid], b[rid], rid)


def test_engine_stats_and_continuous_admission(snap, trained):
    _, _, (q_tokens, q_mask) = trained
    docs = _docs_from(q_tokens, q_mask)
    eng = ServeEngine(snap, slots=2, burnin=BURNIN, impl="sparse",
                      buckets=(32,), base_key=jax.random.key(0))
    for i, doc in enumerate(docs):
        eng.submit(doc, seed=i)
    out = eng.run()
    s = eng.stats.summary()
    assert s["completed"] == len(docs) == len(out)
    # 2 slots x 8 docs: admissions must interleave with sweeps — more
    # than one "generation" of slot occupancy, fewer steps than serial
    assert s["steps"] >= BURNIN * (len(docs) // 2)
    assert s["steps"] < BURNIN * len(docs)
    assert s["docs_per_s"] > 0
    assert s["p50_latency_ms"] is not None
    assert s["p95_latency_ms"] >= s["p50_latency_ms"]
    assert s["compiled_shapes"] == [(2, 32)]


def test_engine_spans_and_counters(snap, trained):
    """Traced, the engine records one ``engine_step`` (with its bucket)
    per bucket sweep with ``engine.upload`` inside it, and
    ``engine.retire_wait`` inside ``engine.retire``; a drained run swept
    each request's slot exactly ``burnin`` times, one blocking read per
    retirement."""
    from collections import Counter

    from repro import obs

    _, _, (q_tokens, q_mask) = trained
    docs = _docs_from(q_tokens, q_mask)
    eng = ServeEngine(snap, slots=2, burnin=BURNIN, impl="sparse",
                      buckets=(16, 32), base_key=jax.random.key(0))
    obs.reset_for_tests()
    obs.enable_tracing()
    try:
        for i, doc in enumerate(docs):
            eng.submit(doc, seed=i)
        out = eng.run()
        evs = [e for e in obs.tracer().events() if e["ph"] == "X"]
    finally:
        obs.reset_for_tests()
    assert len(out) == len(docs)
    names = Counter(e["name"] for e in evs)
    steps = [e for e in evs if e["name"] == "engine_step"]
    assert len(steps) == eng.stats.steps
    assert Counter(e["args"]["bucket"] for e in steps) == {
        b: pool.steps for b, pool in eng._pools.items()}
    assert names["engine.submit"] == len(docs)
    assert names["engine.upload"] == len(steps)
    assert names["engine.retire"] == names["engine.retire_wait"] \
        == eng.stats.host_syncs > 0

    def inside(name, outer):
        eps = 1e-3  # the events' microseconds are rounded to ns
        for e in evs:
            if e["name"] != name:
                continue
            assert any(o["name"] == outer and o["tid"] == e["tid"]
                       and o["ts"] - eps <= e["ts"]
                       and e["ts"] + e["dur"] <= o["ts"] + o["dur"] + eps
                       for o in evs), (name, outer)

    inside("engine.upload", "engine_step")
    inside("engine.retire_wait", "engine.retire")
    assert eng.stats.live_slot_sweeps == eng.stats.completed * BURNIN
    assert eng.stats.completed == len(docs)
    s = eng.stats.summary()
    assert (s["host_syncs"], s["live_slot_sweeps"]) == (
        eng.stats.host_syncs, eng.stats.live_slot_sweeps)


@pytest.mark.parametrize("scope", ["init", "uniforms"])
def test_engine_step_named_scopes(snap, scope):
    """The engine step's named scopes are op metadata of its module."""
    from repro.serve.engine import _engine_step

    b, length = 2, 16
    low = jax.jit(_engine_step, static_argnames=("impl", "has_fresh")).lower(
        snap, np.zeros((b, length), np.int32), np.ones((b, length), bool),
        np.zeros((b, length), np.int32), np.arange(b, dtype=np.int32),
        np.zeros((b,), np.int32), jax.random.key(0), impl="sparse",
        has_fresh=True)
    assert f"/{scope}/" in low.as_text(debug_info=True)


def test_engine_rejects_duplicate_seed_and_drains_results(snap, trained):
    _, _, (q_tokens, q_mask) = trained
    docs = _docs_from(q_tokens, q_mask)
    eng = ServeEngine(snap, slots=2, burnin=2, impl="sparse",
                      buckets=(32,), base_key=jax.random.key(0))
    with pytest.raises(ValueError, match="burnin"):
        ServeEngine(snap, slots=1, burnin=0, base_key=jax.random.key(0))
    eng.submit(docs[0], seed=7)
    with pytest.raises(ValueError, match="already in flight"):
        eng.submit(docs[1], seed=7)
    out1 = eng.run()
    assert sorted(out1) == [7]
    # completed results are drained, not re-returned; the engine keeps
    # no per-request state between runs, so the seed is reusable
    rid2 = eng.submit(docs[1], seed=7)
    out2 = eng.run()
    assert sorted(out2) == [rid2] and len(eng._reqs) == 0


def test_snapshot_save_replaces_previous(trained):
    """Saving a snapshot with a LOWER source iteration must still win:
    a snapshot dir holds exactly the last artifact written, not the
    max-step survivor of checkpoint retention."""
    state, cfg, _ = trained
    hi = SNAP.build_snapshot(state.phi, state.psi, cfg.alpha, it=25)
    lo = SNAP.build_snapshot(state.phi * 0 + 1.0 / V, state.psi, cfg.alpha,
                             it=5)
    with tempfile.TemporaryDirectory() as d:
        SNAP.save(d, hi)
        SNAP.save(d, lo)
        got = SNAP.load(d)
    assert int(got.it) == 5
    np.testing.assert_array_equal(np.asarray(got.phi), np.asarray(lo.phi))


def test_engine_async_admit_bitwise_equal(snap, trained):
    """Admission packing on the bounded daemon stage (the fleet workers'
    configuration) is value-identical to inline packing: timing can
    never leak into a mixture."""
    _, _, (q_tokens, q_mask) = trained
    key = jax.random.key(17)
    docs = _docs_from(q_tokens, q_mask)

    def run(async_admit):
        eng = ServeEngine(snap, slots=3, burnin=BURNIN, impl="sparse",
                          buckets=(16, 32), base_key=key,
                          async_admit=async_admit)
        try:
            for i, doc in enumerate(docs):
                eng.submit(doc, seed=i)
            return eng.run()
        finally:
            eng.close()

    sync, packed = run(False), run(True)
    assert sorted(sync) == sorted(packed)
    for rid in sync:
        np.testing.assert_array_equal(sync[rid], packed[rid], rid)


# -- compact int16 precondition (K* < 32768) ---------------------------------

def test_compact_precondition_enforced_at_build():
    from repro.kernels.hdp_z import ops as zops

    k_bad = 2**15 + 1  # first K whose ids (0..K-1) overflow int16
    phi = jnp.full((k_bad, 4), 1.0 / 4, jnp.float32)
    psi = jnp.full((k_bad,), 1.0 / k_bad, jnp.float32)
    with pytest.raises(ValueError, match="32768"):
        SNAP.build_snapshot(phi, psi, 0.3, w=8, compact=True)
    with pytest.raises(ValueError, match="32768"):
        zops.build_word_sparse_tables(phi, psi, 0.3, 8, compact=True)
    # the boundary-legal case builds (K = 32768: max id 32767 fits int16)
    ok = zops.build_word_sparse_tables(phi[:-1], psi[:-1], 0.3, 8,
                                       compact=True)
    assert ok[2].dtype == jnp.int16


def test_compact_precondition_enforced_at_load(tmp_path):
    """A compact artifact that claims more topics than int16 can address
    must be refused at load, not only at build — snapshots can originate
    from other writers or older code."""
    k_bad = 2**15 + 1
    legal = SNAP.build_snapshot(
        jnp.full((16, 4), 0.25, jnp.float32),
        jnp.full((16,), 1 / 16, jnp.float32), 0.3, compact=True,
    )
    # forge the over-wide model side around the int16 tables
    forged = legal._replace(
        phi=jnp.zeros((k_bad, 4), jnp.bfloat16),
        psi=jnp.zeros((k_bad,), jnp.float32),
    )
    d = str(tmp_path / "forged")
    SNAP.save(d, forged)
    with pytest.raises(ValueError, match="32768"):
        SNAP.load(d)


def test_engine_truncates_overlong_docs(snap):
    eng = ServeEngine(snap, slots=1, burnin=2, impl="sparse",
                      buckets=(8,), base_key=jax.random.key(0))
    rid = eng.submit(np.zeros(50, np.int32) % V)
    out = eng.run()
    assert out[rid].shape == (K,)
    np.testing.assert_allclose(out[rid].sum(), 1.0, rtol=1e-5)


# -- held-out evaluation ------------------------------------------------------

def test_completion_split_partitions_live_tokens():
    mask = jnp.asarray(np.array([[1, 1, 0, 1, 1, 1, 0],
                                 [0, 1, 1, 1, 0, 0, 1]], bool))
    est, pred = EV.completion_split(mask)
    est, pred = np.asarray(est), np.asarray(pred)
    assert not (est & pred).any()
    np.testing.assert_array_equal(est | pred, np.asarray(mask))
    # parity over live positions only: first live token is estimation
    np.testing.assert_array_equal(
        est[0], np.array([1, 0, 0, 1, 0, 1, 0], bool))
    np.testing.assert_array_equal(
        est[1], np.array([0, 1, 0, 1, 0, 0, 0], bool))


def test_heldout_perplexity_trained_beats_untrained(trained, snap):
    state, cfg, (ho_tokens, ho_mask) = trained
    key = jax.random.key(5)
    p_trained = EV.heldout_perplexity(snap, ho_tokens, ho_mask, key,
                                      burnin=BURNIN)
    untrained = H.init_state(jax.random.key(99), jnp.asarray(ho_tokens),
                             jnp.asarray(ho_mask), cfg)
    snap0 = SNAP.snapshot_from_state(untrained, cfg)
    p_untrained = EV.heldout_perplexity(snap0, ho_tokens, ho_mask, key,
                                        burnin=BURNIN)
    # sane range: far better than uniform-over-V, better than untrained
    assert 1.0 < p_trained < V, p_trained
    assert p_trained < p_untrained, (p_trained, p_untrained)


@pytest.mark.parametrize("impl", ["dense", "sparse", "pallas"])
def test_restricted_snapshot_foldin_bitwise(snap, trained, impl):
    """Per-request-batch block-sparse tables: folding a query batch into
    a snapshot restricted to the batch's own vocabulary (tokens remapped)
    must reproduce the full-snapshot fold-in BITWISE — mixtures and final
    assignments — under every execution strategy. The sweep only ever
    row-gathers by token id, so the restriction is free of approximation;
    this is what lets a serving fleet stage O(batch vocab) instead of
    O(V) table bytes per request."""
    state, cfg, (q_tokens, q_mask) = trained
    seeds = jnp.arange(q_tokens.shape[0], dtype=jnp.int32)
    key = jax.random.key(13)
    theta_full, z_full = F.foldin_docs(
        snap, jnp.asarray(q_tokens), jnp.asarray(q_mask), seeds, key,
        burnin=BURNIN, impl=impl, return_z=True)
    sub, remapped = F.restrict_snapshot(snap, q_tokens, bucket=16)
    assert sub.V < snap.V and sub.V % 16 == 0
    assert sub.W == snap.W and sub.K == snap.K
    theta_sub, z_sub = F.foldin_docs(
        sub, remapped, jnp.asarray(q_mask), seeds, key,
        burnin=BURNIN, impl=impl, return_z=True)
    np.testing.assert_array_equal(np.asarray(theta_full),
                                  np.asarray(theta_sub))
    np.testing.assert_array_equal(np.asarray(z_full), np.asarray(z_sub))


def test_restricted_snapshot_bucket_bounds_shapes(snap, trained):
    """Different batches over the same snapshot land on a bounded set of
    restricted shapes (V rounded up to the bucket), so the fold-in jit
    cache cannot grow one program per distinct batch vocabulary."""
    state, cfg, (q_tokens, _) = trained
    sub_a, _ = F.restrict_snapshot(snap, q_tokens[:2], bucket=16)
    sub_b, _ = F.restrict_snapshot(snap, q_tokens[2:5], bucket=16)
    assert sub_a.V % 16 == 0 and sub_b.V % 16 == 0
    # empty batch degrades to the 1-row (bucket-padded) snapshot
    sub_e, rem_e = F.restrict_snapshot(
        snap, np.zeros((0, 4), np.int32), bucket=16)
    assert sub_e.V == 16 and rem_e.shape == (0, 4)
