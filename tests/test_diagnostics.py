"""obs/diagnostics.py: scalar-chain MCMC estimators (ESS, Geweke), the
joint log-likelihood reduction against a pure-python reference, the
topic lifecycle tracker, and the observatory's end-to-end contract on a
real streaming chain: gauges published when a sink is attached, chain
bitwise-identical when it is not (the gate check_health.py also
enforces in CI).
"""

import math

import numpy as np
import pytest

from repro.obs.diagnostics import (ConvergenceDiagnostics, ess, geweke,
                                   make_joint_loglik_fn, make_topic_fn)
from repro.obs.metrics import MetricsRegistry


# -- ESS ----------------------------------------------------------------------

def test_ess_white_noise_near_n():
    rng = np.random.default_rng(0)
    x = rng.normal(size=400)
    e = ess(x)
    assert 0 < e <= 400
    assert e > 200  # iid-ish chain: most samples effective


def test_ess_degenerate_chains():
    assert ess([1.0, 2.0, 3.0]) == 0.0      # too short
    assert ess(np.ones(100)) == 0.0          # zero variance
    assert ess([]) == 0.0


def test_ess_autocorrelated_chain_far_below_n():
    rng = np.random.default_rng(1)
    n = 400
    x = np.empty(n)
    x[0] = 0.0
    for i in range(1, n):  # AR(1), rho=0.95: tau ~ 39
        x[i] = 0.95 * x[i - 1] + rng.normal()
    e = ess(x)
    assert 0 < e < n / 4


# -- Geweke -------------------------------------------------------------------

def test_geweke_stationary_vs_trending():
    rng = np.random.default_rng(2)
    stationary = rng.normal(size=500)
    assert abs(geweke(stationary)) < 3.0
    trending = np.linspace(0, 50, 500) + rng.normal(size=500)
    assert abs(geweke(trending)) > 5.0


def test_geweke_degenerate_chains():
    assert geweke([1.0, 2.0]) == 0.0     # too short for both segments
    assert geweke(np.ones(100)) == 0.0   # zero variance


# -- joint log-likelihood reduction ------------------------------------------

def _ll_reference(n, dh, psi, alpha, beta):
    """Pure-python transcription of the documented expression."""
    K, V = n.shape
    out = 0.0
    for k in range(K):
        nk = int(n[k].sum())
        out += math.lgamma(V * beta) - math.lgamma(V * beta + nk)
        for v in range(V):
            out += math.lgamma(beta + int(n[k, v])) - math.lgamma(beta)
        a = max(alpha * float(psi[k]), 1e-30)
        for p in range(dh.shape[1]):
            if dh[k, p] > 0:
                out += dh[k, p] * (math.lgamma(a + p) - math.lgamma(a))
    return out


def test_joint_loglik_matches_reference():
    from repro.core import hdp as H

    cfg = H.HDPConfig(K=4, V=8, bucket=4, hist_cap=6)
    fn = make_joint_loglik_fn(cfg)
    rng = np.random.default_rng(3)
    n = rng.integers(0, 20, size=(4, 8)).astype(np.int32)
    n[3] = 0  # a dead topic must contribute exactly 0
    dh = rng.integers(0, 5, size=(4, 7)).astype(np.int32)
    dh[:, 0] = 0
    psi = rng.dirichlet(np.ones(4)).astype(np.float32)
    got = float(fn(n, dh, psi))
    want = _ll_reference(n, dh, psi, cfg.alpha, cfg.beta)
    assert got == pytest.approx(want, rel=1e-4)


def test_joint_loglik_finite_with_zero_psi():
    """psi -> 0 on a dead topic must not produce inf - inf = NaN."""
    from repro.core import hdp as H

    cfg = H.HDPConfig(K=2, V=4, bucket=2, hist_cap=4)
    fn = make_joint_loglik_fn(cfg)
    n = np.array([[3, 0, 1, 0], [0, 0, 0, 0]], np.int32)
    dh = np.zeros((2, 5), np.int32)
    dh[0, 2] = 1
    psi = np.array([1.0, 0.0], np.float32)
    assert np.isfinite(float(fn(n, dh, psi)))


def test_topic_fn_occupancy_entropy_topwords():
    fn = make_topic_fn(top_words=2)
    n = np.array([[5, 0, 0], [0, 0, 0], [3, 2, 0]], np.int32)
    live, entropy, max_frac, top = fn(n)
    assert list(np.asarray(live)) == [True, False, True]
    assert float(max_frac) == pytest.approx(0.5)
    assert float(entropy) == pytest.approx(math.log(2), rel=1e-5)
    assert list(np.asarray(top)[0]) == [0, 1]  # ties break by index
    assert list(np.asarray(top)[2]) == [0, 1]


# -- lifecycle + chains through ConvergenceDiagnostics ------------------------

def _mini_cfg():
    from repro.core import hdp as H

    return H.HDPConfig(K=4, V=8, bucket=4, hist_cap=6)


def test_diagnostics_births_deaths_and_drift():
    cfg = _mini_cfg()
    diag = ConvergenceDiagnostics(cfg, num_tokens=100, top_words=2,
                                  min_chain=3)
    reg = MetricsRegistry()
    dh = np.zeros((4, 7), np.int32)
    psi = np.full(4, 0.25, np.float32)
    n0 = np.zeros((4, 8), np.int32)
    n0[0, :2] = 5
    n0[1, 2:4] = 5
    diag.update(reg, n0, dh, psi)
    # first update: counters materialized at 0 (no previous iteration)
    assert reg.get("train.topic_births").value == 0
    assert reg.get("train.topic_deaths").value == 0

    n1 = np.zeros((4, 8), np.int32)
    n1[1, 2:4] = 5   # topic 1 survives with identical top words
    n1[2, 6:8] = 5   # topic 2 born; topic 0 died
    diag.update(reg, n1, dh, psi)
    assert reg.get("train.topic_births").value == 1
    assert reg.get("train.topic_deaths").value == 1
    assert reg.get("train.top_word_drift").value == 0.0  # topic 1 stable

    n2 = np.array(n1)
    n2[1, 2:4] = 0
    n2[1, 4:6] = 5   # topic 1's top words fully churn
    diag.update(reg, n2, dh, psi)
    assert reg.get("train.top_word_drift").value == pytest.approx(0.5)
    assert reg.get("train.k_star") is None  # k_star belongs to streaming
    # chains reached min_chain: MCMC gauges published and sane
    assert reg.get("train.ess_log_lik").value >= 0
    assert reg.get("train.geweke_log_lik").value is not None


def test_diagnostics_window_bounds_chain():
    cfg = _mini_cfg()
    diag = ConvergenceDiagnostics(cfg, num_tokens=10, min_chain=2,
                                  window=5)
    reg = MetricsRegistry()
    dh = np.zeros((4, 7), np.int32)
    psi = np.full(4, 0.25, np.float32)
    rng = np.random.default_rng(0)
    for _ in range(12):
        n = rng.integers(0, 4, size=(4, 8)).astype(np.int32)
        diag.update(reg, n, dh, psi)
    assert len(diag._ll_chain) == 5
    assert len(diag._kstar_chain) == 5


# -- the phase span (train.phase_ms) -------------------------------------------

def test_phase_span_accumulates_and_off_is_noop(tmp_path):
    from repro import obs
    from repro.obs.trace import _NULL_SPAN

    obs.reset_for_tests()
    try:
        # neither a sink nor the tracer: the tracer's shared no-op
        assert obs.phase("sweep", block=0) is _NULL_SPAN
        obs.enable_metrics(str(tmp_path / "m.jsonl"))
        for _ in range(2):
            with obs.phase("sweep", block=0):
                pass
        with obs.phase("tail"):
            pass
        M = obs.metrics()
        assert M.get("train.phase_ms", phase="sweep").value >= 0
        assert M.get("train.phase_ms", phase="tail") is not None
        # a sink alone feeds the counter and records no span
        assert not obs.tracer().enabled and obs.tracer().events() == []
        obs.enable_tracing()
        with obs.phase("stage_wait"):
            pass
        (x,) = [e for e in obs.tracer().events()
                if e["ph"] == "X" and e["cat"] != "gc"]
        assert (x["name"], x["cat"]) == ("stage_wait", "pipeline")
        assert M.get("train.phase_ms", phase="stage_wait") is not None
    finally:
        obs.reset_for_tests()


# -- end-to-end: the observatory on a real streaming chain --------------------

def _tiny_stream():
    import jax

    from repro.core import hdp as H
    from repro.core.sharded import ShardedHDP
    from repro.core.streaming import StreamingHDP
    from repro.data.stream import ShardedCorpusStore
    from repro.data.synthetic import planted_topics_corpus
    from repro.launch.mesh import make_host_mesh

    rng = np.random.default_rng(0)
    corpus, _ = planted_topics_corpus(rng, D=32, V=32, K_true=3,
                                      doc_len=(8, 16))
    mesh = make_host_mesh()
    n_dev = len(jax.devices())
    v_pad = ((corpus.V + mesh.shape["model"] - 1)
             // mesh.shape["model"]) * mesh.shape["model"]
    store = ShardedCorpusStore.from_corpus(corpus, 16, doc_multiple=n_dev)
    cfg = H.HDPConfig(K=8, V=v_pad, bucket=min(8, store.max_len),
                      z_impl="sparse", hist_cap=store.max_len)
    return StreamingHDP(ShardedHDP(mesh, cfg), store)


def _run(stream, iters, metrics_path):
    import jax

    from repro import obs

    if metrics_path:
        obs.enable_metrics(metrics_path)
    try:
        state = stream.init_state(jax.random.key(0))
        for _ in range(iters):
            state = stream.iteration(state)
    finally:
        if metrics_path:
            obs.disable_metrics()
    return state


def test_streaming_diagnostics_published_and_bitwise_inert(tmp_path):
    import jax

    from repro import obs

    obs.reset_for_tests()
    try:
        stream = _tiny_stream()
        state_on = _run(stream, 4, str(tmp_path / "m.jsonl"))
        M = obs.metrics()
        assert M.get("train.log_lik") is not None
        assert M.get("train.log_lik_per_token").value < 0
        assert M.get("train.topic_mass_entropy").value >= 0
        assert M.get("train.topic_births") is not None
        phase = M.get("train.phase_ms", phase="sweep")
        assert phase is not None and phase.value > 0

        obs.reset_for_tests()
        state_off = _run(_tiny_stream(), 4, None)
        # no sink -> no diagnostics compiled, nothing published
        assert obs.metrics().get("train.log_lik") is None
        assert obs.metrics().get("train.phase_ms", phase="sweep") is None
        # ... and the chain itself is bitwise untouched
        for name in ("n", "psi", "l"):
            np.testing.assert_array_equal(
                np.asarray(getattr(state_on, name)),
                np.asarray(getattr(state_off, name)))
        np.testing.assert_array_equal(
            np.asarray(jax.random.key_data(state_on.key)),
            np.asarray(jax.random.key_data(state_off.key)))
    finally:
        obs.reset_for_tests()
