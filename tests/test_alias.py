"""Walker alias tables: construction correctness + sampling distribution."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.core.alias import (
    alias_build, alias_build_np, alias_build_onehot, alias_build_row_onehot,
    alias_build_scan, alias_sample, alias_sample_np,
)


def reconstruct_pmf(prob, alias):
    k = prob.shape[0]
    phat = prob / k
    np.add.at(phat, alias, (1 - prob) / k)
    return phat


@pytest.mark.parametrize("k", [2, 3, 5, 16, 100, 1000])
def test_build_reconstructs_pmf(k, rng):
    p = rng.gamma(0.3, size=k).astype(np.float32)
    p[rng.random(k) < 0.4] = 0.0
    if p.sum() == 0:
        p[0] = 1.0
    prob, alias = jax.tree.map(np.asarray, alias_build(jnp.asarray(p)))
    np.testing.assert_allclose(
        reconstruct_pmf(prob.astype(np.float64), alias), p / p.sum(),
        atol=2e-6,
    )


def test_batched_build(rng):
    p = rng.gamma(0.5, size=(7, 12)).astype(np.float32)
    prob, alias = alias_build(jnp.asarray(p))
    assert prob.shape == (7, 12) and alias.shape == (7, 12)
    for i in range(7):
        np.testing.assert_allclose(
            reconstruct_pmf(np.asarray(prob[i], np.float64), np.asarray(alias[i])),
            p[i] / p[i].sum(), atol=2e-6,
        )


def test_sampling_matches_target(rng):
    p = np.array([0.5, 0.1, 0.0, 0.3, 0.1], dtype=np.float32)
    prob, alias = alias_build(jnp.asarray(p))
    u = jnp.asarray(rng.random((100_000, 2)).astype(np.float32))
    idx = jax.vmap(lambda uu: alias_sample(prob, alias, uu[0], uu[1]))(u)
    freq = np.bincount(np.asarray(idx), minlength=5) / len(u)
    np.testing.assert_allclose(freq, p / p.sum(), atol=7e-3)
    assert freq[2] == 0.0  # zero-weight outcome never sampled


def test_matches_numpy_oracle_distribution(rng):
    p = rng.gamma(0.4, size=32).astype(np.float32)
    prob_j, alias_j = jax.tree.map(np.asarray, alias_build(jnp.asarray(p)))
    prob_n, alias_n = alias_build_np(p)
    # tables may differ (pair order); implied pmfs must agree
    np.testing.assert_allclose(
        reconstruct_pmf(prob_j.astype(np.float64), alias_j),
        reconstruct_pmf(prob_n.astype(np.float64), alias_n), atol=2e-6,
    )


def test_psum_build_matches_scan_reference(rng):
    """The production prefix-sum partition build against the retired
    sequential two-stack scan (kept as ``alias_build_scan``).

    Conformance rationale (recorded per the de-serialization change):
    the two constructions realize the same pairing in exact arithmetic,
    but the prefix-sum build derives residual probabilities from
    cumulative sums instead of chained subtraction, so tables are NOT
    bitwise-identical between them — low-order float bits (and, at exact
    fp ties, the occasional pairing) differ. Every conformance check in
    this repo is relative (shared tables across z-step impls, streaming
    vs monolithic, engine vs direct fold-in) and there are no stored
    golden tables, so the contract asserted here is the meaningful one:
    both builds reconstruct the identical target pmf to fp accuracy, on
    degenerate rows bitwise-identically.
    """
    for k in (2, 5, 16, 100):
        p = rng.gamma(0.3, size=(50, k)).astype(np.float32)
        p[rng.random((50, k)) < 0.4] = 0.0
        p[p.sum(1) == 0, 0] = 1.0
        prob_p, alias_p = jax.tree.map(np.asarray, alias_build(jnp.asarray(p)))
        prob_s, alias_s = jax.tree.map(
            np.asarray, alias_build_scan(jnp.asarray(p)))
        for i in range(p.shape[0]):
            np.testing.assert_allclose(
                reconstruct_pmf(prob_p[i].astype(np.float64), alias_p[i]),
                reconstruct_pmf(prob_s[i].astype(np.float64), alias_s[i]),
                atol=5e-7, err_msg=f"k={k} row={i}",
            )
    # degenerate rows (all-zero => uniform, single entry, one winner)
    for row in ([0.0, 0.0, 0.0], [3.0], [0.0, 0.0, 5.0], [2.0] * 8):
        p = jnp.asarray([row], jnp.float32)
        a = jax.tree.map(np.asarray, alias_build(p))
        b = jax.tree.map(np.asarray, alias_build_scan(p))
        np.testing.assert_array_equal(a[0], b[0], row)
        np.testing.assert_array_equal(a[1], b[1], row)


@pytest.mark.parametrize("k", [2, 3, 255, 256, 257])
def test_onehot_twin_bitwise_equals_flat_build(k, rng):
    """``alias_build_row_onehot`` — the Pallas-safe formulation the
    kernel-prologue alias build runs per token in VMEM — must be BITWISE
    equal to the production ``alias_build``, not just pmf-equivalent:
    the prologue path replaces tables the epilogue materialized, and the
    in-kernel/epilogue conformance tests compare sampled chains exactly.
    Swept across K straddling the 256 lane boundary and the degenerate
    partitions (all-small, all-large, exact ties) where pairing order is
    most fragile."""
    rows = [
        rng.gamma(0.3, size=k).astype(np.float32),        # generic
        np.full(k, 1.0 / (2 * k), np.float32),            # all small
        np.full(k, 2.0, np.float32),                      # all large (tied)
        np.full(k, 1.0 / k, np.float32),                  # exact mean tie
        np.zeros(k, np.float32),                          # padded word
    ]
    hot = np.zeros(k, np.float32)
    hot[k // 2] = 3.0
    rows.append(hot)                                      # single winner
    mixed = rng.gamma(0.3, size=k).astype(np.float32)
    mixed[rng.random(k) < 0.5] = 0.0
    rows.append(mixed)                                    # sparse support
    p = jnp.asarray(np.stack(rows))
    prob_f, alias_f = jax.tree.map(np.asarray, alias_build(p))
    prob_o, alias_o = jax.tree.map(
        np.asarray, jax.jit(jax.vmap(alias_build_row_onehot))(p))
    np.testing.assert_array_equal(prob_f, prob_o)
    np.testing.assert_array_equal(alias_f, alias_o)


@pytest.mark.parametrize("shape", [(5, 16), (2, 3, 128), (4097, 8)])
def test_batched_onehot_build_equals_row_build(shape, rng):
    """``alias_build_onehot`` (the word tables' build, rows in steps of
    2,048) is ``alias_build_row_onehot`` row by row, bit for bit, with
    leading dimensions kept and a last step that is part padding."""
    p = jnp.asarray(rng.gamma(0.3, size=shape).astype(np.float32))
    prob, alias = jax.tree.map(np.asarray, alias_build_onehot(p))
    assert prob.shape == alias.shape == shape
    rows = jax.tree.map(np.asarray, jax.jit(jax.vmap(
        alias_build_row_onehot))(p.reshape(-1, shape[-1])))
    np.testing.assert_array_equal(prob.reshape(-1, shape[-1]), rows[0])
    np.testing.assert_array_equal(alias.reshape(-1, shape[-1]), rows[1])


def test_onehot_twin_parts_from_flat_build_only_by_rounding():
    """Over many random 128-wide rows the two builds agree bitwise on
    every alias and on every keep-probability but one: in a rare row
    whose total deficit exceeds its total surplus by rounding, the flat
    build's binary search, on a ``prefix_sum`` line that dips by an ulp,
    keeps the last large at 1 while the one-hot count demotes it to a
    few ulps of the row total below 1. Both are tables of the same pmf
    to float32 accuracy; the sampled chains part only on a draw that
    lands in that sliver."""
    rng = np.random.default_rng(1)
    p = jnp.asarray(
        rng.lognormal(0.0, 0.5, size=(20000, 128)).astype(np.float32))
    prob_f, alias_f = jax.tree.map(np.asarray, alias_build(p))
    prob_o, alias_o = jax.tree.map(
        np.asarray, jax.jit(jax.vmap(alias_build_row_onehot))(p))
    np.testing.assert_array_equal(alias_f, alias_o)
    diff = prob_f != prob_o
    assert diff.any(axis=1).sum() < 0.01 * p.shape[0]
    assert (diff.sum(axis=1) <= 1).all()
    assert (prob_f[diff] == 1.0).all()
    assert (1.0 - prob_o[diff] < 1e-5).all()


def test_build_is_deterministic(rng):
    p = jnp.asarray(rng.gamma(0.4, size=(9, 33)).astype(np.float32))
    a1, b1 = jax.tree.map(np.asarray, alias_build(p))
    a2, b2 = jax.tree.map(np.asarray, alias_build(p))
    np.testing.assert_array_equal(a1, a2)
    np.testing.assert_array_equal(b1, b2)


@settings(max_examples=30, deadline=None)
@given(st.lists(st.floats(0.0, 100.0), min_size=2, max_size=64),
       st.integers(0, 2**31 - 1))
@example(weights=[0.0, 1.1754943508222875e-38], seed=0)  # total < 1e-30
def test_property_pmf_reconstruction(weights, seed):
    p = np.asarray(weights, dtype=np.float32)
    if p.sum() <= 0:
        p[0] = 1.0
    prob, alias = jax.tree.map(np.asarray, alias_build(jnp.asarray(p)))
    assert (prob >= 0).all() and (prob <= 1 + 1e-6).all()
    np.testing.assert_allclose(
        reconstruct_pmf(prob.astype(np.float64), alias), p / p.sum(),
        atol=5e-6,
    )
