"""hdp_z Pallas kernel: bitwise oracle equality (z and the emitted
per-doc histogram m) + exact conditionals + doc-axis padding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.polya_urn import ppu_sample
from repro.kernels.hdp_z import ops as zops


def make_problem(rng, k, v, d, l, rate=0.8):
    n = rng.poisson(rate, size=(k, v)).astype(np.int32)
    phi, _ = ppu_sample(jax.random.key(1), jnp.asarray(n), 0.01)
    psi = jnp.asarray(rng.dirichlet(np.ones(k)).astype(np.float32))
    tokens = jnp.asarray(rng.integers(0, v, (d, l)).astype(np.int32))
    mask = jnp.asarray(rng.random((d, l)) > 0.2)
    z0 = jnp.asarray(rng.integers(0, k, (d, l)).astype(np.int32))
    u = jax.random.uniform(jax.random.key(2), (d, l, 3))
    return n, phi, psi, tokens, mask, z0, u


@pytest.mark.parametrize("k,v,d,l,w", [
    (8, 24, 4, 16, 8),
    (24, 60, 16, 32, 16),
    (50, 100, 8, 64, 32),
    (16, 40, 12, 24, 16),  # w == k allowed too
])
def test_kernel_bitwise_equals_oracle(rng, k, v, d, l, w):
    n, phi, psi, tokens, mask, z0, u = make_problem(rng, k, v, d, l)
    assert int(zops.max_column_nnz(phi)) <= w
    z_k, m_k = zops.z_step_pallas(tokens, mask, z0, phi, psi, 0.3, u, w)
    z_r, m_r = zops.z_step_ref(tokens, mask, z0, phi, psi, 0.3, u, w)
    np.testing.assert_array_equal(np.asarray(z_k), np.asarray(z_r))
    np.testing.assert_array_equal(np.asarray(m_k), np.asarray(m_r))
    # the emitted histogram IS the histogram of the sampled z
    from repro.core import hdp as H
    np.testing.assert_array_equal(
        np.asarray(m_k), np.asarray(H.doc_topic_counts(z_k, mask, k))
    )


def test_kernel_respects_mask(rng):
    n, phi, psi, tokens, mask, z0, u = make_problem(rng, 8, 24, 4, 16)
    z_k, _ = zops.z_step_pallas(tokens, mask, z0, phi, psi, 0.3, u, 8)
    pad = ~np.asarray(mask)
    np.testing.assert_array_equal(np.asarray(z_k)[pad], np.asarray(z0)[pad])


def test_kernel_single_site_conditional(rng):
    """Empirical distribution of a single resampled site must match the
    exact full conditional phi[k,v] * alpha * psi_k (1-token doc)."""
    k, v = 12, 30
    n = rng.poisson(2.0, size=(k, v)).astype(np.int32)
    phi, _ = ppu_sample(jax.random.key(3), jnp.asarray(n), 0.01)
    psi = jnp.asarray(rng.dirichlet(np.ones(k)).astype(np.float32))
    tokens = jnp.asarray([[3]], jnp.int32)
    mask = jnp.ones((1, 1), bool)
    z0 = jnp.zeros((1, 1), jnp.int32)
    m = 20000
    u = jax.random.uniform(jax.random.key(4), (m, 1, 1, 3))
    zz = jax.vmap(
        lambda uu: zops.z_step_pallas(tokens, mask, z0, phi, psi, 0.5, uu,
                                      12)[0]
    )(u)
    w = np.asarray(phi[:, 3]) * 0.5 * np.asarray(psi)
    target = w / w.sum()
    freq = np.bincount(np.asarray(zz).ravel(), minlength=k) / m
    np.testing.assert_allclose(freq, target, atol=0.012)


def test_kernel_matches_dense_sweep_distribution(rng):
    """Full-sweep distribution agreement between the kernel and the dense
    O(K) oracle (different uniform->sample maps, same law)."""
    from repro.core.hdp import z_step_dense

    k, v, d, l = 10, 25, 1, 8
    n = rng.poisson(1.5, size=(k, v)).astype(np.int32)
    phi, _ = ppu_sample(jax.random.key(5), jnp.asarray(n), 0.01)
    psi = jnp.asarray(rng.dirichlet(np.ones(k)).astype(np.float32))
    tokens = jnp.asarray(rng.integers(0, v, (d, l)).astype(np.int32))
    mask = jnp.ones((d, l), bool)
    z0 = jnp.asarray(rng.integers(0, k, (d, l)).astype(np.int32))
    m = 12000
    u = jax.random.uniform(jax.random.key(6), (m, d, l, 3))
    z_kern = jax.vmap(
        lambda uu: zops.z_step_pallas(tokens, mask, z0, phi, psi, 0.4, uu,
                                      k)[0]
    )(u)
    z_dense = jax.vmap(
        lambda uu: z_step_dense(tokens, mask, z0, phi, psi, 0.4, uu)[0]
    )(u)
    for pos in range(l):
        fk = np.bincount(np.asarray(z_kern)[:, 0, pos], minlength=k) / m
        fd = np.bincount(np.asarray(z_dense)[:, 0, pos], minlength=k) / m
        np.testing.assert_allclose(fk, fd, atol=0.025)


@pytest.mark.parametrize("order,compact", [
    ("value", False), ("topic", False), ("value", True), ("topic", True),
])
def test_zstep_table_options_plumb_through_both_impls(rng, order, compact):
    """order=/compact= must reach the table builder through BOTH public
    z-step wrappers (regression: the kwargs used to be silently dropped
    at the z_step_pallas boundary), and the fused delta_n must stay
    bitwise-consistent with a recount under every table variant."""
    from repro.core import hdp as H

    k, v = 16, 40
    n, phi, psi, tokens, mask, z0, u = make_problem(rng, k, v, 6, 24)
    z_k, m_k, dn_k = zops.z_step_pallas(
        tokens, mask, z0, phi, psi, 0.3, u, k,
        order=order, compact=compact, emit_delta=True)
    z_r, m_r, dn_r = zops.z_step_ref(
        tokens, mask, z0, phi, psi, 0.3, u, k,
        order=order, compact=compact, emit_delta=True)
    np.testing.assert_array_equal(np.asarray(z_k), np.asarray(z_r))
    np.testing.assert_array_equal(np.asarray(m_k), np.asarray(m_r))
    np.testing.assert_array_equal(np.asarray(dn_k), np.asarray(dn_r))
    np.testing.assert_array_equal(
        np.asarray(dn_k),
        np.asarray(H.delta_n(z0, z_k, tokens, mask, k, v)))


def test_zstep_order_kwarg_actually_changes_samples(rng):
    """Sanity that the plumbing is live: topic-ordered tables relayout
    the alias structure, so the same uniforms land on (some) different
    topics than value-ordered tables — same law, different map. If the
    kwarg were dropped, both calls would be bitwise-identical."""
    n, phi, psi, tokens, mask, z0, u = make_problem(rng, 24, 60, 16, 32)
    z_val = zops.z_step_pallas(tokens, mask, z0, phi, psi, 0.3, u, 16)[0]
    z_top = zops.z_step_pallas(tokens, mask, z0, phi, psi, 0.3, u, 16,
                               order="topic")[0]
    assert (np.asarray(z_val) != np.asarray(z_top)).any()


# -- kernel-prologue alias build ----------------------------------------------

@pytest.mark.parametrize("k,v,d,l,w", [
    (8, 24, 4, 16, 8),
    (24, 60, 16, 32, 16),
])
def test_prologue_kernel_bitwise_equals_prologue_oracle(rng, k, v, d, l, w):
    """``alias_in_kernel="on"``: the kernel that builds wa / q_a / the
    alias row per token in VMEM must stay bitwise-equal to the pure-jnp
    prologue oracle, with and without the fused delta_n."""
    n, phi, psi, tokens, mask, z0, u = make_problem(rng, k, v, d, l)
    for emit in (False, True):
        out_k = zops.z_step_pallas(tokens, mask, z0, phi, psi, 0.3, u, w,
                                   alias_in_kernel="on", emit_delta=emit)
        out_r = zops.z_step_ref(tokens, mask, z0, phi, psi, 0.3, u, w,
                                alias_in_kernel="on", emit_delta=emit)
        for a, b in zip(out_k, out_r):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_prologue_bitwise_equals_epilogue_tables(rng):
    """The prologue builds each token's alias row from raw supports with
    ``alias_build_row_onehot`` — bitwise the flat build the epilogue
    tables come from — so the two execution paths must sample the SAME
    chain, not just the same law. This is the exact-arithmetic
    equivalence the ``alias_in_kernel`` switch rests on."""
    n, phi, psi, tokens, mask, z0, u = make_problem(rng, 24, 60, 8, 32)
    z_on, m_on, dn_on = zops.z_step_pallas(
        tokens, mask, z0, phi, psi, 0.3, u, 16,
        alias_in_kernel="on", emit_delta=True)
    z_off, m_off, dn_off = zops.z_step_pallas(
        tokens, mask, z0, phi, psi, 0.3, u, 16,
        alias_in_kernel="off", emit_delta=True)
    np.testing.assert_array_equal(np.asarray(z_on), np.asarray(z_off))
    np.testing.assert_array_equal(np.asarray(m_on), np.asarray(m_off))
    np.testing.assert_array_equal(np.asarray(dn_on), np.asarray(dn_off))
    # and with topic-ordered tables (the conformance layout)
    z_t_on = zops.z_step_pallas(tokens, mask, z0, phi, psi, 0.3, u, 16,
                                order="topic", alias_in_kernel="on")[0]
    z_t_off = zops.z_step_pallas(tokens, mask, z0, phi, psi, 0.3, u, 16,
                                 order="topic", alias_in_kernel="off")[0]
    np.testing.assert_array_equal(np.asarray(z_t_on), np.asarray(z_t_off))


def test_alias_in_kernel_resolver():
    """Precedence and the compact guard of ``resolve_alias_in_kernel``."""
    r = zops.resolve_alias_in_kernel
    assert r("on", interpret=True) is True
    assert r("off", interpret=False) is False
    assert r(True, interpret=True) is True
    assert r(False, interpret=False) is False
    # auto: off on every backend (per-word tables are the faster path
    # on the chip), never on with compact tables
    assert r("auto", interpret=False) is False
    assert r("auto", interpret=True) is False
    assert r("auto", interpret=False, compact=True) is False
    # explicit on + compact is a contradiction, not a silent downgrade
    with pytest.raises(ValueError, match="compact"):
        r("on", interpret=False, compact=True)
    with pytest.raises(ValueError, match="compact"):
        r(True, interpret=False, compact=True)
    with pytest.raises(ValueError, match="alias_in_kernel"):
        r("sometimes", interpret=True)


def test_alias_in_kernel_env_var(monkeypatch):
    monkeypatch.setenv("REPRO_ALIAS_IN_KERNEL", "1")
    assert zops.resolve_alias_in_kernel("auto", interpret=True) is True
    # env force silently degrades with compact (no raise: ambient config)
    assert zops.resolve_alias_in_kernel(
        "auto", interpret=True, compact=True) is False
    monkeypatch.setenv("REPRO_ALIAS_IN_KERNEL", "0")
    assert zops.resolve_alias_in_kernel("auto", interpret=False) is False


# -- block-sparse (vocab-masked) tables ---------------------------------------

def test_masked_tables_bitwise_equal_dense_on_flagged_rows(rng):
    """``build_word_sparse_tables_masked`` must reproduce the dense build
    bitwise on every flagged vocab row (table ops are row-independent),
    and a sweep whose tokens stay inside the mask must not be able to
    tell the builders apart."""
    k, v = 16, 40
    n, phi, psi, tokens, mask, z0, u = make_problem(rng, k, v, 6, 24)
    u_mask = np.zeros((v,), bool)
    u_mask[np.unique(np.asarray(tokens))] = True
    q_d, f_d, i_d = zops.build_word_sparse_tables(phi, psi, 0.3, k)
    q_m, f_m, i_m = zops.build_word_sparse_tables_masked(
        phi, psi, 0.3, k, jnp.asarray(u_mask), int(u_mask.sum()))
    rows = np.flatnonzero(u_mask)
    np.testing.assert_array_equal(np.asarray(q_m)[rows], np.asarray(q_d)[rows])
    np.testing.assert_array_equal(np.asarray(f_m)[rows], np.asarray(f_d)[rows])
    np.testing.assert_array_equal(np.asarray(i_m)[rows], np.asarray(i_d)[rows])
    from repro.kernels.hdp_z.ref import hdp_z_ref
    out_d = hdp_z_ref(tokens, mask, z0, u, q_d, f_d, i_d, kk=k)
    out_m = hdp_z_ref(tokens, mask, z0, u, q_m, f_m, i_m, kk=k)
    for a, b in zip(out_d, out_m):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("d", [3, 5, 7, 11, 13])
def test_kernel_doc_padding_matches_oracle(rng, d):
    """Document counts prime/coprime with doc_block must not degrade the
    grid to db=1: the padded kernel stays bitwise-equal to the oracle at
    the default doc_block for any D."""
    n, phi, psi, tokens, mask, z0, u = make_problem(rng, 8, 24, d, 16)
    z_k, m_k = zops.z_step_pallas(tokens, mask, z0, phi, psi, 0.3, u, 8)
    z_r, m_r = zops.z_step_ref(tokens, mask, z0, phi, psi, 0.3, u, 8)
    assert z_k.shape == (d, 16)
    np.testing.assert_array_equal(np.asarray(z_k), np.asarray(z_r))
    np.testing.assert_array_equal(np.asarray(m_k), np.asarray(m_r))
