"""Traffic generators of the benchmark: seeded, vectorized, and shaped
as the configurations state."""

import json

import numpy as np
import pytest

from benchmarks.hdp_bench import gen
from benchmarks.hdp_bench.bench import HERE

CORPUS = json.loads((HERE / "configs" / "hdp-pubmed.json").read_text())[
    "corpus"]


def _docs(seed, n=2000, v=5000):
    topics = gen.planted_topics(seed, CORPUS, v)
    return gen.draw_docs(gen.rng_for(seed, gen.TRAIN_DOCS), topics, n, CORPUS)


@pytest.mark.parametrize("seed", [0, 2**31 + 17, 98765432101])
def test_same_seed_same_corpus(seed):
    a, b = _docs(seed), _docs(seed)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    c = _docs(seed + 1)
    assert not np.array_equal(a.lengths, c.lengths)


def test_large_seeds_keep_distinct_chains():
    seeds = [2**33 + 5, 5, 2**40 + 5]
    assert len({gen.jax_seed(s) for s in seeds}) == 3
    assert all(0 <= gen.jax_seed(s) < 2**31 for s in seeds)


def test_mean_length_within_sampling_error():
    n = 20000
    lens = gen.lognormal_lengths(gen.rng_for(3, gen.TRAIN_DOCS), n,
                                 CORPUS["mean_doc_len"], CORPUS["len_sigma"])
    mean = CORPUS["mean_doc_len"]
    sd = mean * np.sqrt(np.exp(CORPUS["len_sigma"] ** 2) - 1)
    assert abs(lens.mean() - mean) < 4 * sd / np.sqrt(n) + 0.5
    assert lens.min() >= 1


def test_tokens_follow_planted_topics():
    d = _docs(4, n=500, v=300)
    assert d.words.size == d.lengths.sum()
    assert d.words.min() >= 0 and d.words.max() < 300
    assert d.topics.max() < CORPUS["planted_topics"]
    # a sparse mixture: most documents draw from few planted topics
    starts = d.starts
    per_doc = [np.unique(d.topics[s:s + n]).size
               for s, n in zip(starts, d.lengths)]
    assert np.median(per_doc) < CORPUS["planted_topics"] / 4


def test_pack_rows_splits_long_documents():
    docs = gen.Docs(words=np.arange(11, dtype=np.int32),
                    topics=np.arange(11, dtype=np.int32) % 3,
                    lengths=np.array([2, 9]))
    tokens, z, mask = gen.pack_rows(docs, 4)
    assert tokens.shape == (4, 4)
    np.testing.assert_array_equal(mask.sum(1), [2, 4, 4, 1])
    np.testing.assert_array_equal(tokens[mask], np.arange(11))
    np.testing.assert_array_equal(z[mask], np.arange(11) % 3)


def test_poisson_schedule_rate_and_order():
    t = gen.poisson_schedule(gen.rng_for(5, gen.ARRIVALS), 300.0, 20.0)
    assert np.all(np.diff(t) > 0) and t[-1] < 20.0
    assert abs(t.size - 6000) < 5 * np.sqrt(6000)
    t2 = gen.poisson_schedule(gen.rng_for(5, gen.ARRIVALS), 300.0, 20.0)
    np.testing.assert_array_equal(t, t2)


def test_open_loop_fixes_arrivals_and_lengths_across_seeds():
    a_t, a_n = gen.open_loop(11, 300.0, 5.0, CORPUS)
    b_t, b_n = gen.open_loop(2**35 + 3, 300.0, 5.0, CORPUS)
    np.testing.assert_array_equal(a_t, b_t)
    np.testing.assert_array_equal(np.sort(a_n), np.sort(b_n))
    assert not np.array_equal(a_n, b_n)
    c_t, c_n = gen.open_loop(11, 300.0, 5.0, CORPUS)
    np.testing.assert_array_equal(a_n, c_n)
