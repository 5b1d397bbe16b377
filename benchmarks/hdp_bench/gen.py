"""Seeded inputs for the HDP benchmark: planted-topic corpora and
open-loop arrival schedules.

Everything is drawn on the host with vectorized numpy from one integer
seed, so the same seed gives the same corpus, queries and schedule, and
a PubMed-shaped corpus of a few million tokens takes about a second.
The generator follows the planted LDA/HDP process of
``repro.data.synthetic.planted_topics_corpus`` (which loops per token
and cannot reach these sizes) with three changes of distribution:
log-normal document lengths, Zipf-weighted sparse topics and sparse
document mixtures.

  lengths   n_d ~ round(LogNormal(mu, sigma)), at least 1, with
            mu = ln(mean) - sigma^2 / 2 so that E[n_d] = mean
  topics    phi_t ~ Dirichlet(c_topic * V * zipf), t < T
  mixtures  theta_d ~ Dirichlet(c_doc * 1_T)
  tokens    k ~ theta_d, then v ~ phi_k
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

# independent numpy streams drawn from one run seed
TOPICS, TRAIN_DOCS, QUERY_DOCS, ARRIVALS, ORDER, CHECK, LENGTHS = range(7)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def jax_seed(seed: int) -> int:
    """A 31-bit seed for ``jax.random.key`` from any run seed.
    ``jax.random.key`` drops the high bits of a seed past 32 bits, so
    seeds that differ only there would share a chain."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               & 0x7FFFFFFF)


def zipf_weights(v: int, exponent: float, offset: float) -> np.ndarray:
    """Zipf-Mandelbrot unigram weights 1 / (rank + offset)^exponent."""
    w = 1.0 / (np.arange(1, v + 1, dtype=np.float64) + offset) ** exponent
    return w / w.sum()


def lognormal_lengths(rng, n: int, mean: float, sigma: float) -> np.ndarray:
    mu = np.log(mean) - 0.5 * sigma * sigma
    return np.maximum(np.rint(rng.lognormal(mu, sigma, n)), 1).astype(
        np.int64)


def _dirichlet_rows(rng, conc: np.ndarray, rows: int) -> np.ndarray:
    g = rng.standard_gamma(np.broadcast_to(conc, (rows, conc.size)))
    s = g.sum(1, keepdims=True)
    # a row whose every gamma draw underflowed falls back to its mean
    g = np.where(s > 0, g, conc[None, :])
    return g / g.sum(1, keepdims=True)


def _draw_rows(rng, cdf: np.ndarray, which: np.ndarray) -> np.ndarray:
    """One categorical draw per entry of ``which`` from row ``which[i]``
    of the row-wise CDF table ``cdf`` (rows end at exactly 1): a single
    searchsorted over the rows laid end to end, row r offset by r."""
    n_rows, width = cdf.shape
    flat = (cdf + np.arange(n_rows, dtype=np.float64)[:, None]).ravel()
    u = rng.random(which.size)
    pos = np.searchsorted(flat, which + u, side="right")
    return np.minimum(pos - which * width, width - 1)


def planted_topics(seed: int, corpus: dict, V: int) -> np.ndarray:
    """(T, V) float64 topic-word distributions of the planted model."""
    zipf = zipf_weights(V, corpus["zipf_exponent"], corpus["zipf_offset"])
    return _dirichlet_rows(rng_for(seed, TOPICS),
                           corpus["topic_concentration"] * V * zipf,
                           corpus["planted_topics"])


class Docs(NamedTuple):
    """Documents laid end to end: ``words[starts[d]:starts[d] + lengths[d]]``
    is document d, ``topics`` holds each token's planted topic."""
    words: np.ndarray    # (N,) int32
    topics: np.ndarray   # (N,) int32
    lengths: np.ndarray  # (D,) int64

    @property
    def starts(self) -> np.ndarray:
        return np.concatenate([[0], np.cumsum(self.lengths)[:-1]])

    def doc(self, d: int) -> np.ndarray:
        s = int(self.starts[d])
        return self.words[s:s + int(self.lengths[d])]


def draw_docs(rng, topics: np.ndarray, n_docs: int, corpus: dict,
              lengths=None) -> Docs:
    """``n_docs`` documents of the planted model, of the given lengths or
    of log-normal ones."""
    t = topics.shape[0]
    if lengths is None:
        lengths = lognormal_lengths(rng, n_docs, corpus["mean_doc_len"],
                                    corpus["len_sigma"])
    theta = _dirichlet_rows(rng, np.full(t, corpus["doc_concentration"]),
                            n_docs)
    cth = np.cumsum(theta, 1)
    cth[:, -1] = 1.0
    doc_of = np.repeat(np.arange(n_docs), lengths)
    k = _draw_rows(rng, cth, doc_of)
    ctop = np.cumsum(topics, 1)
    ctop[:, -1] = 1.0
    w = _draw_rows(rng, ctop, k)
    return Docs(words=w.astype(np.int32), topics=k.astype(np.int32),
                lengths=lengths)


def pack_rows(docs: Docs, max_len: int):
    """(rows, max_len) tokens, planted z and mask. A document longer than
    ``max_len`` continues on the next rows, as
    ``repro.data.corpus.pack_documents`` splits it."""
    rows_per = -(-docs.lengths // max_len)
    row0 = np.concatenate([[0], np.cumsum(rows_per)[:-1]])
    n_rows = int(rows_per.sum())
    doc_of = np.repeat(np.arange(docs.lengths.size), docs.lengths)
    pos = np.arange(docs.words.size) - docs.starts[doc_of]
    r = row0[doc_of] + pos // max_len
    c = pos % max_len
    tokens = np.zeros((n_rows, max_len), np.int32)
    z = np.zeros((n_rows, max_len), np.int32)
    mask = np.zeros((n_rows, max_len), bool)
    tokens[r, c] = docs.words
    z[r, c] = docs.topics
    mask[r, c] = True
    return tokens, z, mask


def poisson_schedule(rng, rate: float, seconds: float) -> np.ndarray:
    """Open-loop send times in [0, seconds) of a Poisson process."""
    n = int(rate * seconds + 10 * np.sqrt(rate * seconds + 1) + 16)
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    while t[-1] < seconds:  # never in practice: 10 sigma of headroom
        t = np.concatenate([t, t[-1] + np.cumsum(
            rng.exponential(1.0 / rate, n))])
    return t[t < seconds]


def open_loop(seed: int, rate: float, seconds: float, corpus: dict):
    """(send times, query lengths) of an open-loop Poisson mix.

    Every seed gets the same send times and the same multiset of
    lengths (both drawn from seed 0), the lengths dealt to the send
    times in a seed's own order: the work and the arrivals of a run are
    then fixed, and a seed changes which document comes when."""
    sched = poisson_schedule(rng_for(0, ARRIVALS), rate, seconds)
    lengths = lognormal_lengths(rng_for(0, LENGTHS), sched.size,
                                corpus["mean_doc_len"], corpus["len_sigma"])
    return sched, lengths[rng_for(seed, ORDER).permutation(sched.size)]
