"""Seeded synthetic corpora with the shape of a bag-of-words collection.

Each topic is a Zipf distribution (rank ``r`` has weight ``(r + 1) **
-zipf_s``) laid over its own permutation of the vocabulary; documents
draw a topic mixture from ``Dirichlet(alpha)``, a topic per token from
it, and a word per token from that topic.  Document lengths are
log-normal with the source's mean tokens per document.

Every seed gets the same multiset of document lengths (the log-normal's
quantiles at ``(i + 1/2) / n``), dealt out in a seed-made order, so the
work a run does is the same from seed to seed and only its order and
content change.  A training corpus (``batched_lengths``) goes further:
every minibatch of its fixed schedule holds the same number of tokens,
so a run's steps all take one padded shape whatever the seed.
Everything is vectorised numpy: there is no loop over documents or
tokens.
"""

from __future__ import annotations

import math

import numpy as np
from scipy.special import ndtri


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of one run's seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def lognormal_lengths(n: int, mean: float, sigma: float, rng,
                      cap: int | None = None) -> np.ndarray:
    """``n`` document lengths (int64, at least 1): the log-normal's
    quantiles with mean ``mean`` and log-scale ``sigma``, capped at
    ``cap``, in an order drawn from ``rng``."""
    q = (np.arange(n) + 0.5) / n
    mu = math.log(mean) - sigma * sigma / 2
    lengths = np.maximum(np.rint(np.exp(mu + sigma * ndtri(q))), 1)
    if cap is not None:
        lengths = np.minimum(lengths, cap)
    return rng.permutation(lengths.astype(np.int64))


def batched_lengths(n_docs: int, mean: float, sigma: float, train, hold,
                    batch: int, rng) -> np.ndarray:
    """Lengths of ``n_docs`` documents whose held-out documents are
    ``hold`` and whose training documents ``train`` (sorted) are cut in
    consecutive batches of ``batch``, the same in every epoch.

    The log-normal's quantiles are dealt out by rank: every
    ``n_docs / len(hold)``-th to the held-out set, the rest in snake
    order to the batches; then each batch's longest documents gain a token or a
    few until every batch holds as many tokens as the fullest.  So the
    multiset of each batch and of the held-out set, and every total, is
    the same for every seed; ``rng`` only orders the lengths within
    them."""
    n_hold, n_train = len(hold), len(train)
    if n_train % batch:
        raise ValueError(f"{n_train} training documents do not make whole "
                         f"batches of {batch}")
    q = (np.arange(n_docs) + 0.5) / n_docs
    mu = math.log(mean) - sigma * sigma / 2
    ranked = np.maximum(np.rint(np.exp(mu + sigma * ndtri(q))), 1)
    ranked = ranked.astype(np.int64)
    pick = np.zeros(n_docs, bool)
    pick[((np.arange(n_hold) + 0.5) * n_docs / n_hold).astype(np.int64)] = 1
    n_batches = n_train // batch
    # snake order: rank r goes to batch r mod n, the direction turning
    # every round; rows of ``dealt`` are batches, ascending
    rounds = ranked[~pick].reshape(batch, n_batches)
    rounds[1::2] = rounds[1::2, ::-1]
    dealt = rounds.T.copy()
    short = dealt.sum(axis=1).max() - dealt.sum(axis=1)
    dealt += short[:, None] // batch
    rest = short % batch
    dealt += np.arange(batch)[None, :] >= batch - rest[:, None]
    out = np.empty(n_docs, np.int64)
    out[np.asarray(hold)] = rng.permutation(ranked[pick])
    out[np.asarray(train).reshape(n_batches, batch)] = rng.permuted(dealt,
                                                                   axis=1)
    return out


def exponential_gaps(n: int, mean: float, rng) -> np.ndarray:
    """``n`` inter-arrival gaps of a Poisson process of rate ``1/mean``:
    the exponential's quantiles, in an order drawn from ``rng`` (so every
    seed sends the same number of requests in the same span)."""
    q = (np.arange(n) + 0.5) / n
    return rng.permutation(-mean * np.log1p(-q))


def _zipf_cdf(v: int, s: float) -> np.ndarray:
    w = (np.arange(1, v + 1, dtype=np.float64)) ** -s
    c = np.cumsum(w)
    return c / c[-1]


def topic_maps(k: int, v: int, rng):
    """Per topic an affine map ``rank -> (a * rank + b) mod v`` (``a``
    coprime to ``v``) composed with one vocabulary permutation: topic
    ``t``'s word of rank ``r`` is ``perm[(a[t] * r + b[t]) % v]``."""
    perm = rng.permutation(v).astype(np.int64)
    a = np.empty(0, np.int64)
    while len(a) < k:
        cand = rng.integers(1, v, size=2 * k + 8)
        a = np.concatenate([a, cand[np.gcd(cand, v) == 1]])
    return perm, a[:k], rng.integers(0, v, size=k)


def topic_words(ranks, topics, maps, v: int) -> np.ndarray:
    perm, a, b = maps
    return perm[(a[topics] * ranks + b[topics]) % v].astype(np.int32)


def documents(lengths: np.ndarray, k: int, v: int, alpha: float,
              zipf_s: float, rng, maps=None) -> dict:
    """Tokens of documents of the given lengths, back to back.

    Returns ``tokens`` ``(N,) int32`` and ``lengths`` ``(D,) int64``."""
    lengths = np.asarray(lengths, np.int64)
    d = len(lengths)
    if maps is None:
        maps = topic_maps(k, v, rng)
    theta = rng.gamma(alpha, size=(d, k))
    theta /= theta.sum(axis=1, keepdims=True)
    cdf = np.cumsum(theta, axis=1)
    cdf[:, -1] = 1.0
    n = int(lengths.sum())
    doc = np.repeat(np.arange(d, dtype=np.int64), lengths)
    # topic per token: one search over every document's CDF laid end to
    # end, document d's CDF shifted by d
    flat = (cdf + np.arange(d)[:, None]).ravel()
    z = np.searchsorted(flat, doc + rng.random(n), side="right") - doc * k
    z = np.minimum(z, k - 1)
    ranks = np.searchsorted(_zipf_cdf(v, zipf_s), rng.random(n),
                            side="right")
    ranks = np.minimum(ranks, v - 1)
    return {"tokens": topic_words(ranks, z, maps, v), "lengths": lengths}


def corpus(n_docs: int, cfg: dict, seed: int) -> dict:
    """A run's training corpus for configuration ``cfg`` (keys ``K``,
    ``V``, ``alpha``, ``zipf_s``, ``mean_doc_tokens``, ``doc_length_sigma``
    and optionally ``max_doc_tokens``)."""
    rng = rng_for(seed, 1)
    lengths = lognormal_lengths(n_docs, cfg["mean_doc_tokens"],
                                cfg["doc_length_sigma"], rng,
                                cfg.get("max_doc_tokens"))
    return documents(lengths, cfg["K"], cfg["V"], cfg["alpha"],
                     cfg["zipf_s"], rng)
