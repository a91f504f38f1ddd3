"""The corpus generator: lengths, topics, and their independence of the
seed where the work must not change."""

import numpy as np
import pytest

from bench import corpus as gen


@pytest.mark.parametrize("mean,sigma", [(332, 0.8), (89, 0.8)])
def test_lengths_keep_the_mean_and_the_multiset(mean, sigma):
    a = gen.lognormal_lengths(5000, mean, sigma, gen.rng_for(1, 1))
    b = gen.lognormal_lengths(5000, mean, sigma, gen.rng_for(2**31 + 9, 1))
    assert abs(a.mean() / mean - 1) < 0.01
    assert not np.array_equal(a, b)
    assert np.array_equal(np.sort(a), np.sort(b))
    assert a.min() >= 1


@pytest.mark.parametrize("config", ["lda-nytimes", "lda-pubmed"])
def test_batched_lengths_are_the_same_work_for_every_seed(config):
    """Every minibatch of the fixed schedule holds the same tokens, and
    the batches' and the held-out set's multisets do not depend on the
    seed; the mean stays within 2% of the source's."""
    from bench import harness
    from bench import reference as ref
    cfg = harness.config(config)
    n, n_hold, b = cfg["D"], cfg["holdout_docs"], cfg["batch_docs_per_chip"]
    seen = set()
    for seed in (3, 2**31 + 11):
        train, hold = ref.holdout_split(n, n_hold, seed)
        lengths = gen.batched_lengths(n, cfg["mean_doc_tokens"],
                                      cfg["doc_length_sigma"], train, hold,
                                      b, gen.rng_for(seed, 1))
        batches = lengths[train.reshape(-1, b)]
        assert len(set(batches.sum(axis=1))) == 1
        seen.add((tuple(np.sort(batches, axis=1).ravel()),
                  tuple(np.sort(lengths[hold]))))
        assert abs(lengths.mean() / cfg["mean_doc_tokens"] - 1) < 0.02
        assert lengths.min() >= 1
    assert len(seen) == 1


def test_topic_words_follow_zipf():
    """One topic's words: the frequency of rank r falls as 1/r."""
    rng = gen.rng_for(3, 0)
    v = 5000
    maps = gen.topic_maps(1, v, rng)
    docs = gen.documents(np.full(100, 2000), 1, v, 0.1, 1.0, rng, maps)
    perm, a, b = maps
    # the rank of each word under topic 0's map
    rank_of = np.empty(v, np.int64)
    rank_of[perm[(a[0] * np.arange(v) + b[0]) % v]] = np.arange(v)
    freq = np.bincount(rank_of[docs["tokens"]], minlength=v)[:50]
    slope = np.polyfit(np.log(np.arange(1, 51)), np.log(freq), 1)[0]
    assert -1.1 < slope < -0.9


def test_topic_maps_are_permutations_and_documents_are_seeded():
    v, k = 1031 * 4, 6
    perm, a, b = gen.topic_maps(k, v, gen.rng_for(5, 0))
    for t in range(k):
        words = perm[(a[t] * np.arange(v) + b[t]) % v]
        assert len(np.unique(words)) == v
    cfg = {"K": k, "V": v, "alpha": 0.1, "zipf_s": 1.0,
           "mean_doc_tokens": 50, "doc_length_sigma": 0.8}
    x, y = gen.corpus(40, cfg, 11), gen.corpus(40, cfg, 11)
    assert np.array_equal(x["tokens"], y["tokens"])
    assert len(x["tokens"]) == x["lengths"].sum()
    assert x["tokens"].max() < v
