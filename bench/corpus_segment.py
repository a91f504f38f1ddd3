"""Seeded synthetic corpora with sentence structure, for SLDA cells.

A document of ``L`` tokens holds ``s = max(1, round(L / mean))``
sentences: its ``L - 1`` gaps between tokens are drawn without
replacement, ``s - 1`` of them, and the document is cut there.  So the
lengths vary within a document (a uniform composition of ``L`` into ``s``
positive parts: every cut pattern equally likely, each sentence about
``L / s`` tokens with a nearly geometric spread).  The sentence count is a
function of the length alone, and ``corpus.batched_lengths`` gives every
batch and the held-out set the same multiset of lengths for every seed,
so they hold the same number of sentences for every seed too.

Tokens follow SLDA's generative process (Jo & Oh, WSDM 2011; the paper's
Figure 21): a document draws its topic mixture from ``Dirichlet(alpha)``,
each sentence one topic from it, and every token of the sentence a word
from that topic's Zipf law (``corpus.topic_maps``).  Vectorised numpy, no
loop over documents, sentences or tokens.
"""

from __future__ import annotations

import numpy as np

from bench import corpus as gen


def sentence_lengths(lengths, mean: float, rng):
    """``(doc_sents (D,), sent_lengths (S,))`` int64: each document's
    sentence count and its sentences' token counts, in document order."""
    lengths = np.asarray(lengths, np.int64)
    doc_sents = np.maximum(1, np.rint(lengths / mean)).astype(np.int64)
    # every gap between two tokens of one document, with a random key;
    # a document's s - 1 gaps of least key are its cuts
    gaps = lengths - 1
    first_gap = np.cumsum(gaps) - gaps
    gap_doc = np.repeat(np.arange(len(lengths)), gaps)
    order = np.lexsort((rng.random(len(gap_doc)), gap_doc))
    rank = np.arange(len(order)) - np.repeat(first_gap, gaps)
    chosen = order[rank < np.repeat(doc_sents - 1, gaps)]
    doc_start = np.cumsum(lengths) - lengths
    # a gap's absolute position: its document's start, plus its place
    # within the document (1 .. L - 1)
    cut = chosen - first_gap[gap_doc[chosen]] + 1 + doc_start[gap_doc[chosen]]
    bounds = np.sort(np.concatenate([doc_start, cut]))
    return doc_sents, np.diff(np.append(bounds, lengths.sum()))


def documents(lengths, doc_sents, sent_lengths, k: int, v: int,
              alpha: float, zipf_s: float, rng, maps=None) -> dict:
    """Tokens of SLDA documents of the given sentence structure, back to
    back: ``tokens (N,) int32`` with ``lengths``, ``doc_sents`` and
    ``sent_lengths`` as given (the keys ``write_sharded_corpus`` reads)."""
    lengths = np.asarray(lengths, np.int64)
    doc_sents = np.asarray(doc_sents, np.int64)
    sent_lengths = np.asarray(sent_lengths, np.int64)
    d = len(lengths)
    if maps is None:
        maps = gen.topic_maps(k, v, rng)
    theta = rng.gamma(alpha, size=(d, k))
    theta /= theta.sum(axis=1, keepdims=True)
    cdf = np.cumsum(theta, axis=1)
    cdf[:, -1] = 1.0
    # topic per sentence: one search over every document's CDF laid end to
    # end, document d's shifted by d
    sent_doc = np.repeat(np.arange(d, dtype=np.int64), doc_sents)
    flat = (cdf + np.arange(d)[:, None]).ravel()
    z = np.searchsorted(flat, sent_doc + rng.random(len(sent_doc)),
                        side="right") - sent_doc * k
    z = np.minimum(z, k - 1)
    n = int(lengths.sum())
    ranks = np.searchsorted(gen._zipf_cdf(v, zipf_s), rng.random(n),
                            side="right")
    ranks = np.minimum(ranks, v - 1)
    tokens = gen.topic_words(ranks, np.repeat(z, sent_lengths), maps, v)
    return {"tokens": tokens, "lengths": lengths, "doc_sents": doc_sents,
            "sent_lengths": sent_lengths}


def plate_of(docs, offsets, sent_offsets, sent_lengths, tokens):
    """The reference's view of documents ``docs``: each sentence's
    batch-local document row, each token's batch-local sentence, and the
    tokens' words -- from the generator's arrays, not the program's."""
    docs = np.asarray(docs, np.int64)
    per_doc = sent_offsets[docs + 1] - sent_offsets[docs]
    sent_rows = np.repeat(np.arange(len(docs)), per_doc)
    sents = np.concatenate([np.arange(sent_offsets[x], sent_offsets[x + 1])
                            for x in docs])
    tok_sent = np.repeat(np.arange(len(sents)), sent_lengths[sents])
    idx = np.concatenate([np.arange(offsets[x], offsets[x + 1])
                          for x in docs])
    return sent_rows, tok_sent, tokens[idx]
