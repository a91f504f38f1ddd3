"""The plain reference for SLDA: stochastic variational inference and
held-out scoring of Sentence-LDA in straightforward ``jax.numpy``.

The model is Jo & Oh's (*Aspect and Sentiment Unification Model for
Online Review Analysis*, WSDM 2011), the paper's Figure 21: a document
draws ``theta_d ~ Dirichlet(alpha)``, each topic ``phi_k ~
Dirichlet(beta)``, each sentence ``s`` of the document one topic ``z_s ~
theta_d``, and every token of the sentence its word from ``phi_{z_s}``.
Under mean field (``q(theta_d)``, ``q(phi_k)`` Dirichlet, ``q(z_s)``
categorical) a sentence's logits are ``Elog theta[d] + sum over its tokens
of Elog phi[:, w]``; its responsibilities ``r_s`` are their softmax; the
document statistics sum ``r_s`` over the document's sentences and the
topic statistics add ``r_s`` at every token's word.  The step is
``bench/reference.py``'s SVI step with this plate in place of LDA's.

Departures from Jo & Oh, each the program's definition:

- inference is mean-field variational, fitted by stochastic variational
  inference (Hoffman et al., JMLR 2013: one local pass a minibatch, a
  natural-gradient blend of the topic table at Robbins-Monro steps), not
  collapsed Gibbs sampling;
- ASUM's sentiment layer (a sentiment per sentence, sentiment-specific
  word priors) is left out: SLDA is ASUM with a single sentiment;
- priors are symmetric scalars;
- the batch ELBO is the sum of the sentences' log-normalizers plus the
  Dirichlet terms of the batch's document rows and of the topic table;
  held-out documents are scored by fresh document rows fitted with the
  topics frozen, not by Jo & Oh's held-out likelihood estimate.

It imports nothing of the program: what the configuration fixes (initial
posteriors, the held-out split, the batch order) comes from
``bench/reference.py``, which writes it out from its definition.  Matrix
products run at ``highest``; ``dtype`` narrows every table and
intermediate for the control.  Tokens are processed in chunks, so the
``(tokens, topics)`` intermediates stay small.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import CHUNK, dirichlet_term, elog


@functools.partial(jax.jit, static_argnames=("n_sents",))
def _sentence_messages(elog_words, tok_sent, words, mask, n_sents: int):
    """Chunks of tokens -> each sentence's summed word messages
    ``(n_sents, k)``."""
    def body(acc, xs):
        s, w, m = xs
        return acc + jax.ops.segment_sum(elog_words[w] * m[:, None], s,
                                         n_sents), None

    init = jnp.zeros((n_sents, elog_words.shape[1]), elog_words.dtype)
    acc, _ = jax.lax.scan(body, init, (tok_sent, words, mask))
    return acc


@functools.partial(jax.jit, static_argnames=("v",))
def _word_stats(r, tok_sent, words, mask, v: int):
    """Chunks of tokens -> topic statistics ``(v, k)``: each token adds its
    sentence's responsibilities at its word."""
    def body(cs, xs):
        s, w, m = xs
        return cs + jax.ops.segment_sum(r[s] * m[:, None], w, v), None

    cs, _ = jax.lax.scan(body, jnp.zeros((v, r.shape[1]), r.dtype),
                         (tok_sent, words, mask))
    return cs


def _chunks(a, dtype=np.int32):
    a = np.asarray(a, dtype)
    return jnp.asarray(np.pad(a, (0, -len(a) % CHUNK)).reshape(-1, CHUNK))


def sentence_plate(rows_table, topic_table, plate, dtype=jnp.float32,
                   child: bool = True):
    """One pass over a batch's sentences.  ``plate`` is ``(sent_rows,
    tok_sent, words)``: each sentence's document row, each token's
    sentence and word.  The tables are Dirichlet concentrations ``(b, k)``
    and ``(k, v)``.  Returns per-row lse sums ``(b,)``, row statistics
    ``(b, k)`` and topic statistics ``(k, v)`` (``None`` unless
    ``child``), all in ``dtype``."""
    sent_rows, tok_sent, words = plate
    n_rows, n_sents = int(rows_table.shape[0]), len(sent_rows)
    v = int(topic_table.shape[1])
    e_rows = elog(jnp.asarray(rows_table, dtype))
    e_words = elog(jnp.asarray(topic_table, dtype)).T
    s_c, w_c = _chunks(tok_sent), _chunks(words)
    m_c = _chunks(np.ones(len(words)), np.float32).astype(dtype)
    rows = jnp.asarray(np.asarray(sent_rows, np.int32))
    with jax.default_matmul_precision("highest"):
        logits = e_rows[rows] + _sentence_messages(e_words, s_c, w_c, m_c,
                                                   n_sents=n_sents)
        mx = logits.max(axis=-1, keepdims=True)
        e = jnp.exp(logits - mx)
        s = e.sum(axis=-1, keepdims=True)
        lse = (mx + jnp.log(s))[:, 0]
        r = e / s
        lse_r = jax.ops.segment_sum(lse, rows, n_rows)
        ps = jax.ops.segment_sum(r, rows, n_rows)
        cs = _word_stats(r, s_c, w_c, m_c, v=v) if child else None
    return lse_r, ps, (cs.T if child else None)


def svi_step(theta, phi, docs, plate_of, rho, scale, alpha, beta,
             dtype=jnp.float32):
    """One minibatch step over documents ``docs``; ``plate_of(docs)``
    returns their ``(sent_rows, tok_sent, words)``.  Returns ``(theta',
    phi', batch ELBO)``."""
    th_b = jnp.asarray(theta, dtype)[docs]
    ph = jnp.asarray(phi, dtype)
    lse_r, ps, cs = sentence_plate(th_b, ph, plate_of(docs), dtype)
    elbo = (lse_r.astype(jnp.float32).sum()
            + dirichlet_term(alpha, th_b).astype(jnp.float32).sum()
            + dirichlet_term(beta, ph).astype(jnp.float32).sum())
    theta = jnp.asarray(theta).at[docs].set((alpha + ps).astype(jnp.float32))
    target = beta + scale * cs
    phi = ((1.0 - rho) * ph + rho * target).astype(jnp.float32)
    return theta, phi, float(elbo)


def local_scores(phi, plate, n_docs: int, iters: int, alpha,
                 dtype=jnp.float32):
    """Fresh document rows at the prior, ``iters`` local passes with
    ``phi`` frozen, then each document's score: its sentences' lse sums at
    the fitted rows plus its row's Dirichlet term.  ``(n_docs,)``
    float32."""
    ph = jnp.asarray(phi, dtype)
    th = jnp.full((n_docs, ph.shape[0]), alpha, dtype)
    for _ in range(iters):
        _, ps, _ = sentence_plate(th, ph, plate, dtype, child=False)
        th = alpha + ps
    lse_r, _, _ = sentence_plate(th, ph, plate, dtype, child=False)
    return (lse_r + dirichlet_term(alpha, th)).astype(jnp.float32)
