"""The plain reference: LDA stochastic variational inference and fold-in
scoring in straightforward ``jax.numpy``.

It follows Hoffman et al., *Stochastic Variational Inference* (JMLR 2013)
with one local pass per minibatch, as the configurations state, and
imports nothing of the program: no kernel, no scheduler, no table that
the program made.  What the program's configuration fixes -- the initial
posteriors, the held-out split and the minibatch order drawn from the
seed -- is written out here from its definition.  Matrix products run at
``highest`` precision, though the arithmetic below has none: gathers,
exponentials and segment sums only.  ``dtype`` narrows every table and
every intermediate to a lower precision for the control.

Tokens are processed in chunks so that the ``(tokens, topics)``
intermediates stay small; the statistics accumulate across chunks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import digamma, gammaln

CHUNK = 1 << 15


# ---------------------------------------------------------------------------
# what the configuration fixes
# ---------------------------------------------------------------------------

def robbins_monro(t: int, tau: float, kappa: float) -> float:
    """The step size ``rho_t = min((tau + t) ** -kappa, 1)``."""
    base = tau + t
    return 1.0 if base <= 0 else float(min(base ** (-kappa), 1.0))


def holdout_split(n: int, n_hold: int, seed: int):
    """``(train, holdout)`` document ids: the first ``n_hold`` of a
    permutation drawn from ``seed`` are held out; both sorted."""
    perm = np.random.default_rng(seed).permutation(n)
    return np.sort(perm[n_hold:]), np.sort(perm[:n_hold])


def batch_docs(train: np.ndarray, batch: int, seed: int, t: int,
               shuffle: bool = False):
    """Document ids of minibatch ``t``: the training documents cut in
    consecutive batches, the same in every epoch; with ``shuffle`` each
    epoch deals them in an order drawn from ``(seed, epoch)``."""
    per_epoch = -(-len(train) // batch)
    epoch, idx = divmod(t, per_epoch)
    perm = train
    if shuffle:
        perm = np.random.default_rng(
            np.random.SeedSequence([seed, epoch])).permutation(train)
    return np.sort(perm[idx * batch:(idx + 1) * batch])


def initial_posteriors(seed: int, n_docs: int, k: int, v: int,
                       alpha: float, beta: float) -> dict:
    """Prior plus uniform noise on ``[0.5, 1.5)``, drawn from ``seed`` in
    name order (``phi`` first, then ``theta``)."""
    key = jax.random.PRNGKey(seed)
    out = {}
    for name, (g, kk, prior) in (("phi", (k, v, beta)),
                                 ("theta", (n_docs, k, alpha))):
        key, sub = jax.random.split(key)
        out[name] = prior + jax.random.uniform(sub, (g, kk), jnp.float32,
                                               0.5, 1.5)
    return out


# ---------------------------------------------------------------------------
# Dirichlet algebra
# ---------------------------------------------------------------------------

def elog(a):
    """E[log x] under Dirichlet rows ``a``."""
    return digamma(a) - digamma(a.sum(axis=-1, keepdims=True))


def dirichlet_term(prior, post):
    """E_q[log p] - E_q[log q] of Dirichlet rows ``post`` under a
    symmetric ``prior``, per row."""
    p = jnp.full_like(post, prior)
    lnorm = lambda a: gammaln(a).sum(-1) - gammaln(a.sum(-1))  # noqa: E731
    return lnorm(post) - lnorm(p) + ((p - post) * elog(post)).sum(-1)


# ---------------------------------------------------------------------------
# the token plate
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_rows", "child"))
def _plate(elog_rows, elog_words, rows, words, mask, n_rows: int,
           child: bool):
    """Chunks of tokens -> (per-row lse sums, row stats, word stats)."""
    dt = elog_rows.dtype
    v, k = elog_words.shape

    def body(carry, xs):
        lse_r, ps, cs = carry
        r_ids, w_ids, m = xs
        logits = elog_rows[r_ids] + elog_words[w_ids]
        mx = logits.max(axis=-1, keepdims=True)
        e = jnp.exp(logits - mx)
        s = e.sum(axis=-1, keepdims=True)
        lse = (mx + jnp.log(s))[:, 0] * m
        r = e / s * m[:, None]
        lse_r = lse_r + jax.ops.segment_sum(lse, r_ids, n_rows)
        ps = ps + jax.ops.segment_sum(r, r_ids, n_rows)
        if child:
            cs = cs + jax.ops.segment_sum(r, w_ids, v)
        return (lse_r, ps, cs), None

    init = (jnp.zeros((n_rows,), dt), jnp.zeros((n_rows, k), dt),
            jnp.zeros((v, k) if child else (1, k), dt))
    (lse_r, ps, cs), _ = jax.lax.scan(body, init, (rows, words, mask))
    return lse_r, ps, cs


def token_plate(rows_table, topic_table, rows, words, dtype=jnp.float32,
                child: bool = True):
    """One pass over tokens: ``rows`` (document row of each token) and
    ``words``.  The tables are Dirichlet concentrations ``(b, k)`` and
    ``(k, v)``.  Returns per-row lse sums ``(b,)``, row statistics
    ``(b, k)`` and topic statistics ``(k, v)`` (``None`` unless
    ``child``), all in ``dtype``."""
    n = len(rows)
    pad = -n % CHUNK
    shape = (-1, CHUNK)
    rows_c = jnp.asarray(np.pad(np.asarray(rows, np.int32), (0, pad))
                         .reshape(shape))
    words_c = jnp.asarray(np.pad(np.asarray(words, np.int32), (0, pad))
                          .reshape(shape))
    mask_c = jnp.asarray(np.pad(np.ones(n, np.float32), (0, pad))
                         .reshape(shape), dtype)
    e_rows = elog(jnp.asarray(rows_table, dtype))
    e_words = elog(jnp.asarray(topic_table, dtype)).T
    with jax.default_matmul_precision("highest"):
        lse_r, ps, cs = _plate(e_rows, e_words, rows_c, words_c, mask_c,
                               n_rows=int(rows_table.shape[0]),
                               child=child)
    return lse_r, ps, (cs.T if child else None)


# ---------------------------------------------------------------------------
# SVI steps and local scoring
# ---------------------------------------------------------------------------

def svi_step(theta, phi, docs, tokens_of, rho, scale, alpha, beta,
             dtype=jnp.float32):
    """One minibatch step over documents ``docs``; ``tokens_of(docs)``
    returns ``(rows, words)``.  Returns ``(theta', phi', batch ELBO)``."""
    rows, words = tokens_of(docs)
    th_b = jnp.asarray(theta, dtype)[docs]
    ph = jnp.asarray(phi, dtype)
    lse_r, ps, cs = token_plate(th_b, ph, rows, words, dtype)
    elbo = (lse_r.astype(jnp.float32).sum()
            + dirichlet_term(alpha, th_b).astype(jnp.float32).sum()
            + dirichlet_term(beta, ph).astype(jnp.float32).sum())
    theta = jnp.asarray(theta).at[docs].set((alpha + ps).astype(jnp.float32))
    target = beta + scale * cs
    phi = ((1.0 - rho) * ph + rho * target).astype(jnp.float32)
    return theta, phi, float(elbo)


def local_scores(phi, rows, words, n_docs: int, iters: int, alpha,
                 dtype=jnp.float32):
    """Fresh document rows at the prior, ``iters`` local passes with
    ``phi`` frozen, then each document's score: its tokens' lse sums at
    the fitted rows plus its row's Dirichlet term.  ``(n_docs,)``
    float32."""
    ph = jnp.asarray(phi, dtype)
    th = jnp.full((n_docs, ph.shape[0]), alpha, dtype)
    for _ in range(iters):
        _, ps, _ = token_plate(th, ph, rows, words, dtype, child=False)
        th = alpha + ps
    lse_r, _, _ = token_plate(th, ph, rows, words, dtype, child=False)
    return (lse_r + dirichlet_term(alpha, th)).astype(jnp.float32)
