"""Correctness of the VMP engine against an independent handwritten CAVI
reference, plus the ELBO invariants the algorithm guarantees."""

import numpy as np
import pytest
from jax.scipy.special import digamma, gammaln

from repro.core import models
from repro.core.vmp import init_state

import jax
import jax.numpy as jnp


def _make_corpus(seed=0, K=3, V=30, D=20):
    rng = np.random.default_rng(seed)
    phi = rng.dirichlet(np.full(V, 0.08), size=K)
    theta = rng.dirichlet(np.full(K, 0.3), size=D)
    lens = rng.integers(15, 50, size=D)
    toks, docs = [], []
    for d in range(D):
        zs = rng.choice(K, size=lens[d], p=theta[d])
        for z in zs:
            toks.append(rng.choice(V, p=phi[z]))
            docs.append(d)
    return np.array(toks, np.int32), np.array(docs, np.int32), phi


def _reference_lda_cavi(tokens, docs, K, V, alpha, beta, init, iters):
    """Independent numpy CAVI for LDA (the math the engine must reproduce)."""
    D = docs.max() + 1
    theta_post = init["theta"].copy()       # (D, K)
    phi_post = init["phi"].copy()           # (K, V)
    elbos = []

    def elog(a):
        return digamma(a) - digamma(a.sum(-1, keepdims=True))

    def logB(a):
        return gammaln(a).sum(-1) - gammaln(a.sum(-1))

    for _ in range(iters):
        et, ep = elog(theta_post), elog(phi_post)
        logits = et[docs] + ep[:, tokens].T             # (N, K)
        m = logits.max(1, keepdims=True)
        # ELBO at (r*, current posteriors): logsumexp + Dirichlet terms
        lse = (m[:, 0] + np.log(np.exp(logits - m).sum(1)))
        elbo = lse.sum()
        elbo += (logB(theta_post) - logB(np.full_like(theta_post, alpha))
                 + ((alpha - theta_post) * et).sum(-1)).sum()
        elbo += (logB(phi_post) - logB(np.full_like(phi_post, beta))
                 + ((beta - phi_post) * ep).sum(-1)).sum()
        elbos.append(elbo)
        r = np.exp(logits - m)
        r /= r.sum(1, keepdims=True)
        theta_post = alpha + np.array(
            [r[docs == d].sum(0) for d in range(D)])
        phi_post = beta + np.array(
            [np.bincount(tokens, weights=r[:, k], minlength=V)
             for k in range(K)])
    return elbos


def test_lda_matches_handwritten_cavi():
    K, V = 3, 30
    toks, docs, _ = _make_corpus(K=K, V=V)
    m = models.make("lda", alpha=0.2, beta=0.1, K=K, V=V)
    m["x"].observe(toks, segment_ids=docs)
    prog = m.compile()
    state0 = init_state(prog, seed=0)
    init = {"theta": np.asarray(state0.posteriors["theta"], np.float64),
            "phi": np.asarray(state0.posteriors["phi"], np.float64)}
    ref = _reference_lda_cavi(toks, docs, K, V, 0.2, 0.1, init, iters=8)
    m.infer(steps=8)
    got = m.elbo_trace
    np.testing.assert_allclose(got, ref, rtol=2e-4)


# ELBO traces captured from the pre-fusion step body (gather -> zstep ->
# segment_sum, commit 9c7e323) on fixed seeds: the fused zstats
# restructuring must reproduce them.  On the single-chunk path the stats
# scatters are the same primitives in the same order, so the match was
# bitwise; the assertion allows float32 headroom for future re-chunking.
# They were drawn with jax's original threefry key derivation: since jax
# 0.5 ``jax_threefry_partitionable`` defaults to True, which gives
# ``init_state``'s uniform noise other bits (the traces then differ by up
# to 12% for LDA and 1.3% for SLDA from step 0).  The tests pin the
# derivation the constants were drawn with; nothing else changed them
# (under it they agree to 6.7e-7 and 3.4e-7 relative).
_GOLD_LDA = [-13043.072265625, -7396.16015625, -7368.0078125,
             -7311.3955078125, -7188.77294921875, -6974.115234375,
             -6709.36083984375, -6444.1083984375, -6165.09423828125,
             -5877.9853515625]
_GOLD_SLDA = [-1678.169189453125, -1518.405029296875, -1505.90576171875,
              -1495.37353515625, -1487.3486328125, -1481.5548095703125,
              -1478.287353515625, -1476.63134765625]


def test_fused_step_reproduces_prefusion_elbo_trace():
    """Fixed-seed full-batch VMP through the fused token-plate substep:
    the ELBO trace is unchanged from the pre-refactor engine and monotone."""
    from repro.data import SyntheticCorpus
    c = SyntheticCorpus(n_docs=50, vocab=30, n_topics=3, mean_len=60,
                        seed=0).generate()
    m = models.make("lda", alpha=0.1, beta=0.05, K=3, V=30)
    m["x"].observe(c["tokens"], segment_ids=c["doc_ids"])
    with jax.threefry_partitionable(False):      # see _GOLD_LDA
        m.infer(steps=10, seed=0)
    np.testing.assert_allclose(m.elbo_trace, _GOLD_LDA, rtol=1e-6)
    scale = abs(_GOLD_LDA[0])
    assert (np.diff(m.elbo_trace) >= -1e-6 * scale).all()


def test_fused_step_reproduces_prefusion_elbo_trace_segmented():
    """Same, through the segment-latent (zmap) path: SLDA."""
    rng = np.random.default_rng(3)
    S = 80
    sent_doc = np.sort(rng.integers(0, 12, size=S)).astype(np.int32)
    tok_sent = np.repeat(np.arange(S, dtype=np.int32),
                         rng.integers(3, 9, size=S))
    xs = rng.integers(0, 20, size=len(tok_sent)).astype(np.int32)
    m = models.make("slda", alpha=0.2, beta=0.2, K=3, V=20)
    m["x"].observe(xs, segment_ids=tok_sent)
    m.bind("sents", sent_doc)
    with jax.threefry_partitionable(False):      # see _GOLD_LDA
        m.infer(steps=8, seed=0)
    np.testing.assert_allclose(m.elbo_trace, _GOLD_SLDA, rtol=1e-6)


def test_lda_posterior_counts_conserved():
    toks, docs, _ = _make_corpus(seed=1)
    m = models.make("lda", alpha=0.1, beta=0.1, K=3, V=30)
    m["x"].observe(toks, segment_ids=docs)
    m.infer(steps=5)
    theta = m["theta"].get_result()
    # sum of (posterior - prior) over all docs == number of tokens
    total = theta.sum() - theta.shape[0] * theta.shape[1] * 0.1
    assert abs(total - len(toks)) < 1e-2 * len(toks)
    phi = m["phi"].get_result()
    total_phi = phi.sum() - phi.shape[0] * phi.shape[1] * 0.1
    assert abs(total_phi - len(toks)) < 1e-2 * len(toks)


def test_two_coins_posterior_predictive():
    """A single toss per draw makes the mixture unidentifiable (only
    pi1*phi1 + pi2*phi2 is observable), so the verifiable quantity is the
    posterior predictive P(head), which must match the empirical rate."""
    rng = np.random.default_rng(0)
    pick = rng.random(4000) < 0.5
    x = np.where(pick, rng.random(4000) < 0.9,
                 rng.random(4000) < 0.1).astype(np.int32)
    m = models.make("two_coins")
    m["x"].observe(x)
    m.infer(steps=60)
    pi = m["pi"].get_result()[0]            # Dirichlet(2) posterior
    phi = m["phi"].get_result()             # (2, 2) Beta posteriors
    e_pi = pi / pi.sum()
    e_head = phi[:, 1] / phi.sum(axis=1)
    predictive = float((e_pi * e_head).sum())
    assert abs(predictive - x.mean()) < 0.02
    # monotone up to float32 noise at convergence (relative tolerance)
    tol = 1e-5 * abs(m.elbo_trace[-1])
    assert (np.diff(m.elbo_trace) >= -tol).all()


@pytest.mark.parametrize("name,kw", [
    ("lda", dict(alpha=0.1, beta=0.1, K=4, V=25)),
    ("dcmlda", dict(alpha=0.4, beta=0.4, K=3, V=25)),
    ("naive_bayes", dict(alpha=1.0, beta=0.3, C=3, V=25)),
])
def test_elbo_monotone(name, kw):
    toks, docs, _ = _make_corpus(seed=2, V=25)
    m = models.make(name, **kw)
    m["x"].observe(toks, segment_ids=docs)
    m.infer(steps=12)
    diffs = np.diff(m.elbo_trace)
    assert (diffs >= -1e-3).all(), diffs


def test_slda_nested_plates():
    rng = np.random.default_rng(3)
    S = 80
    sent_doc = np.sort(rng.integers(0, 12, size=S)).astype(np.int32)
    tok_sent = np.repeat(np.arange(S, dtype=np.int32),
                         rng.integers(3, 9, size=S))
    xs = rng.integers(0, 20, size=len(tok_sent)).astype(np.int32)
    m = models.make("slda", alpha=0.2, beta=0.2, K=3, V=20)
    m["x"].observe(xs, segment_ids=tok_sent)
    m.bind("sents", sent_doc)
    m.infer(steps=10)
    assert (np.diff(m.elbo_trace) >= -1e-3).all()
    # phi is shared across docs: shape (K, V)
    assert m["phi"].get_result().shape == (3, 20)
    # theta per doc
    assert m["theta"].get_result().shape == (12, 3)


def test_callback_early_stop():
    toks, docs, _ = _make_corpus(seed=4)
    m = models.make("lda", alpha=0.1, beta=0.1, K=3, V=30)
    m["x"].observe(toks, segment_ids=docs)
    calls = []

    def cb(i, elbo):
        calls.append(elbo)
        return len(calls) < 4          # stop after 4 iterations

    m.infer(steps=50, callback=cb)
    assert len(calls) == 4
    assert len(m.elbo_trace) == 4


def test_lda_recovers_planted_topics():
    K, V = 3, 30
    toks, docs, true_phi = _make_corpus(seed=5, K=K, V=V, D=60)
    m = models.make("lda", alpha=0.1, beta=0.1, K=K, V=V)
    m["x"].observe(toks, segment_ids=docs)
    m.infer(steps=40)
    post = m["phi"].get_result()
    est = post / post.sum(-1, keepdims=True)
    # greedy-match estimated topics to planted ones by TV distance
    used, dists = set(), []
    for k in range(K):
        best, best_d = None, 2.0
        for j in range(K):
            if j in used:
                continue
            d = 0.5 * np.abs(est[j] - true_phi[k]).sum()
            if d < best_d:
                best, best_d = j, d
        used.add(best)
        dists.append(best_d)
    assert np.mean(dists) < 0.35, dists
