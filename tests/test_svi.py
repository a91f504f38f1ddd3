"""The streaming minibatch (SVI) engine: exact degenerate cases, padding
invariance, and held-out ELBO agreement with the full-batch engine."""

import jax
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import models
from repro.core.compiler import local_dirichlets, slice_arrays, sliced_shadow
from repro.core.runtime import make_step
from repro.core.svi import (SVI, SVIConfig, build_local_scorer, device_batch,
                            heldout_elbo, make_svi_step, robbins_monro)
from repro.core.vmp import VMPState, _step_body, init_state


def _one_svi_step(prog, state, groups, rho=1.0, scale=1.0, caps_fn=None):
    batch, caps, _ = device_batch(prog, groups, caps_fn)
    step = make_svi_step(prog, caps, local_iters=1, donate=False)
    return step(state, batch, jnp.float32(rho), jnp.float32(scale))


def test_full_batch_rho1_is_bitwise_vmp(lda_program):
    """|B| = all docs and rho = 1: one SVI step IS the full-batch VMP
    update — bitwise, not approximately."""
    prog = lda_program
    state0 = init_state(prog, seed=0)
    s_full, e_full = make_step(prog, donate=False)(state0)
    s_svi, e_svi = _one_svi_step(
        prog, state0, np.arange(prog.meta["pstar_size"]))
    for name in s_full.posteriors:
        np.testing.assert_array_equal(np.asarray(s_full.posteriors[name]),
                                      np.asarray(s_svi.posteriors[name]))
    assert float(e_full) == float(e_svi)


@pytest.mark.parametrize("name,kw", [
    ("dcmlda", dict(alpha=0.4, beta=0.4, K=3, V=30)),      # local phi + base
    ("naive_bayes", dict(alpha=1.0, beta=0.3, C=3, V=30)), # doc-level latent
])
def test_full_batch_bitwise_other_models(small_corpus, name, kw):
    m = models.make(name, **kw)
    m["x"].observe(small_corpus["tokens"],
                   segment_ids=small_corpus["doc_ids"])
    prog = m.compile()
    state0 = init_state(prog, seed=0)
    s_full, _ = make_step(prog, donate=False)(state0)
    s_svi, _ = _one_svi_step(prog, state0,
                             np.arange(prog.meta["pstar_size"]))
    for n in s_full.posteriors:
        np.testing.assert_array_equal(np.asarray(s_full.posteriors[n]),
                                      np.asarray(s_svi.posteriors[n]))


def test_padding_does_not_change_the_update(lda_program):
    """Masked padding of every sliced axis must be update-invariant."""
    prog = lda_program
    state0 = init_state(prog, seed=0)
    groups = np.arange(0, 20)
    s_exact, e_exact = _one_svi_step(prog, state0, groups, rho=0.5, scale=2.0)
    s_pad, e_pad = _one_svi_step(
        prog, state0, groups, rho=0.5, scale=2.0,
        caps_fn=lambda name, n: -(-max(n, 1) // 64) * 64)
    np.testing.assert_allclose(float(e_exact), float(e_pad), rtol=1e-5)
    for n in s_exact.posteriors:
        np.testing.assert_allclose(np.asarray(s_exact.posteriors[n]),
                                   np.asarray(s_pad.posteriors[n]),
                                   rtol=2e-5, atol=2e-5)


def test_untouched_docs_keep_their_posterior(lda_program):
    """A minibatch step only writes the batch's local rows."""
    prog = lda_program
    state0 = init_state(prog, seed=0)
    groups = np.arange(5, 15)
    s1, _ = _one_svi_step(prog, state0, groups, rho=0.3, scale=5.0)
    theta0 = np.asarray(state0.posteriors["theta"])
    theta1 = np.asarray(s1.posteriors["theta"])
    out = np.setdiff1d(np.arange(prog.meta["pstar_size"]), groups)
    np.testing.assert_array_equal(theta0[out], theta1[out])
    assert not np.allclose(theta0[groups], theta1[groups])


def test_robbins_monro_schedule():
    rhos = [robbins_monro(t, tau=10.0, kappa=0.7) for t in range(200)]
    assert all(0 < r <= 1 for r in rhos)
    assert all(a > b for a, b in zip(rhos, rhos[1:]))      # monotone decay
    with pytest.raises(ValueError):
        SVIConfig(kappa=0.4)                               # outside (0.5, 1]
    with pytest.raises(ValueError):
        SVIConfig(tau=-1.0)


@pytest.mark.parametrize("tau", [0.0, 0.5])
def test_robbins_monro_small_tau_clamped(tau):
    """tau=0 used to return inf at t=0 (``0 ** -kappa``) — one such step
    replaces the posterior state with inf — and any tau < 1 exceeded the
    documented ``rho_0 <= 1``.  The schedule is clamped to 1.0."""
    rhos = [robbins_monro(t, tau=tau, kappa=0.7) for t in range(50)]
    assert np.isfinite(rhos).all()
    assert all(0.0 < r <= 1.0 for r in rhos)
    assert rhos[0] == 1.0
    assert all(a >= b for a, b in zip(rhos, rhos[1:]))


def test_svi_tau_zero_fit_stays_finite(lda_program):
    """Regression: SVIConfig accepts tau=0, so the first step must be a
    finite rho=1 natural-gradient step, not a state-destroying inf."""
    svi = SVI(lda_program, SVIConfig(batch_size=16, tau=0.0, seed=0))
    state, history = svi.fit(steps=3)
    assert np.isfinite(history["elbo"]).all()
    for p in state.posteriors.values():
        assert np.isfinite(np.asarray(p)).all()


def test_sviconfig_validates_constant_rho():
    """The constant-rho override is validated like the schedule it
    replaces: rho outside (0, 1] diverges silently."""
    for bad in (2.0, 1.5, 0.0, -1.0):
        with pytest.raises(ValueError, match="rho"):
            SVIConfig(rho=bad)
    assert SVIConfig(rho=1.0).rho == 1.0
    assert SVIConfig(rho=0.3, kappa=7.0).rho == 0.3   # kappa unused w/ rho


def test_svi_heldout_elbo_matches_batch_vmp(lda_program):
    """On a planted corpus the streaming engine must converge to (within
    tolerance of) the full-batch optimum, measured by held-out per-token
    ELBO with the identical holdout split."""
    prog = lda_program
    # full-batch reference: rho=1, |B|=train — exact VMP on the train slice
    vmp = SVI(prog, SVIConfig(batch_size=10**9, rho=1.0, shuffle=False,
                              pad_multiple=0, holdout_frac=0.1,
                              holdout_every=0, seed=0))
    v_state, _ = vmp.fit(steps=25)
    v_held = vmp.heldout_elbo(v_state)

    svi = SVI(prog, SVIConfig(batch_size=12, holdout_frac=0.1,
                              holdout_every=0, pad_multiple=64,
                              kappa=0.7, tau=10.0, seed=0))
    s_state, _ = svi.fit(steps=80)
    s_held = svi.heldout_elbo(s_state)

    np.testing.assert_array_equal(vmp.holdout, svi.holdout)
    assert np.isfinite(v_held) and np.isfinite(s_held)
    assert abs(s_held - v_held) < 0.05, (s_held, v_held)


def test_svi_resumes_schedule_from_state(lda_program):
    """fit() continues the Robbins-Monro schedule at state.step: two
    segments equal one long run."""
    cfg = SVIConfig(batch_size=10, pad_multiple=32, holdout_frac=0.0,
                    seed=3)
    one = SVI(lda_program, cfg)
    s_long, _ = one.fit(steps=12)
    two = SVI(lda_program, cfg)
    s_a, _ = two.fit(steps=5)
    s_b, _ = two.fit(steps=7, state=s_a)
    assert int(s_b.step) == int(s_long.step) == 12
    for n in s_long.posteriors:
        np.testing.assert_allclose(np.asarray(s_long.posteriors[n]),
                                   np.asarray(s_b.posteriors[n]),
                                   rtol=1e-6)


def test_heldout_elbo_excludes_training_docs(lda_program):
    """Held-out groups never enter a training batch."""
    svi = SVI(lda_program, SVIConfig(batch_size=7, holdout_frac=0.2, seed=1))
    seen = set()
    for t in range(3 * svi.sampler.batches_per_epoch):
        seen.update(svi.sampler.batch_at(t).tolist())
    assert seen == set(svi.train.tolist())
    assert not seen & set(svi.holdout.tolist())


def _slda_program(small_corpus):
    """SLDA over the shared corpus, sentences of 7 tokens."""
    n = len(small_corpus["tokens"])
    sent_of_tok = (np.arange(n) // 7).astype(np.int32)
    doc_of_sent = small_corpus["doc_ids"][::7][:sent_of_tok.max() + 1]
    m = models.make("slda", alpha=0.2, beta=0.2, K=3, V=30)
    m["x"].observe(small_corpus["tokens"], segment_ids=sent_of_tok)
    m.bind("sents", doc_of_sent)
    return m.compile()


def test_slda_minibatch_runs(small_corpus):
    """The zmap (nested-plate) path under slicing: SLDA minibatches."""
    svi = SVI(_slda_program(small_corpus),
              SVIConfig(batch_size=8, pad_multiple=32, holdout_frac=0.1,
                        holdout_every=5, seed=0))
    state, hist = svi.fit(steps=10)
    assert len(hist["elbo"]) == 10
    assert np.isfinite(hist["heldout"][-1][1])


# ---------------------------------------------------------------------------
# local-only passes: the held-out and fold-in scorer keeps only the locals
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["lda", "slda"])
def test_step_body_keep_leaves_other_posteriors(name, lda_program,
                                                small_corpus):
    """_step_body(keep=...) returns each Dirichlet outside ``keep`` as its
    input posterior, and the kept posteriors and the ELBO bitwise equal to
    keep=None (a flat and a segment latent, padded and masked)."""
    prog = lda_program if name == "lda" else _slda_program(small_corpus)
    arrays, _, caps, _ = slice_arrays(prog, np.arange(20),
                                      lambda _, n: -(-n // 32) * 32)
    shadow = sliced_shadow(prog, caps)
    local = local_dirichlets(prog)
    state = init_state(shadow, seed=0)
    run = jax.jit(lambda st, arr, keep: _step_body(shadow, arr, st,
                                                   keep=keep),
                  static_argnums=2)
    all_new, all_elbo = run(state, arrays, None)
    new, elbo = run(state, arrays, local)
    assert local and set(new.posteriors) > local
    for n, p in new.posteriors.items():
        want = all_new.posteriors[n] if n in local else state.posteriors[n]
        np.testing.assert_array_equal(np.asarray(p), np.asarray(want))
    assert float(elbo) == float(all_elbo)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


def _nytimes_lda(docs: int, tokens: int):
    """An LDA program at the NYTimes widths (K=256, V=102,660) and the
    shapes of one of its padded batches: ``docs`` documents, ``tokens``
    token slots.  Posteriors are shapes only; nothing runs."""
    rng = np.random.default_rng(0)
    v, k = 102_660, 256
    m = models.make("lda", alpha=0.1, beta=0.05, K=k, V=v)
    m["x"].observe(rng.integers(0, v, 2 * docs).astype(np.int32),
                   segment_ids=np.repeat(np.arange(docs, dtype=np.int32), 2))
    prog = m.compile()
    cap = {"theta": docs, "z": tokens, "x": tokens}
    arrays, dirs, caps, _ = slice_arrays(prog, np.arange(docs),
                                         lambda name, n: cap[name])
    posts = {"theta": jax.ShapeDtypeStruct((docs, k), jnp.float32),
             "phi": jax.ShapeDtypeStruct((k, v), jnp.float32)}
    return prog, caps, arrays, dirs, posts


def test_nytimes_scorer_leaves_streamed_kernel(monkeypatch):
    """The NYTimes held-out scorer (1,024 documents, 339,712 token slots,
    10 local passes) under REPRO_FORCE_PALLAS=1: no Pallas kernel and no
    scatter into the topic table; all 11 token passes run under the
    ``kernels.zstats.local`` scope.  The training step at the same widths
    still streams the table through the fused kernel."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    table = {(256, 102_660), (102_660, 256)}
    prog, caps, arrays, _, posts = _nytimes_lda(1024, 339_712)
    scorer = build_local_scorer(prog, caps, 10)
    eqns = list(_eqns(jax.make_jaxpr(scorer)(posts, arrays).jaxpr))
    assert not [e for e in eqns if e.primitive.name == "pallas_call"]
    assert not [e for e in eqns if e.primitive.name.startswith("scatter")
                and tuple(e.invars[0].aval.shape) in table]
    passes = [e for e in eqns if e.primitive.name == "scan"
              and "kernels.zstats.local" in str(e.source_info.name_stack)]
    assert len(passes) == 11

    prog, caps, arrays, dirs, posts = _nytimes_lda(512, 169_984)
    posts["theta"] = jax.ShapeDtypeStruct((prog.dirichlets["theta"].g, 256),
                                          jnp.float32)
    step = make_svi_step(prog, caps, donate=False)
    eqns = list(_eqns(jax.make_jaxpr(step)(
        VMPState(posts, jax.ShapeDtypeStruct((), jnp.int32)),
        {"arrays": arrays, "dirs": dirs}, jnp.float32(0.5),
        jnp.float32(2.0)).jaxpr))
    assert [e for e in eqns if e.primitive.name == "pallas_call"]
