"""The InferenceEngine API: one switchable surface over VMP, SVI, Gibbs —
and cross-engine agreement on a planted corpus."""

import numpy as np
import pytest

from repro.core import EngineConfig, aligned_tv, make_engine, models
from repro.data import SyntheticCorpus


def test_make_engine_selection():
    assert make_engine("vmp").name == "vmp"
    assert make_engine({"backend": "svi", "steps": 7}).cfg.steps == 7
    assert make_engine(EngineConfig(backend="gibbs"), steps=3).cfg.steps == 3
    with pytest.raises(ValueError):
        make_engine("annealed_ais")


def test_engine_svi_knobs_round_trip():
    """Every SVI knob on EngineConfig must reach the SVIConfig the engine
    builds — holdout_local_iters, prefetch, and the constant-rho override
    used to be dropped silently (engine users always got defaults)."""
    from repro.core.engine import _svi_config
    cfg = EngineConfig(backend="svi", batch_size=17, kappa=0.9, tau=3.0,
                       rho=0.25, local_iters=4, pad_multiple=64,
                       holdout_frac=0.125, holdout_every=7,
                       holdout_local_iters=21, prefetch=False,
                       elog_dtype="bfloat16", seed=11)
    s = _svi_config(cfg, full_batch=False, n_groups=100)
    assert (s.batch_size, s.kappa, s.tau, s.rho) == (17, 0.9, 3.0, 0.25)
    assert (s.local_iters, s.pad_multiple) == (4, 64)
    assert (s.holdout_frac, s.holdout_every) == (0.125, 7)
    assert (s.holdout_local_iters, s.prefetch) == (21, False)
    assert (s.elog_dtype, s.seed, s.shuffle) == ("bfloat16", 11, True)
    # make_engine keyword overrides carry the new knobs too
    eng = make_engine("svi", rho=0.25, holdout_local_iters=21,
                      prefetch=False)
    assert (eng.cfg.rho, eng.cfg.holdout_local_iters,
            eng.cfg.prefetch) == (0.25, 21, False)
    # the full-batch reference pins the exactness knobs regardless
    fb = _svi_config(cfg, full_batch=True, n_groups=100)
    assert (fb.rho, fb.batch_size, fb.pad_multiple, fb.shuffle) == \
        (1.0, 100, 0, False)
    assert (fb.holdout_local_iters, fb.prefetch) == (21, False)


def test_gibbs_rejects_non_lda_shapes(small_corpus):
    m = models.make("dcmlda", alpha=0.4, beta=0.4, K=3, V=30)
    m["x"].observe(small_corpus["tokens"],
                   segment_ids=small_corpus["doc_ids"])
    with pytest.raises(ValueError, match="LDA-shaped"):
        make_engine("gibbs", steps=5).fit(m)


def test_bf16_elog_mode_tracks_f32(lda_model):
    """elog_dtype="bfloat16" narrows only the gathered message tables; the
    fit must land within bf16 noise of the f32 run, on both engines."""
    for backend in ("vmp", "svi"):
        r32 = make_engine(backend, steps=8, batch_size=16,
                          seed=0).fit(lda_model)
        r16 = make_engine(backend, steps=8, batch_size=16, seed=0,
                          elog_dtype="bfloat16").fit(lda_model)
        e32, e16 = r32.elbo_trace[-1], r16.elbo_trace[-1]
        assert abs(e16 - e32) / abs(e32) < 1e-2, (backend, e32, e16)
        tv = aligned_tv(r32.topics("phi"), r16.topics("phi"))
        assert tv < 0.05, (backend, tv)


def test_all_backends_run_and_expose_topics(lda_model):
    for backend, steps in (("vmp", 5), ("svi", 8), ("gibbs", 20)):
        r = make_engine(backend, steps=steps, batch_size=16).fit(lda_model)
        t = r.topics("phi")
        assert t.shape == (3, 30)
        np.testing.assert_allclose(t.sum(-1), 1.0, rtol=1e-4)
        assert len(r.elbo_trace) > 0


@pytest.mark.parametrize("steps_g,steps_v,seed,tol", [
    pytest.param(100, 20, 1, 0.30, id="quick"),
    pytest.param(250, 50, 0, 0.20, id="full", marks=pytest.mark.slow),
])
def test_cross_engine_planted_topic_agreement(steps_g, steps_v, seed, tol):
    """Gibbs posterior means and VMP posteriors on the same planted corpus
    both recover the planted topics (permutation-aligned) and agree with
    each other — two inference paradigms, one model, one API."""
    K, V = 3, 40
    c = SyntheticCorpus(n_docs=60, vocab=V, n_topics=K, mean_len=80,
                        seed=2).generate()

    def model():
        m = models.make("lda", alpha=0.1, beta=0.05, K=K, V=V)
        m["x"].observe(c["tokens"], segment_ids=c["doc_ids"])
        return m

    r_v = make_engine("vmp", steps=steps_v, seed=seed).fit(model())
    r_g = make_engine("gibbs", steps=steps_g, seed=seed).fit(model())
    phi_v, phi_g = r_v.topics("phi"), r_g.topics("phi")
    assert aligned_tv(phi_v, c["true_phi"]) < tol
    assert aligned_tv(phi_g, c["true_phi"]) < tol
    # engine-vs-engine: aligned topics agree
    assert aligned_tv(phi_v, phi_g) < tol


def test_svi_engine_reports_heldout(lda_model):
    r = make_engine("svi", steps=20, batch_size=10, holdout_frac=0.1,
                    holdout_every=10).fit(lda_model)
    assert r.backend == "svi"
    assert len(r.heldout_trace) >= 1
    assert np.isfinite(r.heldout_elbo)
    assert r.meta["n_holdout_groups"] == 5


def test_vmp_engine_with_holdout_matches_plain_vmp_topics(lda_model,
                                                          small_corpus):
    """The holdout-aware VMP path (SVI machinery at rho=1) finds the same
    topics as the classic full-batch path."""
    r_plain = make_engine("vmp", steps=20, seed=0).fit(lda_model)
    m2 = models.make("lda", alpha=0.1, beta=0.05, K=3, V=30)
    m2["x"].observe(small_corpus["tokens"],
                    segment_ids=small_corpus["doc_ids"])
    r_hold = make_engine("vmp", steps=20, seed=0,
                         holdout_frac=0.1).fit(m2)
    assert aligned_tv(r_plain.topics("phi"), r_hold.topics("phi")) < 0.1
    assert np.isfinite(r_hold.heldout_elbo)


_INFERENCE_IMPORTS = """
import sys; sys.path.insert(0, {src!r})
import jax
import numpy as np
import repro.core.svi, repro.query, repro.gateway
from repro.core import EngineConfig, models
from repro.core.runtime import build_infer_step
from repro.core.svi import replicated_array, shard_stacked_array
from repro.data import SyntheticCorpus
c = SyntheticCorpus(n_docs=20, vocab=30, n_topics=3, mean_len=20,
                    seed=0).generate()
m = models.make("lda", alpha=0.1, beta=0.05, K=3, V=30)
m["x"].observe(c["tokens"], segment_ids=c["doc_ids"])
step, state = build_infer_step(m.compile(),
                               EngineConfig(backend="svi", batch_size=8))
state, elbo = step(state)
mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("d",))
rep = replicated_array(mesh, np.arange(3.0))
stk = shard_stacked_array(mesh, "d", (1, 2), np.float32,
                          {{0: np.ones(2, np.float32)}})
assert np.isfinite(float(elbo))
assert rep.tolist() == [0.0, 1.0, 2.0] and stk.tolist() == [[1.0, 1.0]]
lm = sorted(n for n in sys.modules
            if n.split(".")[:2] in (["repro", "models"], ["repro", "configs"],
                                    ["repro", "optim"], ["repro", "launch"]))
print("LM", lm)
"""


def test_inference_path_imports_no_lm_stack():
    """The SVI engine, query and gateway packages, ``build_infer_step``
    and the multi-host array builders, imported and run in a fresh
    interpreter, load nothing of the seed transformer-LM stack
    (``repro.models``, ``repro.configs``, ``repro.optim``) or of
    ``repro.launch``."""
    import os

    from repro.testing import faults
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    r = faults.run_child(_INFERENCE_IMPORTS.format(src=src), timeout=300)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "LM []" in r.stdout, r.stdout


def test_build_infer_step_selects_backend(lda_program):
    """core.runtime.build_infer_step: both step-machine backends drive
    run_inference (callbacks, checkpointing) interchangeably."""
    from repro.core.runtime import build_infer_step, run_inference

    for engine in ("vmp", EngineConfig(backend="svi", batch_size=16,
                                       pad_multiple=32)):
        step_fn, state0 = build_infer_step(lda_program, engine)
        state, trace = run_inference(lda_program, steps=4, state=state0,
                                     step_fn=step_fn)
        assert len(trace) == 4
        assert np.isfinite(trace).all()
        assert int(state.step) == 4
    with pytest.raises(ValueError):
        build_infer_step(lda_program, "gibbs")
