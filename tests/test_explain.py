"""EXPLAIN-plan accuracy: predictions vs the actual dispatch.

The contract under test: for every zoo model at the ``BENCH_kernels``
grid shapes, ``explain_plan``'s per-latent route equals the path
``kernels.ops.zstats`` actually dispatches to under
``REPRO_FORCE_PALLAS=1``, and the predicted SVI cap signature equals the
key ``SVI.step`` caches its jitted step under.

The grid dispatch runs with the kernel *bodies* stubbed out (recording
which one was entered) and ``jax.ShapeDtypeStruct`` stand-ins for the
tables, so BENCH-sized configurations — dcmlda's (docs*K, V) table alone
is ~5 GiB — are exercised without materializing a byte; the routing
logic, budget checks, and the dispatch's own trace-time
``routing()``-agreement asserts all still run on the real shapes.
"""

import importlib

import jax
import numpy as np
import pytest

from repro.analysis.explain import explain_plan, synthesize_model
from repro.kernels import ops as kops

# (name, shape knobs) — the BENCH_kernels grid from benchmarks/bench_kernels
# plus one VMEM-resident config so every route appears
GRID = [
    ("tiny", dict(docs=200, vocab=500, topics=8, mean_len=50)),
    ("bench-small", dict(docs=2_000, vocab=10_000, topics=64, mean_len=100)),
    ("bench-large", dict(docs=5_000, vocab=20_000, topics=128, mean_len=120)),
    ("bench-largev", dict(docs=2_000, vocab=60_000, topics=32, mean_len=200)),
]
ZOO = ["lda", "slda", "dcmlda", "naive_bayes", "two_coins"]


def _stub_kernels(monkeypatch, taken: list):
    """Replace the three zstats implementations with recorders."""
    fused_zstats = importlib.import_module("repro.kernels.fused_zstats")
    fused_zmap = importlib.import_module("repro.kernels.fused_zmap")
    ref = importlib.import_module("repro.kernels.ref")
    monkeypatch.setattr(fused_zstats, "zstats",
                        lambda *a, **k: taken.append("fused"))
    monkeypatch.setattr(fused_zmap, "zstats_zmap",
                        lambda *a, **k: taken.append("fused-zmap"))
    monkeypatch.setattr(ref, "zstats",
                        lambda *a, **k: taken.append("ref"))


def _dispatch_shapes(program):
    """Call ``ops.zstats`` per latent with ShapeDtypeStruct stand-ins
    shaped exactly as the full-batch step's arguments."""
    out = []
    for spec in program.latents:
        pd = program.dirichlets[spec.prior_dir]
        tp = jax.ShapeDtypeStruct((pd.g, pd.k), np.float32)
        pr = jax.ShapeDtypeStruct((spec.n,), np.int32)
        children = tuple(
            kops.ZChild(
                elog=jax.ShapeDtypeStruct(
                    (program.dirichlets[f.dir_name].g,
                     program.dirichlets[f.dir_name].k), np.float32),
                values=jax.ShapeDtypeStruct((len(f.values),), np.int32),
                stride=f.stride,
                zmap=(jax.ShapeDtypeStruct((len(f.values),), np.int32)
                      if f.zmap is not None else None),
                base=(jax.ShapeDtypeStruct((len(f.values),), np.int32)
                      if f.base is not None else None))
            for f in spec.children)
        out.append((spec.name, tp, pr, children))
    return out


@pytest.mark.parametrize("model_name", ZOO)
@pytest.mark.parametrize("grid_name,knobs", GRID,
                         ids=[g[0] for g in GRID])
def test_plan_matches_dispatch(monkeypatch, model_name, grid_name, knobs):
    m = synthesize_model(model_name, **knobs)
    plan = explain_plan(m, None, backend="pallas_interpret")
    assert not any(d.severity == "error" for d in plan.diagnostics)
    program = m.compile()
    assert plan.signature == tuple(sorted(plan.caps.items()))

    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    kops.reset_backend_cache()
    taken: list = []
    _stub_kernels(monkeypatch, taken)
    by_latent = {r.latent: r for r in plan.routes}
    for name, tp, pr, children in _dispatch_shapes(program):
        del taken[:]
        # the dispatch itself asserts routing() agreement at this call
        kops.zstats(tp, pr, children, tables="alpha")
        assert len(taken) == 1
        r = by_latent[name]
        expected = "fused" if r.path == "fused-streamed" else r.path
        assert taken[0] == expected, (model_name, grid_name, name, r)
        # full RouteInfo equality against an independent routing() call
        ri = kops.routing(tp, pr, children, tables="alpha")
        assert (ri.path, ri.target, ri.tile, ri.n_tiles, ri.table_bytes) \
            == (r.path, r.target, r.tile, r.n_tiles, r.table_bytes)
        # the plan's padded-shape signature covers this latent's extents
        assert plan.caps[name] == pr.shape[0]
        assert r.table_shapes[r.prior_dir] == tp.shape


def test_grid_covers_every_route(monkeypatch):
    """The zoo x grid matrix must exercise all four kernel paths —
    otherwise the matrix silently stopped testing anything interesting."""
    paths = set()
    for _, knobs in GRID:
        for name in ZOO:
            plan = explain_plan(synthesize_model(name, **knobs), None,
                                backend="pallas")
            paths |= {r.path for r in plan.routes}
    assert paths == {"ref", "fused", "fused-streamed", "fused-zmap"}, paths


def test_ref_backend_short_circuits():
    m = synthesize_model("lda", docs=50, vocab=40, topics=3, mean_len=20)
    plan = explain_plan(m, None, backend="ref")
    assert all(r.path == "ref" for r in plan.routes)
    assert "ref backend" in plan.routes[0].reason


# ---------------------------------------------------------------------------
# SVI signature: the plan's cap tuple is the step-cache key, exactly
# ---------------------------------------------------------------------------

def test_svi_signature_matches_step_cache(lda_model):
    from repro.core.svi import SVI, SVIConfig
    cfg = SVIConfig(batch_size=8, pad_multiple=4, holdout_frac=0.1, seed=3)
    plan = explain_plan(lda_model, cfg)
    assert plan.engine == "svi" and plan.signature is not None
    svi = SVI(lda_model.compile(), cfg)
    try:
        svi.step(0, svi.program.init_state(cfg.seed))
        assert set(svi._steps) == {plan.signature}
    finally:
        svi.close()


def test_engineconfig_svi_roundtrip(lda_model):
    from repro.core.engine import EngineConfig
    cfg = EngineConfig(backend="svi", batch_size=8, pad_multiple=4, seed=3)
    plan = explain_plan(lda_model, cfg)
    assert plan.engine == "svi"
    assert plan.caps and plan.routes


def test_no_partition_plate_falls_back_to_full_batch():
    from repro.core.svi import SVIConfig
    from repro.core.dsl import Model
    import numpy as np

    def fixed(m):
        grid = m.plate(4, name="grid")
        d = m.dirichlet("d", 1.0, dim=3, plate=grid)
        m.categorical("x", given=d, plate=grid)
    m = Model(fixed)
    m["x"].observe(np.array([0, 1, 2, 0]),
                   segment_ids=np.arange(4, dtype=np.int32) // 2)
    plan = explain_plan(m, SVIConfig(batch_size=2))
    assert any("planning full batch" in n for n in plan.notes)
    assert plan.caps


# ---------------------------------------------------------------------------
# end-to-end: a real traced step under forced Pallas agrees with its plan
# ---------------------------------------------------------------------------

def test_traced_step_agrees_with_plan(monkeypatch, small_corpus):
    from repro.core import models
    m = models.make("lda", alpha=0.1, beta=0.05, K=3, V=30)
    m["x"].observe(small_corpus["tokens"],
                   segment_ids=small_corpus["doc_ids"])
    plan = explain_plan(m, None, backend="pallas_interpret")
    assert [r.path for r in plan.routes] == ["fused"]
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    kops.reset_backend_cache()
    # dispatch asserts routing() agreement inside the traced step; a
    # mispredicted plan would abort this infer call
    m.infer(steps=1)
    assert np.isfinite(m.lower_bound)


def test_sharded_slda_plan_at_the_nytimes_step(monkeypatch, tmp_path):
    """SLDA over a sharded corpus at the step shapes of the benchmark's
    ``train.slda-nytimes`` cell (V=102,660, K=256, 512 documents of 335
    tokens in 28 sentences a step): the plan slices the first batch from
    the corpus, reports the sentence axis, and routes the segment latent
    to ``ref`` (its tables and logits exceed VMEM); ``ops.zstats`` at the
    same shapes dispatches there."""
    from repro.core import models
    from repro.core.svi import SVIConfig
    from repro.data import write_sharded_corpus
    v, k, n_docs = 102_660, 256, 576
    sents = np.array([12] * 27 + [11])
    store = write_sharded_corpus(
        {"tokens": np.zeros(335 * n_docs, np.int32),
         "lengths": np.full(n_docs, 335), "doc_sents": np.full(n_docs, 28),
         "sent_lengths": np.tile(sents, n_docs)}, str(tmp_path), vocab=v)
    model = models.make("slda", alpha=0.1, beta=0.05, K=k, V=v)
    cfg = SVIConfig(batch_size=512, holdout_frac=64 / n_docs,
                    pad_multiple=256)
    plan = explain_plan(model, cfg, corpus=store, backend="pallas")
    assert not plan.diagnostics and not model.observations
    assert plan.caps == {"theta": 512, "z": 14336, "x": 171520}
    (r,) = plan.routes
    assert (r.path, r.n_latent, r.n_tokens) == ("ref", 14336, 171520)
    assert "exceed the VMEM table budget" in r.reason
    assert any(n.startswith("sentence axis: latent z on plate sents, "
                            "16128 instances") for n in plan.notes)

    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    kops.reset_backend_cache()
    taken: list = []
    _stub_kernels(monkeypatch, taken)
    sds = jax.ShapeDtypeStruct
    child = kops.ZChild(elog=sds((k, v), np.float32),
                        values=sds((171520,), np.int32), stride=1,
                        zmap=sds((171520,), np.int32), base=None)
    kops.zstats(sds((512, k), np.float32), sds((14336,), np.int32),
                (child,), tables="alpha")
    assert taken == ["ref"]


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_explain_cli_json(capsys):
    import json
    from repro.analysis.explain import _main
    rc = _main(["--model", "lda", "--docs", "100", "--vocab", "200",
                "--topics", "4", "--mean-len", "20", "--engine", "svi",
                "--batch-docs", "16", "--backend", "pallas", "--json"])
    assert rc == 0
    plan = json.loads(capsys.readouterr().out)
    assert plan["engine"] == "svi" and plan["backend"] == "pallas"
    assert plan["routes"] and plan["caps"]
    assert plan["working_set"]["table_bytes"] > 0


def test_explain_cli_render(capsys):
    from repro.analysis.explain import _main
    rc = _main(["--model", "slda", "--docs", "60", "--vocab", "100",
                "--topics", "4", "--engine", "vmp", "--backend", "pallas"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "EXPLAIN slda" in out
    assert "route=" in out and "HBM/step" in out
