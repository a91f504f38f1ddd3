"""Pallas kernels vs pure-jnp oracles: deterministic shape/dtype sweeps
(interpret mode).  This module stays hypothesis-free so tier-1 always
collects; the hypothesis property tests live in test_property.py behind
``pytest.importorskip("hypothesis")``."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.dirichlet_expectation import dirichlet_expectation as de_pallas

SHAPES = [(1, 2), (3, 5), (7, 128), (33, 96), (128, 130), (257, 4),
          (64, 300), (1000, 3)]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [np.float32])
def test_dirichlet_expectation_allclose(shape, dtype):
    rng = np.random.default_rng(hash(shape) % 2**32)
    a = jnp.asarray(rng.gamma(1.0, 1.0, size=shape).astype(dtype) + 1e-2)
    got = de_pallas(a, interpret=True)
    want = ref.dirichlet_expectation(a)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


FLASH_SHAPES = [(1, 32, 16, 16, 16), (2, 64, 16, 16, 32), (1, 100, 32, 32, 32),
                (3, 96, 8, 64, 32), (2, 48, 64, 16, 16)]


@pytest.mark.parametrize("bh,s,dh,bq,bk", FLASH_SHAPES)
def test_flash_attention_allclose(bh, s, dh, bq, bk):
    from repro.kernels.flash_attention import flash_attention as fa
    rng = np.random.default_rng(bh * 1000 + s)
    q = jnp.asarray(rng.normal(size=(bh, s, dh)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(bh, s, dh)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(bh, s, dh)).astype(np.float32))
    got = fa(q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True)
    want = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# fused zstats: Pallas kernel + chunked oracle vs a dense legacy reference
# ---------------------------------------------------------------------------

def _dense_zstats(elog_prior, prior_rows, children, zmask=None):
    """The pre-fusion step-body semantics, materialized densely: the
    independent reference both the chunked oracle and the kernel must match."""
    import jax
    k = elog_prior.shape[1]
    logits = elog_prior[prior_rows].astype(jnp.float32)
    for c in children:
        if c.base is None and c.stride == 1:
            e = c.elog[:, c.values].T
        else:
            kk = jnp.arange(k, dtype=jnp.int32)
            b = c.base[:, None] if c.base is not None else 0
            e = c.elog[b + c.stride * kk[None, :], c.values[:, None]]
        e = e.astype(jnp.float32)
        if c.mask is not None:
            e = e * c.mask[:, None]
        if c.zmap is not None:
            e = jax.ops.segment_sum(e, c.zmap,
                                    num_segments=prior_rows.shape[0])
        logits = logits + e
    r, lse = ref.zstep(logits)
    if zmask is not None:
        r = r * zmask[:, None]
        lse = lse * zmask
    pstats = jnp.zeros(elog_prior.shape, jnp.float32).at[prior_rows].add(r)
    cstats = []
    for c in children:
        w = r if c.zmap is None else r[c.zmap]
        if c.mask is not None:
            w = w * c.mask[:, None]
        gf, kf = c.elog.shape
        if c.base is None and c.stride == 1:
            cstats.append(jax.ops.segment_sum(w, c.values,
                                              num_segments=kf).T)
        else:
            kk = jnp.arange(k, dtype=jnp.int32)
            b = c.base[:, None] if c.base is not None else 0
            rows = (b + c.stride * kk[None, :]).astype(jnp.int32)
            s = jax.ops.segment_sum(w.ravel(),
                                    (rows * kf + c.values[:, None]).ravel(),
                                    num_segments=gf * kf)
            cstats.append(s.reshape(gf, kf))
    return lse.sum(), pstats, tuple(cstats)


def _zcase(seed, n, k, gp, cfgs, zmask=False, nz=None):
    """Build (elog_prior, prior_rows, children, zmask) from a case spec."""
    rng = np.random.default_rng(seed)
    nz = nz or n
    et = jnp.asarray(rng.normal(size=(gp, k)).astype(np.float32))
    rows = jnp.asarray(rng.integers(0, gp, nz).astype(np.int32))
    children = []
    for (gf, kf, stride, has_base, has_mask, has_zmap) in cfgs:
        nt = n if has_zmap else nz
        vals = jnp.asarray(rng.integers(0, kf, nt).astype(np.int32))
        base = None
        if has_base:
            hi = max(gf - stride * (k - 1), 1)
            base = jnp.asarray(rng.integers(0, hi, nt).astype(np.int32))
        mask = jnp.asarray((rng.random(nt) > 0.25).astype(np.float32)) \
            if has_mask else None
        zmap = jnp.asarray(np.sort(rng.integers(0, nz, nt)).astype(np.int32)) \
            if has_zmap else None
        tab = jnp.asarray(rng.normal(size=(gf, kf)).astype(np.float32))
        children.append(ref.ZChild(tab, vals, stride, zmap, base, mask))
    zm = jnp.asarray((rng.random(nz) > 0.15).astype(np.float32)) \
        if zmask else None
    return et, rows, tuple(children), zm


# (n, k, gp, [(gf, kf, stride, base?, mask?, zmap?)...], zmask, nz)
ZSTATS_CASES = [
    # LDA fast path, several shapes incl. K > 128 (lane boundary)
    (64, 3, 5, [(3, 17, 1, False, False, False)], False, None),
    (300, 4, 20, [(4, 33, 1, False, False, False)], False, None),
    (129, 130, 7, [(130, 5, 1, False, False, False)], False, None),
    # masked tokens (the sliced-program path)
    (200, 4, 12, [(4, 21, 1, False, True, False)], True, None),
    # strided child factors (DCMLDA-shaped: row = base + stride*z)
    (150, 3, 9, [(30, 11, 3, True, False, False)], False, None),
    (150, 3, 9, [(30, 11, 3, True, True, False)], True, None),
    # stride-1 with base (general path even though stride == 1)
    (100, 5, 8, [(5, 12, 1, True, False, False)], False, None),
    # multiple children of one latent
    (120, 3, 6, [(3, 19, 1, False, False, False),
                 (21, 9, 7, True, True, False)], True, None),
    # segment latents (SLDA-shaped zmap): routed to the chunked oracle
    (240, 3, 10, [(3, 15, 1, False, False, True)], False, 40),
    (240, 3, 10, [(3, 15, 1, False, True, True)], True, 40),
]


@pytest.mark.parametrize("case", range(len(ZSTATS_CASES)))
def test_zstats_ref_matches_dense(case):
    n, k, gp, cfgs, zm, nz = ZSTATS_CASES[case]
    et, rows, children, zmask = _zcase(case, n, k, gp, cfgs, zm, nz)
    want = _dense_zstats(et, rows, children, zmask)
    got = ref.zstats(et, rows, children, zmask, chunk=49)  # force chunking
    np.testing.assert_allclose(float(got[0]), float(want[0]),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-5)
    for g, w in zip(got[2], want[2]):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", range(len(ZSTATS_CASES)))
def test_zstats_forced_pallas_parity(case, monkeypatch):
    """ops.zstats under REPRO_FORCE_PALLAS=1 (interpret-mode kernels: the
    fused flat kernel for token-plate latents, the two-phase fused_zmap
    kernel for segment latents) matches the ref oracle across shapes,
    masks, zmap, and child-factor layouts."""
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    n, k, gp, cfgs, zm, nz = ZSTATS_CASES[case]
    et, rows, children, zmask = _zcase(case, n, k, gp, cfgs, zm, nz)
    want = ref.zstats(et, rows, children, zmask)
    got = ops.zstats(et, rows, children, zmask)
    np.testing.assert_allclose(float(got[0]), float(want[0]),
                               rtol=2e-5, atol=2e-4)
    np.testing.assert_allclose(got[1], want[1], rtol=2e-4, atol=2e-5)
    for g, w in zip(got[2], want[2]):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)


def test_zstats_kernel_used_on_flat_latents(monkeypatch):
    """The flat (token-plate) case must actually route through the fused
    Pallas kernel under force-pallas, not silently fall back."""
    import repro.kernels.fused_zstats as fz
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    et, rows, children, zmask = _zcase(0, 64, 3, 5,
                                       [(3, 17, 1, False, False, False)])
    calls = []
    orig = fz.zstats

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(fz, "zstats", spy)
    ops.zstats(et, rows, children, zmask)
    assert calls, "flat latent did not reach the fused Pallas kernel"


def test_zstats_bf16_tables_f32_accum():
    """bf16 Elog tables (the engine's elog_dtype mode): the oracle upcasts
    and accumulates in f32, staying close to the f32 result."""
    et, rows, children, _ = _zcase(1, 300, 4, 20,
                                   [(4, 33, 1, False, False, False)])
    want = ref.zstats(et, rows, children)
    got = ref.zstats(et.astype(jnp.bfloat16), rows,
                     (children[0]._replace(
                         elog=children[0].elog.astype(jnp.bfloat16)),))
    assert got[1].dtype == jnp.float32
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=2e-2)
    np.testing.assert_allclose(got[1], want[1], rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# streamed (large-table) path, zmap kernel, and fused dirichlet_expectation
# ---------------------------------------------------------------------------

def _assert_zstats_close(got, want, rtol=2e-4, atol=2e-4):
    np.testing.assert_allclose(float(got[0]), float(want[0]),
                               rtol=2e-5, atol=2e-4)
    np.testing.assert_allclose(got[1], want[1], rtol=rtol, atol=atol)
    for g, w in zip(got[2], want[2]):
        np.testing.assert_allclose(g, w, rtol=rtol, atol=atol)


def _assert_zstats_bitwise(got, want):
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    for g, w in zip(got[2], want[2]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _gamma_case(case_args):
    """A ZSTATS-style case with positive (concentration-like) tables, for
    the ``tables="alpha"`` mode."""
    et, rows, children, zm = _zcase(*case_args)
    rng = np.random.default_rng(101)

    def pos(t):
        return jnp.asarray((rng.gamma(1.0, 1.0, t.shape) + 1e-2)
                           .astype(np.float32))

    return (pos(et), rows,
            tuple(c._replace(elog=pos(c.elog)) for c in children), zm)


# padded f32 table bytes: child 128 x 33024 ~ 16.9 MiB, prior 70016 x 128
# ~ 35.8 MiB — both > 2x the 8 MiB _TABLE_BUDGET, so they must stream.
STREAM_CASES = {
    "child": (5, 6000, 4, 11, [(4, 33000, 1, False, False, False)], False,
              None),
    "prior": (6, 5000, 16, 70000, [(16, 33, 1, False, False, False)], True,
              None),
}


@pytest.mark.parametrize("name", sorted(STREAM_CASES))
def test_streamed_table_routes_and_matches_ref(name, monkeypatch):
    """Tables >2x _TABLE_BUDGET no longer fall off the fast path: under
    REPRO_FORCE_PALLAS=1 they route through the fused kernel (routing spy),
    with the over-budget table streamed tile-by-tile, and match the ref
    oracle within float tolerance and the blocked oracle bitwise."""
    import repro.kernels.fused_zstats as fz
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    et, rows, children, zmask = _zcase(*STREAM_CASES[name])
    plan = fz._plan(et, children)
    assert plan is not None and plan.target is not None, \
        "case must exercise the streamed path"
    assert plan.target == ("prior" if name == "prior" else 0)
    assert plan.n_tiles > 1

    calls = []
    orig = fz.zstats

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(fz, "zstats", spy)
    got = ops.zstats(et, rows, children, zmask)
    assert calls, "large table did not reach the fused Pallas kernel"
    _assert_zstats_close(got, ref.zstats(et, rows, children, zmask))
    _assert_zstats_bitwise(got, ref.zstats_blocked(et, rows, children,
                                                   zmask))


ZMAP_KERNEL_CASES = [
    # masked specialized zmap child
    (240, 3, 10, [(3, 15, 1, False, True, True)], True, 40),
    # strided (base + stride*z) zmap child
    (200, 3, 9, [(30, 11, 3, True, True, True)], False, 35),
    # multi-child: zmap child + flat (latent-plate) child
    (300, 3, 8, [(3, 12, 1, False, False, True),
                 (21, 9, 7, True, True, False)], True, 50),
]


@pytest.mark.parametrize("case", range(len(ZMAP_KERNEL_CASES)))
def test_zmap_routes_to_two_phase_kernel(case, monkeypatch):
    """Segment latents no longer fall back to the oracle: under
    REPRO_FORCE_PALLAS=1 they take the two-phase fused_zmap kernel
    (routing spy) and match both oracles."""
    import repro.kernels.fused_zmap as fzm
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    n, k, gp, cfgs, zm, nz = ZMAP_KERNEL_CASES[case]
    et, rows, children, zmask = _zcase(1000 + case, n, k, gp, cfgs, zm, nz)

    calls = []
    orig = fzm.zstats_zmap

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(fzm, "zstats_zmap", spy)
    got = ops.zstats(et, rows, children, zmask)
    assert calls, "zmap latent did not reach the two-phase Pallas kernel"
    _assert_zstats_close(got, ref.zstats(et, rows, children, zmask))
    _assert_zstats_bitwise(got, ref.zstats_blocked(et, rows, children,
                                                   zmask))


ALPHA_CASES = [
    ("resident", (20, 300, 4, 20, [(4, 33, 1, False, False, False)], False,
                  None)),
    ("strided-masked", (21, 150, 3, 9, [(30, 11, 3, True, True, False)],
                        True, None)),
    ("streamed-child", (22, 4000, 4, 11,
                        [(4, 33000, 1, False, False, False)], False, None)),
    ("streamed-prior", (23, 4000, 16, 70000,
                        [(16, 33, 1, False, False, False)], True, None)),
    ("zmap", (24, 240, 3, 10, [(3, 15, 1, False, True, True)], True, 40)),
]


@pytest.mark.parametrize("name,case_args", ALPHA_CASES)
def test_fused_dirichlet_expectation_bitwise(name, case_args, monkeypatch):
    """``tables="alpha"`` (dirichlet_expectation fused into the gather)
    is bitwise equal in f32 to the two-call composition — the standalone
    DE kernel materializing every Elog table, then the ``tables="elog"``
    kernel — on the resident, streamed, and zmap paths."""
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    alpha_p, rows, children, zmask = _gamma_case(case_args)
    composed = ops.zstats(
        ops.dirichlet_expectation(alpha_p), rows,
        tuple(c._replace(elog=ops.dirichlet_expectation(c.elog))
              for c in children),
        zmask, tables="elog")
    fused = ops.zstats(alpha_p, rows, children, zmask, tables="alpha")
    _assert_zstats_bitwise(fused, composed)
    # and both agree with the semantic oracle fed the same concentrations
    _assert_zstats_close(fused, ref.zstats(alpha_p, rows, children, zmask,
                                           tables="alpha"))


def test_fused_de_bf16_elog_dtype(monkeypatch):
    """The narrow-table mode composes with the fused expectation: bf16
    concentration tables are upcast in-kernel, digamma/softmax/stats stay
    f32, and the result lands within bf16 noise of the f32 run."""
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    alpha_p, rows, children, zmask = _gamma_case(
        (30, 300, 4, 20, [(4, 33, 1, False, False, False)], False, None))
    want = ops.zstats(alpha_p, rows, children, zmask, tables="alpha")
    got = ops.zstats(
        alpha_p.astype(jnp.bfloat16), rows,
        tuple(c._replace(elog=c.elog.astype(jnp.bfloat16))
              for c in children),
        zmask, tables="alpha")
    assert got[1].dtype == jnp.float32
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=2e-2)
    np.testing.assert_allclose(got[1], want[1], rtol=5e-2, atol=5e-2)
    for g, w in zip(got[2], want[2]):
        np.testing.assert_allclose(g, w, rtol=5e-2, atol=5e-2)


def test_large_vocab_model_routes_streamed_kernel(monkeypatch):
    """End to end: an LDA model whose phi table is >2x _TABLE_BUDGET runs
    its step through the streamed Pallas kernel under REPRO_FORCE_PALLAS=1
    (the acceptance shape for the large-vocabulary fast path)."""
    import repro.kernels.fused_zstats as fz
    from repro.core import models
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    rng = np.random.default_rng(0)
    V = 33000
    toks = rng.integers(0, V, 1200).astype(np.int32)
    docs = np.sort(rng.integers(0, 40, 1200)).astype(np.int32)
    m = models.make("lda", alpha=0.1, beta=0.05, K=4, V=V)
    m["x"].observe(toks, segment_ids=docs)

    seen = []
    orig = fz.zstats

    def spy(table_prior, prior_rows, children, zmask=None, **kw):
        seen.append(fz._plan(table_prior, children,
                             kw.get("tables", "elog")))
        return orig(table_prior, prior_rows, children, zmask, **kw)

    monkeypatch.setattr(fz, "zstats", spy)
    m.infer(steps=1, seed=0)
    assert seen, "model step did not reach the fused Pallas kernel"
    assert any(p is not None and p.target == 0 and p.n_tiles > 1
               for p in seen), "phi was not streamed"
    assert np.isfinite(m.elbo_trace).all()


def test_slda_model_routes_zmap_kernel(monkeypatch):
    """End to end: an SLDA (segment-latent) model runs its step through
    the two-phase zmap Pallas kernel under REPRO_FORCE_PALLAS=1."""
    import repro.kernels.fused_zmap as fzm
    from repro.core import models
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    rng = np.random.default_rng(3)
    S = 60
    sent_doc = np.sort(rng.integers(0, 10, size=S)).astype(np.int32)
    tok_sent = np.repeat(np.arange(S, dtype=np.int32),
                         rng.integers(3, 9, size=S))
    xs = rng.integers(0, 20, size=len(tok_sent)).astype(np.int32)
    m = models.make("slda", alpha=0.2, beta=0.2, K=3, V=20)
    m["x"].observe(xs, segment_ids=tok_sent)
    m.bind("sents", sent_doc)

    calls = []
    orig = fzm.zstats_zmap

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(fzm, "zstats_zmap", spy)
    m.infer(steps=2, seed=0)
    assert calls, "SLDA step did not reach the two-phase Pallas kernel"
    assert np.isfinite(m.elbo_trace).all()
    assert m.elbo_trace[-1] >= m.elbo_trace[0] - 1e-3


def test_plan_rejects_tiles_wider_than_budget():
    """A single row/column wider than a stream tile cannot be tiled along
    the gather axis: _plan must answer None (ref fallback), not hand out
    a layout whose double-buffered tiles blow VMEM.  Shape-only check
    (ShapeDtypeStructs) — these tables would be GBs if materialized."""
    import jax
    import repro.kernels.fused_zstats as fz
    from repro.kernels import ops
    # specialized child, K=8192 topics: one 128-column tile is 4 MiB
    tp = jax.ShapeDtypeStruct((16, 8192), jnp.float32)
    big = ref.ZChild(jax.ShapeDtypeStruct((8192, 40000), jnp.float32),
                     values=None)
    assert fz._plan(tp, (big,)) is None
    assert ops.routing(tp, None, (big,), backend="pallas").path == "ref"
    # streamed-prior flavor: K=70000 lanes, one 8-row tile is >2 MiB
    tp = jax.ShapeDtypeStruct((100000, 70000), jnp.float32)
    small = ref.ZChild(jax.ShapeDtypeStruct((10, 5), jnp.float32),
                       values=None, stride=2)
    assert fz._plan(tp, (small,)) is None


def test_fusable_zmap_requires_n_latent():
    """The (n_latent, K) budget is not derivable from the tables (SLDA can
    have far more sentences than its prior has rows), so routing with an
    unknown n_latent must answer ``ref`` — never claim an over-VMEM
    layout fits."""
    from repro.kernels import ops
    ch = (ref.ZChild(jnp.zeros((3, 5), jnp.float32),
                     jnp.zeros((4,), jnp.int32), 1,
                     zmap=jnp.zeros((4,), jnp.int32)),)
    tp = jnp.zeros((10, 3), jnp.float32)
    assert ops.routing(tp, None, ch, backend="pallas").path == "ref"
    assert ops.routing(tp, None, ch, backend="pallas",
                       n_latent=4).path == "fused-zmap"


def test_zmap_kernel_refuses_streamed_prior():
    """zstats_zmap matches phase-1 logits and the emitted r to latent
    instances positionally, which a bucketed (streamed-table) latent
    layout would permute: direct calls past routing's budget must
    raise, not silently corrupt."""
    import repro.kernels.fused_zmap as fzm
    rng = np.random.default_rng(0)
    nz, k = 200, 16
    tp = jnp.asarray(rng.normal(size=(70000, k)).astype(np.float32))
    rows = jnp.asarray(rng.integers(0, 70000, nz).astype(np.int32))
    ch = (ref.ZChild(jnp.asarray(rng.normal(size=(k, 7))
                                 .astype(np.float32)),
                     jnp.asarray(rng.integers(0, 7, 500).astype(np.int32)),
                     1, zmap=jnp.asarray(np.sort(rng.integers(0, nz, 500))
                                         .astype(np.int32))),)
    with pytest.raises(ValueError, match="streamed"):
        fzm.zstats_zmap(tp, rows, ch, interpret=True)
    with pytest.raises(ValueError, match="streamed"):
        ref.zstats_blocked(tp, rows, ch)


# ---------------------------------------------------------------------------
# local-only passes: child_stats=False
# ---------------------------------------------------------------------------

# (prior rows, K, V, tokens): the NYTimes held-out scorer (1,024 documents,
# 339,643 tokens padded to 256, phi 256 x 102,660), the PubMed one (K=1,000,
# V=141,043, 89 tokens a document) and a VMEM-resident LDA
ROUTE_SHAPES = {
    "nytimes": (1024, 256, 102_660, 339_712),
    "pubmed": (1024, 1000, 141_043, 91_136),
    "resident": (64, 128, 2000, 4096),
}


@pytest.mark.parametrize("name,child_stats,path", [
    ("nytimes", True, "fused-streamed"),
    ("nytimes", False, "ref"),
    ("pubmed", True, "ref"),
    ("pubmed", False, "ref"),
    ("resident", True, "fused"),
    ("resident", False, "fused"),
])
def test_routing_local_only_passes(name, child_stats, path):
    """A call that reads no child statistics leaves the streamed kernel for
    the ref route; every other call routes as with child statistics."""
    import jax
    from repro.kernels import ops
    g, k, v, n = ROUTE_SHAPES[name]
    r = ops.routing(jax.ShapeDtypeStruct((g, k), jnp.float32), None,
                    (ref.ZChild(jax.ShapeDtypeStruct((k, v), jnp.float32),
                                None),),
                    tables="alpha", backend="pallas", n_latent=n,
                    child_stats=child_stats)
    assert r.path == path, r
    if name == "nytimes" and not child_stats:
        assert "local-only" in r.reason and r.n_tiles == 1


# masked flat latents (specialized, strided, two children) and a masked
# segment latent, each in one chunk and scanned over chunks of 49 tokens
@pytest.mark.parametrize("chunk", [ref.ZSTATS_CHUNK, 49])
@pytest.mark.parametrize("case", [3, 5, 7, 9])
def test_ref_zstats_without_child_stats_bitwise(case, chunk):
    """child_stats=False drops the child statistics and nothing else: the
    latent's lse_sum and prior statistics are bitwise those of the full
    pass."""
    n, k, gp, cfgs, zm, nz = ZSTATS_CASES[case]
    et, rows, children, zmask = _zcase(case, n, k, gp, cfgs, zm, nz)
    assert zmask is not None and any(c.mask is not None for c in children)
    full = ref.zstats(et, rows, children, zmask, chunk=chunk)
    local = ref.zstats(et, rows, children, zmask, chunk=chunk,
                       child_stats=False)
    assert local[2] == ()
    _assert_zstats_bitwise(local, full[:2] + ((),))


@pytest.mark.parametrize("name", sorted(STREAM_CASES))
def test_local_only_streamed_call_takes_ref(name, monkeypatch):
    """Under REPRO_FORCE_PALLAS=1 a child_stats=False call over a streamed
    table never enters the fused kernel and is bitwise the ref route's
    lse_sum and prior statistics."""
    import repro.kernels.fused_zstats as fz
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    et, rows, children, zmask = _zcase(*STREAM_CASES[name])
    assert ops.routing(et, rows, children, child_stats=False).path == "ref"

    def refuse(*a, **kw):
        raise AssertionError("local-only pass entered the streamed kernel")

    monkeypatch.setattr(fz, "zstats", refuse)
    got = ops.zstats(et, rows, children, zmask, child_stats=False)
    want = ref.zstats(et, rows, children, zmask)
    _assert_zstats_bitwise(got, want[:2] + ((),))
    assert got[2] == ()


def test_local_only_resident_call_keeps_kernel(monkeypatch):
    """A resident table has no streamed statistics to save: the local-only
    call stays on the fused kernel and returns no child statistics."""
    import repro.kernels.fused_zstats as fz
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    et, rows, children, zmask = _zcase(0, 64, 3, 5,
                                       [(3, 17, 1, False, False, False)])
    calls = []
    orig = fz.zstats

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(fz, "zstats", spy)
    got = ops.zstats(et, rows, children, zmask, child_stats=False)
    full = ops.zstats(et, rows, children, zmask)
    assert len(calls) == 2 and got[2] == ()
    _assert_zstats_bitwise(got, full[:2] + ((),))


# ---------------------------------------------------------------------------
# dispatch: ops.zstats runs what ops.routing names
# ---------------------------------------------------------------------------

# name -> (_zcase args, child_stats, the route routing must give)
DISPATCH_CASES = {
    # a strided child over budget: no table the kernel can stream
    "ref": ((7, 300, 4, 20, [(70000, 33, 3, True, False, False)], False,
             None), True, "ref"),
    "fused": ((0, 64, 3, 5, [(3, 17, 1, False, False, False)], False, None),
              True, "fused"),
    "fused-streamed": (STREAM_CASES["child"], True, "fused-streamed"),
    "fused-zmap": ((1000,) + ZMAP_KERNEL_CASES[0], True, "fused-zmap"),
    "local-only-streamed": (STREAM_CASES["prior"], False, "ref"),
}


@pytest.mark.parametrize("name", sorted(DISPATCH_CASES))
def test_dispatch_follows_routing(name, monkeypatch):
    """Under REPRO_FORCE_PALLAS=1, ``ops.zstats`` runs exactly the one
    implementation ``ops.routing``'s ``path`` names (the fused kernel
    streaming a table exactly when the route is ``fused-streamed``), and
    returns child statistics only when asked."""
    import repro.kernels.fused_zmap as fzm
    import repro.kernels.fused_zstats as fz
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    args, child_stats, path = DISPATCH_CASES[name]
    et, rows, children, zmask = _zcase(*args)
    assert ops.routing(et, rows, children,
                       child_stats=child_stats).path == path

    ran = []

    def spy(mod, fn, label):
        orig = getattr(mod, fn)

        def run(*a, **kw):
            ran.append(label(*a, **kw))
            return orig(*a, **kw)

        monkeypatch.setattr(mod, fn, run)

    spy(ref, "zstats", lambda *a, **kw: "ref")
    spy(fz, "zstats", lambda tp, rows, ch, *a, **kw:
        "fused" if fz._plan(tp, ch, kw.get("tables", "elog")).target is None
        else "fused-streamed")
    spy(fzm, "zstats_zmap", lambda *a, **kw: "fused-zmap")
    got = ops.zstats(et, rows, children, zmask, child_stats=child_stats)
    assert ran == [path]
    assert len(got[2]) == (len(children) if child_stats else 0)


# every ZSTATS case and every fused-expectation case: elog and alpha
# tables, resident, strided, multi-child, streamed and zmap layouts
BLOCKED_CASES = {
    **{f"elog-{i}": ((i,) + c, "elog") for i, c in enumerate(ZSTATS_CASES)},
    **{f"alpha-{name}": (args, "alpha") for name, args in ALPHA_CASES},
}


@pytest.mark.parametrize("name", sorted(BLOCKED_CASES))
def test_kernel_matches_blocked_oracle_bitwise(name, monkeypatch):
    """The interpret-mode kernels equal ``ref.zstats_blocked`` bitwise,
    both called eagerly: the oracle replays each kernel's grid as one
    compiled program."""
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    args, tables = BLOCKED_CASES[name]
    case = _gamma_case(args) if tables == "alpha" else _zcase(*args)
    assert ops.routing(*case[:3], tables=tables).path != "ref"
    _assert_zstats_bitwise(ops.zstats(*case, tables=tables),
                           ref.zstats_blocked(*case, tables=tables))


def test_ops_dispatch_cpu_uses_ref(monkeypatch):
    from repro.kernels import ops
    monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)
    a = jnp.asarray(np.random.default_rng(0).gamma(1, 1, (4, 8))
                    .astype(np.float32) + .01)
    np.testing.assert_allclose(ops.dirichlet_expectation(a),
                               ref.dirichlet_expectation(a), rtol=1e-6)


def test_ops_dispatch_forced_pallas(monkeypatch):
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    a = jnp.asarray(np.random.default_rng(0).gamma(1, 1, (4, 8))
                    .astype(np.float32) + .01)
    np.testing.assert_allclose(ops.dirichlet_expectation(a),
                               ref.dirichlet_expectation(a),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# hoisted (host-side) streamed-path token bucketing
# ---------------------------------------------------------------------------

def test_host_bucketing_matches_traced_bitwise():
    """The numpy bucketing twin must reproduce the traced version
    op-for-op, so a hoisted permutation is bitwise the in-trace one."""
    from repro.kernels.fused_zstats import _bucket, _bucket_host
    rng = np.random.default_rng(0)
    for n, tl, n_tiles, bn in [(1000, 128, 7, 64), (5, 8, 3, 8),
                               (4096, 256, 16, 512), (64, 512, 1, 64)]:
        key = rng.integers(0, tl * n_tiles, n).astype(np.int32)
        traced = _bucket(jnp.asarray(key), n, tl, n_tiles, bn)
        host = _bucket_host(key, n, tl, n_tiles, bn)
        for t, h in zip(traced, host):
            np.testing.assert_array_equal(np.asarray(t), h)


@pytest.mark.parametrize("name", sorted(STREAM_CASES))
def test_host_bucketing_streamed_zstats_bitwise(name, monkeypatch):
    """zstats with the hoisted bucketing equals zstats computing it in
    trace, bitwise, on both streamed flavors."""
    import repro.kernels.fused_zstats as fz
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    et, rows, children, zmask = _zcase(*STREAM_CASES[name])
    bucketing = ops.host_bucketing(et, rows, children)
    assert bucketing is not None, "streamed case must be hoistable"
    assert all(isinstance(b, np.ndarray) for b in bucketing)
    got = ops.zstats(et, rows, children, zmask, bucketing=bucketing)
    want = ops.zstats(et, rows, children, zmask)
    _assert_zstats_bitwise(got, want)
    # a stale bucketing (wrong token count) is rejected, not misapplied
    half = rows.shape[0] // 2
    with pytest.raises(ValueError, match="stale bucketing"):
        fz.zstats(et, rows[:half], tuple(
            c._replace(values=c.values[:half],
                       mask=None if c.mask is None else c.mask[:half])
            for c in children),
            None if zmask is None else zmask[:half],
            interpret=True, bucketing=bucketing)


def test_host_bucketing_none_for_resident_and_traced(monkeypatch):
    """Nothing to hoist: resident layouts and traced index streams both
    answer None (always safe to pass through)."""
    import jax
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    et, rows, children, zmask = _zcase(20, 300, 4, 20,
                                       [(4, 33, 1, False, False, False)])
    assert ops.host_bucketing(et, rows, children) is None  # resident

    et_s, rows_s, children_s, _ = _zcase(*STREAM_CASES["prior"])
    assert ops.host_bucketing(et_s, rows_s, children_s) is not None

    got = []

    @jax.jit
    def probe(r):
        got.append(ops.host_bucketing(et_s, r, children_s))
        return r

    probe(rows_s)
    assert got == [None]                                   # traced key


def test_full_batch_step_hoists_bucketing(monkeypatch):
    """The full-batch engine's step caches a host bucketing on the program
    for a streamed-table latent (the ROADMAP follow-up): the device-side
    argsort leaves the jitted step."""
    from repro.core import models
    from repro.core.runtime import make_step
    from repro.core.vmp import init_state
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    rng = np.random.default_rng(0)
    v = 40000                       # phi (K, V) padded f32 > _TABLE_BUDGET
    m = models.make("lda", alpha=0.1, beta=0.05, K=4, V=v)
    toks = rng.integers(0, v, 3000).astype(np.int32)
    docs = np.sort(rng.integers(0, 20, 3000)).astype(np.int32)
    m["x"].observe(toks, segment_ids=docs)
    prog = m.compile()
    step = make_step(prog, donate=False)
    state, _ = step(init_state(prog, 0))
    cache = prog.meta.get("_zstats_bucketing")
    assert cache and cache.get(("z", 3000)) is not None
    src, slot_tile, blk_tile = cache[("z", 3000)]
    assert isinstance(src, np.ndarray)
    # the cached permutation covers every token exactly once
    assert np.array_equal(np.sort(src[src >= 0]), np.arange(3000))
    for p in state.posteriors.values():
        assert np.isfinite(np.asarray(p)).all()


# ---------------------------------------------------------------------------
# the streamed path's slot layout, against numpy
# ---------------------------------------------------------------------------

def _layout_case(target, n, tables, *, zmask=True, extra_child=False,
                 keys=None, seed=0):
    """A zstats call whose ``target`` table ("prior", "child" or None) is
    streamed; ``keys`` overrides the streamed index stream (prior rows or
    the streamed child's values); ``zmask`` gives the latent and the first
    child a mask; ``extra_child`` adds a strided child with a base and a
    mask."""
    rng = np.random.default_rng(seed)
    k, gp, kf = {"prior": (16, 70000, 33), "child": (4, 11, 33000),
                 None: (4, 20, 33)}[target]

    def tab(shape):
        return jnp.asarray((rng.gamma(1.0, 1.0, shape) + 1e-2)
                           .astype(np.float32))

    rows = rng.integers(0, gp, n).astype(np.int32)
    vals = rng.integers(0, kf, n).astype(np.int32)
    if keys is not None:
        if target == "prior":
            rows = keys.astype(np.int32)
        else:
            vals = keys.astype(np.int32)
    children = [ref.ZChild(tab((k, kf)), jnp.asarray(vals), 1,
                           mask=jnp.asarray((rng.random(n) > 0.25)
                                            .astype(np.float32))
                           if zmask else None)]
    if extra_child:
        stride = 3
        gf = stride * k + 8
        base = rng.integers(0, gf - stride * (k - 1), n).astype(np.int32)
        children.append(ref.ZChild(
            tab((gf, 9)), jnp.asarray(rng.integers(0, 9, n).astype(np.int32)),
            stride, base=jnp.asarray(base),
            mask=jnp.asarray((rng.random(n) > 0.5).astype(np.float32))))
    zm = jnp.asarray((rng.random(n) > 0.15).astype(np.float32)) \
        if zmask else None
    return tab((gp, k)), jnp.asarray(rows), tuple(children), zm


def _numpy_layout(et, rows, children, zmask, plan, tables):
    """The layout's arrays built in numpy from ``_bucket_host`` (streamed)
    or the tokens in order (resident), by plain indexing."""
    from repro.kernels import fused_zstats as fz
    n, bn = rows.shape[0], plan.bn
    if plan.target is None:
        np_ = -(-max(n, 1) // bn) * bn
        src = np.concatenate([np.arange(n), np.full(np_ - n, -1)])
        slot_tile = np.zeros(np_, np.int32)
        blk_tile = np.zeros(np_ // bn, np.int32)
    else:
        key = rows if plan.target == "prior" else \
            children[plan.target].values
        src, slot_tile, blk_tile = fz._bucket_host(
            np.asarray(key), n, plan.tl, plan.n_tiles, bn)

    def ptok(a, dtype, fill=0):
        a = np.asarray(a).astype(dtype)
        return np.where(src >= 0, a[np.clip(src, 0, None)], fill)[:, None] \
            .astype(dtype)

    def pad(t, r, c):
        t = np.asarray(t)
        return np.pad(t, ((0, r - t.shape[0]), (0, c - t.shape[1])),
                      constant_values=1.0 if tables == "alpha" else 0.0)

    tfill = slot_tile * plan.tl
    zm = np.ones(n, np.float32) if zmask is None else zmask
    out = {"prow": ptok(rows, np.int32,
                        tfill if plan.target == "prior" else 0),
           "zm": ptok(zm, np.float32), "blk_tile": blk_tile,
           "ptab": pad(et, plan.gpp, plan.kp), "cvals": [], "cbases": [],
           "cmasks": [], "ctabs": [], "dg0": None}
    for ci, (c, (_, _, gfp, kfp)) in enumerate(zip(children,
                                                   plan.child_dims)):
        out["ctabs"].append(pad(c.elog, gfp, kfp))
        if tables == "alpha" and plan.target == ci:
            d = np.asarray(fz.rowsum_digamma(c.elog))
            out["dg0"] = np.pad(d, (0, plan.kp - d.shape[0]))[:, None]
        out["cvals"].append(ptok(c.values, np.int32,
                                 tfill if plan.target == ci else 0))
        out["cbases"].append(None if c.base is None
                             else ptok(c.base, np.int32))
        out["cmasks"].append(None if c.mask is None
                             else ptok(c.mask, np.float32))
    return out


def _assert_layout_equal(lo, want):
    def same(a, b):
        if b is None:
            assert a is None
            return
        a = np.asarray(a)
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.array_equal(np.atleast_1d(a).view(np.uint8),
                              np.atleast_1d(b).view(np.uint8))

    for f in ("prow", "zm", "blk_tile", "ptab", "dg0"):
        same(getattr(lo, f), want[f])
    for f in ("cvals", "cbases", "cmasks", "ctabs"):
        assert len(getattr(lo, f)) == len(want[f])
        for a, b in zip(getattr(lo, f), want[f]):
            same(a, b)
    assert lo.nblocks * lo.plan.bn == lo.prow.shape[0]


def _tile_keys(counts, tl, hi, seed=0):
    """A streamed index stream of values below ``hi`` with ``counts[t]``
    tokens in tile ``t``, in shuffled order."""
    rng = np.random.default_rng(seed)
    keys = np.concatenate([rng.integers(t * tl, min((t + 1) * tl, hi), c)
                           for t, c in enumerate(counts)])
    return rng.permutation(keys)


# the streamed child's tiles are 2,048 values (17 tiles), the prior's
# 2,048 rows (35 tiles); block_n=128 keeps several blocks in a tile
LAYOUT_CASES = {
    "prior-elog": dict(target="prior", n=3000, tables="elog"),
    "prior-alpha-base-no-zmask": dict(target="prior", n=2500,
                                      tables="alpha", zmask=False,
                                      extra_child=True),
    "child-elog-no-masks": dict(target="child", n=3000, tables="elog",
                                zmask=False),
    "child-alpha-base-masks": dict(target="child", n=2000, tables="alpha",
                                   extra_child=True),
    "child-empty-tiles": dict(target="child", n=700, tables="elog",
                              counts=[300, 0, 0, 250, 0, 0, 0, 0, 0, 150]),
    "child-exact-multiples": dict(target="child", n=896, tables="alpha",
                                  counts=[128, 0, 256, 384, 0, 0, 0, 0, 0, 0,
                                          0, 0, 0, 0, 0, 0, 128]),
    "child-single-tile": dict(target="child", n=1000, tables="alpha",
                              counts=[0] * 16 + [1000]),
    "prior-single-tile-one-token": dict(target="prior", n=1, tables="elog",
                                        counts=[0, 0, 1]),
    "resident-elog-base-masks": dict(target=None, n=300, tables="elog",
                                     extra_child=True),
    "resident-alpha-no-masks": dict(target=None, n=257, tables="alpha",
                                    zmask=False),
}


@pytest.mark.parametrize("name", sorted(LAYOUT_CASES))
def test_layout_matches_numpy_bitwise(name):
    """Every field of the token layout the kernel reads equals, bitwise,
    one built in numpy from the host bucketing by plain indexing — for
    the in-trace layout and for the hoisted (``bucketing=``) one."""
    from repro.kernels import fused_zstats as fz
    spec = dict(LAYOUT_CASES[name])
    counts = spec.pop("counts", None)
    tables = spec["tables"]
    if counts is not None:
        hi = 70000 if spec["target"] == "prior" else 33000
        spec["keys"] = _tile_keys(counts, 2048, hi)
        assert len(spec["keys"]) == spec["n"]
    et, rows, children, zm = _layout_case(**spec)
    lo = fz._layout(et, rows, children, zm, tables=tables, block_n=128)
    want_target = {"prior": "prior", "child": 0, None: None}[spec["target"]]
    assert lo.plan.target == want_target
    if counts is not None:
        assert lo.plan.tl == 2048
    want = _numpy_layout(et, rows, children,
                         None if zm is None else np.asarray(zm),
                         lo.plan, tables)
    _assert_layout_equal(lo, want)
    # the hoisted bucketing, built from the plan in hand as
    # ops.host_bucketing builds it from the route
    if lo.plan.target is not None:
        key = np.asarray(rows if lo.plan.target == "prior"
                         else children[lo.plan.target].values)
        hb = fz._bucket_host(key, key.shape[0], lo.plan.tl,
                             lo.plan.n_tiles, lo.plan.bn)
        _assert_layout_equal(
            fz._layout(et, rows, children, zm, tables=tables, block_n=128,
                       bucketing=hb), want)


def _token_axis_ops(jaxpr, n: int) -> dict:
    """Counts of device loops (``while``, and ``scan`` not fully
    unrolled), of gathers whose result has at least ``n`` elements, and
    of scatters and sorts with an operand or result that large, in a
    jaxpr and every jaxpr it calls."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    got = {"loop": 0, "gather": 0, "placement": 0}

    def size(v):
        return int(np.prod(v.aval.shape))

    def walk(jx):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            out = max(size(v) for v in eqn.outvars)
            big = max([out] + [size(v) for v in eqn.invars
                               if hasattr(v, "aval")]) >= n
            if name == "while" or (name == "scan" and eqn.params["unroll"]
                                   not in (True, eqn.params["length"])):
                got["loop"] += 1
            elif name == "gather" and out >= n:
                got["gather"] += 1
            elif (name == "sort" or name.startswith("scatter")) and big:
                got["placement"] += 1
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else [p]):
                    if isinstance(sub, ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, Jaxpr):
                        walk(sub)
    walk(jaxpr.jaxpr)
    return got


def test_streamed_layout_has_no_loop_and_one_placement():
    """The streamed layout at the NYTimes SVI step's shapes (171,505
    tokens, V=102,660, K=256, 101 tiles): no device loop (the tiles are
    found per block, not by a per-slot binary search), one sort that
    carries the token streams and one gather over the token axis."""
    import jax
    from repro.kernels import fused_zstats as fz
    n, v, k, docs = 171_505, 102_660, 256, 512
    f32, i32 = jnp.float32, jnp.int32

    def layout(prior, rows, phi, words, zm, mask):
        lo = fz._layout(prior, rows, (ref.ZChild(phi, words, 1, mask=mask),),
                        zm, tables="alpha")
        assert lo.plan.target == 0 and lo.plan.n_tiles == 101
        return lo.prow, lo.zm, lo.cvals, lo.cmasks, lo.blk_tile

    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in
              [((docs, k), f32), ((n,), i32), ((k, v), f32), ((n,), i32),
               ((n,), f32), ((n,), f32)]]
    got = _token_axis_ops(jax.make_jaxpr(layout)(*shapes), n)
    assert got == {"loop": 0, "gather": 1, "placement": 1}
    assert "while" not in jax.jit(layout).lower(*shapes).as_text()
