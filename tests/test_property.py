"""Hypothesis property tests on system invariants.

The whole module skips where hypothesis is absent (it is a dev-only
dependency, see requirements-dev.txt); the deterministic suite elsewhere
still runs — tier-1 must collect with zero errors either way.
"""

import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import models  # noqa: E402
from repro.core.partition import lpt_pack, strategy_costs  # noqa: E402


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), k=st.integers(2, 5),
       v=st.integers(5, 40), d=st.integers(2, 15))
def test_elbo_monotone_random_lda(seed, k, v, d):
    """CAVI guarantees a non-decreasing ELBO for ANY corpus and model size."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(20, 200))
    toks = rng.integers(0, v, n).astype(np.int32)
    docs = np.sort(rng.integers(0, d, n)).astype(np.int32)
    m = models.make("lda", alpha=float(rng.uniform(0.05, 2.0)),
                    beta=float(rng.uniform(0.05, 2.0)), K=k, V=v)
    m["x"].observe(toks, segment_ids=docs)
    m.infer(steps=6, seed=seed % 7)
    diffs = np.diff(m.elbo_trace)
    scale = max(abs(m.elbo_trace[0]), 1.0)
    assert (diffs >= -1e-5 * scale).all(), diffs


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 10_000), k=st.integers(2, 4),
       v=st.integers(5, 25), d=st.integers(2, 8))
def test_elbo_monotone_fused_pallas_path(seed, k, v, d):
    """The CAVI monotonicity guarantee must survive the fused zstats
    kernel path (REPRO_FORCE_PALLAS=1 routes the step body through the
    Pallas kernel in interpret mode)."""
    import os
    rng = np.random.default_rng(seed)
    n = int(rng.integers(20, 80))
    toks = rng.integers(0, v, n).astype(np.int32)
    docs = np.sort(rng.integers(0, d, n)).astype(np.int32)
    m = models.make("lda", alpha=0.3, beta=0.3, K=k, V=v)
    m["x"].observe(toks, segment_ids=docs)
    old = os.environ.get("REPRO_FORCE_PALLAS")
    os.environ["REPRO_FORCE_PALLAS"] = "1"
    try:
        m.infer(steps=4, seed=seed % 5)
    finally:
        if old is None:
            os.environ.pop("REPRO_FORCE_PALLAS", None)
        else:
            os.environ["REPRO_FORCE_PALLAS"] = old
    diffs = np.diff(m.elbo_trace)
    scale = max(abs(m.elbo_trace[0]), 1.0)
    assert (diffs >= -1e-5 * scale).all(), diffs


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), n=st.integers(2, 400),
       k=st.integers(2, 6), chunk=st.integers(1, 64))
def test_zstats_chunk_invariance(seed, n, k, chunk):
    """zstats results are invariant (up to float tolerance) to the chunk
    size of the streaming scan — chunking is an implementation detail."""
    import jax.numpy as jnp
    from repro.kernels import ref
    rng = np.random.default_rng(seed)
    d, v = int(rng.integers(1, 20)), int(rng.integers(2, 30))
    et = jnp.asarray(rng.normal(size=(d, k)).astype(np.float32))
    ep = jnp.asarray(rng.normal(size=(k, v)).astype(np.float32))
    rows = jnp.asarray(rng.integers(0, d, n).astype(np.int32))
    vals = jnp.asarray(rng.integers(0, v, n).astype(np.int32))
    ch = (ref.ZChild(ep, vals, 1),)
    one = ref.zstats(et, rows, ch, chunk=10**9)
    many = ref.zstats(et, rows, ch, chunk=chunk)
    np.testing.assert_allclose(float(one[0]), float(many[0]),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(one[1], many[1], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(one[2][0], many[2][0], rtol=1e-4, atol=1e-5)
    # stats conservation: total responsibility mass == unmasked token count
    np.testing.assert_allclose(float(many[1].sum()), n, rtol=1e-4)


@settings(max_examples=50, deadline=None)
@given(seed=st.integers(0, 10_000), m=st.integers(1, 32),
       n=st.integers(1, 500))
def test_lpt_pack_balance(seed, m, n):
    """Greedy LPT: max load <= mean + max weight (and every group placed)."""
    rng = np.random.default_rng(seed)
    w = rng.integers(1, 100, size=n)
    assign = lpt_pack(w, m)
    assert assign.shape == (n,)
    assert (assign >= 0).all() and (assign < m).all()
    loads = np.bincount(assign, weights=w, minlength=m)
    assert loads.max() <= w.sum() / m + w.max() + 1e-9


@settings(max_examples=30, deadline=None)
@given(n=st.integers(1_000, 10_000_000), d=st.integers(10, 10_000),
       k=st.integers(1, 256), m=st.integers(2, 1024))
def test_inferspark_partitioning_dominates(n, d, k, m):
    """Paper Tables 1-2: the tailor-made strategy has no replication of data
    vertices and the smallest (asymptotic) largest-partition bound."""
    costs = strategy_costs(n, d, k, m)
    inf = costs["InferSpark"]
    assert inf["E_Nxi"] == 1.0
    for other in ("1D", "RVC", "CRVC"):
        assert inf["E_Nxi"] <= costs[other]["E_Nxi"] + 1e-9
    # largest partition: O(N/M) beats 1D's O(N) whenever M >= 4
    if m >= 4:
        assert inf["E_NB"] <= costs["1D"]["E_NB"]


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1000), b=st.integers(1, 3),
       s=st.integers(4, 24), h=st.integers(1, 4))
def test_rope_preserves_norm(seed, b, s, h):
    import jax.numpy as jnp
    from repro.models.layers import rope
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(b, s, h, 16)).astype(np.float32))
    pos = jnp.arange(s)[None, :]
    y = rope(x, pos, 10_000.0)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-4)


# -- Pallas kernels vs oracles (moved from test_kernels.py so that module
#    stays hypothesis-free and always collects) ------------------------------

@settings(max_examples=25, deadline=None)
@given(g=st.integers(1, 40), k=st.integers(2, 150),
       scale=st.floats(0.05, 50.0))
def test_dirichlet_expectation_property(g, k, scale):
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.dirichlet_expectation import \
        dirichlet_expectation as de_pallas
    rng = np.random.default_rng(g * 1000 + k)
    a = jnp.asarray(rng.gamma(1.0, scale, size=(g, k)).astype(np.float32)
                    + 1e-2)
    got = de_pallas(a, interpret=True)
    want = ref.dirichlet_expectation(a)
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)
    # invariant: every entry is negative (log of a probability's expectation)
    assert (np.asarray(got) < 0).all()


@settings(max_examples=25, deadline=None)
@given(n=st.integers(1, 60), k=st.integers(1, 200),
       shift=st.floats(-50.0, 50.0))
def test_zstep_property(n, k, shift):
    """``ref.zstep``, the softmax every token-plate route reduces to."""
    import jax.numpy as jnp
    from repro.kernels import ref
    rng = np.random.default_rng(n * 997 + k)
    x = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32) + shift)
    r, lse = ref.zstep(x)
    r = np.asarray(r)
    # rows are distributions; lse is shift-equivariant
    np.testing.assert_allclose(r.sum(-1), 1.0, rtol=1e-5)
    assert (r >= 0).all()
    r2, lse2 = ref.zstep(x - shift)
    np.testing.assert_allclose(np.asarray(lse) - shift, np.asarray(lse2),
                               rtol=1e-4, atol=1e-3)


@settings(max_examples=10, deadline=None)
@given(bh=st.integers(1, 3), nq=st.integers(1, 4), dh=st.sampled_from([8, 16]),
       seed=st.integers(0, 100))
def test_flash_attention_property(bh, nq, dh, seed):
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention as fa
    rng = np.random.default_rng(seed)
    s = nq * 16
    q = jnp.asarray(rng.normal(size=(bh, s, dh)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(bh, s, dh)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(bh, s, dh)).astype(np.float32))
    got = fa(q, k, v, causal=True, block_q=16, block_k=16, interpret=True)
    want = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-5)
    # row 0 attends only to position 0: output equals v[:, 0]
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(v[:, 0]),
                               rtol=1e-5, atol=1e-6)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000),
       nb=st.integers(1, 200).flatmap(
           lambda n: st.tuples(st.just(n), st.integers(1, min(n, 32)))),
       epochs=st.integers(1, 3))
def test_minibatch_sampler_partitions_epoch(seed, nb, epochs):
    """Every epoch visits every group exactly once, whatever the sizes
    the sampler accepts (a batch of more groups than there are is
    refused by design)."""
    from repro.data import MinibatchSampler
    n, b = nb
    s = MinibatchSampler(groups=np.arange(n), batch_size=b, seed=seed)
    for e in range(epochs):
        seen = np.concatenate(
            [s.batch_at(e * s.batches_per_epoch + i)
             for i in range(s.batches_per_epoch)])
        assert np.array_equal(np.sort(seen), np.arange(n))


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 128),
       h=st.integers(1, 8))
def test_shard_ownership_exactly_one_owner(seed, n, h):
    """Every shard has exactly one owner, in range, on a map that is a
    pure function of ``(n_shards, n_hosts, seed)`` — hosts agree on it
    with no communication."""
    from repro.data import shard_ownership
    own = shard_ownership(n, h, seed)
    assert own.shape == (n,) and own.dtype == np.int32
    assert (own >= 0).all() and (own < h).all()
    np.testing.assert_array_equal(own, shard_ownership(n, h, seed))


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 128),
       h=st.integers(1, 7))
def test_shard_ownership_minimal_movement(seed, n, h):
    """Rendezvous hashing: adding host ``h`` moves shards only TO the new
    host (survivors keep everything they had), and removing it restores
    the old map exactly — the elastic-remesh property, shards moved is
    the theoretical minimum."""
    from repro.data import shard_ownership
    before = shard_ownership(n, h, seed)
    after = shard_ownership(n, h + 1, seed)
    moved = before != after
    assert (after[moved] == h).all()
    # leave == inverse of join: recomputing at h hosts is bitwise `before`
    np.testing.assert_array_equal(shard_ownership(n, h, seed), before)
    # expected movement is ~n/(h+1); allow generous slack but catch a
    # reshuffle-everything regression
    assert moved.sum() <= max(8, 4 * n // (h + 1))


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), grow=st.integers(1, 30),
       n=st.integers(1, 100), h=st.integers(1, 6))
def test_shard_ownership_append_stable(seed, grow, n, h):
    """Appending shards (a growing corpus) never reassigns existing ones:
    the map for the first ``n`` shards is a prefix of the map for
    ``n + grow`` — per-shard hashing has no dependence on n_shards."""
    from repro.data import shard_ownership
    np.testing.assert_array_equal(
        shard_ownership(n + grow, h, seed)[:n], shard_ownership(n, h, seed))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1000), n=st.integers(1, 64), e=st.integers(2, 8),
       k=st.integers(1, 3))
def test_moe_router_weights_sum_to_one(seed, n, e, k):
    import jax
    import jax.numpy as jnp
    k = min(k, e)
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(size=(n, e)).astype(np.float32))
    w, ids = jax.lax.top_k(logits, k)
    w = jax.nn.softmax(w, axis=-1)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-5)
