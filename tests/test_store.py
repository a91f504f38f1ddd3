"""Sharded corpus store: on-disk roundtrip, shard-local reads, sampler
determinism (resident == sharded, resume), prefetch transparency, and the
headline bitwise sharded-vs-resident SVI equivalence."""

import numpy as np
import pytest

from repro.core import models
from repro.core.compiler import slice_arrays
from repro.core.svi import SVI, SVIConfig
from repro.data import (MinibatchSampler, ShardedCorpus, ShardedCorpusWriter,
                        ShardedMinibatchSampler, sharded_template,
                        slice_sharded, write_sharded_corpus)


@pytest.fixture(scope="module")
def store(small_corpus, tmp_path_factory):
    """The shared small corpus written as ~6 on-disk shards."""
    path = tmp_path_factory.mktemp("shards")
    return write_sharded_corpus(small_corpus, str(path), shard_tokens=500)


def _lda():
    return models.make("lda", alpha=0.1, beta=0.05, K=3, V=30)


# ---------------------------------------------------------------------------
# format: write / open / gather
# ---------------------------------------------------------------------------

def test_roundtrip(small_corpus, store):
    assert store.n_docs == 50 and store.n_shards > 1
    r = store.resident()
    np.testing.assert_array_equal(r["tokens"], small_corpus["tokens"])
    np.testing.assert_array_equal(r["doc_ids"], small_corpus["doc_ids"])
    np.testing.assert_array_equal(r["lengths"], small_corpus["lengths"])
    # shards partition the docs contiguously
    shards = store.manifest["shards"]
    assert shards[0]["doc_start"] == 0 and shards[-1]["doc_end"] == 50
    assert all(a["doc_end"] == b["doc_start"]
               for a, b in zip(shards, shards[1:]))


def test_reopen_and_gather(small_corpus, store):
    sc = ShardedCorpus.open(store.path)
    docs = np.array([3, 11, 12, 13, 40])
    exp = np.concatenate([small_corpus["tokens"]
                          [small_corpus["doc_ids"] == d] for d in docs])
    np.testing.assert_array_equal(sc.gather_tokens(docs), exp)


def test_gather_touches_only_needed_shards(store):
    sc = ShardedCorpus.open(store.path)
    first = store.manifest["shards"][0]
    sc.gather_tokens(np.arange(first["doc_end"] - 1))
    assert set(sc._mmaps) == {0}          # later shards never opened
    assert sc.bytes_read == int(store.offsets[first["doc_end"] - 1]) * 4


def test_streaming_writer_matches_one_shot(small_corpus, tmp_path):
    """Chunked ingestion produces the same corpus as one-shot conversion."""
    w = ShardedCorpusWriter(str(tmp_path / "chunked"), shard_tokens=500)
    lo = 0
    for chunk in np.array_split(np.arange(50), 7):
        n = int(small_corpus["lengths"][chunk].sum())
        w.add_docs(small_corpus["tokens"][lo:lo + n],
                   small_corpus["lengths"][chunk])
        lo += n
    sc = w.close()
    r = sc.resident()
    np.testing.assert_array_equal(r["tokens"], small_corpus["tokens"])
    np.testing.assert_array_equal(r["lengths"], small_corpus["lengths"])


def test_writer_validates(tmp_path):
    w = ShardedCorpusWriter(str(tmp_path / "w"))
    with pytest.raises(ValueError):
        w.add_docs(np.arange(5, dtype=np.int32), [2, 2])   # lengths mismatch
    with pytest.raises(ValueError):
        ShardedCorpusWriter(str(tmp_path / "w2")).close()  # empty corpus
    with pytest.raises(ValueError):                        # unsorted doc_ids
        write_sharded_corpus({"tokens": np.ones(4, np.int32),
                              "doc_ids": np.array([1, 0, 1, 0])},
                             str(tmp_path / "w3"))
    with pytest.raises(FileNotFoundError):
        ShardedCorpus.open(str(tmp_path / "nowhere"))


# ---------------------------------------------------------------------------
# sharded slicing == resident slicing
# ---------------------------------------------------------------------------

def test_slice_sharded_bitwise(small_corpus, store, lda_program):
    tmpl = sharded_template(_lda(), store)
    pad = (lambda name, n: -(-max(n, 1) // 64) * 64)
    for groups in (np.arange(50), np.array([3, 17, 4, 44, 9]),
                   np.array([0])):
        for caps_fn in (None, pad):
            a1, d1, c1, n1 = slice_arrays(lda_program, groups, caps_fn)
            a2, d2, c2, n2 = slice_sharded(tmpl, store, groups, caps_fn)
            assert c1 == c2 and n1 == n2
            for k in a1:
                for kk, x in a1[k].items():
                    if x is None:
                        assert a2[k][kk] is None
                    else:
                        assert x.dtype == a2[k][kk].dtype
                        np.testing.assert_array_equal(x, a2[k][kk])
            for k in d1:
                for kk, x in d1[k].items():
                    np.testing.assert_array_equal(x, d2[k][kk])


def test_sharded_caps_probe_matches_slicer(store):
    """The distributed path's I/O-free caps probe must predict exactly the
    caps slice_sharded realizes (shared-caps bitwise contract)."""
    from repro.data.store import sharded_caps
    tmpl = sharded_template(_lda(), store)
    for groups in (np.arange(50), np.array([3, 17, 4, 44, 9]),
                   np.array([0])):
        assert sharded_caps(tmpl, store, groups) == \
            slice_sharded(tmpl, store, groups, None)[2]


def test_template_matches_resident_program(store, lda_program):
    tmpl = sharded_template(_lda(), store)
    assert tmpl.meta["sharded"] and tmpl.meta["pstar_size"] == 50
    for name, d in lda_program.dirichlets.items():
        t = tmpl.dirichlets[name]
        assert (t.g, t.k) == (d.g, d.k)
        np.testing.assert_array_equal(t.prior, d.prior)
    assert tmpl.vertex_layout == lda_program.vertex_layout
    assert tmpl.plate_sizes == lda_program.plate_sizes


@pytest.mark.parametrize("name,kw", [
    ("naive_bayes", dict(alpha=1.0, beta=0.3, C=3, V=30)),  # doc-level latent
    ("dcmlda", dict(alpha=0.4, beta=0.4, K=3, V=30)),       # per-doc rows
])
def test_template_rejects_non_token_plate_models(store, name, kw):
    with pytest.raises(ValueError, match="sharded|token plate"):
        sharded_template(models.make(name, **kw), store)


def test_template_rejects_undersized_vocab(store):
    with pytest.raises(ValueError, match="vocab"):
        sharded_template(models.make("lda", alpha=0.1, beta=0.05,
                                     K=3, V=5), store)


# ---------------------------------------------------------------------------
# sampler determinism + prefetch
# ---------------------------------------------------------------------------

def test_sharded_sampler_matches_resident_order(store):
    """Same (seed, epoch) -> identical batch order, resident vs sharded."""
    groups = np.arange(store.n_docs)
    res = MinibatchSampler(groups=groups, batch_size=8, seed=4)
    sh = ShardedMinibatchSampler(corpus=store, groups=groups, batch_size=8,
                                 seed=4)
    assert sh.batches_per_epoch == res.batches_per_epoch
    for t in range(3 * res.batches_per_epoch):
        np.testing.assert_array_equal(res.batch_at(t), sh.batch_at(t))


def test_sharded_sampler_resume_mid_schedule(store):
    """host_batch_at(t..) from a fresh sampler reproduces the remaining
    schedule of a sampler that already consumed steps 0..t-1."""
    def mk():
        return ShardedMinibatchSampler(
            corpus=store, groups=np.arange(store.n_docs), batch_size=7,
            seed=2, loader=store.gather_tokens)
    full, resumed = mk(), mk()
    want = [full.host_batch_at(t) for t in range(9)]
    got = [resumed.host_batch_at(t) for t in range(4, 9)]
    for w, g in zip(want[4:], got):
        np.testing.assert_array_equal(w, g)
    full.close(), resumed.close()


def test_prefetch_is_transparent(store):
    """Prefetch on/off yields identical host batches, and prefetch-thread
    exceptions surface at the matching get."""
    def mk(prefetch, loader=store.gather_tokens):
        return ShardedMinibatchSampler(
            corpus=store, groups=np.arange(store.n_docs), batch_size=10,
            seed=0, loader=loader, prefetch=prefetch)
    on, off = mk(True), mk(False)
    for t in range(12):
        np.testing.assert_array_equal(on.host_batch_at(t),
                                      off.host_batch_at(t))
    on.close()

    calls = {"n": 0}

    def boom(groups):
        calls["n"] += 1
        if calls["n"] > 1:
            raise RuntimeError("loader failed")
        return groups
    bad = mk(True, loader=boom)
    bad.host_batch_at(0)                  # ok; schedules the failing t=1
    with pytest.raises(RuntimeError, match="loader failed"):
        bad.host_batch_at(1)              # prefetched exception re-raises
    bad.close()


def test_prefetch_close_abandons_blocked_loader(store):
    """Regression: close() used to join the prefetch worker with no
    timeout, so a loader blocked on a hung filesystem (or a dead writer's
    refresh) hung shutdown forever.  Now the worker is abandoned after the
    timeout (close returns False), it can never write into newer state,
    and a clean close leaks no prefetch threads."""
    import threading
    import time
    release = threading.Event()
    entered = threading.Event()

    def stuck(groups):
        entered.set()
        release.wait()                    # a hung shard read
        return groups

    s = ShardedMinibatchSampler(corpus=store, groups=np.arange(store.n_docs),
                                batch_size=8, seed=0, loader=stuck)
    # schedule the worker directly (get() itself would block on the stuck
    # synchronous load before ever reaching the prefetcher)
    s._prefetcher._schedule(0)
    assert entered.wait(timeout=10)
    t0 = time.monotonic()
    assert s.close(timeout=0.2) is False      # worker abandoned, not joined
    assert time.monotonic() - t0 < 5
    # the abandoned worker finishing late must not resurrect any state
    release.set()
    time.sleep(0.05)
    assert s._prefetcher._thread is None and s._prefetcher._box is None
    # clean path: a drained close really joins — no leaked threads
    s2 = ShardedMinibatchSampler(corpus=store,
                                 groups=np.arange(store.n_docs),
                                 batch_size=8, seed=0,
                                 loader=store.gather_tokens)
    s2.host_batch_at(0)
    assert s2.close() is True
    assert not [th for th in threading.enumerate()
                if th.name == "sharded-corpus-prefetch" and th.is_alive()]


# ---------------------------------------------------------------------------
# SVI: sharded == resident, bitwise
# ---------------------------------------------------------------------------

def test_sharded_svi_bitwise_equals_resident(small_corpus, store,
                                             lda_program):
    cfg = SVIConfig(batch_size=12, holdout_frac=0.1, holdout_every=5,
                    pad_multiple=64, seed=0)
    res = SVI(lda_program, cfg)
    s_res, h_res = res.fit(steps=9)
    sh = SVI(_lda(), cfg, corpus=ShardedCorpus.open(store.path))
    s_sh, h_sh = sh.fit(steps=9)
    sh.close()
    np.testing.assert_array_equal(res.train, sh.train)
    np.testing.assert_array_equal(res.holdout, sh.holdout)
    for n in s_res.posteriors:
        np.testing.assert_array_equal(np.asarray(s_res.posteriors[n]),
                                      np.asarray(s_sh.posteriors[n]))
    assert h_res["elbo"] == h_sh["elbo"]
    assert h_res["heldout"] == h_sh["heldout"]
    assert sh.sampler.peak_buffer_bytes > 0


def test_engine_api_out_of_core(store):
    from repro.core import make_engine
    m = _lda()
    result = make_engine("svi", steps=6, batch_size=16, holdout_frac=0.1,
                         corpus=ShardedCorpus.open(store.path)).fit(m)
    # the caller's model really stays unobserved (templating deep-copies)
    assert not m.observations and not m.net.rvs["x"].observed
    assert result.backend == "svi"
    assert len(result.elbo_trace) == 6
    assert np.isfinite(result.heldout_elbo)
    assert result.topics("phi").shape == (3, 30)
    with pytest.raises(ValueError, match="resident"):
        make_engine("vmp", corpus=ShardedCorpus.open(store.path)).fit(_lda())


def test_build_infer_step_out_of_core(store):
    from repro.core.engine import EngineConfig
    from repro.core.runtime import build_infer_step
    step_fn, state = build_infer_step(
        _lda(), EngineConfig(backend="svi", batch_size=16, seed=0),
        corpus=ShardedCorpus.open(store.path))
    for _ in range(2):
        state, elbo = step_fn(state)
    assert np.isfinite(float(elbo)) and int(state.step) == 2
    step_fn.svi.close()


# ---------------------------------------------------------------------------
# sentence structure: SLDA on the sharded path
# ---------------------------------------------------------------------------

def _sentences(lengths, seed=1):
    """Each document cut at random into about one sentence per 8 tokens
    (a document of a few tokens keeps one sentence)."""
    rng = np.random.default_rng(seed)
    doc_sents, sent_lengths = [], []
    for n in np.asarray(lengths):
        s = max(1, int(round(n / 8)))
        cuts = np.sort(rng.choice(np.arange(1, n), s - 1, replace=False))
        sent_lengths.extend(np.diff(np.concatenate([[0], cuts, [n]])))
        doc_sents.append(s)
    return np.asarray(doc_sents), np.asarray(sent_lengths)


@pytest.fixture(scope="module")
def slda_corpus(small_corpus):
    doc_sents, sent_lengths = _sentences(small_corpus["lengths"])
    return dict(small_corpus, doc_sents=doc_sents, sent_lengths=sent_lengths)


@pytest.fixture(scope="module")
def slda_store(slda_corpus, tmp_path_factory):
    path = tmp_path_factory.mktemp("slda_shards")
    return write_sharded_corpus(slda_corpus, str(path), shard_tokens=500)


def _slda():
    return models.make("slda", alpha=0.1, beta=0.05, K=3, V=30)


@pytest.fixture(scope="module")
def slda_program(slda_corpus):
    """The resident SLDA program over the same corpus and sentences."""
    n_s = len(slda_corpus["sent_lengths"])
    m = _slda()
    m["x"].observe(slda_corpus["tokens"],
                   segment_ids=np.repeat(np.arange(n_s),
                                         slda_corpus["sent_lengths"]))
    m.bind("sents", np.repeat(np.arange(len(slda_corpus["doc_sents"])),
                              slda_corpus["doc_sents"]))
    return m.compile()


def test_sentences_roundtrip(slda_corpus, slda_store):
    sc = ShardedCorpus.open(slda_store.path)
    assert sc.manifest["n_sents"] == len(slda_corpus["sent_lengths"])
    r = sc.resident()
    np.testing.assert_array_equal(r["doc_sents"], slda_corpus["doc_sents"])
    np.testing.assert_array_equal(r["sent_lengths"],
                                  slda_corpus["sent_lengths"])
    np.testing.assert_array_equal(r["tokens"], slda_corpus["tokens"])


@pytest.mark.parametrize("groups", [np.arange(50), np.array([3, 17, 4, 44,
                                                            9]),
                                    np.array([0])])
@pytest.mark.parametrize("padded", [False, True])
def test_slda_slice_sharded_bitwise(slda_store, slda_program, groups,
                                    padded):
    """Sentence prior rows, zmaps, values, masks, sentinels and caps are
    bitwise what the resident slicer gives."""
    pad = (lambda name, n: -(-max(n, 1) // 64) * 64) if padded else None
    tmpl = sharded_template(_slda(), slda_store)
    a1, d1, c1, n1 = slice_arrays(slda_program, groups, pad)
    a2, d2, c2, n2 = slice_sharded(tmpl, slda_store, groups, pad)
    assert list(c1.items()) == list(c2.items()) and n1 == n2
    assert a1["x"]["zmap"] is not None
    for k in a1:
        assert list(a1[k]) == list(a2[k])
        for kk, x in a1[k].items():
            if x is None:
                assert a2[k][kk] is None
            else:
                assert x.dtype == a2[k][kk].dtype
                np.testing.assert_array_equal(x, a2[k][kk])
    for k in d1:
        for kk, x in d1[k].items():
            np.testing.assert_array_equal(x, d2[k][kk])
    from repro.data.store import sharded_caps
    assert sharded_caps(tmpl, slda_store, groups) == \
        slice_sharded(tmpl, slda_store, groups, None)[2]


def test_slda_template_matches_resident_program(slda_store, slda_program):
    tmpl = sharded_template(_slda(), slda_store)
    assert tmpl.latents[0].n == slda_program.latents[0].n
    assert tmpl.vertex_layout == slda_program.vertex_layout
    assert tmpl.plate_sizes == slda_program.plate_sizes
    with pytest.raises(TypeError, match="slice_sharded"):
        np.asarray(tmpl.latents[0].children[0].zmap)


def test_sharded_slda_svi_bitwise_equals_resident(slda_store, slda_program):
    """10 steps and two held-out scores: the sharded SLDA fit is bitwise
    the resident one."""
    cfg = SVIConfig(batch_size=12, holdout_frac=0.1, holdout_every=5,
                    pad_multiple=64, seed=0)
    res = SVI(slda_program, cfg)
    s_res, h_res = res.fit(steps=10)
    sh = SVI(_slda(), cfg, corpus=ShardedCorpus.open(slda_store.path))
    s_sh, h_sh = sh.fit(steps=10)
    sh.close()
    for n in s_res.posteriors:
        np.testing.assert_array_equal(np.asarray(s_res.posteriors[n]),
                                      np.asarray(s_sh.posteriors[n]))
    assert h_res["elbo"] == h_sh["elbo"]
    assert len(h_sh["heldout"]) == 2
    assert h_res["heldout"] == h_sh["heldout"]


def test_manifest_without_sentences_opens_as_lda(small_corpus, tmp_path):
    """A store whose manifest predates sentence structure (no ``n_sents``,
    no sentence files) opens and trains as an LDA corpus; SLDA asks for
    sentence lengths."""
    import json
    import os
    sc = write_sharded_corpus(small_corpus, str(tmp_path), shard_tokens=500)
    with open(os.path.join(sc.path, "manifest.json")) as fh:
        manifest = json.load(fh)
    old_keys = ["format", "version", "commit", "n_docs", "n_tokens", "vocab",
                "dtype", "shards", "writer"]
    assert list(manifest) == old_keys
    assert set(os.listdir(sc.path)) == {"manifest.json", "lengths.npy"} | {
        s["path"] for s in manifest["shards"]}
    old = ShardedCorpus.open(sc.path)
    assert old.sentences is None and "doc_sents" not in old.resident()
    svi = SVI(_lda(), SVIConfig(batch_size=16, seed=0), corpus=old)
    _, hist = svi.fit(steps=2)
    svi.close()
    assert np.isfinite(hist["elbo"]).all()
    with pytest.raises(ValueError, match="sentence lengths"):
        sharded_template(_slda(), old)


def test_writer_sentences_validate_and_reopen(slda_corpus, tmp_path):
    toks, lens = slda_corpus["tokens"], slda_corpus["lengths"]
    ds, sl = slda_corpus["doc_sents"], slda_corpus["sent_lengths"]
    w = ShardedCorpusWriter(str(tmp_path / "bad"))
    with pytest.raises(ValueError, match="both"):
        w.add_docs(toks, lens, sent_lengths=sl)
    bad = sl.copy()
    bad[0] += 1
    with pytest.raises(ValueError, match="document 0"):
        w.add_docs(toks, lens, bad, ds)
    # two chunks, a commit between, a reopen: the sentences carry on
    n1 = int(lens[:20].sum())
    s1 = int(ds[:20].sum())
    w = ShardedCorpusWriter(str(tmp_path / "ok"), shard_tokens=500)
    w.add_docs(toks[:n1], lens[:20], sl[:s1], ds[:20])
    live = w.commit()
    with pytest.raises(ValueError, match="every chunk"):
        w.add_docs(toks[n1:], lens[20:])
    w2 = ShardedCorpusWriter.reopen(str(tmp_path / "ok"))
    w2.add_docs(toks[n1:], lens[20:], sl[s1:], ds[20:])
    w2.close()
    assert live.refresh()
    np.testing.assert_array_equal(live.sent_lengths, sl)
    np.testing.assert_array_equal(live.doc_sents, ds)
    np.testing.assert_array_equal(live.sent_offsets[1:], np.cumsum(ds))
