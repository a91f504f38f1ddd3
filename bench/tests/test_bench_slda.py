"""The SLDA cell on the CPU at a small size: the program against
``bench/reference_slda.py`` (sound runs pass, the bfloat16 reference and
each planted fault fail), the sentence generator, and the work counts."""

import json

import numpy as np
import pytest

from bench import corpus as gen
from bench import corpus_segment as seg
from bench import counts, counts_segment, faults_plan, harness
from bench import reference as ref
from bench.tests.conftest import ROOT, run_small

LIMITS = harness.limits("train.slda-nytimes")


@pytest.fixture
def tiny_slda():
    """The slda-nytimes configuration's shape at K=8, V=300: one step
    signature, a held-out evaluation every third step."""
    with open(ROOT / "bench/configs/slda-nytimes.json") as f:
        cfg = json.load(f)
    cfg.update(K=8, V=300, D=160, N=3200, mean_doc_tokens=20,
               mean_sentence_tokens=4, batch_docs_per_chip=32,
               holdout_docs=32, holdout_every=3, source_tokens=10000)
    with open(ROOT / "bench/traffic/svi-plan.json") as f:
        return cfg, json.load(f)


def test_sound_run_is_correct(tiny_slda):
    out = run_small("train.slda-nytimes", *tiny_slda, lims=LIMITS)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 3 and out["e2e"]["train_tokens_per_s"] > 0


@pytest.mark.parametrize("control", [{"reference_dtype": "bfloat16"},
                                     {"elog_dtype": "bfloat16"}],
                         ids=["reference", "program"])
def test_control_is_not_correct(tiny_slda, control):
    out = run_small("train.slda-nytimes", *tiny_slda, lims=LIMITS,
                    seconds=0, **control)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "sentence_altered"])
def test_fault_is_not_correct(tiny_slda, fault):
    with faults_plan.PLAN[fault]():
        out = run_small("train.slda-nytimes", *tiny_slda, lims=LIMITS,
                        seconds=0)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("seed", [7, 2**31 + 11])
def test_sentences_are_the_same_work_for_every_seed(seed):
    """The cell's sentences: lengths that vary within a document, cuts that
    add up to every document, and per batch and held-out set a sentence
    count that is the same for every seed."""
    train, hold = ref.holdout_split(5120, 1024, seed)
    rng = gen.rng_for(seed, 1)
    lengths = gen.batched_lengths(5120, 332, 0.8, train, hold, 512, rng)
    doc_sents, sent_lengths = seg.sentence_lengths(lengths, 12, rng)
    first = np.concatenate([[0], np.cumsum(doc_sents)])
    tok = np.concatenate([[0], np.cumsum(sent_lengths)])
    np.testing.assert_array_equal(tok[first[1:]] - tok[first[:-1]], lengths)
    assert sent_lengths.min() >= 1 and 11.5 < sent_lengths.mean() < 12.5
    per_batch = [int(doc_sents[b].sum()) for b in train.reshape(8, 512)]
    assert per_batch == [14291, 14291, 14291, 14288, 14284, 14294, 14290,
                         14292]
    assert int(doc_sents[hold].sum()) == 28304
    long_docs = np.flatnonzero(doc_sents >= 5)
    spread = [np.ptp(sent_lengths[first[d]:first[d + 1]])
              for d in long_docs[:50]]
    assert min(spread) > 0


def test_generator_follows_slda():
    """Every token of a sentence is drawn from the sentence's one topic:
    with a Zipf exponent of 60 a topic emits its top-ranked word alone, so
    a sentence holds one word, its topic's."""
    rng = gen.rng_for(3, 1)
    lengths = np.full(40, 30)
    doc_sents, sent_lengths = seg.sentence_lengths(lengths, 6, rng)
    maps = gen.topic_maps(5, 50, rng)
    docs = seg.documents(lengths, doc_sents, sent_lengths, 5, 50, 0.1, 60.0,
                         rng, maps)
    perm, _, b = maps
    top = set(perm[b % 50].tolist())    # each topic's rank-0 word
    sent = np.repeat(np.arange(len(sent_lengths)), sent_lengths)
    words = [set(docs["tokens"][sent == s].tolist())
             for s in range(len(sent_lengths))]
    assert all(len(w) == 1 and w <= top for w in words)
    assert len(set().union(*words)) > 1
    assert docs["tokens"].dtype == np.int32 and len(docs["tokens"]) == 1200


def test_counts_segment_hand_count():
    # 10 tokens in 3 sentences, 2 topics, 3 words, 2 documents:
    # tokens 2 ops x 2 topics x 10; sentences 7 ops x 2 topics x 3;
    # tables (2*2 + 2*3) entries x 2 ops plus their row sums (10);
    # bytes: 2 int32 a token, 1 a sentence, the tables read once and the
    # statistics written once, 4 bytes an entry
    c = counts_segment.zstats(10, 3, 2, 3, 2)
    assert c == {"flops": 40.0 + 42.0 + 20.0 + 10.0,
                 "bytes": 80.0 + 12.0 + 80.0}
    s = counts_segment.svi_step(10, 3, 2, 3, 2)
    lda = counts.svi_step(10, 2, 3, 2)
    lz = counts.zstats(10, 2, 3, 2)
    assert s == {kk: c[kk] + lda[kk] - lz[kk] for kk in c}
    h = counts_segment.local_scorer(10, 3, 2, 3, 2, passes=11)
    assert h == {"flops": 11 * c["flops"], "bytes": 11 * c["bytes"]}


def test_collective_share_reads_collectives_only():
    read = harness.metric_reader("collective_share.train")
    # op names as a v5e trace gives them: the psum of the statistics is
    # an all-reduce instruction named after the psum
    names = {
        "%psum.21 = f32[1000,141043]{0,1:T(8,128)} all-reduce(f32[1000,"
        "141043]{0,1:T(8,128)} %bitcast.8), channel_id=1": 2e8,
        "%all-reduce = (f32[9216,1000]{1,0:T(8,128)S(1)}, f32[]{:T(128)}) "
        "all-reduce(f32[9216,1000]{1,0:T(8,128)S(1)} %x)": 1e8,
        "%all-reduce-start.1 = f32[8]{0} all-reduce-start(%p)": 1e8,
        "%all-gather.2 = f32[4]{0} all-gather(%x)": 1e8,
        "%fusion.5 = f32[] fusion(f32[8]{0} %all-reduce.3), calls=%f": 5e8,
        "%collective-permute-done = f32[2] collective-permute-done(%y)": 1e8,
    }
    run = {"n_devices": 2, "trace": {"window_s": 1.0, "devices": [
        {"by_name": names}, {"by_name": {"%reduce-scatter.1 = f32[2] "
                                         "reduce-scatter(%z)": 2e8}}]}}
    assert read(run) == pytest.approx(100.0 * 0.8 / 2)
    assert read({"trace": None}) is None
