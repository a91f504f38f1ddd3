"""``correct`` on a small training cell: sound runs pass, the control
(the program's bfloat16 table path) and each planted fault fail."""

import numpy as np
import pytest

from bench import faults, harness
from bench.tests.conftest import run_small

LIMITS = harness.limits("train.lda-nytimes")


def test_sound_run_is_correct(tiny_train):
    out = run_small("train.lda-nytimes", *tiny_train, lims=LIMITS)
    assert out["correct"], out["checks"]


def test_control_is_not_correct(tiny_train):
    out = run_small("train.lda-nytimes", *tiny_train, lims=LIMITS,
                    elog_dtype="bfloat16")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_fault_is_not_correct(tiny_train, fault):
    with faults.TRAIN[fault]():
        out = run_small("train.lda-nytimes", *tiny_train, lims=LIMITS)
    assert not out["correct"], out["checks"]


def test_signatures_are_the_engines(tiny_train, tmp_path):
    """``step_signature`` predicts the padded shapes the engine's own
    batches take, so set-up warms what the window runs: one shape, since
    every batch holds the same tokens."""
    from repro.core import models
    from repro.core.svi import SVI, SVIConfig
    from repro.data import write_sharded_corpus

    from bench import corpus as gen
    from bench import reference as ref
    from bench.train import Schedule, step_signature
    cfg, _ = tiny_train
    seed = 2**31 + 1
    rng = gen.rng_for(seed, 1)
    train, hold = ref.holdout_split(cfg["D"], 32, seed)
    lengths = gen.batched_lengths(cfg["D"], cfg["mean_doc_tokens"],
                                  cfg["doc_length_sigma"], train, hold, 32,
                                  rng)
    docs = gen.documents(lengths, cfg["K"], cfg["V"], 0.1, 1.0, rng)
    store = write_sharded_corpus(docs, str(tmp_path), vocab=cfg["V"])
    svi = SVI(models.make("lda", alpha=0.1, beta=0.05, K=cfg["K"],
                          V=cfg["V"]),
              SVIConfig(batch_size=32, pad_multiple=64,
                        holdout_frac=32 / cfg["D"], prefetch=False,
                        shuffle=False, seed=seed), corpus=store)
    sched = Schedule(docs["lengths"], 32, 32, seed)
    for t in range(12):
        assert np.array_equal(svi.sampler.batch_at(t), sched.docs(t))
        _, caps, n_tok, _ = svi.sampler.host_batch_at(t)
        assert n_tok == sched.tokens(t)
        assert (caps["theta"], caps["z"]) == step_signature(sched, t, 64)
    assert len({step_signature(sched, t, 64) for t in range(12)}) == 1
    svi.close()
