"""Small cells for the CPU tests of the benchmark harness.  Nothing here
touches a TPU: the cells run on the CPU devices JAX gives the tests."""

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def _load(rel):
    with open(ROOT / rel) as f:
        return json.load(f)


@pytest.fixture
def tiny_train():
    """The NYTimes configuration's shape at a small size: one step
    signature, a held-out evaluation every third step."""
    cfg = dict(_load("bench/configs/lda-nytimes.json"))
    cfg.update(K=8, V=300, D=160, N=3200, mean_doc_tokens=20,
               batch_docs_per_chip=32, holdout_docs=32, holdout_every=3,
               source_tokens=10000)
    tr = _load("bench/traffic/svi.json")
    return cfg, tr


@pytest.fixture
def tiny_serve():
    cfg = dict(_load("bench/configs/lda-nytimes.json"))
    cfg.update(K=8, V=300, mean_doc_tokens=20, source_tokens=10000)
    tr = dict(_load("bench/traffic/predict-poisson.json"), rate_per_s=40,
              max_batch_docs=8, max_doc_tokens=64, clients=8, deadline_s=3)
    return cfg, tr


def run_small(workload, cfg, tr, seed=2**31 + 5, seconds=0.5, **kw):
    import time

    import jax

    from bench import harness
    return harness.run_cell(workload, seed, seconds, False,
                            jax.devices()[:1], time.perf_counter(),
                            cfg=cfg, traffic_mix=tr, **kw)
