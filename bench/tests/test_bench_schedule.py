"""The open-loop schedule, and latency measured from the due time."""

import threading
import time
import types

import numpy as np
from bench import corpus as gen
from bench import serve


def _cfg():
    return {"K": 4, "V": 50, "alpha": 0.1, "zipf_s": 1.0,
            "mean_doc_tokens": 20, "doc_length_sigma": 0.8}


def _tr(rate):
    return {"rate_per_s": rate, "max_doc_tokens": 64, "deadline_s": 5,
            "clients": 4}


def test_every_seed_offers_the_same_queries_in_another_order():
    maps = gen.topic_maps(4, 50, gen.rng_for(0, 0))
    a = serve.Schedule(_cfg(), _tr(200), 1, 5.0, maps)
    b = serve.Schedule(_cfg(), _tr(200), 2**31 + 3, 5.0, maps)
    assert abs(len(a.due) - 1000) <= 1 and abs(len(b.due) - 1000) <= 1
    assert np.all(np.diff(a.due) >= 0) and a.due[0] == 0 and a.due[-1] < 5
    assert len(a.due) == len(b.due)
    assert np.array_equal(np.sort(a.lengths), np.sort(b.lengths))
    assert not np.array_equal(a.lengths, b.lengths)
    assert a.lengths.max() <= 64
    assert sum(len(a.doc(i)) for i in range(len(a.due))) == a.lengths.sum()


class _SlowGateway:
    """Answers one query at a time, each after ``delay`` seconds."""

    def __init__(self, delay):
        self.delay = delay
        self.lock = threading.Lock()

    def query(self, text, params, tenant, timeout_s):
        with self.lock:
            time.sleep(self.delay)
        return types.SimpleNamespace(value={"doc_ll": np.array([-1.0])})


def test_latency_runs_from_the_due_time():
    """A server that takes 50 ms per query, offered 40 queries a second:
    the queue grows, and each query's latency counts the wait behind the
    earlier ones, not only its own 50 ms."""
    import concurrent.futures as cf
    maps = gen.topic_maps(4, 50, gen.rng_for(0, 0))
    sched = serve.Schedule(_cfg(), _tr(40), 7, 1.0, maps)
    svc = object.__new__(serve.Service)
    svc.tr, svc.text = _tr(40), "PREDICT"
    svc.gw = _SlowGateway(0.05)
    counters = {"batches": 0, "docs": 0, "tokens": 0}
    svc.entry = types.SimpleNamespace(server=types.SimpleNamespace(
        stats=lambda: dict(counters)))
    svc.ctx = types.SimpleNamespace(compile_log=types.SimpleNamespace(
        compiles=0))
    svc.pool = cf.ThreadPoolExecutor(max_workers=8)
    try:
        r = svc.drive(sched, 1.0, False)
    finally:
        svc.pool.shutdown(wait=True)
    lat = r["lat"]
    n = len(sched.due)
    assert n == 40 and not np.isnan(lat).any()
    # served back to back from the first due time: query i is done at
    # about (i + 1) * 50 ms, whatever its own due time
    done = r["done_at"] - r["win"].t0
    assert np.all(np.diff(np.sort(done)) >= 0.045)
    assert np.allclose(lat, done - sched.due, atol=1e-6)
    assert lat.max() > 0.9           # the last waits behind the queue
    assert r["late"].max() < 0.05    # the generator kept its schedule
    assert r["backlog"] > 0          # unanswered at the close
