"""``correct`` on a small serving cell: a sound run passes, the control
(the reference in bfloat16 in the program's place) and each planted
fault fail."""

import pytest

from bench import faults, harness
from bench.tests.conftest import run_small

CELL = "serve.lda-nytimes.predict"
LIMITS = harness.limits(CELL)
# the serving cell is not in BENCHMARK.json until it is calibrated on the
# chip (PERF.md, Open questions); the tests run it from its files alone
BENCH = harness.benchmark()
BENCH["workloads"].append({"name": CELL, "config": "lda-nytimes",
                           "traffic": "predict-poisson", "chips": 1,
                           "why": "CPU test of the serving driver"})


def test_sound_run_is_correct(tiny_serve):
    out = run_small(CELL, *tiny_serve, lims=LIMITS, seconds=1.0, bench=BENCH)
    assert out["correct"] and out["attempted"] == 40, out["checks"]


def test_control_is_not_correct(tiny_serve):
    out = run_small(CELL, *tiny_serve, lims=LIMITS, seconds=1.0, bench=BENCH,
                    reference_dtype="bfloat16")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", sorted(faults.SERVE))
def test_fault_is_not_correct(tiny_serve, fault):
    with faults.SERVE[fault]():
        out = run_small(CELL, *tiny_serve, lims=LIMITS, seconds=1.0,
                        bench=BENCH)
    assert not out["correct"], (out["checks"], out["failed"])
