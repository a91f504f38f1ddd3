"""The trace reduction of ``bench/trace.py`` on a small trace whose
answers are worked out by hand."""

import pathlib

import pytest

from bench import trace
from bench.metrics_common import ZSTATS_KERNEL, kernel_seconds

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "trace_small.json"


def test_interval_algebra():
    assert trace.merge([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.clip([(-5, 5), (8, 20)], 0, 10) == [(0, 5), (8, 10)]
    assert trace.length([(0, 3), (5, 6)]) == 4


def test_summary_of_the_fixture():
    s = trace.load_fixture(FIXTURE).summary()
    ns = 1e-9
    assert s["window_s"] == pytest.approx(1000 * ns)
    # device 0 busy: [0, 350] + [400, 500] + [900, 1000]; device 1 whole
    assert s["devices"][0]["busy_s"] == pytest.approx(550 * ns)
    assert s["busy_s"] == pytest.approx((550 + 1000) / 2 * ns)
    # the two kernel events, summed as they ran (they overlap)
    assert kernel_seconds(s, ZSTATS_KERNEL) == pytest.approx(300 * ns)
    assert s["device_ops"][0][1] == pytest.approx(300 * ns)
    # gaps [350, 400] under the held-out span, [500, 900] under batch
    # assembly (spans are open at their end)
    assert s["idle_gaps"] == [["bench.host_batch", pytest.approx(400 * ns)],
                              ["bench.heldout_elbo", pytest.approx(50 * ns)]]


def test_metric_readers_on_the_fixture():
    from bench import harness
    s = trace.load_fixture(FIXTURE).summary()
    run = {"trace": s, "device_kind": "TPU v5 lite", "n_devices": 2,
           "window_s": s["window_s"], "compiles_in_window": 0,
           "work": {"window": {"flops": 0.0, "bytes": 819e9 * 300e-9},
                    "zstats": {"flops": 0.0, "bytes": 819e9 * 150e-9}}}
    idle = harness.metric_reader("device_idle_share.train")(run)
    assert idle == pytest.approx(100 * (1 - 0.775))
    share = harness.metric_reader("zstats_roofline_share.train")(run)
    assert share == pytest.approx(50.0)
    mfu = harness.metric_reader("svi_step_mfu")(run)
    assert mfu == pytest.approx(100 * 300 / (1000 * 2))
    assert harness.metric_reader("compiles_in_window.train")(run) == 0.0
    # nothing to read: no kernel events
    empty = dict(run, n_devices=1, trace=dict(s, devices=[
        dict(d, by_name={}) for d in s["devices"]]))
    assert harness.metric_reader("zstats_roofline_share.train")(empty) is None


def test_a_trace_without_a_window_or_device_is_refused():
    doc = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["bench.window", 0, 10]]}]}]}
    with pytest.raises(ValueError):
        trace.Trace.from_json(doc).summary()
    with pytest.raises(ValueError):
        trace.Trace.from_json({"planes": []}).window()


def test_summary_of_a_recorded_tpu_trace():
    """30 ms of a held-out evaluation in a traced `train.lda-nytimes`
    window on one v5e chip (the device's op line and the benchmark's
    host spans, recorded in PR 12): op names are HLO text, and the
    token-plate kernel is the step's one `tpu_custom_call`."""
    path = FIXTURE.parent / "trace_tpu_nytimes.json"
    s = trace.load_fixture(path).summary()
    assert s["window_s"] == pytest.approx(0.03)
    assert 0 < s["busy_s"] <= s["window_s"]
    kern = kernel_seconds(s, r'custom_call_target="tpu_custom_call"')
    assert 0 < kern <= s["busy_s"]
    assert kernel_seconds(s, ZSTATS_KERNEL) == pytest.approx(kern)
    assert s["device_ops"][0][1] == pytest.approx(kern)
    assert all(label.startswith("bench.") for label, _ in s["idle_gaps"])
