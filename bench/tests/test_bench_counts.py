"""The work counts of ``bench/counts.py`` against hand counts."""

import pytest

from bench import counts, peaks


def test_zstats_hand_count():
    # 10 tokens, 2 topics, 3 words, 2 documents:
    # tokens 8 ops x 2 topics x 10; tables (2*2 + 2*3) entries x 2 ops,
    # plus their row sums (10); bytes: 2 int32 per token, the tables
    # read once and the statistics written once, 4 bytes an entry
    c = counts.zstats(10, 2, 3, 2)
    assert c == {"flops": 160.0 + 20.0 + 10.0, "bytes": 80.0 + 80.0}


def test_step_and_scorer_hand_count():
    z = counts.zstats(10, 2, 3, 2)
    s = counts.svi_step(10, 2, 3, 2)
    # ELBO terms 4 ops per entry of both tables, blend 5 per topic entry;
    # rows gathered and written back, topic table read for its ELBO term,
    # and read, read (statistics) and written by the blend
    assert s["flops"] == z["flops"] + 4 * (6 + 4) + 5 * 6
    assert s["bytes"] == z["bytes"] + 8 * 4 + 4 * 6 + 12 * 6
    h = counts.local_scorer(10, 2, 3, 2, passes=11)
    assert h == {"flops": 11 * z["flops"], "bytes": 11 * z["bytes"]}
    assert counts.add(z, z) == {"flops": 2 * z["flops"],
                                "bytes": 2 * z["bytes"]}


def test_least_time_names_its_bound():
    t, bound = peaks.least_time(197e12, 1.0, "TPU v5 lite")
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = peaks.least_time(1.0, 819e9, "TPU v5 lite")
    assert (t, bound) == (pytest.approx(1.0), "memory")
    with pytest.raises(KeyError):
        peaks.least_time(1.0, 1.0, "TPU v99")
