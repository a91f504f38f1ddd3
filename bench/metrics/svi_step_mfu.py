"""The window's share of the chips' roofline: the least time the work of
every SVI step and held-out evaluation in the traced window needs at the
published peaks (``bench/counts.py``: the larger of its operations over
peak FLOP/s and its bytes over HBM bandwidth), over the window's length
times the chips used."""

from bench import peaks


def read(run):
    t, work = run.get("trace"), run.get("work", {}).get("window")
    if not t or not work or t["window_s"] <= 0:
        return None
    least, _ = peaks.least_time(work["flops"], work["bytes"],
                                run["device_kind"])
    return 100.0 * least / (t["window_s"] * run["n_devices"])
