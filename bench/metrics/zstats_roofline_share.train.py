"""The streamed token-plate kernel's share of its roofline: the least
time its calls in the traced window need at the published peaks (the
work the algorithm needs, ``bench/counts.py``), over the device time of
the fused kernel's events in the trace.  Nothing to read where no such
event ran (the ``ref`` route)."""

from bench import peaks
from bench.metrics_common import ZSTATS_KERNEL, kernel_seconds


def read(run):
    t, work = run.get("trace"), run.get("work", {}).get("zstats")
    if not t or not work:
        return None
    busy = kernel_seconds(t, ZSTATS_KERNEL)
    if busy <= 0:
        return None
    least, _ = peaks.least_time(work["flops"], work["bytes"],
                                run["device_kind"])
    return 100.0 * least / busy
