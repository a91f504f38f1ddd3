"""Reduction of a JAX profiler trace to device busy time, kernel time
and idle gaps labelled by host spans.

A trace is planes of lines of events (name, start, end in nanoseconds on
one clock).  Device planes are named ``/device:TPU:<n>``; the operations
the device ran are the events of their ``XLA Ops`` line.  Host spans that
the benchmark records around its own calls are events named ``bench.*``
on the host planes; ``bench.window`` bounds the measured window.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


def merge(intervals) -> list:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b) -> list:
    """Parts of merged intervals ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


class Trace:
    """A reduced profiler trace."""

    def __init__(self, planes: list):
        self.planes = planes

    # -- loading -------------------------------------------------------

    @classmethod
    def from_dir(cls, log_dir: str) -> "Trace":
        """The newest ``.xplane.pb`` under ``log_dir``."""
        from jax.profiler import ProfileData
        paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        pd = ProfileData.from_file(paths[-1])
        planes = []
        for p in pd.planes:
            lines = []
            for ln in p.lines:
                lines.append(Line(ln.name, [
                    Event(ev.name, float(ev.start_ns),
                          float(ev.start_ns) + float(ev.duration_ns))
                    for ev in ln.events]))
            planes.append(Plane(p.name, lines))
        return cls(planes)

    @classmethod
    def from_json(cls, doc: dict) -> "Trace":
        """The fixture format: ``{"planes": [{"name", "lines": [{"name",
        "events": [[name, start_ns, end_ns], ...]}]}]}``."""
        return cls([Plane(p["name"], [Line(ln["name"], [
            Event(n, float(s), float(e)) for n, s, e in ln["events"]])
            for ln in p["lines"]]) for p in doc["planes"]])

    # -- structure -----------------------------------------------------

    def device_planes(self) -> list:
        return sorted((p for p in self.planes if DEVICE_PLANE.match(p.name)),
                      key=lambda p: int(DEVICE_PLANE.match(p.name).group(1)))

    def ops(self, plane: Plane) -> list:
        return [ev for ln in plane.lines if ln.name == OPS_LINE
                for ev in ln.events]

    def host_spans(self) -> list:
        return [ev for p in self.planes if p.name.startswith("/host:")
                for ln in p.lines for ev in ln.events
                if ev.name.startswith("bench.")]

    def window(self) -> tuple:
        spans = [ev for ev in self.host_spans() if ev.name == WINDOW]
        if not spans:
            raise ValueError(f"the trace holds no {WINDOW} span")
        w = max(spans, key=lambda ev: ev.end - ev.start)
        return w.start, w.end

    # -- reductions ----------------------------------------------------

    def summary(self) -> dict:
        """``window_s``; per device (``devices``) ``busy_s`` and the
        nanoseconds of each op name (``by_name``); the mean ``busy_s``
        over the devices; the ten device-0 ops that took most time; and
        device 0's idle time by the innermost ``bench.*`` host span open
        at each gap's midpoint."""
        lo, hi = self.window()
        devs = []
        for p in self.device_planes():
            ops = [ev for ev in self.ops(p) if ev.end > lo and ev.start < hi]
            busy = merge(clip([(ev.start, ev.end) for ev in ops], lo, hi))
            by_name: dict = {}
            for ev in ops:
                s, e = max(ev.start, lo), min(ev.end, hi)
                by_name[ev.name] = by_name.get(ev.name, 0.0) + (e - s)
            devs.append({
                "plane": p.name, "busy": busy,
                "busy_s": length(busy) * 1e-9,
                "by_name": by_name})
        if not devs:
            raise ValueError("the trace holds no TPU device plane")
        n = len(devs)
        top = sorted(devs[0]["by_name"].items(), key=lambda kv: -kv[1])[:10]
        return {
            "window_s": (hi - lo) * 1e-9,
            "busy_s": sum(d["busy_s"] for d in devs) / n,
            "device_ops": [[name, t * 1e-9] for name, t in top],
            "idle_gaps": self._idle_by_span(devs[0]["busy"], lo, hi),
            "devices": [{k: d[k] for k in ("plane", "busy_s", "by_name")}
                        for d in devs],
        }

    def _idle_by_span(self, busy, lo, hi) -> list:
        gaps = subtract([(lo, hi)], busy)
        spans = [ev for ev in self.host_spans() if ev.name != WINDOW]
        totals: dict = {}
        for s, e in gaps:
            mid = (s + e) / 2
            open_ = [ev for ev in spans if ev.start <= mid < ev.end]
            label = (min(open_, key=lambda ev: ev.end - ev.start).name
                     if open_ else "no bench span")
            totals[label] = totals.get(label, 0.0) + (e - s)
        top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
        return [[name, t * 1e-9] for name, t in top]


def load_fixture(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
