"""The program's own spans and named scopes in a profiler trace.

``bench/trace.py`` reduces a trace to device busy time and labels idle
gaps with the harness's ``bench.*`` spans.  The program records spans of
its own (``jax.profiler.TraceAnnotation``: ``svi.step``, ``svi.dispatch``,
``store.load_groups``, ...) and names parts of its jitted programs
(``jax.named_scope``: ``svi.global_update``, ``kernels.zstats``, ...).
This module reads those from a :class:`bench.trace.Trace`:

- ``spans``: per program span name, the count, total and self seconds
  (self: less the program spans nested in it on its thread) in the window;
- ``idle_gaps``: device 0's idle time by the innermost ``bench.*`` or
  program span open at each gap's midpoint on the thread that holds
  ``bench.window``, or on any other thread where that one has nothing
  else open;
- ``device_by_scope``: device op seconds (each op's own time) under each
  named scope and in each program of the device's ``XLA Modules`` line.

A TPU op event carries no scope: its name is the optimized HLO
instruction's text.  The scope comes from that instruction's ``op_name``
metadata (:func:`op_scopes`) in the program's HLO, which the profiler
records with the trace (:func:`program_hlo`).

The harness does not call this yet; ``readings`` gives the per-layer
numbers it would report (PERF.md, Open questions).
"""

from __future__ import annotations

import bisect
import re

from bench import trace as tr

MODULES_LINE = "XLA Modules"
# a span or scope the program names: dotted lower-case words
PROGRAM_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")
# a program run on the device, without the run's fingerprint
MODULE_NAME = re.compile(r"^(.*?)(\(\d+\))?$")
# the instruction an op event ran: the name its HLO text starts with
INSTRUCTION = re.compile(r"^%?([^\s=]+)")
# in HLO text: a computation's first line, an instruction, its op_name
# metadata, and the computations it calls (a fusion's body, a reducer)
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+).*\{\s*$")
INSTR_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s*(.*)$")
OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')
CALLS = re.compile(r"(?:calls|to_apply)=\{?%?([^\s,}]+)")


def span_name(name: str) -> str:
    """A host event's name without the ``#key=value#`` arguments a
    ``TraceAnnotation`` may carry."""
    return name.split("#", 1)[0]


def is_program(name: str) -> bool:
    """A span or scope that the program names (``bench.*`` are the
    harness's)."""
    return bool(PROGRAM_NAME.match(name)) and not name.startswith("bench.")


def host_lines(trace: tr.Trace) -> list:
    """One list per host thread of its ``bench.*`` and program spans,
    named without their arguments."""
    out = []
    for p in trace.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            spans = []
            for ev in ln.events:
                name = span_name(ev.name)
                if name.startswith("bench.") or is_program(name):
                    spans.append(tr.Event(name, ev.start, ev.end))
            if spans:
                out.append(spans)
    return out


def window_line(lines: list) -> int:
    """The index of the thread that holds the longest ``bench.window``."""
    found = [(ev.end - ev.start, i) for i, spans in enumerate(lines)
             for ev in spans if ev.name == tr.WINDOW]
    if not found:
        raise ValueError(f"the trace holds no {tr.WINDOW} span")
    return max(found)[1]


def nesting(spans: list) -> list:
    """For each span of one thread, the index of the span it is directly
    nested in, or ``None``."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i].start, -spans[i].end))
    parent: list = [None] * len(spans)
    stack: list = []
    for i in order:
        ev = spans[i]
        while stack and spans[stack[-1]].end <= ev.start:
            stack.pop()
        if stack and ev.end <= spans[stack[-1]].end:
            parent[i] = stack[-1]
        stack.append(i)
    return parent


def program_spans(lines: list, lo: float, hi: float) -> dict:
    """For each program span name: ``count`` (spans that overlap
    ``[lo, hi]``), ``total_s`` and ``self_s``, clipped to ``[lo, hi]``."""
    out: dict = {}
    for line in lines:
        prog = [ev for ev in line if is_program(ev.name)]
        own = [tr.length(tr.clip([(ev.start, ev.end)], lo, hi))
               for ev in prog]
        inner = [0.0] * len(prog)
        for i, parent in enumerate(nesting(prog)):
            if parent is not None:
                inner[parent] += own[i]
        for ev, t, sub in zip(prog, own, inner):
            if t <= 0:
                continue
            acc = out.setdefault(ev.name, {"count": 0, "total_s": 0.0,
                                           "self_s": 0.0})
            acc["count"] += 1
            acc["total_s"] += t * 1e-9
            acc["self_s"] += (t - sub) * 1e-9
    return out


class _Open:
    """The shortest of some spans that is open at a time.  Spans of one
    thread nest, so there it is the open span that started last."""

    def __init__(self, spans: list, nested: bool):
        # of spans that start together the shorter comes later
        self.spans = sorted(spans,
                            key=lambda ev: (ev.start, ev.start - ev.end))
        self.starts = [ev.start for ev in self.spans]
        self.longest = max((ev.end - ev.start for ev in self.spans),
                           default=0.0)
        self.nested = nested

    def innermost(self, t: float):
        best = None
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.starts[i] > t - self.longest:
            ev = self.spans[i]
            if t < ev.end and (best is None
                               or ev.end - ev.start < best.end - best.start):
                if self.nested:
                    return ev
                best = ev
            i -= 1
        return best


def idle_by_span(lines: list, busy: list, lo: float, hi: float) -> list:
    """Idle time in ``[lo, hi]`` outside ``busy`` by the innermost span
    open at each gap's midpoint on the window's thread, else on any
    other thread; the ten largest, ``[[label, seconds], ...]``."""
    w = window_line(lines)
    mine = _Open([ev for ev in lines[w] if ev.name != tr.WINDOW], True)
    others = _Open([ev for i, line in enumerate(lines) if i != w
                    for ev in line], False)
    totals: dict = {}
    for s, e in tr.subtract([(lo, hi)], busy):
        mid = (s + e) / 2
        ev = mine.innermost(mid) or others.innermost(mid)
        label = ev.name if ev else "no bench span"
        totals[label] = totals.get(label, 0.0) + (e - s)
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    return [[name, t * 1e-9] for name, t in top]


def op_scopes(hlo_text: str) -> dict:
    """``{instruction: [op_name, ...]}`` of an HLO module's text printed
    with its metadata: an instruction's own ``op_name`` and those of the
    instructions in the computations it calls (a fusion's body), whose
    work it does."""
    own: dict = {}
    calls: dict = {}
    body: dict = {}
    comp = None
    for line in hlo_text.splitlines():
        head = COMPUTATION.match(line)
        if head and " = " not in line:
            comp = head.group(1)
            continue
        m = INSTR_LINE.match(line)
        if not m or comp is None:
            continue
        name, rest = m.groups()
        got = OP_NAME.search(rest)
        own[name] = {got.group(1)} if got else set()
        calls[name] = CALLS.findall(rest)
        body.setdefault(comp, []).append(name)
    done: dict = {}

    def names(instr):
        if instr not in done:
            out = set(own[instr])
            for c in calls[instr]:
                for i in body.get(c, ()):
                    out |= names(i)
            done[instr] = out
        return done[instr]

    return {i: sorted(names(i)) for i in own}


def program_scopes(op_names) -> set:
    """The program's scopes named in some ``op_name`` paths (merged
    metadata joins paths with ``;``)."""
    return {part for op in op_names for path in op.split(";")
            for part in path.split("/") if is_program(part)}


def device_by_scope(trace: tr.Trace, lo: float, hi: float,
                    scopes: dict) -> dict:
    """Device op seconds in ``[lo, hi]``, summed over the chips, each op
    counted for its self time (a loop's event holds its body's events):
    ``ops_s`` in all, which is the busy time; ``scopes``, in ops that do
    work of each program scope (an op counts once for each scope it
    holds); ``unscoped_s``, in ops that hold none; ``modules``, in each
    program of the ``XLA Modules`` line.  ``scopes`` maps a program
    run's name, as that line gives it, to its :func:`op_scopes`."""
    total = unscoped = 0.0
    by_scope: dict = {}
    modules: dict = {}
    for p in trace.device_planes():
        runs = sorted((ev.start, ev.end, ev.name)
                      for ln in p.lines if ln.name == MODULES_LINE
                      for ev in ln.events)
        starts = [r[0] for r in runs]
        ops = trace.ops(p)
        own = [tr.length(tr.clip([(ev.start, ev.end)], lo, hi))
               for ev in ops]
        for i, parent in enumerate(nesting(ops)):
            if parent is not None:
                own[parent] -= own[i]
        for ev, t in zip(ops, own):
            if t <= 0:
                continue
            total += t
            j = bisect.bisect_right(starts, ev.start) - 1
            run = runs[j][2] if j >= 0 and ev.start < runs[j][1] else ""
            if run:
                module = MODULE_NAME.match(run).group(1)
                modules[module] = modules.get(module, 0.0) + t
            names = program_scopes(scopes.get(run, {}).get(
                INSTRUCTION.match(ev.name).group(1), ()))
            for c in names:
                by_scope[c] = by_scope.get(c, 0.0) + t
            if not names:
                unscoped += t
    return {"ops_s": total * 1e-9, "unscoped_s": unscoped * 1e-9,
            "scopes": {k: v * 1e-9 for k, v in by_scope.items()},
            "modules": {k: v * 1e-9 for k, v in modules.items()}}


def summary(trace: tr.Trace, scopes: dict | None = None) -> dict:
    """``spans``, ``idle_gaps`` and ``device_by_scope`` of the trace's
    window."""
    lines = host_lines(trace)
    lo, hi = trace.window()
    planes = trace.device_planes()
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    busy = tr.merge(tr.clip([(ev.start, ev.end)
                             for ev in trace.ops(planes[0])], lo, hi))
    return {"spans": program_spans(lines, lo, hi),
            "idle_gaps": idle_by_span(lines, busy, lo, hi),
            "device_by_scope": device_by_scope(trace, lo, hi, scopes or {})}


def _mean_ms(red: dict, name: str, key: str = "total_s"):
    got = red["spans"].get(name)
    return 1e3 * got[key] / got["count"] if got else None


def _share(red: dict, scope: str):
    dev = red["device_by_scope"]
    t = dev["scopes"].get(scope)
    return 100.0 * t / dev["ops_s"] if t is not None and dev["ops_s"] else None


def readings(red: dict) -> dict:
    """The per-layer numbers of a training window's :func:`summary`, each
    ``None`` where its span or scope is absent: the mean milliseconds of
    ``svi.host_batch``, ``svi.device_put`` and ``svi.heldout`` per call,
    the mean self milliseconds of ``svi.dispatch`` (without
    ``svi.compile``), and the percent of device op time under
    ``svi.global_update`` and under ``kernels.zstats``."""
    return {
        "host_batch_ms.train": _mean_ms(red, "svi.host_batch"),
        "device_put_ms.train": _mean_ms(red, "svi.device_put"),
        "dispatch_ms.train": _mean_ms(red, "svi.dispatch", "self_s"),
        "heldout_eval_ms.train": _mean_ms(red, "svi.heldout"),
        "global_update_share.train": _share(red, "svi.global_update"),
        "zstats_device_share.train": _share(red, "kernels.zstats"),
    }


def program_hlo(xplane: bytes) -> dict:
    """``{program run: HLO text with metadata}`` of every program a
    profiler session recorded (the ``Hlo Proto`` stats of its
    ``/host:metadata`` plane), keyed as the ``XLA Modules`` line names
    the runs, e.g. ``jit_svi_step(1234)``.  ``xplane`` is the bytes of an
    ``.xplane.pb`` file (an ``XSpace``)."""
    from jaxlib import _jax
    opts = _jax.HloPrintOptions.short_parsable()
    opts.print_metadata = True
    out = {}
    for plane in _message(xplane).get(1, ()):           # XSpace.planes
        plane = _message(plane)
        if bytes(plane.get(2, [b""])[0]) != b"/host:metadata":
            continue
        hlo_stat = {_message(_message(e)[2][0])[1][0]        # stat_metadata
                    for e in plane.get(5, ())
                    if bytes(_message(_message(e)[2][0])[2][0]) ==
                    b"Hlo Proto"}
        for entry in plane.get(4, ()):                  # event_metadata
            meta = _message(_message(entry)[2][0])
            for stat in map(_message, meta.get(5, ())):
                if stat.get(1, [None])[0] in hlo_stat and 6 in stat:
                    module = _message(stat[6][0])[1][0]   # HloProto
                    hlo = _jax.HloModule.from_serialized_hlo_module_proto(
                        bytes(module))
                    out[bytes(meta[2][0]).decode()] = hlo.to_string(opts)
    return out


def _message(buf) -> dict:
    """A protobuf message's fields: ``{number: [value, ...]}``, a varint
    as an int and any other value as a ``memoryview``."""
    out: dict = {}
    for number, value in _fields(buf):
        out.setdefault(number, []).append(value)
    return out


def _fields(buf):
    """``(field number, value)`` of each field of a protobuf message: an
    int for a varint, a ``memoryview`` for any other wire type."""
    buf = memoryview(buf)
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"unknown protobuf wire type {wire}")
        yield key >> 3, value


def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7
