"""What every cell shares: finding its files by name, the chip check, the
compile counter, the traced window and the result line."""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(name: str, bench: dict | None = None) -> dict:
    bench = bench or benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def limits(workload: str) -> dict:
    """The cell's limits on the numbers that decide ``correct``."""
    return load_json(BENCH / "limits" / f"{workload}.json")["limits"]


def _module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(name: str):
    """The general driver a traffic mix names (``bench/<name>.py``)."""
    return importlib.import_module(f"bench.{name}")


def metric_reader(name: str):
    """``read(run) -> float | None`` of ``metrics/<name>.py``."""
    return _module(BENCH / "metrics" / f"{name}.py",
                   "bench_metric_" + name.replace(".", "_")).read


def cell_metrics(bench: dict, workload: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def devices(chips: int):
    """The first ``chips`` TPU chips; :class:`NoChip` otherwise."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees "
                     f"{len(devs)}")
    return devs[:chips]


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class CompileLog:
    """Backend compiles, from ``jax.monitoring`` (a compile that the
    persistent cache served counts too: the program was not in memory)."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Window:
    """The measured window: host-clock bounds and, when traced, the
    reduced trace."""
    t0: float = 0.0
    t1: float = 0.0
    trace: object = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@contextlib.contextmanager
def window(traced: bool, out: Window):
    """Time the block; with ``traced`` record a profiler trace of it (the
    block runs inside a ``bench.window`` host span) and reduce it."""
    import jax
    tmp = None
    if traced:
        from bench import trace as tr
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            out.t0 = time.perf_counter()
            yield out
            out.t1 = out.t1 or time.perf_counter()
    finally:
        if traced:
            jax.profiler.stop_trace()
            try:
                out.trace = tr.Trace.from_dir(tmp)
            finally:
                shutil.rmtree(tmp, ignore_errors=True)


def span(name: str, fn):
    """``fn`` wrapped in a host span of the profiler trace."""
    import jax

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return wrapped


def compare(readings: dict, lims: dict) -> tuple[bool, list]:
    """``(all within limits, [(name, reading, limit)])``.  A reading that
    is missing or not finite fails."""
    import math
    rows, ok = [], True
    for name, limit in lims.items():
        got = readings.get(name)
        good = got is not None and math.isfinite(got) and got <= limit
        ok = ok and good
        rows.append((name, got, limit))
    return ok, rows


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, checks: list, breakdown=None) -> str:
    """The last line of standard output; the numbers compared come last,
    each beside its limit."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return json.dumps(out)


@dataclasses.dataclass
class Ctx:
    """One run of one cell, as a driver sees it."""
    workload: str
    cfg: dict
    traffic: dict
    seed: int
    seconds: float
    traced: bool
    devs: list
    compile_log: CompileLog
    t_start: float
    elog_dtype: str | None = None       # the program's narrow-table path
    reference_dtype: str = "float32"


def run_cell(workload: str, seed: int, seconds: float, traced: bool, devs,
             t_start: float, *, bench: dict | None = None,
             cfg: dict | None = None, traffic_mix: dict | None = None,
             lims: dict | None = None, **ctx_kw) -> dict:
    """Drive one run of ``workload`` on ``devs`` and judge it.  The
    keyword overrides (a benchmark, configuration, traffic or limits
    given inline) serve the tests, which run small cells on the CPU.
    Returns the outcome with its ``line`` (the result line)."""
    bench = bench or benchmark()
    w = cell(workload, bench)
    cfg = cfg or config(w["config"])
    traffic_mix = traffic_mix or traffic(w["traffic"])
    lims = lims if lims is not None else limits(workload)
    ctx = Ctx(workload, cfg, traffic_mix, seed, seconds, traced, devs,
              CompileLog(), t_start, **ctx_kw)
    out = driver(traffic_mix["driver"]).run(ctx)

    ok, checks = compare(out["readings"], lims)
    correct = ok and out["failed"] == 0
    metrics, breakdown = {}, None
    if traced:
        run = dict(out["layer_run"])
        if run.get("trace") is not None:
            summary = run["trace"].summary()
            run["trace"] = summary
            out["device"]["busy_s"] = summary["busy_s"]
            out["device"]["window_s"] = summary["window_s"]
            breakdown = {"device_ops": summary["device_ops"],
                         "idle_gaps": summary["idle_gaps"]}
        for m in cell_metrics(bench, workload, "per_layer"):
            val = metric_reader(m["name"])(run)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    else:
        for m in cell_metrics(bench, workload, "end_to_end"):
            metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                  "unit": m["unit"]}
    for name, got, limit in checks:
        say(f"check {name}: {got} (limit {limit})")
    out["correct"] = correct
    out["checks"] = checks
    out["line"] = result_line(correct, out["attempted"], out["failed"],
                              metrics, out["device"], checks, breakdown)
    return out
