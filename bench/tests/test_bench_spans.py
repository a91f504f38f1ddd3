"""The program's own spans and named scopes: recorded by the JAX profiler
around a small ``SVI.fit`` on a sharded corpus (with the programs' HLO),
and reduced by ``bench/spans.py`` from traces worked out by hand."""

import glob
import json
import pathlib

import numpy as np
import pytest

from bench import corpus as gen
from bench import spans, trace

FIXTURES = pathlib.Path(__file__).parent / "fixtures"
THREADS = FIXTURES / "trace_threads.json"

STEPS, EVERY = 6, 3


def _svi(path):
    """A small LDA engine over a sharded corpus at ``path``, held-out
    set included."""
    from repro.core import models
    from repro.core.svi import SVI, SVIConfig
    from repro.data import ShardedCorpus, write_sharded_corpus

    if not path.exists():
        rng = gen.rng_for(7, 1)
        docs = gen.documents(rng.integers(5, 30, size=48), 4, 60, 0.1, 1.0,
                             rng)
        write_sharded_corpus(docs, str(path), vocab=60)
    cfg = SVIConfig(batch_size=8, holdout_frac=0.25, holdout_every=EVERY,
                    holdout_local_iters=2, pad_multiple=64, seed=3)
    return SVI(models.make("lda", alpha=0.1, beta=0.05, K=4, V=60), cfg,
               corpus=ShardedCorpus.open(str(path)))


def _fit(path, profile=None):
    """``SVI.fit`` of the small engine, under a profiler session writing
    to ``profile`` if one is given."""
    import jax
    svi = _svi(path)
    try:
        if profile is None:
            return svi.fit(STEPS)
        with jax.profiler.trace(str(profile)):
            return svi.fit(STEPS)
    finally:
        svi.close()


@pytest.fixture(scope="module")
def profile(tmp_path_factory):
    """The profiler's directory of a traced fit."""
    tmp = tmp_path_factory.mktemp("fit")
    _fit(tmp / "corpus", tmp / "profile")
    return tmp / "profile"


def _inside(ev, outer):
    return outer.start <= ev.start and ev.end <= outer.end


def test_fit_records_its_spans_per_step(profile):
    lines = spans.host_lines(trace.Trace.from_dir(str(profile)))
    main = [ln for ln in lines if any(ev.name == "svi.step" for ev in ln)]
    assert len(main) == 1
    main = main[0]
    steps = [ev for ev in main if ev.name == "svi.step"]
    assert len(steps) == STEPS
    for st in steps:
        for name in ("svi.host_batch", "svi.device_put", "svi.dispatch",
                     "svi.elbo_sync", "store.batch_wait"):
            assert sum(ev.name == name and _inside(ev, st)
                       for ev in main) == 1, name
    # a new step signature compiles inside the first step's dispatch
    compiles = [ev for ev in main if ev.name == "svi.compile"]
    assert compiles and _inside(compiles[0], steps[0])
    assert all(any(_inside(c, d) for d in main if d.name == "svi.dispatch")
               for c in compiles)

    # the prefetcher loads every later step's batch on threads of its own
    loads = [ev for ln in lines if ln is not main for ev in ln
             if ev.name == "store.load_groups"]
    assert len(loads) >= STEPS - 1

    held = [ev for ev in main if ev.name == "svi.heldout"]
    assert len(held) == STEPS // EVERY
    for h in held:
        inner = [ev.name for ev in main if ev.name.startswith(
            "svi.heldout.") and _inside(ev, h)]
        assert inner == ["svi.heldout.slice", "svi.heldout.put",
                         "svi.heldout.dispatch", "svi.heldout.sync"]
        assert any(_inside(h, st) for st in steps)


def test_spans_record_nothing_without_a_profiler(tmp_path):
    """The spans are the profiler's: a fit with no session running gives
    the same numbers as with one."""
    (plain, h0), (traced, h1) = (
        _fit(tmp_path / "corpus", profile)
        for profile in (None, tmp_path / "profile"))
    np.testing.assert_array_equal(np.asarray(plain.posteriors["phi"]),
                                  np.asarray(traced.posteriors["phi"]))
    assert h0 == h1


def _reduce(path):
    with open(path) as f:
        doc = json.load(f)
    return spans.summary(trace.Trace.from_json(doc), doc.get("op_scopes"))


def test_program_spans_of_the_two_thread_fixture():
    """Host spans on two threads (the fit's and the prefetcher's): no
    arguments in the names, self time less the program spans nested on
    the same thread (not the harness's), clipped to the window."""
    ns = 1e-9
    got = _reduce(THREADS)["spans"]
    assert not any(n.startswith("bench.") or "#" in n for n in got)
    assert "PjitFunction(svi_step)" not in got
    assert got["svi.step"] == {"count": 2, "total_s": pytest.approx(900 * ns),
                               "self_s": pytest.approx(100 * ns)}
    assert got["svi.dispatch"]["total_s"] == pytest.approx(250 * ns)
    assert got["svi.dispatch"]["self_s"] == pytest.approx(150 * ns)
    assert got["svi.heldout"]["self_s"] == pytest.approx(40 * ns)
    assert got["svi.host_batch"]["self_s"] == pytest.approx(20 * ns)
    assert got["store.load_groups"] == {
        "count": 3, "total_s": pytest.approx(135 * ns),
        "self_s": pytest.approx(135 * ns)}


def test_idle_gaps_follow_the_window_thread():
    """Gaps [0, 160], [440, 700] and [850, 1000] on device 0.  The first
    two take the innermost span of the window's thread, though a shorter
    load is open on the prefetch thread at 80; where the window's thread
    has nothing else open (at 925) the other thread's span is taken."""
    ns = 1e-9
    assert _reduce(THREADS)["idle_gaps"] == [
        ["svi.step", pytest.approx(260 * ns)],
        ["store.batch_wait", pytest.approx(160 * ns)],
        ["store.load_groups", pytest.approx(150 * ns)]]


def test_idle_gaps_of_harness_spans_alone_are_as_before():
    """On a trace with the harness's spans alone the thread rule gives
    ``bench/trace.py``'s labels."""
    tr = trace.load_fixture(FIXTURES / "trace_small.json")
    lo, hi = tr.window()
    busy = trace.merge(trace.clip([(ev.start, ev.end) for ev in
                                   tr.ops(tr.device_planes()[0])], lo, hi))
    assert spans.idle_by_span(spans.host_lines(tr), busy, lo, hi) == \
        tr.summary()["idle_gaps"]


def test_device_time_by_scope_and_program():
    ns = 1e-9
    d = _reduce(THREADS)["device_by_scope"]
    # the loop's event [260, 360] holds fusion.2's [265, 355]: each op
    # counts its own time, so the ops sum to the busy time
    assert d["ops_s"] == pytest.approx(430 * ns)
    # the same instruction name in two programs takes each one's scope
    assert d["scopes"] == {"svi.local_rows": pytest.approx(100 * ns),
                           "kernels.zstats": pytest.approx(250 * ns),
                           "svi.global_update": pytest.approx(40 * ns),
                           "vmp.elbo": pytest.approx(20 * ns)}
    assert d["unscoped_s"] == pytest.approx(20 * ns)
    assert d["modules"] == {"jit_svi_step": pytest.approx(280 * ns),
                            "jit_local_score": pytest.approx(150 * ns)}


READINGS = {
    "host_batch_ms.train": 100e-6, "device_put_ms.train": 50e-6,
    "dispatch_ms.train": 150e-6, "heldout_eval_ms.train": 300e-6,
    "global_update_share.train": 100 * 40 / 430,
    "zstats_device_share.train": 100 * 250 / 430}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_readings(name):
    assert spans.readings(_reduce(THREADS))[name] == \
        pytest.approx(READINGS[name])
    # nothing to read where the program recorded no spans or scopes
    assert spans.readings(_reduce(FIXTURES / "trace_small.json"))[name] \
        is None


def test_programs_are_named(tmp_path):
    """The step (both forms) and the local scorer (both builds) jit
    functions named for the program: a profile names their runs
    ``jit_svi_step`` and ``jit_local_score``."""
    from repro.compat import make_mesh
    from repro.core.partition import ShardingPlan
    from repro.core.svi import build_local_scorer, make_svi_step

    svi = _svi(tmp_path / "corpus")
    try:
        _, caps, _, _ = svi._load_groups(svi.sampler.batch_at(0))
    finally:
        svi.close()
    plan = ShardingPlan(make_mesh((1,), ("data",)), ("data",))
    assert make_svi_step(svi.program, caps).__name__ == "svi_step"
    assert make_svi_step(svi.program, caps, plan=plan).__name__ == \
        "svi_step"
    for extras in (False, True):
        assert build_local_scorer(svi.program, caps, 2,
                                  extras=extras).__name__ == "local_score"


def test_recorded_programs_carry_their_scopes(profile):
    """The profiler records each program's HLO with the trace; the step's
    and the scorer's keep the named scopes in their metadata, the token
    plate's on the ``ref`` route too."""
    path = sorted(glob.glob(f"{profile}/**/*.xplane.pb", recursive=True))[-1]
    with open(path, "rb") as f:
        hlo = spans.program_hlo(f.read())
    by_program = {spans.MODULE_NAME.match(run).group(1): text
                  for run, text in hlo.items()}

    def scopes(program):
        return spans.program_scopes(
            n for names in spans.op_scopes(by_program[program]).values()
            for n in names)

    assert {"svi.local_rows", "svi.global_update", "kernels.zstats",
            "vmp.elbo"} <= scopes("jit_svi_step")
    assert {"kernels.zstats", "vmp.elbo"} <= scopes("jit_local_score")


def test_op_scopes_follow_a_fusion_into_its_body():
    gu = 'metadata={op_name="jit(svi_step)/svi.global_update/mul"}'
    merged = 'metadata={op_name="jit(svi_step)/vmp.elbo/add;jit(f)/add"}'
    text = f"""fused_computation.1 {{
  param_0 = f32[8]{{0}} parameter(0)
  mul.0 = f32[8]{{0}} multiply(param_0, param_0), {gu}
  ROOT add.0 = f32[8]{{0}} add(mul.0, param_0), {merged}
}}

ENTRY main.2 {{
  x.1 = f32[8]{{0}} parameter(0), metadata={{op_name="x"}}
  ROOT fusion.1 = f32[8]{{0}} fusion(x.1), kind=kLoop, \
calls=fused_computation.1, {merged}
}}
"""
    got = spans.op_scopes(text)
    assert spans.program_scopes(got["fusion.1"]) == {"svi.global_update",
                                                     "vmp.elbo"}
    assert spans.program_scopes(got["x.1"]) == set()


def test_a_recorded_chip_trace():
    """Two steps of a traced ``train.lda-nytimes`` window on one v5e chip,
    the second with its held-out evaluation: the program's spans, the
    device's ``XLA Modules`` and ``XLA Ops`` lines (op names cut to the
    instruction) and the op scopes of the programs the profiler recorded.
    The host spans and the device share a clock: each step's program
    starts after its ``svi.dispatch`` starts (and before the next one
    does), and the scorer runs inside ``svi.heldout``.  Every reading has
    a value, and the harness's wrapper around ``SVI.step`` no longer
    labels the idle."""
    with open(FIXTURES / "trace_tpu_nytimes_spans.json") as f:
        doc = json.load(f)
    tr = trace.Trace.from_json(doc)
    lines = spans.host_lines(tr)
    main = lines[spans.window_line(lines)]
    runs = {}
    for p in tr.device_planes():
        for ln in p.lines:
            if ln.name == spans.MODULES_LINE:
                for ev in ln.events:
                    name = spans.MODULE_NAME.match(ev.name).group(1)
                    runs.setdefault(name, []).append(ev)
    steps = sorted(ev.start for ev in runs["jit_svi_step"])
    calls = sorted(ev.start for ev in main if ev.name == "svi.dispatch")
    assert len(steps) == len(calls) == 2
    assert all(c < s for c, s in zip(calls, steps))
    assert steps[0] < calls[1]
    (held,) = [ev for ev in main if ev.name == "svi.heldout"]
    (score,) = runs["jit_local_score"]
    assert _inside(score, held)

    red = spans.summary(tr, doc["op_scopes"])
    assert all(v is not None and v > 0
               for v in spans.readings(red).values())
    idle = dict(red["idle_gaps"])
    assert idle.get("bench.svi_step", 0.0) < 0.1 * sum(idle.values())
