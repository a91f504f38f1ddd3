"""Run the main path once on a TPU and check what comes out.

    python chip_smoke.py [--seed 0]             # one chip
    python chip_smoke.py --chips 4 [--seed 0]   # four chips against one

One chip: LDA at the width of the UCI "Bag of Words" NYTimes corpus (V =
102,660 words, K = 256 topics) over a synthetic sharded corpus of 4,096
documents (about 1.36M tokens) made from ``--seed``.

  1. ``explain_plan`` must route the token plate to ``fused-streamed``.
  2. ``make_engine("svi")`` takes 5 steps of 512 documents over the sharded
     corpus; every batch ELBO and the held-out per-token ELBO are finite.
  3. The SVI step is compiled for the schedule's next batch: it must hold
     the Pallas kernels (``tpu_custom_call``); three warm steps are timed.
  4. One ``ops.zstats`` call at that step's shapes is compared with the
     plain ``ref.zstats`` oracle on the same chip.
  5. The posterior is frozen, registered in a ``Gateway`` and asked a
     PREDICT on 32 held-out documents, a TOPICS, a CREDIBLE INTERVAL and
     an EXPLAIN; every answer is finite and every kernel route is fused.

``--chips 4`` runs only the sharded path: the same 5 SVI steps under a
four-chip ``ShardingPlan``, against the same schedule on one chip in the
same process.

Every number printed before the last line is a smoke reading of one run,
not a benchmark.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
a failed check exits non-zero before it.  Without a TPU, or outside a
checkout of this repository, the script exits non-zero and prints no
result.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent

V, K = 102_660, 256               # NYTimes vocabulary; topics in the hundreds
N_DOCS, MEAN_LEN = 4096, 332
STEPS, BATCH, HOLDOUT = 5, 512, 0.05
N_PREDICT = 32
# ops.zstats (Pallas) vs ref.zstats, as max|got - want| / max|want| per
# output: float32 sums over ~170k tokens in another order.  A bf16-sized
# gap (1e-3 .. 1e-2) means a matmul ran at reduced MXU precision.
KERNEL_RTOL = 1e-4
# one chip vs four: the same updates, with the batch statistics summed over
# four shards (a psum) in place of one pass
SHARDED_RTOL = 1e-4


class SmokeFailure(RuntimeError):
    """A check of this script failed."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileLog:
    """Compile seconds and persistent-cache hits, from ``jax.monitoring``.
    A backend compile that the persistent cache served counts its read
    time, so a warm cache shows up as fewer seconds and more hits."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def line(self) -> str:
        return (f"{self.compiles} backend compiles, {self.seconds:.2f} s; "
                f"persistent cache {self.hits} hits, {self.misses} misses")


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def all_finite(x) -> bool:
    import numpy as np
    return bool(np.all(np.isfinite(np.asarray(x, np.float64))))


def make_corpus(tmp: str, seed: int):
    from repro.data import SyntheticCorpus, write_sharded_corpus
    t0 = time.perf_counter()
    corpus = SyntheticCorpus(n_docs=N_DOCS, vocab=V, n_topics=K,
                             mean_len=MEAN_LEN, seed=seed).generate()
    store = write_sharded_corpus(corpus, tmp, vocab=V)
    say(f"corpus: {store.n_docs} docs, {store.n_tokens} tokens, V={V}, "
        f"{store.n_shards} shards, made in {time.perf_counter() - t0:.2f} s")
    return corpus, store


def lda():
    from repro.core import models
    return models.make("lda", alpha=0.1, beta=0.05, K=K, V=V)


def plan_phase(corpus, store, cfg):
    """EXPLAIN must route the token plate to the streamed fused kernel."""
    from repro.analysis.explain import explain_plan
    m = lda()
    m["x"].observe(corpus["tokens"], segment_ids=corpus["doc_ids"])
    plan = explain_plan(m, cfg, corpus=store)
    check(plan.backend == "pallas", f"EXPLAIN plans backend {plan.backend}")
    (route,) = plan.routes
    say(f"route {route.latent}: {route.path}, streamed target "
        f"{route.target!r}, {route.n_tiles} tiles of {route.tile}, "
        f"{route.block_tokens} tokens per block, caps {plan.caps}")
    check(route.latent == "z" and route.path == "fused-streamed",
          f"EXPLAIN routes z to {route.path}, not fused-streamed")


def train_phase(store, seed, log):
    from repro.core import make_engine
    t0 = time.perf_counter()
    result = make_engine("svi", steps=STEPS, batch_size=BATCH,
                         holdout_frac=HOLDOUT, corpus=store,
                         seed=seed).fit(lda())
    fit_s = time.perf_counter() - t0
    say(f"fit: {STEPS} SVI steps in {fit_s:.2f} s wall, compile included "
        f"({log.line()})")
    say(f"batch ELBOs: {result.elbo_trace}")
    say(f"held-out per-token ELBO: {result.heldout_elbo}")
    check(len(result.elbo_trace) == STEPS and all_finite(result.elbo_trace),
          f"batch ELBOs not {STEPS} finite values: {result.elbo_trace}")
    check(all_finite(result.heldout_elbo), "held-out ELBO is not finite")
    return result


def step_phase(store, result, seed):
    """Compile the SVI step for the schedule's next batch: the Pallas
    kernels must be in it.  Time three warm steps from the fitted state."""
    import jax
    import jax.numpy as jnp
    from repro.core.svi import (SVI, SVIConfig, device_put_batch,
                                make_svi_step, robbins_monro)
    from repro.core.vmp import VMPState

    cfg = SVIConfig(batch_size=BATCH, holdout_frac=HOLDOUT, seed=seed,
                    prefetch=False)
    svi = SVI(lda(), cfg, corpus=store)
    hb, caps, n_tok, n_b = svi.sampler.host_batch_at(STEPS)
    svi.close()
    step = make_svi_step(svi.program, caps)
    state = VMPState({n: jnp.asarray(v) for n, v in result.posteriors.items()},
                     jnp.int32(STEPS))
    batch = device_put_batch(hb)
    rho = jnp.float32(robbins_monro(STEPS))
    scale = jnp.float32(len(svi.train) / n_b)
    t0 = time.perf_counter()
    compiled = step.lower(state, batch, rho, scale).compile()
    say(f"SVI step compile: {time.perf_counter() - t0:.2f} s")
    hlo = compiled.as_text()
    n_kernels = hlo.count("tpu_custom_call")
    check(n_kernels > 0, "the compiled SVI step holds no tpu_custom_call")
    say(f"SVI step holds {n_kernels} tpu_custom_call sites; batch {n_b} "
        f"docs, {n_tok} tokens, caps {caps}")
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        state, elbo = compiled(state, batch, rho, scale)
        jax.block_until_ready((state, elbo))
        times.append(time.perf_counter() - t0)
        check(all_finite(elbo), "warm step ELBO is not finite")
    say(f"warm step seconds: {times} ({n_tok / min(times):.0f} tokens/s "
        f"at the fastest)")
    return hb


def kernel_phase(hb, result):
    """One ``ops.zstats`` (Pallas) call against the ``ref.zstats`` oracle at
    the step's shapes, on the fitted tables."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    z, x = hb["arrays"]["z"], hb["arrays"]["x"]
    theta = np.asarray(result.posteriors["theta"])
    rows = np.clip(hb["dirs"]["theta"]["rows"], 0, theta.shape[0] - 1)
    args = (jnp.asarray(theta[rows]), jnp.asarray(z["prior_rows"]),
            jnp.asarray(result.posteriors["phi"]), jnp.asarray(x["values"]),
            None if x["mask"] is None else jnp.asarray(x["mask"]),
            None if z["mask"] is None else jnp.asarray(z["mask"]))

    def call(fn):
        def run(tp, pr, tab, vals, mask, zm):
            return fn(tp, pr, (ops.ZChild(tab, vals, mask=mask),), zm,
                      tables="alpha")
        return jax.block_until_ready(jax.jit(run)(*args))

    got, want = call(ops.zstats), call(ref.zstats)
    errs = {"lse_sum": rel_err(got[0], want[0]),
            "prior_stats": rel_err(got[1], want[1]),
            "child_stats": rel_err(got[2][0], want[2][0])}
    say(f"zstats Pallas vs ref, max|diff|/max|ref|: {errs} "
        f"(tolerance {KERNEL_RTOL})")
    for name, e in errs.items():
        check(e <= KERNEL_RTOL, f"zstats {name} differs from ref by {e}")


def serve_phase(corpus, store, result, seed):
    import numpy as np
    from repro.data.pipeline import holdout_split
    from repro.data.store import sharded_template
    from repro.gateway import Gateway

    m = lda()
    post = result.freeze(m, program=sharded_template(m, store))
    _, held = holdout_split(store.n_docs, HOLDOUT, seed)
    docs = held[:N_PREDICT]
    offs = np.concatenate([[0], np.cumsum(corpus["lengths"])])
    payload = {"values": np.concatenate(
                   [corpus["tokens"][offs[d]:offs[d + 1]] for d in docs]),
               "lengths": corpus["lengths"][docs]}
    art = " USING ARTIFACT 'lda-nytimes'"
    predict = "PREDICT LL FOR DOCS $d" + art
    with Gateway() as gw:
        gw.register("lda-nytimes", post, version="smoke")
        t0 = time.perf_counter()
        r = gw.query(predict, params={"d": payload}, timeout_s=900)
        say(f"PREDICT on {r.value['n_docs']} held-out docs "
            f"({r.value['n_tokens']} tokens): {r.value['per_token_ll']} "
            f"nats/token in {time.perf_counter() - t0:.2f} s, compile "
            f"included; route {r.route}")
        check(r.value["n_docs"] == N_PREDICT, "PREDICT scored the wrong docs")
        check(all_finite(r.value["doc_ll"])
              and all_finite(r.value["per_token_ll"]),
              "PREDICT answered a non-finite log-likelihood")

        t = gw.query("TOPICS OF phi TOP 10" + art)
        check(t.value["indices"].shape == (K, 10)
              and all_finite(t.value["probs"]), "TOPICS answer malformed")
        say(f"TOPICS: topic 0 top words {t.value['indices'][0].tolist()}")

        c = gw.query("CREDIBLE INTERVAL 0.9 FOR phi[0]" + art)
        lo, hi = np.asarray(c.value["lo"]), np.asarray(c.value["hi"])
        check(lo.shape == (V,) and all_finite(lo) and all_finite(hi)
              and bool(np.all(lo <= hi)), "CREDIBLE INTERVAL malformed")
        say(f"CREDIBLE INTERVAL 0.9 of phi[0]: widest "
            f"{float(np.max(hi - lo))}")

        e = gw.query("EXPLAIN " + predict, params={"d": payload})
        text = e.value["text"]
        routes = [ln.strip() for ln in text.splitlines() if "route=" in ln]
        say(f"EXPLAIN PREDICT: {routes}")
        check(e.route == r.route, "EXPLAIN route differs from executed")
        check(routes and all("route=fused" in ln for ln in routes),
              f"fold-in kernel routes are not fused: {routes}")


def four_chip_phase(store, seed):
    """The same schedule on one chip and under a four-chip plan."""
    import jax
    import numpy as np
    from repro.compat import make_mesh
    from repro.core.partition import ShardingPlan
    from repro.core.svi import SVI, SVIConfig

    devs = jax.devices()
    cfg = SVIConfig(batch_size=BATCH, holdout_frac=HOLDOUT, seed=seed)
    plan = ShardingPlan(make_mesh((len(devs),), ("data",)), ("data",),
                        "inferspark")
    runs = {}
    for name, p in (("1 chip", None), (f"{len(devs)} chips", plan)):
        svi = SVI(lda(), cfg, plan=p, corpus=store)
        t0 = time.perf_counter()
        try:
            state, hist = svi.fit(STEPS)
        finally:
            svi.close()
        say(f"{name}: {STEPS} steps in {time.perf_counter() - t0:.2f} s "
            f"wall, compile included; ELBOs {hist['elbo']}; held-out "
            f"{hist['heldout']}")
        check(len(hist["elbo"]) == STEPS and all_finite(hist["elbo"]),
              f"{name}: batch ELBOs not finite")
        runs[name] = (state, hist)
    (s1, h1), (s4, h4) = runs.values()
    theta = s4.posteriors["theta"]
    on = {s.device for s in theta.addressable_shards}
    say(f"theta {theta.shape} sharding {theta.sharding}; shards on "
        f"{sorted(d.id for d in on)}")
    check(on == set(devs), "theta does not sit on every chip")
    errs = {"elbo": rel_err(h4["elbo"], h1["elbo"]),
            "heldout": rel_err([v for _, v in h4["heldout"]],
                               [v for _, v in h1["heldout"]]),
            "phi": rel_err(np.asarray(s4.posteriors["phi"]),
                           np.asarray(s1.posteriors["phi"]))}
    say(f"{len(devs)} chips vs 1, max|diff|/max|1 chip|: {errs} "
        f"(tolerance {SHARDED_RTOL})")
    for name, e in errs.items():
        check(e <= SHARDED_RTOL, f"{name} differs across chip counts by {e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the corpus and of the engine")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip comparison")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro" / "core").is_dir():
        print(f"chip_smoke: no repository source at {ROOT / 'src'}; run "
              f"the script from a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r}); "
              f"this check runs only on the chip", file=sys.stderr)
        return 2
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)} "
              f"chips", file=sys.stderr)
        return 2

    from repro import compile_cache
    from repro.kernels import ops
    cache_dir = compile_cache.enable()
    log = CompileLog()
    say(f"{len(devs)} x {dev.device_kind}; jax {jax.__version__}; kernel "
        f"backend {ops._backend()}; compile cache {cache_dir}")
    say("every time, rate and byte count below is a smoke reading of this "
        "one run, not a benchmark number")
    check(ops._backend() == "pallas", "kernels do not dispatch to Pallas")

    t_all = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        corpus, store = make_corpus(tmp, args.seed)
        if args.chips == 4:
            four_chip_phase(store, args.seed)
        else:
            from repro.core.engine import EngineConfig
            plan_phase(corpus, store,
                       EngineConfig(backend="svi", batch_size=BATCH,
                                    holdout_frac=HOLDOUT, seed=args.seed))
            result = train_phase(store, args.seed, log)
            hb = step_phase(store, result, args.seed)
            kernel_phase(hb, result)
            serve_phase(corpus, store, result, args.seed)
    peak = dev.memory_stats().get("peak_bytes_in_use")
    say(f"whole run {time.perf_counter() - t_all:.2f} s; {log.line()}; "
        f"peak device bytes in use {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
