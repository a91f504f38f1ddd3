"""Driver of SVI training cells (traffic ``"driver": "train"``).

Set-up makes the run's corpus from the seed, writes it as a sharded
corpus under ``TMPDIR``, builds one ``SVI`` engine and drives it through
its first steps with the window's own call (``SVI.step``) on the
schedule's own batches: those steps are the ones the reference checks.
The engine takes its minibatches in a fixed order, the same in every
epoch, and the corpus gives every minibatch the same number of tokens
(``corpus.batched_lengths``), so every seed's steps take the same padded
shapes.  Set-up warms the step signatures of one epoch, which are all
the window can reach, and the held-out scorer, and hands the same engine
and state to the window, which runs ``SVI.fit`` until ``--seconds`` have
passed (the fit callback ends it).
After the window the state is freed and the reference replays the first
steps and the held-out score.
"""

from __future__ import annotations

import gc
import tempfile
import time

import numpy as np

from bench import corpus as gen
from bench import counts, harness
from bench import reference as ref

CHECKED_STEPS = 3


class Schedule:
    """The configuration's minibatch schedule and the token counts of its
    batches, from the reference's definition of it."""

    def __init__(self, lengths, n_hold: int, batch: int, seed: int,
                 shuffle: bool = False):
        self.lengths = np.asarray(lengths, np.int64)
        self.train, self.hold = ref.holdout_split(len(lengths), n_hold, seed)
        self.batch, self.seed, self.shuffle = batch, seed, shuffle
        self.per_epoch = -(-len(self.train) // batch)

    def docs(self, t: int) -> np.ndarray:
        return ref.batch_docs(self.train, self.batch, self.seed, t,
                              self.shuffle)

    def tokens(self, t: int) -> int:
        return int(self.lengths[self.docs(t)].sum())


def padded(n: int, multiple: int) -> int:
    return -(-max(n, 1) // multiple) * multiple if multiple else max(n, 1)


def step_signature(sched: Schedule, t: int, pad: int) -> tuple:
    """The padded (document rows, tokens) step ``t`` is compiled for."""
    docs = sched.docs(t)
    return (padded(len(docs), pad),
            padded(int(sched.lengths[docs].sum()), pad))


def _norms(after, before, divisor=None):
    import jax.numpy as jnp
    out = {}
    for n in after:
        d = (after[n] - before[n]).astype(jnp.float32)
        if divisor and n in divisor:
            d = d / divisor[n]
        out[n] = float(jnp.sqrt(jnp.sum(d * d)))
    return out


def worst_leaf_gap(prog: dict, want: dict) -> float:
    """Largest gap between the program's and the reference's norm of a
    leaf, over the reference's norm of that leaf or of the median leaf,
    whichever is larger.  Leaves whose reference norm is under a
    thousandth of the median leaf's are left out (they move by
    round-off alone)."""
    med = float(np.median(list(want.values())))
    gaps = [abs(prog[n] - w) / max(w, med) for n, w in want.items()
            if w >= 1e-3 * med]
    return max(gaps)


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.core import models
    from repro.core.svi import SVI, SVIConfig
    from repro.core.vmp import VMPState, init_state
    from repro.data import write_sharded_corpus

    cfg = ctx.cfg
    k, v, alpha, beta = cfg["K"], cfg["V"], cfg["alpha"], cfg["beta"]
    batch = cfg["batch_docs_per_chip"]
    n_docs, n_hold = cfg["D"], cfg["holdout_docs"]
    seed = ctx.seed

    rng = gen.rng_for(seed, 1)
    train_ids, hold_ids = ref.holdout_split(n_docs, n_hold, seed)
    lengths = gen.batched_lengths(n_docs, cfg["mean_doc_tokens"],
                                  cfg["doc_length_sigma"], train_ids,
                                  hold_ids, batch, rng)
    docs = gen.documents(lengths, k, v, alpha, cfg["zipf_s"], rng)
    tokens = docs["tokens"]
    offsets = np.concatenate([[0], np.cumsum(lengths)])
    sched = Schedule(lengths, n_hold, batch, seed)

    tmp = tempfile.TemporaryDirectory(prefix="bench_corpus_")
    store = write_sharded_corpus(docs, tmp.name, vocab=v)
    svi_cfg = SVIConfig(batch_size=batch, kappa=cfg["kappa"],
                        tau=cfg["tau"], pad_multiple=cfg["pad_multiple"],
                        holdout_frac=n_hold / n_docs,
                        holdout_every=cfg["holdout_every"],
                        holdout_local_iters=cfg["holdout_local_iters"],
                        elog_dtype=ctx.elog_dtype, shuffle=False, seed=seed)
    model = models.make("lda", alpha=alpha, beta=beta, K=k, V=v)
    svi = SVI(model, svi_cfg, corpus=store)
    try:
        if ctx.traced:
            svi.step = harness.span("bench.svi_step", svi.step)
            svi.heldout_elbo = harness.span("bench.heldout_elbo",
                                            svi.heldout_elbo)
            svi.sampler.host_batch_at = harness.span(
                "bench.host_batch", svi.sampler.host_batch_at)

        # the checked steps, through the window's own call and feed
        state = init_state(svi.program, seed)
        p0 = {n: jnp.copy(a) for n, a in state.posteriors.items()}
        rho0 = ref.robbins_monro(0, cfg["tau"], cfg["kappa"])
        elbos = []
        state, e = svi.step(0, state)
        elbos.append(float(e))
        grad = _norms(state.posteriors, p0, {"phi": rho0})
        upd1 = np.asarray(state.posteriors["phi"] - p0["phi"])
        for t in range(1, CHECKED_STEPS):
            state, e = svi.step(t, state)
            elbos.append(float(e))
        change = _norms(state.posteriors, p0)
        del p0
        heldout = svi.heldout_elbo(state)

        # calibration reads the checked steps alone: --seconds 0 runs no
        # window
        ran, win = [], harness.Window()
        compiles0 = ctx.compile_log.compiles
        setup_s = time.perf_counter() - ctx.t_start
        if ctx.seconds > 0:
            # the other signatures of one epoch: every epoch repeats them
            pad = cfg["pad_multiple"]
            first_at: dict = {}
            for t in range(sched.per_epoch):
                first_at.setdefault(step_signature(sched, t, pad), t)
            seen = {step_signature(sched, t, pad)
                    for t in range(CHECKED_STEPS)}
            scratch = VMPState({n: jnp.copy(a)
                                for n, a in state.posteriors.items()},
                               jnp.copy(state.step))
            for sig, t in sorted(first_at.items()):
                if sig not in seen:
                    scratch, e = svi.step(t, scratch)
            jax.block_until_ready(scratch)
            del scratch
            log = ctx.compile_log
            harness.say(f"{len(first_at)} step signatures {sorted(first_at)}"
                        f"; {log.compiles} programs loaded, {log.hits} "
                        f"from the persistent cache, {log.seconds:.1f} s "
                        f"compiling or loading")

            # the window
            compiles0 = log.compiles
            every = cfg["holdout_every"]

            def stop(t, _elbo):
                # the window closes at the first held-out evaluation
                # after --seconds, so that it holds whole cycles of the
                # schedule's periodic work
                ran.append(t)
                if (time.perf_counter() - win.t0 >= ctx.seconds
                        and (not every or (t + 1) % every == 0)):
                    win.t1 = time.perf_counter()
                    return False
                return None

            setup_s = time.perf_counter() - ctx.t_start
            with harness.window(ctx.traced, win):
                state, hist = svi.fit(1 << 40, state=state, callback=stop)
        compiles = ctx.compile_log.compiles - compiles0
        device = harness.device_info(ctx.devs)
        del state
    finally:
        svi.close()
        tmp.cleanup()
    del svi
    gc.collect()

    n_tok = sum(sched.tokens(t) for t in ran)
    n_eval = sum(1 for t in ran if (t + 1) % cfg["holdout_every"] == 0)

    # the reference, after the window
    t_ref = time.perf_counter()
    readings = check_training(ctx, sched, tokens, offsets, elbos, grad,
                              change, upd1, heldout)
    harness.say(f"reference check took {time.perf_counter() - t_ref:.1f} s")

    step_work = counts.add(*[counts.svi_step(sched.tokens(t), k, v, batch)
                             for t in ran])
    plate_work = counts.add(*[counts.zstats(sched.tokens(t), k, v, batch)
                              for t in ran])
    n_hold_tok = int(lengths[sched.hold].sum())
    passes = cfg["holdout_local_iters"] + 1
    eval_work = counts.local_scorer(n_hold_tok, k, v, n_hold, passes)
    eval_work = {kk: n_eval * x for kk, x in eval_work.items()}
    return {
        "attempted": len(ran), "failed": 0,
        "e2e": {"train_tokens_per_s": (n_tok / win.seconds if ran
                                       else float("nan")),
                "setup_s": setup_s},
        "layer_run": {
            "trace": win.trace, "window_s": win.seconds, "steps": len(ran),
            "compiles_in_window": compiles, "device_kind": device["kind"],
            "n_devices": len(ctx.devs),
            "work": {"window": counts.add(step_work, eval_work),
                     "zstats": counts.add(plate_work, eval_work)}},
        "device": device, "readings": readings,
    }


def check_training(ctx, sched, tokens, offsets, elbos, grad, change, upd1,
                   heldout) -> dict:
    """The reference's first steps and held-out score against the
    program's: the numbers ``correct`` compares."""
    import jax.numpy as jnp
    cfg = ctx.cfg
    k, v, alpha, beta = cfg["K"], cfg["V"], cfg["alpha"], cfg["beta"]
    dtype = jnp.dtype(ctx.reference_dtype)
    n_docs = len(sched.lengths)

    def tokens_of(docs):
        lens = sched.lengths[docs]
        rows = np.repeat(np.arange(len(docs)), lens)
        idx = np.concatenate([np.arange(offsets[d], offsets[d + 1])
                              for d in docs])
        return rows, tokens[idx]

    post = ref.initial_posteriors(ctx.seed, n_docs, k, v, alpha, beta)
    theta, phi = post["theta"], post["phi"]
    theta0, phi0 = theta, phi
    want_elbo = []
    scale = len(sched.train) / sched.batch
    for t in range(CHECKED_STEPS):
        rho = ref.robbins_monro(t, cfg["tau"], cfg["kappa"])
        theta, phi, e = ref.svi_step(theta, phi, sched.docs(t), tokens_of,
                                     rho, scale, alpha, beta, dtype)
        want_elbo.append(e)
        if t == 0:
            rho0 = rho
            want_grad = _norms({"phi": phi, "theta": theta},
                               {"phi": phi0, "theta": theta0},
                               {"phi": rho0})
            ref_upd1 = np.asarray(phi - phi0)
    want_change = _norms({"phi": phi, "theta": theta},
                         {"phi": phi0, "theta": theta0})
    del theta, theta0, phi0
    hold = sched.hold
    rows, words = tokens_of(hold)
    want_held = float(ref.local_scores(
        phi, rows, words, len(hold), cfg["holdout_local_iters"], alpha,
        dtype).sum()) / len(words)
    del phi
    return {
        "elbo_gap": max(abs(g - w) / abs(w)
                        for g, w in zip(elbos, want_elbo)),
        "grad_norm_gap": worst_leaf_gap(grad, want_grad),
        "change_norm_gap": worst_leaf_gap(change, want_change),
        "update_max_gap": float(np.max(np.abs(upd1 - ref_upd1))
                                / np.max(np.abs(ref_upd1))),
        "heldout_gap": abs(heldout - want_held) / abs(want_held),
    }
