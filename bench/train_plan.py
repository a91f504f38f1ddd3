"""Driver of SVI training cells that build the model their configuration
names (traffic ``"driver": "train_plan"``): LDA or SLDA
(``cfg["model"]``), on one chip, or under a ``ShardingPlan`` over all
the chips the cell asks for.

It runs ``bench/train.py``'s schedule, checked steps, window and
held-out evaluations, with that driver's ``Schedule``, signatures and
gaps imported from it; what differs is the model, the plan, and for SLDA
the corpus (sentences from ``corpus_segment.py``), the reference
(``reference_slda.py``) and the work counts (``counts_segment.py``).  A
minibatch holds ``batch_docs_per_chip`` documents a chip: under a plan
the step's batch is split over the chips by token mass and the global
statistics are added over them; the held-out scorer runs on the first
chip.  ``train_tokens_per_s`` and ``setup_s`` are defined as in
``bench/train.py``.  Set-up prints the kernel route that
``explain_plan`` predicts for the token plate; ``ops.zstats`` asserts at
trace time that the route it runs is that one.
"""

from __future__ import annotations

import gc
import tempfile
import time

import numpy as np

from bench import corpus as gen
from bench import corpus_segment as seg
from bench import counts, counts_segment, harness
from bench import reference as ref
from bench import reference_slda
from bench.train import (CHECKED_STEPS, Schedule, _norms, check_training,
                         step_signature, worst_leaf_gap)


def _corpus(cfg, lengths, rng) -> dict:
    k, v = cfg["K"], cfg["V"]
    if cfg["model"] == "slda":
        doc_sents, sent_lengths = seg.sentence_lengths(
            lengths, cfg["mean_sentence_tokens"], rng)
        return seg.documents(lengths, doc_sents, sent_lengths, k, v,
                             cfg["alpha"], cfg["zipf_s"], rng)
    return gen.documents(lengths, k, v, cfg["alpha"], cfg["zipf_s"], rng)


def _plan(devs):
    """One chip: no plan.  More: data-parallel SVI over all of them."""
    if len(devs) == 1:
        return None
    from repro.compat import make_mesh
    from repro.core.partition import ShardingPlan
    return ShardingPlan(make_mesh((len(devs),), ("data",), devices=devs),
                        ("data",), "inferspark")


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.analysis.explain import explain_plan
    from repro.core import models
    from repro.core.svi import SVI, SVIConfig
    from repro.core.vmp import VMPState, init_state
    from repro.data import write_sharded_corpus

    cfg = ctx.cfg
    k, v, alpha, beta = cfg["K"], cfg["V"], cfg["alpha"], cfg["beta"]
    segments = cfg["model"] == "slda"
    batch = cfg["batch_docs_per_chip"] * len(ctx.devs)
    n_docs, n_hold = cfg["D"], cfg["holdout_docs"]
    seed = ctx.seed

    rng = gen.rng_for(seed, 1)
    train_ids, hold_ids = ref.holdout_split(n_docs, n_hold, seed)
    lengths = gen.batched_lengths(n_docs, cfg["mean_doc_tokens"],
                                  cfg["doc_length_sigma"], train_ids,
                                  hold_ids, batch, rng)
    docs = _corpus(cfg, lengths, rng)
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

    def model():
        return models.make(cfg["model"], alpha=alpha, beta=beta, K=k, V=v)

    try:
        route = explain_plan(model(), svi_cfg, corpus=store).routes[0]
        harness.say(f"EXPLAIN route of {route.latent}: {route.path} "
                    f"({route.reason}); {route.n_latent} instances, "
                    f"{route.n_tokens} tokens a step on one chip")
    except ValueError as e:     # a program that cannot plan over a corpus
        harness.say(f"EXPLAIN gives no plan over this corpus: {e}")
    svi = SVI(model(), svi_cfg, plan=_plan(ctx.devs), corpus=store)
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
    if segments:
        readings = check_segments(ctx, sched, docs, offsets, elbos, grad,
                                  change, upd1, heldout)
    else:
        readings = check_training(ctx, sched, tokens, offsets, elbos, grad,
                                  change, upd1, heldout)
    harness.say(f"reference check took {time.perf_counter() - t_ref:.1f} s")

    passes = cfg["holdout_local_iters"] + 1
    n_hold_tok = int(lengths[sched.hold].sum())
    if segments:
        sents = docs["doc_sents"]
        step_work = counts.add(*[counts_segment.svi_step(
            sched.tokens(t), int(sents[sched.docs(t)].sum()), k, v, batch)
            for t in ran])
        eval_work = counts_segment.local_scorer(
            n_hold_tok, int(sents[sched.hold].sum()), k, v, n_hold, passes)
    else:
        step_work = counts.add(*[counts.svi_step(sched.tokens(t), k, v,
                                                 batch) for t in ran])
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
            "work": {"window": counts.add(step_work, eval_work)}},
        "device": device, "readings": readings,
    }


def check_segments(ctx, sched, docs, offsets, elbos, grad, change, upd1,
                   heldout) -> dict:
    """``train.check_training`` for SLDA: the reference's first steps and
    held-out score (``reference_slda.py``) against the program's."""
    import jax.numpy as jnp
    cfg = ctx.cfg
    k, v, alpha, beta = cfg["K"], cfg["V"], cfg["alpha"], cfg["beta"]
    dtype = jnp.dtype(ctx.reference_dtype)
    sent_offsets = np.concatenate([[0], np.cumsum(docs["doc_sents"])])

    def plate_of(ids):
        return seg.plate_of(ids, offsets, sent_offsets, docs["sent_lengths"],
                            docs["tokens"])

    post = ref.initial_posteriors(ctx.seed, len(sched.lengths), k, v, alpha,
                                  beta)
    theta, phi = post["theta"], post["phi"]
    theta0, phi0 = theta, phi
    want_elbo = []
    scale = len(sched.train) / sched.batch
    for t in range(CHECKED_STEPS):
        rho = ref.robbins_monro(t, cfg["tau"], cfg["kappa"])
        theta, phi, e = reference_slda.svi_step(
            theta, phi, sched.docs(t), plate_of, rho, scale, alpha, beta,
            dtype)
        want_elbo.append(e)
        if t == 0:
            want_grad = _norms({"phi": phi, "theta": theta},
                               {"phi": phi0, "theta": theta0},
                               {"phi": rho})
            ref_upd1 = np.asarray(phi - phi0)
    want_change = _norms({"phi": phi, "theta": theta},
                         {"phi": phi0, "theta": theta0})
    del theta, theta0, phi0
    plate = plate_of(sched.hold)
    want_held = float(reference_slda.local_scores(
        phi, plate, len(sched.hold), cfg["holdout_local_iters"], alpha,
        dtype).sum()) / len(plate[2])
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
