"""Streaming minibatch VMP: stochastic variational inference (SVI).

The full-batch engine in ``vmp.py`` touches all N tokens per jitted step, so
corpus size is capped by one step's working set.  This module removes that
cap with the standard scalable counterpart of coordinate-ascent VMP
(Hoffman et al., *Stochastic Variational Inference*, JMLR 2013): sample a
minibatch B of partition-plate groups (documents), coordinate-ascent the
batch's LOCAL posteriors (theta rows), and take a natural-gradient step on
every GLOBAL Dirichlet

    post <- (1 - rho_t) * post + rho_t * (prior + (G / |B|) * stats_B)

with the Robbins-Monro step size ``rho_t = (tau + t) ** -kappa``
(kappa in (0.5, 1] guarantees convergence).  Because a Dirichlet's natural
parameter IS its concentration vector, the natural gradient of the ELBO is
exactly ``prior + scaled-stats - post``, so the update above is plain SGD in
natural-parameter space — no extra geometry code.

Degenerate case, tested bitwise: with |B| = G (every group) and rho = 1 the
update is ``prior + stats`` on every Dirichlet — one SVI step IS one
full-batch VMP step.

Per-step working set scales with |B| (the batch's token arrays and (|B_tok|,
K) responsibilities), not with N: only the posterior state — O(sum G_d K_d)
— persists.  Under a :class:`~repro.core.partition.ShardingPlan` each shard
receives its own sub-minibatch and the global stats are psum'd, matching the
full-batch engine's partitioning.

The corpus itself need not be resident either: with ``SVI(corpus=...)`` a
:class:`repro.data.ShardedCorpus` supplies each minibatch straight from
memory-mapped disk shards (double-buffered host prefetch), bitwise
equivalent to the resident path — see ``docs/data_pipeline.md``.

And the corpus need not fit one *machine*: with ``hosts=`` a
:class:`repro.data.HostAssignment` (plus a plan over a global mesh, in a
``jax.distributed`` multi-process run), each host owns a deterministic
subset of the corpus shards, minibatches partition the shared global
permutation by document ownership, sufficient statistics and the held-out
ELBO are psum'd across the mesh, and a single process with the same global
device count reproduces the run bitwise — ``docs/distributed.md``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from . import dists
from .compiler import (VMPProgram, local_dirichlets, slice_arrays,
                       sliced_shadow)
from .vmp import VMPState, _step_body, init_state


@dataclasses.dataclass
class SVIConfig:
    """Knobs of the streaming engine (defaults follow Hoffman et al.)."""
    batch_size: int = 64           # documents (partition groups) per step
    kappa: float = 0.7             # Robbins-Monro forgetting rate, (0.5, 1]
    tau: float = 10.0              # Robbins-Monro delay (down-weights early steps)
    local_iters: int = 1           # local coordinate-ascent passes per batch
    pad_multiple: int = 256        # pad sliced axes up to a multiple (0 = exact)
    elog_dtype: object = None      # narrow the token plate's message
                                   # tables (e.g. "bfloat16"); since the
                                   # fused-expectation change these are the
                                   # posterior concentration tables
    holdout_frac: float = 0.0      # fraction of groups held out for ELBO eval
    holdout_every: int = 10        # evaluate held-out ELBO every k steps
    holdout_local_iters: int = 10  # local passes when evaluating held-out docs
    shuffle: bool = True           # reshuffle group order every epoch
    rho: Optional[float] = None    # constant step size override (rho=1 +
                                   # batch_size=G == exact full-batch VMP)
    prefetch: bool = True          # sharded-corpus mode: overlap batch t+1's
                                   # shard I/O with step t (double-buffered)
    growing: bool = False          # sharded-corpus mode: re-snapshot the doc
                                   # population every epoch (streaming
                                   # corpora; needs capacity_docs headroom)
    capacity_docs: int = 0         # growing mode: pre-allocated local-row
                                   # ceiling the corpus may grow into (the
                                   # jitted step never retraces); 0 = let the
                                   # caller's template decide
    population_size: int = 0       # growing mode: fixed assumed population
                                   # for the stochastic scale G/|B|
                                   # (population-VI, for unbounded streams);
                                   # 0 = use the epoch snapshot size
    seed: int = 0

    def __post_init__(self):
        if self.rho is None and not (0.5 < self.kappa <= 1.0):
            raise ValueError(f"kappa must be in (0.5, 1], got {self.kappa}")
        if self.rho is not None and not (0.0 < self.rho <= 1.0):
            raise ValueError(f"constant rho must be in (0, 1] — rho > 1 "
                             f"overshoots the natural-gradient step and "
                             f"diverges silently — got {self.rho}")
        if self.tau < 0:
            raise ValueError("tau must be >= 0")
        if self.capacity_docs < 0 or self.population_size < 0:
            raise ValueError("capacity_docs / population_size must be >= 0")
        if (self.capacity_docs or self.population_size) and not self.growing:
            raise ValueError("capacity_docs / population_size only apply to "
                             "growing=True (streaming) mode")


def robbins_monro(t: int, tau: float = 10.0, kappa: float = 0.7) -> float:
    """Step size rho_t = min((tau + t) ** -kappa, 1.0); sum rho = inf,
    sum rho^2 < inf — the conditions for SVI convergence.

    The clamp makes the ``rho_t <= 1`` guarantee real: any ``tau < 1``
    yields ``(tau + 0) ** -kappa > 1`` at the first step, and ``tau = 0``
    (which ``SVIConfig`` accepts) used to return ``inf`` — one such step
    replaces the posterior state with ``inf * target`` and destroys the
    fit.  ``rho_0 = 1`` (a pure natural-gradient step to the first batch's
    target) is the correct degenerate limit instead.
    """
    base = tau + t
    if base <= 0:
        return 1.0
    return float(min(base ** (-kappa), 1.0))


# ---------------------------------------------------------------------------
# the jitted minibatch step
# ---------------------------------------------------------------------------

def _priors(program: VMPProgram) -> dict[str, jnp.ndarray]:
    return {n: jnp.asarray(d.prior)[None, :]
            for n, d in program.dirichlets.items()}


def make_svi_step(program: VMPProgram, caps: dict[str, int], plan=None,
                  local_iters: int = 1, donate: bool = True,
                  elog_dtype=None):
    """Build ``step(state, batch, rho, scale) -> (state', batch_elbo)``,
    jitted once per cap signature: every batch padded to the same ``caps``
    reuses the trace.

    ``batch`` is the output of :func:`device_batch`; ``rho`` the step size;
    ``scale`` the stochastic-stats multiplier G/|B| (both traced scalars, so
    schedules never retrace).  With ``plan`` the body runs inside shard_map:
    batch arrays carry a leading shard dim, global stats are psum'd by
    ``_step_body`` and local-row write-backs merge via a psum of deltas.
    """
    from .runtime import _resolve_elog_dtype
    local = local_dirichlets(program)
    shadow = sliced_shadow(program, caps)
    priors = _priors(program)
    axes = plan.axes if plan is not None else ()
    n_replicas = plan.n_shards if plan is not None else 1
    elog_dtype = _resolve_elog_dtype(elog_dtype)

    def body(state: VMPState, batch, rho, scale):
        # gather the batch's local rows; padding rows sit exactly at the
        # prior so their Dirichlet ELBO terms and stats are identically zero
        sliced = {}
        for name, d in program.dirichlets.items():
            if name in local:
                with jax.named_scope("svi.local_rows"):
                    rows = batch["dirs"][name]["rows"]
                    mask = batch["dirs"][name]["mask"]
                    got = state.posteriors[name][jnp.clip(rows, 0, d.g - 1)]
                    sliced[name] = jnp.where(mask[:, None] > 0, got,
                                             priors[name])
            else:
                sliced[name] = state.posteriors[name]

        st = VMPState(sliced, state.step)
        for _ in range(max(local_iters - 1, 0)):     # local refinement only
            ref, _ = _step_body(shadow, batch["arrays"], st,
                                axis_names=axes, local_dirs=local,
                                n_replicas=n_replicas, elog_dtype=elog_dtype)
            st = VMPState({n: (ref.posteriors[n] if n in local else sliced[n])
                           for n in sliced}, state.step)
        new, elbo = _step_body(shadow, batch["arrays"], st,
                               axis_names=axes, local_dirs=local,
                               n_replicas=n_replicas, elog_dtype=elog_dtype)

        posts = {}
        for name, d in program.dirichlets.items():
            if name in local:
                with jax.named_scope("svi.local_rows"):
                    rows = batch["dirs"][name]["rows"]
                    upd = new.posteriors[name]
                    if axes:
                        # shards own disjoint rows; merge deltas, stay
                        # replicated
                        delta = jnp.zeros_like(state.posteriors[name]) \
                            .at[rows].add(upd - sliced[name], mode="drop")
                        posts[name] = state.posteriors[name] + \
                            jax.lax.psum(delta, axes)
                    else:
                        posts[name] = state.posteriors[name].at[rows] \
                            .set(upd, mode="drop")
            else:
                # natural gradient: target = prior + scale * stats_B; the
                # where()s keep the |B|=G, rho=1 case bitwise equal to the
                # full-batch VMP update (no x-p+p float round-trip)
                with jax.named_scope("svi.global_update"):
                    target = priors[name] + scale * \
                        (new.posteriors[name] - priors[name])
                    target = jnp.where(scale == 1.0, new.posteriors[name],
                                       target)
                    blend = (1.0 - rho) * state.posteriors[name] + \
                        rho * target
                    posts[name] = jnp.where(rho == 1.0, target, blend)
        return VMPState(posts, state.step + 1), elbo

    # both forms jit a function named svi_step: a profile names the
    # program after it (jit_svi_step)
    if plan is None:
        def svi_step(state, batch, rho, scale):
            return body(state, batch, rho, scale)
        return jax.jit(svi_step, donate_argnums=(0,) if donate else ())

    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map

    def svi_step(state, batch, rho, scale):
        sq = {"arrays": {k: {kk: (None if vv is None else vv[0])
                             for kk, vv in v.items()}
                         for k, v in batch["arrays"].items()},
              "dirs": {k: {kk: vv[0] for kk, vv in v.items()}
                       for k, v in batch["dirs"].items()}}
        return body(state, sq, rho, scale)

    state_spec = VMPState({n: P() for n in program.dirichlets}, P())
    arr_spec = {}
    for spec_l in program.latents:
        arr_spec[spec_l.name] = {"prior_rows": P(axes), "mask": P(axes)}
        for f in spec_l.children:
            arr_spec[f.x_name] = {"values": P(axes), "zmap": P(axes),
                                  "base": P(axes), "mask": P(axes)}
    for s in program.statics:
        arr_spec[s.x_name] = {"rows": P(axes), "values": P(axes),
                              "mask": P(axes)}
    dir_spec = {n: {"rows": P(axes), "mask": P(axes)} for n in local}
    sharded = shard_map(svi_step, plan.mesh,
                        in_specs=(state_spec,
                                  {"arrays": arr_spec, "dirs": dir_spec},
                                  P(), P()),
                        out_specs=(state_spec, P()))
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def host_batch(program: VMPProgram, groups, caps_fn=None, plan=None,
               group_weights: Optional[np.ndarray] = None, slicer=None,
               caps_probe=None):
    """Build one minibatch's host-side (numpy) arrays.

    Returns ``(batch, caps, n_tokens)`` where ``batch = {"arrays", "dirs"}``
    holds numpy leaves — :func:`device_put_batch` places them on device and
    :func:`make_svi_step`'s step consumes the result.  Pure host work (no
    jax), so it can run on a prefetch thread.

    ``slicer(groups, caps_fn) -> (arrays, dirs, caps, n_tokens)`` selects
    the corpus view: default is :func:`repro.core.compiler.slice_arrays`
    over the resident ``program``; the out-of-core path binds
    :func:`repro.data.store.slice_sharded` instead (same contract, reads
    only the shards the batch touches).  With ``plan``, the batch's groups
    are LPT-packed into ``plan.n_shards`` sub-minibatches by token mass
    (``group_weights``), each shard's slice padded to shared caps and
    stacked on a leading shard dim.  ``caps_probe(groups) -> caps`` — an
    optional cheap predictor of the caps ``slicer(groups, None)`` would
    realize; when given, the plan path learns shared caps without slicing
    every sub-minibatch twice (the sharded probe reads no shards).
    """
    if slicer is None:
        slicer = lambda g, cf: slice_arrays(program, g, cf)  # noqa: E731
    groups = np.asarray(groups, np.int64)
    if plan is None:
        arrays, dirs, caps, n_tok = slicer(groups, caps_fn)
        return {"arrays": arrays, "dirs": dirs}, caps, n_tok

    from .partition import lpt_pack
    m = plan.n_shards
    w = (group_weights[groups] if group_weights is not None
         else np.ones(len(groups), np.int64))
    shard_of = lpt_pack(np.maximum(w, 1), m)
    parts = [groups[shard_of == s] for s in range(m)]

    # shared caps: probe (or slice) each shard exact, take maxima, re-pad
    if caps_probe is not None:
        part_caps = [caps_probe(p) for p in parts]
    else:
        part_caps = [slicer(p, None)[2] for p in parts]
    caps: dict[str, int] = {}
    for c in part_caps:
        for k, v in c.items():
            caps[k] = max(caps.get(k, 1), v)
    if caps_fn is not None:
        caps = {k: max(int(caps_fn(k, v)), v) for k, v in caps.items()}
    resliced = [slicer(p, lambda name, n: caps[name]) for p in parts]

    arrays = {}
    for name in resliced[0][0]:
        arrays[name] = {}
        for kk in resliced[0][0][name]:
            leaves = [r[0][name][kk] for r in resliced]
            if leaves[0] is None:
                arrays[name][kk] = None
            else:
                arrays[name][kk] = np.stack(leaves)
    dirs = {}
    for name in resliced[0][1]:
        dirs[name] = {kk: np.stack([r[1][name][kk] for r in resliced])
                      for kk in resliced[0][1][name]}
    n_tok = sum(r[3] for r in resliced)
    return {"arrays": arrays, "dirs": dirs}, caps, n_tok


class _ShardParts:
    """Host-local rows of one leading-shard-dim batch array — the
    multi-process analogue of the ``np.stack`` in :func:`host_batch`'s plan
    path.  In a multi-host run each process materializes only the rows of
    the mesh shards it hosts; :func:`device_put_batch` assembles them into
    one global array (:func:`shard_stacked_array`)."""

    __slots__ = ("shape", "dtype", "parts")

    def __init__(self, n_shards: int, parts: dict):
        row = next(iter(parts.values()))
        self.shape = (n_shards,) + row.shape
        self.dtype = row.dtype
        self.parts = parts

    @property
    def nbytes(self) -> int:
        return sum(p.nbytes for p in self.parts.values())


# In a multi-process run no process can jnp.asarray a *global* array — each
# supplies only the pieces that live on its own devices.  These two builders
# are the multi-host analogues of the single-process device placement
# (:func:`device_put_batch`): the replicated one for state and scalars, the
# stacked one for leading-shard-dim batch arrays.

def replicated_array(mesh, value):
    """A fully-replicated global ``jax.Array`` over ``mesh`` from a host
    value.  Every participating process must pass bitwise-identical data —
    the multi-host engine's inputs are deterministic functions of the
    shared manifest + seed, so this holds by construction (no collective
    needed to build it)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    value = np.asarray(value)
    return jax.make_array_from_callback(
        value.shape, NamedSharding(mesh, P()), lambda idx: value[idx])


def shard_stacked_array(mesh, axes, shape, dtype, parts: dict):
    """A global array sharded on dim 0 over the mesh ``axes`` from per-shard
    host rows.  ``shape[0]`` must equal the axes' total size; ``parts`` maps
    *global* shard index -> that shard's ``shape[1:]`` row, and only this
    process's shards need be present (the callback is invoked per
    addressable device, with the global index of its slice)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    sharding = NamedSharding(mesh, P(axes))

    def cb(idx):
        return np.asarray(parts[idx[0].start or 0], dtype)[None]

    return jax.make_array_from_callback(tuple(shape), sharding, cb)


def _put_leaf(vv, mesh=None, axes=()):
    """One batch leaf onto the device(s): ``None`` passes through,
    :class:`_ShardParts` becomes a global leading-dim-sharded array over
    ``mesh``/``axes`` (each process contributes its own shards' rows).
    Plain numpy is split the same way when ``mesh`` is given, each shard's
    rows copied straight to its device, and otherwise becomes a ``jnp``
    array on the default device."""
    if vv is None:
        return None
    if isinstance(vv, _ShardParts):
        return shard_stacked_array(mesh, axes, vv.shape, vv.dtype, vv.parts)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(vv, NamedSharding(mesh, P(axes)))
    return jnp.asarray(vv)


def device_put_batch(batch: dict, mesh=None, axes=()) -> dict:
    """Place a :func:`host_batch` result's numpy leaves on device
    (``None`` leaves pass through).  ``mesh``/``axes`` shard the plan
    path's leading shard dim over the mesh — see :func:`_put_leaf`."""
    return {"arrays": {k: {kk: _put_leaf(vv, mesh, axes)
                           for kk, vv in v.items()}
                       for k, v in batch["arrays"].items()},
            "dirs": {k: {kk: _put_leaf(vv, mesh, axes)
                         for kk, vv in v.items()}
                     for k, v in batch["dirs"].items()}}


def device_batch(program: VMPProgram, groups, caps_fn=None, plan=None,
                 group_weights: Optional[np.ndarray] = None, slicer=None):
    """Slice one minibatch and place it on device:
    :func:`host_batch` + :func:`device_put_batch` (see those for the
    parameter contracts).  Returns ``(batch, caps, n_tokens)``."""
    batch, caps, n_tok = host_batch(program, groups, caps_fn, plan,
                                    group_weights, slicer)
    return device_put_batch(batch), caps, n_tok


# ---------------------------------------------------------------------------
# held-out ELBO
# ---------------------------------------------------------------------------

def build_local_scorer(program: VMPProgram, caps: dict[str, int],
                       inner_iters: int, *, extras: bool = False,
                       n_seg: int = 0):
    """Compile the frozen-globals local-inference evaluator: fresh local
    posteriors start at the prior, take ``inner_iters`` coordinate-ascent
    passes with the global Dirichlets frozen at the caller's values, and
    the global Dirichlets' KL terms (training-objective bookkeeping, not
    predictive quality) are excluded from the returned score.

    This is the machinery behind both the SVI convergence signal
    (:func:`heldout_elbo`) and the query layer's fold-in engine
    (``repro.query.foldin``) — one compile per ``caps`` signature, every
    batch padded to the same caps reuses the trace.

    ``extras=False`` (the held-out ELBO path) returns a jitted
    ``fn(posteriors, arrays) -> elbo`` — ``posteriors`` need only hold the
    global (non-local) Dirichlets; local entries, if present, are ignored.

    ``extras=True`` (the fold-in path) returns a jitted
    ``fn(posteriors, arrays, seg) -> (elbo, locals, group_elbo)`` where
    ``elbo`` is the same scalar (identical ops, so it stays bitwise with
    the extras=False build at matching caps/iters), ``locals`` maps each
    local Dirichlet to its fitted ``(caps[name], k)`` posterior
    concentrations (MAP mixtures after normalization), and ``group_elbo``
    is the ``(n_seg,)`` per-partition-group decomposition of the score:
    per-instance logsumexp terms plus each group's local-Dirichlet ELBO
    terms, segment-summed by the ``seg`` arrays (one ``(cap,) int32``
    group-id array per latent / static / local Dirichlet, out-of-range
    ids dropped).  ``group_elbo.sum()`` equals ``elbo`` up to float
    reassociation.
    """
    from repro.kernels import ops as kops, ref as kref
    local = local_dirichlets(program)
    shadow = sliced_shadow(program, caps)
    priors = _priors(program)

    def _local_init(posteriors):
        posts = {}
        for name, d in program.dirichlets.items():
            if name in local:
                posts[name] = jnp.broadcast_to(priors[name],
                                               (caps[name], d.k))
            else:
                posts[name] = posteriors[name]
        return posts

    def _fit_locals(posts, arrays):
        # keep=local: the frozen globals come back as given, and no pass
        # computes statistics of a global table
        st = VMPState(posts, jnp.zeros((), jnp.int32))
        for _ in range(inner_iters):
            st, _ = _step_body(shadow, arrays, st, keep=local)
        _, elbo = _step_body(shadow, arrays, st, keep=local)
        return st, elbo

    def _drop_global_kl(elbo, posteriors):
        for name in program.dirichlets:
            if name not in local:
                elbo = elbo - dists.dirichlet_elbo_term(
                    priors[name], posteriors[name])
        return elbo

    # both builds jit a function named local_score (the held-out scorer
    # and fold-in): a profile names the program after it
    if not extras:
        @jax.jit
        def local_score(posteriors, arrays):
            st, elbo = _fit_locals(_local_init(posteriors), arrays)
            return _drop_global_kl(elbo, posteriors)

        return local_score

    from .vmp import _messages_to_latent

    @jax.jit
    def local_score(posteriors, arrays, seg):
        st, elbo = _fit_locals(_local_init(posteriors), arrays)
        elbo = _drop_global_kl(elbo, posteriors)

        # per-group decomposition: an explicit (materializing) pass at the
        # fitted locals — the fused elbo above stays the bitwise artifact
        elog = {n: kops.dirichlet_expectation(p)
                for n, p in st.posteriors.items()}
        grp = jnp.zeros((n_seg,), jnp.float32)
        for spec in shadow.latents:
            logits = _messages_to_latent(shadow, spec, elog, arrays)
            _, lse = kref.zstep(logits)
            m = arrays[spec.name].get("mask")
            if m is not None:
                lse = lse * m
            grp = grp + jax.ops.segment_sum(lse, seg[spec.name],
                                            num_segments=n_seg)
        for s in shadow.statics:
            a = arrays[s.x_name]
            e = elog[s.dir_name][a["rows"], a["values"]]
            if a.get("mask") is not None:
                e = e * a["mask"]
            grp = grp + jax.ops.segment_sum(e, seg[s.x_name],
                                            num_segments=n_seg)
        for name in local:
            post = st.posteriors[name]
            prior = jnp.broadcast_to(priors[name], post.shape)
            term = dists.dirichlet_log_norm(post) \
                - dists.dirichlet_log_norm(prior) \
                + ((prior - post) * elog[name]).sum(axis=-1)
            grp = grp + jax.ops.segment_sum(term, seg[name],
                                            num_segments=n_seg)
        return elbo, {n: st.posteriors[n] for n in local}, grp

    return local_score


def _build_heldout_fn(program: VMPProgram, caps: dict[str, int],
                      inner_iters: int):
    return build_local_scorer(program, caps, inner_iters, extras=False)


def build_sharded_scorer(program: VMPProgram, caps: dict[str, int],
                         inner_iters: int, plan):
    """Distributed counterpart of :func:`build_local_scorer` (extras=False):
    each mesh shard fits fresh local posteriors on its *own* held-out
    sub-slice with the global Dirichlets frozen (replicated), and the
    per-shard scores are psum'd over the plan's axes.

    Correctness of the psum: after the per-shard score drops the global
    Dirichlets' KL terms, what remains is purely shard-local — per-instance
    logsumexp terms (masked) plus local-Dirichlet terms, and padding rows
    sit exactly at the prior so they contribute 0 — so the sum over shards
    is the score of the union.  The arrays carry a leading shard dim
    (:func:`host_batch`'s plan layout) and in a multi-process mesh the
    result is fully replicated, so every host reads the same scalar.
    """
    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map
    fn = build_local_scorer(program, caps, inner_iters, extras=False)
    axes = plan.axes

    def body(posteriors, arrays):
        sq = {k: {kk: (None if vv is None else vv[0])
                  for kk, vv in v.items()} for k, v in arrays.items()}
        return jax.lax.psum(fn(posteriors, sq), axes)

    arr_spec = {}
    for spec_l in program.latents:
        arr_spec[spec_l.name] = {"prior_rows": P(axes), "mask": P(axes)}
        for f in spec_l.children:
            arr_spec[f.x_name] = {"values": P(axes), "zmap": P(axes),
                                  "base": P(axes), "mask": P(axes)}
    for s in program.statics:
        arr_spec[s.x_name] = {"rows": P(axes), "values": P(axes),
                              "mask": P(axes)}
    post_spec = {n: P() for n in program.dirichlets}
    return jax.jit(shard_map(body, plan.mesh,
                             in_specs=(post_spec, arr_spec),
                             out_specs=P()))


def heldout_elbo(program: VMPProgram, state: VMPState, groups,
                 inner_iters: int = 10, cache: Optional[dict] = None,
                 slicer=None) -> float:
    """Per-token ELBO on held-out groups under the current global
    posteriors: fresh local posteriors start at the prior, take
    ``inner_iters`` coordinate-ascent passes with the globals frozen, and
    the global Dirichlets' KL terms (training-objective bookkeeping, not
    predictive quality) are excluded.  Comparable across engines and batch
    sizes — the convergence metric of the streaming engine.  Returns a
    python float (nats/token); NaN when the groups hold no tokens.

    ``cache`` (a caller-owned dict, e.g. the :class:`SVI` instance's)
    memoizes the jitted evaluator per (caps, inner_iters) signature; without
    it each call retraces.  ``slicer`` as in :func:`host_batch` (the
    out-of-core path reads the held-out documents from their shards)."""
    groups = np.asarray(groups, np.int64)
    if slicer is None:
        slicer = lambda g, cf: slice_arrays(program, g, cf)  # noqa: E731
    with TraceAnnotation("svi.heldout.slice"):
        arrays, dirs, caps, n_tok = slicer(groups, None)
    if n_tok == 0:
        return float("nan")
    with TraceAnnotation("svi.heldout.put"):
        dev = {k: {kk: None if vv is None else jnp.asarray(vv)
                   for kk, vv in v.items()} for k, v in arrays.items()}
    with TraceAnnotation("svi.heldout.dispatch"):
        fn = None
        sig = (tuple(sorted(caps.items())), inner_iters)
        if cache is not None:
            fn = cache.get(sig)
        if fn is None:
            fn = _build_heldout_fn(program, caps, inner_iters)
            if cache is not None:
                cache[sig] = fn
        score = fn(state.posteriors, dev)
    with TraceAnnotation("svi.heldout.sync"):
        return float(score) / n_tok


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------

class SVI:
    """Streaming minibatch inference over a compiled :class:`VMPProgram`.

    Usage::

        svi = SVI(program, SVIConfig(batch_size=128, holdout_frac=0.05))
        state, history = svi.fit(steps=500)

    ``history["elbo"]`` is the per-step batch ELBO (noisy — a stochastic
    estimate at batch scale); ``history["heldout"]`` is the per-token
    held-out ELBO trace ``[(step, value), ...]`` (the convergence signal).

    **Out-of-core mode**: pass ``corpus=`` a
    :class:`~repro.data.store.ShardedCorpus` and, as the first argument,
    either an unobserved :class:`~repro.core.dsl.Model` (it is compiled
    into a full-size template via
    :func:`repro.data.store.sharded_template`) or such a template
    directly.  Minibatches are then read from the corpus's on-disk shards
    (only the shards the batch touches), host-side batch construction is
    double-buffered on a prefetch thread (``SVIConfig.prefetch``), and the
    per-process resident corpus state is O(n_docs) (the lengths array) +
    two batches' buffers.  The holdout split and the ``(seed, epoch)``
    minibatch permutation are byte-identical to resident mode, so on a
    corpus small enough to run both ways the fitted posteriors are
    **bitwise equal** (``tests/test_store.py``)::

        corpus = ShardedCorpus.open("/data/corpus")
        svi = SVI(models.make("lda", ...), SVIConfig(batch_size=256),
                  corpus=corpus)
    """

    def __init__(self, program, config: SVIConfig = None, plan=None,
                 corpus=None, hosts=None, validate=False):
        from repro.data.pipeline import MinibatchSampler, holdout_split
        self.cfg = config or SVIConfig()
        if validate:
            # opt-in pre-flight: structural diagnostics + retrace-hazard
            # audit, before any template/device work (docs/static_analysis.md)
            from repro.analysis.audit import audit_config
            from repro.analysis.validate import PreflightError, preflight
            diags = list(preflight(program)) if not isinstance(
                program, VMPProgram) else []
            diags += audit_config(
                self.cfg, n_docs=corpus.n_docs if corpus is not None
                else None,
                n_hosts=hosts.n_hosts if hosts is not None else None)
            if any(d.severity == "error" for d in diags):
                raise PreflightError(diags)
        self.plan = plan
        self.corpus = corpus
        self.hosts = hosts
        self._multiproc = False
        self._slicer = None
        self._caps_probe = None
        if self.cfg.growing and corpus is None:
            raise ValueError("growing=True needs corpus= (a ShardedCorpus "
                             "being appended to by a live writer)")
        if corpus is not None:
            from repro.data import store as _store
            if not isinstance(program, VMPProgram):
                cap = None
                if self.cfg.growing:
                    cap = self.cfg.capacity_docs
                    if not cap:
                        raise ValueError(
                            "growing=True needs capacity_docs — the "
                            "pre-allocated local-row ceiling the corpus "
                            "may grow into (or pass a sharded_template "
                            "built with capacity_docs=)")
                program = _store.sharded_template(program, corpus,
                                                  capacity_docs=cap)
            if self.cfg.growing and (program.meta.get("capacity_docs", 0)
                                     <= program.meta.get("pstar_size", 0)):
                raise ValueError(
                    "growing=True but the template has no growth headroom; "
                    "build it with sharded_template(..., capacity_docs=N) "
                    "for some N above the current document count")
            if not program.meta.get("sharded"):
                raise ValueError(
                    "corpus= needs a sharded template program; build one "
                    "with repro.data.store.sharded_template(model, corpus)")
            self._slicer = functools.partial(_store.slice_sharded,
                                             program, corpus)
            self._caps_probe = functools.partial(_store.sharded_caps,
                                                 program, corpus)
        if hosts is not None:
            self._init_hosts()
        self.program = program
        if program.meta.get("pstar") is None:
            raise ValueError("SVI needs a '?' partition plate "
                             "(documents) to sample minibatches over")
        n_groups = program.meta["pstar_size"]
        if self.cfg.holdout_frac == 0:
            self.train = np.arange(n_groups, dtype=np.int64)
            self.holdout = np.zeros(0, np.int64)
        else:
            self.train, self.holdout = holdout_split(
                n_groups, self.cfg.holdout_frac, self.cfg.seed)
        batch_size = min(self.cfg.batch_size, len(self.train))
        if corpus is not None:
            from repro.data.store import ShardedMinibatchSampler
            self._weights = np.asarray(corpus.lengths, np.int64)
            self.sampler = ShardedMinibatchSampler(
                corpus=corpus, groups=self.train, batch_size=batch_size,
                seed=self.cfg.seed, shuffle=self.cfg.shuffle,
                loader=(self._load_groups_hosts if hosts is not None
                        else self._load_groups),
                prefetch=self.cfg.prefetch,
                grow=self.cfg.growing,
                exclude=self.holdout if self.cfg.growing else None,
                max_group=(program.meta["capacity_docs"]
                           if self.cfg.growing else None))
        else:
            self.sampler = MinibatchSampler(
                groups=self.train, batch_size=batch_size,
                seed=self.cfg.seed, shuffle=self.cfg.shuffle)
            self._weights = self._group_token_weights()
        self._steps: dict = {}
        self._heldout_cache: dict = {}

    def _group_token_weights(self) -> np.ndarray:
        """Per-group observed-token counts ``(pstar_size,) int64`` — the
        LPT packing weights of the distributed path."""
        w = np.zeros(self.program.meta["pstar_size"], np.int64)
        for spec in self.program.latents:
            for f in spec.children:
                g = spec.group if f.zmap is None else spec.group[f.zmap]
                np.add.at(w, g, 1)
        for s in self.program.statics:
            if s.group is not None:
                np.add.at(w, s.group, 1)
        return w

    def _caps_fn(self, name, n):
        m = self.cfg.pad_multiple
        return n if not m else -(-max(n, 1) // m) * m

    def _load_groups(self, groups):
        """Host-batch loader for one group set (runs on the prefetch
        thread in sharded mode — numpy only).  Returns
        ``(batch, caps, n_tokens, n_groups)``."""
        if self.cfg.growing:
            # refresh() rebinds corpus.lengths wholesale; re-fetch so the
            # LPT packing weights cover newly committed documents
            self._weights = np.asarray(self.corpus.lengths, np.int64)
        hb, caps, n_tok = host_batch(self.program, groups, self._caps_fn,
                                     plan=self.plan,
                                     group_weights=self._weights,
                                     slicer=self._slicer,
                                     caps_probe=self._caps_probe)
        return hb, caps, n_tok, len(groups)

    # -- multi-host partitioned batching ----------------------------------

    def _init_hosts(self):
        """Validate the topology and build the shard->host map.

        ``hosts`` (a :class:`repro.data.HostAssignment`) turns the plan
        path into ownership-partitioned batching: documents go to the mesh
        shards of the host that *owns* them (``doc_ownership``), not to
        whichever shard the global LPT pack prefers.  In a real
        ``jax.distributed`` run (``jax.process_count() > 1``) the mesh
        shards of host ``h`` are the devices of process ``h`` and the
        corpus must be opened with the matching host view; in a single
        process the same ``n_hosts`` are *virtual* — the mesh's devices are
        split into ``n_hosts`` contiguous groups, which makes the SPMD
        program identical to the real multi-process one at an equal global
        device count (the bitwise 2-process-vs-virtual contract of
        ``tests/test_multihost.py``).
        """
        from repro.data import store as _store
        hosts = self.hosts
        if self.corpus is None or self.plan is None:
            raise ValueError("hosts= needs both corpus= (a partitioned "
                             "ShardedCorpus) and plan= (the global mesh)")
        if self.cfg.growing:
            raise NotImplementedError(
                "growing corpora are single-host for now: a multi-host "
                "epoch snapshot needs a refresh barrier so every host "
                "adopts the same commit")
        devs = list(self.plan.mesh.devices.flat)
        import jax as _jax
        if _jax.process_count() > 1:
            self._multiproc = True
            if hosts.n_hosts != _jax.process_count():
                raise ValueError(
                    f"hosts.n_hosts={hosts.n_hosts} but this is a "
                    f"{_jax.process_count()}-process run")
            if hosts.host_id != _jax.process_index():
                raise ValueError(
                    f"hosts.host_id={hosts.host_id} but this process is "
                    f"index {_jax.process_index()}")
            if (self.corpus.hosts is None
                    or self.corpus.hosts.host_id != hosts.host_id
                    or self.corpus.hosts.n_hosts != hosts.n_hosts):
                raise ValueError(
                    "in a multi-process run the corpus must be opened with "
                    "the matching host view: ShardedCorpus.open(path, "
                    "hosts=HostAssignment(n_hosts, host_id, seed))")
            self._shard_host = np.asarray(
                [d.process_index for d in devs], np.int32)
        else:
            if self.corpus.hosts is not None:
                raise ValueError("virtual-host mode (single process) needs "
                                 "an unrestricted corpus — all shards are "
                                 "local")
            m = len(devs)
            if m % hosts.n_hosts:
                raise ValueError(f"{m} mesh devices do not split evenly "
                                 f"into {hosts.n_hosts} virtual hosts")
            self._shard_host = np.repeat(
                np.arange(hosts.n_hosts, dtype=np.int32),
                m // hosts.n_hosts)
        ownership_seed = (self.corpus.hosts.seed
                          if self.corpus.hosts is not None else hosts.seed)
        self._doc_owner = _store.doc_ownership(
            self.corpus.manifest, hosts.n_hosts, ownership_seed)

    def _host_parts(self, groups: np.ndarray) -> list:
        """Partition one *global* batch onto the mesh shards: each document
        goes to its owner host (``doc_ownership`` — the only host that can
        read it), then LPT-packs by token mass across that host's shards.
        A pure function of (lengths, manifest, seed, mesh), so every host
        computes the identical global partition with no communication."""
        from .partition import lpt_pack
        owner = self._doc_owner[groups]
        parts: list = [None] * len(self._shard_host)
        for h in range(self.hosts.n_hosts):
            gh = groups[owner == h]
            sids = np.flatnonzero(self._shard_host == h)
            shard_of = lpt_pack(np.maximum(self._weights[gh], 1), len(sids))
            for j, s in enumerate(sids):
                parts[int(s)] = gh[shard_of == j]
        return parts

    def _stack_parts(self, leaves: dict, n_shards: int):
        """Assemble per-shard leaf rows into one leading-shard-dim batch
        leaf: a plain ``np.stack`` when every shard is local (the
        single-process layout :func:`host_batch` produces), a
        :class:`_ShardParts` carrier otherwise."""
        if self._multiproc:
            return _ShardParts(n_shards, leaves)
        return np.stack([leaves[s] for s in sorted(leaves)])

    def _load_groups_hosts(self, groups):
        """Multi-host loader: the *schedule* stays the global ``(seed,
        epoch)`` permutation (every host computes the same ``batch_at``);
        only the slicing is partitioned.  Shared caps are agreed from the
        lengths-only probe of **every** shard's part — no cross-host
        traffic, no shard I/O — so all hosts pad to identical shapes and
        the jitted step never diverges across processes."""
        groups = np.unique(np.asarray(groups, np.int64))
        parts = self._host_parts(groups)
        caps: dict[str, int] = {}
        for p in parts:
            for k, v in self._caps_probe(p).items():
                caps[k] = max(caps.get(k, 1), int(v))
        caps = {k: max(int(self._caps_fn(k, v)), v) for k, v in caps.items()}
        cf = lambda name, n: caps[name]                       # noqa: E731
        local = (np.flatnonzero(self._shard_host == self.hosts.host_id)
                 if self._multiproc else np.arange(len(parts)))
        sliced = {int(s): self._slicer(parts[int(s)], cf) for s in local}
        ref_a, ref_d = sliced[int(local[0])][0], sliced[int(local[0])][1]
        arrays: dict = {}
        for name in ref_a:
            arrays[name] = {}
            for kk, vv in ref_a[name].items():
                arrays[name][kk] = None if vv is None else self._stack_parts(
                    {int(s): sliced[int(s)][0][name][kk] for s in local},
                    len(parts))
        dirs = {name: {kk: self._stack_parts(
            {int(s): sliced[int(s)][1][name][kk] for s in local},
            len(parts)) for kk in ref_d[name]} for name in ref_d}
        n_tok = int(np.asarray(self.corpus.lengths)[groups].sum())
        return {"arrays": arrays, "dirs": dirs}, caps, n_tok, len(groups)

    def _scalar(self, x):
        """A step scalar every mesh shard can read: plain ``jnp.float32``
        in-process, a replicated global array in a multi-process mesh."""
        if not self._multiproc:
            return jnp.float32(x)
        return replicated_array(self.plan.mesh, np.float32(x))

    def _globalize(self, state: VMPState) -> VMPState:
        """Re-home a host-local state as fully-replicated global arrays on
        the multi-process mesh (no-op otherwise).  Every process holds
        bitwise-identical values (seeded init, or a shared session file),
        so no collective is needed."""
        if not self._multiproc:
            return state
        mesh = self.plan.mesh
        return VMPState(
            {n: replicated_array(mesh, np.asarray(v))
             for n, v in state.posteriors.items()},
            replicated_array(mesh, np.asarray(state.step, np.int32)))

    def step(self, t: int, state: VMPState):
        """One SVI step at schedule position ``t``; returns (state', elbo).

        Host spans (profiler trace only): ``svi.host_batch``,
        ``svi.device_put``, ``svi.dispatch`` (``svi.compile`` inside it on
        a new step signature, which also holds that first call)."""
        with TraceAnnotation("svi.host_batch"):
            if self.corpus is not None:
                hb, caps, _, n_b = self.sampler.host_batch_at(t)
            else:
                hb, caps, _, n_b = self._load_groups(
                    self.sampler.batch_at(t))
        with TraceAnnotation("svi.device_put"):
            batch = device_put_batch(
                hb, mesh=self.plan.mesh if self.plan is not None else None,
                axes=self.plan.axes if self.plan is not None else ())
        with TraceAnnotation("svi.dispatch"):
            rho = (self.cfg.rho if self.cfg.rho is not None
                   else robbins_monro(t, self.cfg.tau, self.cfg.kappa))
            # n_b is the true batch size (the epoch's tail batch may be
            # short).  The stochastic scale G/|B|: G is the training
            # population — fixed in batch mode, the epoch snapshot size
            # under a growing corpus, or a pinned assumed population
            # (population-VI) for unbounded streams.  Traced as a scalar
            # either way: growth never retraces.
            if self.cfg.growing:
                n_pop = (self.cfg.population_size
                         or self.sampler.population_at(t))
            else:
                n_pop = len(self.train)
            args = (state, batch, self._scalar(rho),
                    self._scalar(n_pop / n_b))
            sig = tuple(sorted(caps.items()))
            fn = self._steps.get(sig)
            if fn is None:
                with TraceAnnotation("svi.compile"):
                    fn = self._steps[sig] = make_svi_step(
                        self.program, caps, plan=self.plan,
                        local_iters=self.cfg.local_iters,
                        elog_dtype=self.cfg.elog_dtype)
                    return fn(*args)
            return fn(*args)

    def heldout_elbo(self, state: VMPState) -> float:
        """Per-token held-out ELBO at ``state`` (NaN without a holdout)."""
        if len(self.holdout) == 0:
            return float("nan")
        if self.hosts is not None:
            return self._heldout_hosts(state)
        if self.plan is not None:
            # the scorer is a one-device program, and jit cannot partition
            # its Pallas kernels over the mesh the replicated state sits on
            dev = self.plan.mesh.devices.flat[0]
            state = VMPState(jax.device_put(state.posteriors, dev),
                             state.step)
        return heldout_elbo(self.program, state, self.holdout,
                            self.cfg.holdout_local_iters,
                            cache=self._heldout_cache, slicer=self._slicer)

    def _heldout_hosts(self, state: VMPState) -> float:
        """Multi-host held-out ELBO: the holdout is partitioned by document
        ownership exactly like a training batch (each host reads only its
        shards), scored per shard with frozen globals, and psum'd
        (:func:`build_sharded_scorer`).  Every host returns the identical
        replicated scalar."""
        groups = np.asarray(self.holdout, np.int64)
        parts = self._host_parts(groups)
        caps: dict[str, int] = {}
        for p in parts:
            for k, v in self._caps_probe(p).items():
                caps[k] = max(caps.get(k, 1), int(v))
        cf = lambda name, n: caps[name]                       # noqa: E731
        local = (np.flatnonzero(self._shard_host == self.hosts.host_id)
                 if self._multiproc else np.arange(len(parts)))
        sliced = {int(s): self._slicer(parts[int(s)], cf)[0] for s in local}
        ref = sliced[int(local[0])]
        arrays: dict = {}
        for name in ref:
            arrays[name] = {}
            for kk, vv in ref[name].items():
                arrays[name][kk] = None if vv is None else self._stack_parts(
                    {int(s): sliced[int(s)][name][kk] for s in local},
                    len(parts))
        n_tok = int(np.asarray(self.corpus.lengths)[groups].sum())
        if n_tok == 0:
            return float("nan")
        sig = (tuple(sorted(caps.items())), self.cfg.holdout_local_iters,
               "sharded")
        fn = self._heldout_cache.get(sig)
        if fn is None:
            fn = build_sharded_scorer(self.program, caps,
                                      self.cfg.holdout_local_iters,
                                      self.plan)
            self._heldout_cache[sig] = fn
        mesh = self.plan.mesh if self._multiproc else None
        axes = self.plan.axes if self._multiproc else ()
        dev = {k: {kk: _put_leaf(vv, mesh, axes) for kk, vv in v.items()}
               for k, v in arrays.items()}
        return float(fn(state.posteriors, dev)) / n_tok

    def close(self):
        """Stop the sharded sampler's prefetch thread (no-op in resident
        mode; further ``fit`` calls restart prefetching lazily)."""
        if hasattr(self.sampler, "close"):
            self.sampler.close()

    # -- crash-safe sessions -------------------------------------------------

    def _fingerprint(self) -> dict:
        from repro.checkpoint.session import session_fingerprint
        return session_fingerprint(self.program, self.cfg,
                                   batch_size=self.sampler.batch_size)

    def _snapshot_session(self, state: VMPState, history: dict):
        """Host-side resumable snapshot of the fit at ``state.step``."""
        from repro.checkpoint.session import TrainSession
        epochs = []
        snap = getattr(self.sampler, "epoch_snapshots", None)
        if snap is not None:
            epochs = snap()
        corpus = None
        if self.corpus is not None:
            corpus = {"n_docs": int(self.corpus.n_docs),
                      "n_tokens": int(self.corpus.n_tokens),
                      "n_shards": int(self.corpus.n_shards)}
        return TrainSession(
            posteriors={n: np.asarray(v)
                        for n, v in state.posteriors.items()},
            t=int(state.step),
            history={"elbo": list(history["elbo"]),
                     "heldout": list(history["heldout"])},
            epochs=epochs, holdout=np.asarray(self.holdout, np.int64),
            corpus=corpus, fingerprint=self._fingerprint())

    def _adopt_session(self, sess, where: str):
        """Rebuild (state, history) from a session; reseats the sampler
        cursor and the held-out split so the continuation is bitwise."""
        from repro.checkpoint.session import check_fingerprint
        check_fingerprint(sess.fingerprint, self._fingerprint(), where)
        if self.corpus is not None and sess.corpus:
            self.corpus.refresh()
            if int(self.corpus.n_docs) < int(sess.corpus["n_docs"]):
                raise ValueError(
                    f"refusing to resume from {where}: corpus has "
                    f"{self.corpus.n_docs} docs but the session saw "
                    f"{sess.corpus['n_docs']} — append-only stores never "
                    f"shrink; is this the right corpus directory?")
        hold = np.asarray(sess.holdout, np.int64)
        if self.cfg.growing:
            # the split was drawn against the corpus size at first build;
            # adopt it (and the epoch snapshots) rather than re-deriving
            self.holdout = hold
            self.sampler.exclude = hold
            self.sampler.restore_epochs(sess.epochs)
        elif not np.array_equal(hold, self.holdout):
            raise ValueError(
                f"refusing to resume from {where}: held-out split differs "
                f"from the session's (corpus or seed changed?)")
        state = VMPState(
            {n: jnp.asarray(v) for n, v in sess.posteriors.items()},
            jnp.asarray(sess.t, jnp.int32))
        history = {"elbo": list(sess.history["elbo"]),
                   "heldout": list(sess.history["heldout"])}
        return state, history

    def fit(self, steps: int, state: Optional[VMPState] = None,
            callback=None, *, checkpoint_dir: Optional[str] = None,
            checkpoint_every: int = 10, checkpoint_keep: int = 3,
            resume_from=None):
        """Run ``steps`` minibatch updates; resumes the schedule from
        ``state.step``.  ``callback(t, batch_elbo) -> False`` stops early
        (the full-batch engine's callback contract).

        **Crash safety**: with ``checkpoint_dir`` a resumable
        :class:`~repro.checkpoint.TrainSession` is committed (async,
        self-validating — see ``docs/fault_tolerance.md``) every
        ``checkpoint_every`` steps and at the end of the run.
        ``resume_from=`` a directory (or ``True`` for ``checkpoint_dir``
        itself) restores the newest valid session and continues
        bitwise-identically: state, Robbins-Monro position, sampler
        cursor, held-out split, and the accumulated history all carry
        over; a session written by a mismatched model/config is refused.
        ``resume_from=True`` with no session yet is a cold start, so the
        always-on loop can use one code path.  ``steps`` counts the
        updates *this call* runs (on resume: the remaining budget).

        Under a profiler session each iteration is a ``svi.step`` step
        span holding ``svi.elbo_sync`` and ``svi.heldout`` (see "Tracing a
        fit" in ``docs/inference_engines.md``).
        """
        from repro.checkpoint import CheckpointStore
        from repro.checkpoint import session as _session
        from repro.testing import faults

        store = None
        if checkpoint_dir is not None:
            store = CheckpointStore(checkpoint_dir,
                                    every=max(1, checkpoint_every),
                                    keep=checkpoint_keep)
            if self._multiproc and jax.process_index() != 0:
                # one writer per cluster: the state is replicated, so host 0
                # persists for everyone (sessions are read by all on resume
                # — a shared filesystem is the multi-host contract)
                store = None
        resume_dir = None
        if resume_from is True:
            if checkpoint_dir is None:
                raise ValueError("resume_from=True needs checkpoint_dir=")
            resume_dir = checkpoint_dir
        elif resume_from:
            resume_dir = str(resume_from)
        history = {"elbo": [], "heldout": []}
        if resume_dir is not None:
            if state is not None:
                raise ValueError("pass state= or resume_from=, not both")
            try:
                sess = _session.load_session(resume_dir)
            except FileNotFoundError:
                if resume_from is not True:
                    raise
                sess = None                      # cold start of the loop
            if sess is not None:
                state, history = self._adopt_session(sess, resume_dir)
        if state is None:
            state = init_state(self.program, self.cfg.seed)
        # multi-process: re-home the (identical-everywhere) host state as
        # replicated global arrays so the shard_map'd step can consume it
        state = self._globalize(state)
        start = int(state.step)
        try:
            for t in range(start, start + steps):
                with StepTraceAnnotation("svi.step", step_num=t):
                    faults.trip("svi.step")
                    state, elbo = self.step(t, state)
                    with TraceAnnotation("svi.elbo_sync"):
                        elbo_f = float(elbo)
                    history["elbo"].append(elbo_f)
                    if (len(self.holdout) and self.cfg.holdout_every
                            and ((t + 1) % self.cfg.holdout_every == 0
                                 or t == start + steps - 1)):
                        with TraceAnnotation("svi.heldout"):
                            held = self.heldout_elbo(state)
                        history["heldout"].append((t, held))
                    if store is not None and (
                            (t + 1) % store.every == 0
                            or t == start + steps - 1):
                        _session.save_session(
                            store, self._snapshot_session(state, history),
                            force=True)
                    if callback is not None and callback(t, elbo_f) is False:
                        break
        finally:
            if store is not None:
                store.wait()
        return state, history
