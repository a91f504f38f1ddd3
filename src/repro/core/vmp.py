"""The VMP engine: one compiled update step per iteration.

For the conjugate class InferSpark supports, VMP coincides with coordinate
ascent variational inference: messages into a latent Categorical are
Dirichlet log-expectation gathers, the latent's update is a softmax, and each
Dirichlet's update is its prior plus (responsibility-weighted) count
statistics.  The paper's per-iteration update schedule "(pi and phi) -> x ->
z -> x" (section 3.4) becomes a fixed substep order inside one jitted step:

    Elog tables -> latent responsibilities -> sufficient stats -> posteriors

The ELBO returned each step is exact: with responsibilities at their
coordinate optimum the latent+likelihood contribution collapses to
``sum_i logsumexp_k(logits_i)``, so monitoring costs one extra reduction.
The sequence of per-step ELBOs is provably non-decreasing (property-tested).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from . import dists
from .compiler import VMPProgram


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class VMPState:
    """Inference state: posterior concentrations of every Dirichlet node.

    Latent responsibilities are *not* state — they are recomputed from the
    posteriors each iteration (they are the messages, not the marginals),
    which keeps the state small: O(sum G_d * K_d), independent of N.
    """
    posteriors: dict[str, jax.Array]
    step: jax.Array                      # iteration counter (checkpointing)

    def tree_flatten(self):
        names = sorted(self.posteriors)
        return ([self.posteriors[n] for n in names] + [self.step], names)

    @classmethod
    def tree_unflatten(cls, names, children):
        return cls(dict(zip(names, children[:-1])), children[-1])


def init_state(program: VMPProgram, seed: int = 0) -> VMPState:
    """Prior + multiplicative noise: symmetry breaking is required for any
    mixture (all-equal posteriors are a saddle point of the ELBO)."""
    key = jax.random.PRNGKey(seed)
    posts = {}
    for name, d in sorted(program.dirichlets.items()):
        key, sub = jax.random.split(key)
        noise = jax.random.uniform(sub, (d.g, d.k), jnp.float32, 0.5, 1.5)
        posts[name] = jnp.asarray(d.prior)[None, :] * jnp.ones((d.g, 1)) + noise
    return VMPState(posts, jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# message computation
# ---------------------------------------------------------------------------

def _messages_to_latent(program, spec, elog, arrays):
    """Sum of prior + child messages -> logits (n, K)."""
    logits = elog[spec.prior_dir][arrays[spec.name]["prior_rows"]]
    for f in spec.children:
        a = arrays[f.x_name]
        if f.specialized:
            e = elog[f.dir_name][:, a["values"]].T
        else:
            kk = jnp.arange(spec.k, dtype=jnp.int32)
            base = a["base"][:, None] if a.get("base") is not None else 0
            rows = base + f.stride * kk[None, :]
            e = elog[f.dir_name][rows, a["values"][:, None]]
        if a.get("mask") is not None:
            e = e * a["mask"][:, None]
        if a.get("zmap") is not None:
            e = jax.ops.segment_sum(e, a["zmap"], num_segments=spec.n)
        logits = logits + e
    return logits


# ---------------------------------------------------------------------------
# the jitted step
# ---------------------------------------------------------------------------

def _program_arrays(program: VMPProgram) -> dict:
    """Device constants: observed values, maps, static rows (paper: the MPG's
    edge structure, here dense index arrays)."""
    arrays: dict[str, dict] = {}
    for spec in program.latents:
        arrays[spec.name] = {"prior_rows": jnp.asarray(spec.prior_rows)}
        for f in spec.children:
            arrays[f.x_name] = {
                "values": jnp.asarray(f.values),
                "zmap": None if f.zmap is None else jnp.asarray(f.zmap),
                "base": None if f.base is None else jnp.asarray(f.base),
                "mask": None,
            }
    for s in program.statics:
        arrays[s.x_name] = {"rows": jnp.asarray(s.rows),
                            "values": jnp.asarray(s.values), "mask": None}
    return arrays


def _step_body(program: VMPProgram, arrays: dict, state: VMPState,
               axis_names: tuple = (), local_dirs: frozenset = frozenset(),
               n_replicas: int = 1, elog_dtype=None, keep=None):
    """One VMP iteration.  ``axis_names`` non-empty => running inside
    shard_map; stats of non-local Dirichlets are psum'd (the InferSpark
    partitioning: replicate the small posteriors, keep big plates local).

    The token plate runs through the fused ``kops.zstats`` substep: per
    latent, the Elog gathers, softmax/logsumexp, and sufficient-statistics
    scatters happen in one streaming pass, so the (N, K) responsibilities
    are never materialized (see docs/performance.md).  The substep is fed
    the posterior *concentrations* (``tables="alpha"``): on TPU the
    ``dirichlet_expectation`` is fused into the gather kernels, so no Elog
    message table is materialized in HBM for the token plate at all —
    statics and the Dirichlet ELBO terms compute their own expectations
    (element-wise reductions XLA fuses without a round trip).
    ``elog_dtype`` (e.g. ``jnp.bfloat16``) optionally narrows the
    concentration tables the token plate reads — halving their HBM traffic
    — while the in-kernel digamma, softmax, stats accumulation, and the
    Dirichlet ELBO terms stay f32.

    ``keep`` names the Dirichlets whose new posteriors the caller reads
    (``None``: all of them).  A Dirichlet outside it is returned as its
    input posterior, and a latent none of whose children's Dirichlets is
    kept asks the token plate for no child statistics
    (``kops.zstats(child_stats=False)``); the ELBO is the same either way.
    """
    from repro.kernels import ops as kops

    def kept(name):
        return keep is None or name in keep

    amsg = state.posteriors if elog_dtype is None else \
        {n: p.astype(elog_dtype) for n, p in state.posteriors.items()}

    elbo = jnp.zeros((), jnp.float32)
    stats = {n: jnp.zeros((d.g, d.k), jnp.float32)
             for n, d in program.dirichlets.items() if kept(n)}

    def add_stats(name, st):
        if name in stats:
            stats[name] = stats[name] + st

    # host-precomputed streamed-table bucketing: the permutation depends
    # only on the program's static observed values, so it is computed once
    # (numpy, off-device) and cached on the program; the sliced/SVI path,
    # whose index streams are tracers, caches None and keeps the in-trace
    # fallback.  Keyed per (latent name, token count): a differently
    # shaped view of the program (a per-shard or padded shadow sharing
    # this meta dict) can never pick up a permutation computed for
    # another extent.
    bcache = program.meta.setdefault("_zstats_bucketing", {})

    for spec in program.latents:
        children = tuple(
            kops.ZChild(elog=amsg[f.dir_name],
                        values=arrays[f.x_name]["values"],
                        stride=f.stride,
                        zmap=arrays[f.x_name].get("zmap"),
                        base=arrays[f.x_name].get("base"),
                        mask=arrays[f.x_name].get("mask"))
            for f in spec.children)
        child_stats = any(kept(f.dir_name) for f in spec.children)
        bucketing = None   # a local-only pass streams no table
        if child_stats:
            bkey = (spec.name, arrays[spec.name]["prior_rows"].shape[0])
            if bkey not in bcache:
                bcache[bkey] = kops.host_bucketing(
                    amsg[spec.prior_dir], arrays[spec.name]["prior_rows"],
                    children, tables="alpha")
            bucketing = bcache[bkey]
        lse_sum, pstats, cstats = kops.zstats(
            amsg[spec.prior_dir], arrays[spec.name]["prior_rows"], children,
            zmask=arrays[spec.name].get("mask"), tables="alpha",
            bucketing=bucketing, child_stats=child_stats)
        elbo = elbo + lse_sum
        # prior-factor stats (theta <- z)
        add_stats(spec.prior_dir, pstats)
        # child-factor stats (phi <- x weighted by r); none when not kept
        for f, cs in zip(spec.children, cstats):
            add_stats(f.dir_name, cs)

    selog: dict[str, jax.Array] = {}   # statics' Elog tables, on demand
    for s in program.statics:
        a = arrays[s.x_name]
        d = program.dirichlets[s.dir_name]
        if s.dir_name not in selog:
            selog[s.dir_name] = kops.dirichlet_expectation(
                state.posteriors[s.dir_name])
        e = selog[s.dir_name][a["rows"], a["values"]]
        if a.get("mask") is not None:
            e = e * a["mask"]
        elbo = elbo + e.sum()
        if kept(s.dir_name):
            ones = jnp.ones_like(a["values"], jnp.float32)
            if a.get("mask") is not None:
                ones = ones * a["mask"]
            flat = a["rows"].astype(jnp.int32) * d.k + a["values"]
            add = jax.ops.segment_sum(ones, flat, num_segments=d.g * d.k)
            add_stats(s.dir_name, add.reshape(d.g, d.k))

    # Dirichlet ELBO terms + posterior updates
    new_posts = {}
    for name, d in program.dirichlets.items():
        prior = jnp.asarray(d.prior)[None, :]
        with jax.named_scope("vmp.elbo"):
            term = dists.dirichlet_elbo_term(prior, state.posteriors[name],
                                             selog.get(name))
        st = stats.get(name)
        if axis_names and name not in local_dirs:
            if st is not None:
                st = jax.lax.psum(st, axis_names)
            # local-dirichlet ELBO terms are per-shard disjoint (summed by the
            # final psum); a replicated dirichlet's term would be counted once
            # per shard, so scale it out here.
            term = term / n_replicas
        elbo = elbo + term
        new_posts[name] = state.posteriors[name] if st is None \
            else prior * jnp.ones_like(st) + st

    if axis_names:
        elbo = jax.lax.psum(elbo, axis_names)
    return VMPState(new_posts, state.step + 1), elbo


def latent_responsibilities(program: VMPProgram, state: VMPState, name: str):
    """Recompute q(z) for one latent from the current posteriors.

    The only path that still materializes explicit (N, K) responsibilities —
    the step body streams them through ``kops.zstats`` without ever storing
    them, so callers who want q(z) itself pay for it here, on demand.
    """
    from repro.kernels import ops as kops, ref as kref
    arrays = _program_arrays(program)
    elog = {n: kops.dirichlet_expectation(p)
            for n, p in state.posteriors.items()}
    for spec in program.latents:
        if spec.name == name:
            logits = _messages_to_latent(program, spec, elog, arrays)
            r, _ = kref.zstep(logits)
            return r
    raise KeyError(name)


def full_elbo(program: VMPProgram, state: VMPState) -> float:
    """ELBO at the current posteriors with optimal responsibilities."""
    arrays = _program_arrays(program)
    _, elbo = _step_body(program, arrays, state)
    return float(elbo)
