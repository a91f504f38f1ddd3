"""Inference execution: the paper's section 4.3 ("Execution") analogue.

Runs the jitted VMP step in a loop with:
  - the paper's callback API (Figure 12): ``callback(iteration, elbo) ->
    bool`` — return False to stop early (e.g. small ELBO improvement);
  - checkpoint-every-k with crash resume (paper section 4.2's lineage
    checkpointing, repurposed for fault tolerance);
  - buffer donation so posterior updates are in-place in HBM (the paper's
    cache/anti-cache dance: GraphX had to materialize + evict the previous
    graph; XLA donation makes the old state's buffers the new state's).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax

from ..checkpoint import CheckpointStore
from .compiler import VMPProgram
from .vmp import VMPState, _program_arrays, _step_body, init_state


def make_step(program: VMPProgram, donate: bool = True, elog_dtype=None):
    """``elog_dtype`` (e.g. ``jnp.bfloat16`` or ``"bfloat16"``) narrows the
    message tables the token plate reads (the posterior concentration
    tables, since ``zstats`` fuses the Dirichlet expectation into its
    gathers) — see ``_step_body``."""
    arrays = _program_arrays(program)
    elog_dtype = _resolve_elog_dtype(elog_dtype)

    def step(state: VMPState):
        return _step_body(program, arrays, state, elog_dtype=elog_dtype)

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def _resolve_elog_dtype(elog_dtype):
    import jax.numpy as jnp
    if elog_dtype is None or isinstance(elog_dtype, str) and \
            elog_dtype in ("", "float32", "f32"):
        return None
    return getattr(jnp, elog_dtype) if isinstance(elog_dtype, str) \
        else elog_dtype


def run_inference(program: VMPProgram, steps: int = 20,
                  callback: Optional[Callable] = None,
                  checkpoint_every: int = 0,
                  checkpoint_dir: Optional[str] = None,
                  state: Optional[VMPState] = None,
                  seed: int = 0,
                  step_fn=None,
                  elog_dtype=None):
    """Run ``steps`` VMP iterations; returns (state, elbo_trace)."""
    if step_fn is None:
        if program.meta.get("sharding") is not None:
            from .partition import make_distributed_step
            step_fn, state0 = make_distributed_step(
                program, program.meta["sharding"], seed=seed,
                elog_dtype=elog_dtype)
            state = state or state0
        else:
            step_fn = make_step(program, elog_dtype=elog_dtype)
    if state is None:
        state = init_state(program, seed)

    store = None
    if checkpoint_every and checkpoint_dir:
        store = CheckpointStore(checkpoint_dir, every=checkpoint_every)
        latest = store.latest()
        if latest is not None:
            state = store.restore(state)

    trace: list[float] = []
    start = int(state.step)
    for i in range(start, start + steps):
        state, elbo = step_fn(state)
        elbo_f = float(elbo)
        trace.append(elbo_f)
        if store is not None:
            store.maybe_save(i + 1, state)
        if callback is not None and callback(i, elbo_f) is False:
            break
    if store is not None:
        store.wait()              # final async checkpoint durable on return
    return state, trace


def build_infer_step(program, engine="vmp", corpus=None):
    """Build ``(step_fn, state0)`` for a compiled
    :class:`~repro.core.compiler.VMPProgram` with the backend picked by
    config — full-batch VMP or streaming SVI (optionally sharded via
    ``EngineConfig.sharding``).  The result feeds :func:`run_inference`
    directly, so callbacks and checkpointing work identically across
    backends.  Gibbs is not a
    step machine; use ``repro.core.engine.make_engine("gibbs").fit``.

    ``corpus`` (or ``EngineConfig.corpus``) — a
    :class:`repro.data.ShardedCorpus` for out-of-core SVI: ``program`` may
    then be an unobserved :class:`~repro.core.dsl.Model` or a template from
    :func:`repro.data.store.sharded_template`; minibatches stream from the
    corpus's on-disk shards with double-buffered prefetch.
    """
    from .engine import EngineConfig
    from .svi import SVI, SVIConfig

    if isinstance(engine, str):
        engine = EngineConfig(backend=engine)
    corpus = corpus if corpus is not None else engine.corpus
    if engine.backend == "vmp":
        if corpus is not None:
            raise ValueError("full-batch VMP needs a resident corpus; use "
                             "engine='svi' for out-of-core inference")
        if engine.sharding is not None:
            from .partition import make_distributed_step
            return make_distributed_step(program, engine.sharding,
                                         seed=engine.seed,
                                         elog_dtype=engine.elog_dtype)
        return make_step(program, elog_dtype=engine.elog_dtype), \
            init_state(program, engine.seed)
    if engine.backend == "svi":
        svi = SVI(program, SVIConfig(
            batch_size=engine.batch_size, kappa=engine.kappa, tau=engine.tau,
            local_iters=engine.local_iters, pad_multiple=engine.pad_multiple,
            holdout_frac=engine.holdout_frac,
            holdout_every=engine.holdout_every, seed=engine.seed,
            elog_dtype=engine.elog_dtype),
            plan=engine.sharding, corpus=corpus, hosts=engine.hosts)

        def step_fn(state):
            return svi.step(int(state.step), state)

        step_fn.svi = svi                   # heldout_elbo / sampler access
        return step_fn, init_state(svi.program, engine.seed)
    raise ValueError(f"no step builder for backend {engine.backend!r}")
