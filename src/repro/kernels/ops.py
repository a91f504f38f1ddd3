"""Jit'd dispatch layer over the Pallas kernels.

On TPU the Pallas kernels run compiled; everywhere else (the CPU, tests)
the pure-jnp oracles from ``ref.py`` are used, except when
``REPRO_FORCE_PALLAS=1`` forces the kernels through interpret mode (slow but
exercises the kernel bodies end-to-end).

The token plate's route is chosen in one place: :func:`routing`.
:func:`zstats` calls it once and runs the implementation its ``path``
names; EXPLAIN (``analysis/explain.py``) and :func:`host_bucketing` read
the same answer.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
import numpy as np

from . import ref
from .dirichlet_expectation import dirichlet_expectation as _de_pallas
from .ref import ZChild


@functools.lru_cache(maxsize=None)
def _backend_cached() -> str:
    if os.environ.get("REPRO_FORCE_PALLAS") == "1":
        return "pallas_interpret"
    # a device that fails to initialize raises here: answering "ref" would
    # run the chip's work on the host's CPU without saying so
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _backend() -> str:
    """Which kernel implementation this process dispatches to: ``"pallas"``
    (TPU, compiled), ``"pallas_interpret"`` (``REPRO_FORCE_PALLAS=1``:
    kernel bodies under the interpreter — slow, for testing), or ``"ref"``
    (pure-jnp oracles, the CPU/GPU default).

    The answer is process-constant (an env var plus the jax backend), so it
    is cached — this sits on every kernel dispatch in the VMP hot loop, and
    re-reading the environment plus ``jax.default_backend()`` per call cost
    real trace time.  Tests that flip ``REPRO_FORCE_PALLAS`` must call
    :func:`reset_backend_cache` after changing the environment (the test
    suite does this automatically around every test via an autouse
    fixture in ``tests/conftest.py``)."""
    return _backend_cached()


def reset_backend_cache() -> None:
    """Forget the cached :func:`_backend` answer (call after changing
    ``REPRO_FORCE_PALLAS`` or the jax platform at runtime)."""
    _backend_cached.cache_clear()


def dirichlet_expectation(alpha: jax.Array) -> jax.Array:
    """Rowwise expected log under a Dirichlet: ``digamma(alpha) -
    digamma(alpha.sum(-1, keepdims=True))``.  ``alpha`` is a ``(G, K)``
    float32 concentration table (other ranks fall back to the reference
    path); the result matches ``alpha``'s shape and dtype.  This is the
    Elog message table every VMP/SVI substep gathers from — though the
    token plate itself now fuses this computation into ``zstats``
    (``tables="alpha"``); explicit tables remain for statics, diagnostics,
    and ``latent_responsibilities``."""
    b = _backend()
    if b == "ref" or alpha.ndim != 2:
        return ref.dirichlet_expectation(alpha)
    return _de_pallas(alpha, interpret=(b == "pallas_interpret"))


class RouteInfo(NamedTuple):
    """The kernel-routing decision for one :func:`zstats` call, as pure
    metadata.  ``path`` is what will run:

      - ``"ref"``           — the chunked pure-jnp oracle,
      - ``"fused"``         — the fused Pallas kernel, all tables
                              VMEM-resident,
      - ``"fused-streamed"``— the fused kernel with one over-budget table
                              tiled HBM -> VMEM (``target``/``tile``/
                              ``n_tiles`` describe the streaming layout),
      - ``"fused-zmap"``    — the two-phase segment-latent kernel.

    ``table_bytes`` is the padded-f32 resident footprint
    (``fused_zstats._resident_bytes``) the budget check compared against
    ``budget`` (``_TABLE_BUDGET``); ``table_dtype`` records
    the bf16-table mode; ``block_tokens`` the grid block size (0 when not
    applicable); ``reason`` says why this path was chosen in one sentence.
    """
    path: str
    backend: str
    tables: str
    table_dtype: str
    target: object
    tile: int
    n_tiles: int
    block_tokens: int
    table_bytes: int
    budget: int
    reason: str


def routing(table_prior, prior_rows=None, children=(), *,
            tables: str = "elog", backend: Optional[str] = None,
            n_latent: Optional[int] = None,
            child_stats: bool = True) -> RouteInfo:
    """Predict which kernel :func:`zstats` will dispatch to — without
    touching any backend or device state.

    Arguments mirror :func:`zstats`, but only *shapes* are read:
    ``table_prior`` and each child's ``elog`` may be real arrays,
    ``jax.ShapeDtypeStruct`` stand-ins, or anything with ``.shape`` (and
    optionally ``.dtype``); ``prior_rows`` supplies ``n_latent`` via its
    leading dim (or pass ``n_latent=`` directly and ``prior_rows=None``).
    This is the only place a route is chosen: :func:`zstats` dispatches
    on this function's ``path``, so EXPLAIN's prediction and the dispatch
    are one decision.  It reads ``fused_zstats._plan`` (the streamed
    layout) and ``fused_zstats._resident_bytes`` (the one footprint
    formula, also ``table_bytes``).  ``backend`` defaults to this process's
    :func:`_backend` answer; pass ``"pallas"`` to plan for TPU from
    anywhere.  ``child_stats=False`` plans a call whose caller reads no
    child statistics: where the fused kernel would stream a table, such a
    call takes ``ref`` (XLA's gathers, and no statistics of the streamed
    table), since the kernel would gather and scatter every tile by
    one-hot matmuls for statistics nobody reads.
    """
    from .fused_zstats import _TABLE_BUDGET, _plan, _resident_bytes

    b = backend if backend is not None else _backend()
    if n_latent is None and prior_rows is not None:
        n_latent = int(prior_rows.shape[0])
    dtype = str(getattr(table_prior, "dtype", "float32"))
    byt = _resident_bytes(table_prior, children, tables, n_latent)

    def _route(path, target=None, tile=0, n_tiles=1, bn=0, reason=""):
        return RouteInfo(path, b, tables, dtype, target, tile, n_tiles,
                         bn, byt, _TABLE_BUDGET, reason)

    if b == "ref":
        return _route("ref", reason="ref backend: pure-jnp oracles "
                      "(CPU/GPU default)")
    if any(c.zmap is not None for c in children):
        # n_latent is not derivable from the tables (SLDA has far more
        # sentences than document rows): unknown, the logits may not fit
        if n_latent is not None and byt <= _TABLE_BUDGET:
            return _route("fused-zmap",
                          reason="segment latent (zmap child); tables + "
                                 "(n_latent, K) logits fit VMEM")
        return _route("ref",
                      reason="segment latent whose tables + logits exceed "
                             "the VMEM table budget; chunked oracle"
                      if n_latent is not None else
                      "segment latent with unknown n_latent; chunked oracle")
    plan = _plan(table_prior, children, tables)
    if plan is None:
        return _route("ref",
                      reason="not fusable: more than one over-budget table, "
                             "or only strided tables over budget; chunked "
                             "oracle")
    if plan.target is None:
        return _route("fused", bn=plan.bn,
                      reason="all tables VMEM-resident")
    if not child_stats:
        return _route("ref",
                      reason="local-only pass over a table above the VMEM "
                             "budget; XLA gathers, no statistics of the "
                             "streamed table")
    return _route("fused-streamed", target=plan.target, tile=plan.tl,
                  n_tiles=plan.n_tiles, bn=plan.bn,
                  reason=f"table over the VMEM budget; streaming "
                         f"{'prior rows' if plan.target == 'prior' else 'child %d values' % plan.target}"
                         f" in {plan.n_tiles} tiles of {plan.tl}")


def host_bucketing(table_prior, prior_rows, children, *,
                   tables: str = "elog"):
    """Precompute the streamed-table token bucketing for a :func:`zstats`
    call whose observed index streams are trace-time constants (the
    full-batch engine's arrays).  Returns the numpy triple to pass back as
    ``zstats(..., bucketing=...)``, or ``None`` when there is nothing to
    hoist (a call that does not take ``fused-streamed``, or a traced
    bucketing key) — ``None`` is always safe to pass through.  Built from
    :func:`routing`'s answer: its target, tile, tile count and block."""
    route = routing(table_prior, prior_rows, children, tables=tables)
    if route.path != "fused-streamed":
        return None
    key = prior_rows if route.target == "prior" \
        else children[route.target].values
    if isinstance(key, jax.core.Tracer):
        return None
    from .fused_zstats import _bucket_host
    key = np.asarray(key)
    return _bucket_host(key, key.shape[0], route.tile, route.n_tiles,
                        route.block_tokens)


def zstats(table_prior: jax.Array, prior_rows: jax.Array, children: tuple,
           zmask=None, *, tables: str = "elog", bucketing=None,
           child_stats: bool = True):
    """Fused token-plate substep: ``(lse_sum, prior_stats, child_stats)``.

    Inputs: ``table_prior`` — the ``(G, K)`` prior-Dirichlet table;
    ``prior_rows`` — ``(N,) int32`` row of each latent instance;
    ``children`` — a tuple of :class:`ZChild` (each bundles a child's
    ``(Gc, Kc)`` table, ``(N,) int32`` observed values, row base/stride,
    optional ``(N,) int32`` zmap and ``(N,) float32`` mask); ``zmask`` —
    optional ``(n_latent,) float32`` validity mask.  With the default
    ``tables="elog"`` the tables hold Elog expectations (float32, or the
    ``EngineConfig.elog_dtype`` narrow type); with ``tables="alpha"`` they
    hold Dirichlet *concentrations* and the ``dirichlet_expectation`` is
    fused into the gather (in-kernel digamma on TPU — one less table
    materialization per Dirichlet per step).  Returns ``lse_sum`` — scalar
    float32 sum of per-instance logsumexp (the token plate's ELBO term);
    ``prior_stats`` — ``(G, K)`` float32 responsibility scatters onto the
    prior rows; ``child_stats`` — per child a ``(Gc, Kc)`` float32 stats
    table.  With ``child_stats=False`` the caller reads no child
    statistics and gets ``()`` in their place; ``lse_sum`` and
    ``prior_stats`` are unchanged.

    ``bucketing`` — an optional :func:`host_bucketing` result: the
    streamed-table path's token permutation precomputed on the host (and
    cached per program by ``_step_body``), so the per-step device argsort
    it replaces never enters the trace.

    The hot path of every VMP/SVI iteration (see ``core/vmp.py:_step_body``).
    The call asks :func:`routing` once and runs what its ``path`` names.
    On TPU the fused Pallas kernels keep responsibilities out of HBM:

      - flat latents take ``fused_zstats`` — tables too large for VMEM are
        streamed tile-by-tile with trace-time token bucketing (the
        large-vocabulary path);
      - segment latents (a child with a ``zmap``) take the two-phase
        ``fused_zmap`` kernel, which materializes only the (n_latent, K)
        logits/responsibilities;
      - what neither supports (several over-budget tables at once, an
        over-budget table behind a strided row computation, a segment
        latent whose tables exceed VMEM) falls back to the chunked ``ref``
        oracle, which streams token chunks through a ``lax.scan`` and so
        also never materializes the (N_token, K) working set;
      - a ``child_stats=False`` call that would stream a table takes
        ``ref`` too: XLA's gathers read the table, and the streamed
        kernel's one-hot matmuls would only build statistics nobody reads.

    Every route runs under the ``kernels.zstats`` named scope, so its
    device ops carry that name in a profile; a segment latent's logits
    reduction and its scatter back run under ``kernels.zstats.segments``
    inside it (the whole ``fused-zmap`` kernel, or the ``ref`` route's two
    token passes).  A ``child_stats=False`` call runs under
    ``kernels.zstats.local`` inside ``kernels.zstats``, on every route.
    """
    with jax.named_scope("kernels.zstats"):
        if child_stats:
            return _zstats(table_prior, prior_rows, children, zmask, tables,
                           bucketing, True)
        with jax.named_scope("kernels.zstats.local"):
            return _zstats(table_prior, prior_rows, children, zmask, tables,
                           bucketing, False)


def _zstats(table_prior, prior_rows, children, zmask, tables, bucketing,
            child_stats):
    route = routing(table_prior, prior_rows, children, tables=tables,
                    child_stats=child_stats)
    interp = route.backend == "pallas_interpret"
    if route.path == "fused-zmap":
        from .fused_zmap import zstats_zmap
        with jax.named_scope("kernels.zstats.segments"):
            out = zstats_zmap(table_prior, prior_rows, children, zmask,
                              tables=tables, interpret=interp)
    elif route.path in ("fused", "fused-streamed"):
        from .fused_zstats import zstats as _zstats_pallas
        out = _zstats_pallas(table_prior, prior_rows, children, zmask,
                             tables=tables, interpret=interp,
                             bucketing=bucketing)
    else:
        return ref.zstats(table_prior, prior_rows, children, zmask,
                          tables=tables, child_stats=child_stats)
    # a kernel builds every child's statistics: drop those not asked for
    return out if child_stats else (out[0], out[1], ())


def flash_attention(q, k, v, *, causal: bool = True):
    """Tiled attention ``softmax(q k^T / sqrt(Dh)) v`` without the (S, S)
    score matrix in HBM.  ``q``/``k``/``v`` are ``(BH, S, Dh)`` — batch and
    heads flattened together — bf16 or f32; returns ``q``'s shape and
    dtype.  ``causal`` applies the autoregressive mask."""
    from .flash_attention import flash_attention as _fa_pallas
    b = _backend()
    if b == "ref":
        return ref.flash_attention(q, k, v, causal=causal)
    return _fa_pallas(q, k, v, causal=causal,
                      interpret=(b == "pallas_interpret"))


__all__ = ["ZChild", "RouteInfo", "routing", "dirichlet_expectation",
           "host_bucketing", "zstats", "flash_attention",
           "reset_backend_cache"]
