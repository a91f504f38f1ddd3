"""Pallas TPU kernel: fused z-substep for segment latents (zmap children).

A segment latent (e.g. an SLDA sentence topic) owns a token plate nested
*below* its own plate: each child factor carries a ``zmap`` mapping tokens to
latent instances, so the latent's logits need a cross-token reduction before
its softmax.  The fused flat kernel cannot express that in one pass; this
module runs the substep in two phases (the ROADMAP's "two-phase" follow-up):

  **Phase 1 — logits accumulation** (token grid, one ``pallas_call`` per
  zmap child): stream the child's token blocks, form each block's Elog
  message rows with the shared one-hot MXU gather, and scatter them into a
  VMEM-resident ``(n_latent, K)`` logits accumulator keyed by ``zmap``
  (``one_hot(zmap).T @ messages`` — also an MXU matmul).

  **Phase 2 — softmax + stats**: (a) a latent-plate grid pass — the shared
  flat kernel body with the phase-1 logits as an extra additive input —
  computes the prior gather, any non-zmap child messages, the masked
  softmax/logsumexp, the prior-stats scatter, and the non-zmap child stats,
  and emits the ``(n_latent, K)`` responsibilities (the one intermediate
  this path materializes: the (N_token, K) working set — the large one —
  still never exists); (b) a second token-grid pass per zmap child gathers
  ``r[zmap]`` rows and scatters the responsibility-weighted counts into the
  child's stats table.

All gathers/scatters, the softmax, and the ``tables="alpha"`` fused
``dirichlet_expectation`` (concentrations in, Elog computed in-kernel into
VMEM scratch) are shared with ``fused_zstats``; ``ref.zstats_blocked``
mirrors the exact block structure as the bitwise parity target, and
``ref.zstats`` (the segmented chunked oracle) is the tolerance target.

Budget: all Elog tables, the ``(n_latent, K)`` logits/responsibility
arrays, and the stats accumulators must be VMEM-resident
(``fused_zstats._resident_bytes`` with ``n_latent``, which
``ops.routing`` compares to the budget: it alone sends a call here);
combining segment latents with HBM-streamed tables falls back to the
chunked oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dirichlet_expectation import COMPILER_PARAMS
from .fused_zstats import (_LANE, _block_tokens, _child_dims,
                           _child_message, _child_scatter, _dot,
                           _elog_from_alpha, _layout, _onehot, _pad_to,
                           _zstats_call)
from .ref import ZChild


def _dims(table_prior, children, n_latent: int):
    """``(k, kp, nzp, child dims)``: the latent's topic count, padded to
    whole lanes, its padded instance count, and each child's
    ``(gf, kf, gfp, kfp)``."""
    k = table_prior.shape[1]
    kp = _pad_to(max(k, 1), _LANE)
    return (k, kp, _pad_to(max(n_latent, 1), _LANE),
            _child_dims(children, kp))


def _pad_tok(a, np_, fill=0):
    """A token stream padded to ``np_`` slots, as a (np_, 1) column."""
    return jnp.pad(a, (0, np_ - a.shape[0]), constant_values=fill)[:, None]


# ---------------------------------------------------------------------------
# phase 1: per-child logits accumulation over the token grid
# ---------------------------------------------------------------------------

def _logits_kernel(*refs, k: int, meta1: tuple, lane_pad: int, mode: str):
    """refs: table, values, zmap, tmask[, base], out (nzp, kp) accumulator
    [, Elog scratch].  ``tmask`` is the child mask merged with the token
    padding (all-ones when the child has no mask)."""
    specialized, stride, has_base = meta1
    pos = 0
    tab_ref, vals_ref, zmi_ref, tm_ref = refs[pos:pos + 4]; pos += 4
    base_ref = None
    if has_base:
        base_ref = refs[pos]; pos += 1
    zacc_ref = refs[pos]; pos += 1
    scratch = refs[pos:]

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        zacc_ref[...] = jnp.zeros(zacc_ref.shape, zacc_ref.dtype)
        if mode == "alpha":
            scratch[0][...] = _elog_from_alpha(
                tab_ref[...].astype(jnp.float32), lane_pad)

    tab = scratch[0][...] if mode == "alpha" \
        else tab_ref[...].astype(jnp.float32)
    vals = vals_ref[...]
    lane = jax.lax.broadcasted_iota(
        jnp.int32, (vals.shape[0], zacc_ref.shape[1]), 1)
    base = None if base_ref is None else base_ref[...]
    e = _child_message(tab, vals, base, tm_ref[...], k, lane,
                       specialized, stride)
    oh_z = _onehot(zmi_ref[...], zacc_ref.shape[0])
    zacc_ref[...] += _dot(oh_z.T, e)


def _phase_inputs(c: ZChild, kp: int, nzp: int, cdim: tuple, tables: str,
                  block_n):
    """Padded token-plate arrays of one zmap child, shared between the
    phase kernels and ``ref.zstats_blocked``: ``(bn, tab, vals, zmi, tm,
    base)`` with all token streams padded to whole ``bn`` blocks and
    ``tm`` the child mask merged with the token-padding mask."""
    gf, kf, gfp, kfp = cdim
    bn = _block_tokens(block_n, kp, nzp, gfp, kfp)
    nt = c.values.shape[0]
    np_ = _pad_to(max(nt, 1), bn)
    fill = 1.0 if tables == "alpha" else 0.0
    tab = jnp.pad(c.elog, ((0, gfp - gf), (0, kfp - kf)),
                  constant_values=jnp.asarray(fill, c.elog.dtype))
    tm = jnp.ones((nt,), jnp.float32) if c.mask is None \
        else c.mask.astype(jnp.float32)
    return (bn, tab,
            _pad_tok(c.values.astype(jnp.int32), np_),
            _pad_tok(c.zmap.astype(jnp.int32), np_),
            _pad_tok(tm, np_, 0.0),
            None if c.base is None
            else _pad_tok(c.base.astype(jnp.int32), np_))


def _phase_logits(c: ZChild, k: int, kp: int, nzp: int, cdim: tuple,
                  tables: str, block_n, interpret: bool):
    gf, kf, gfp, kfp = cdim
    bn, tab, vals, zmi, tm, base = _phase_inputs(c, kp, nzp, cdim,
                                                 tables, block_n)
    np_ = vals.shape[0]

    tok = pl.BlockSpec((bn, 1), lambda i: (i, 0))
    inputs = [tab, vals, zmi, tm]
    in_specs = [pl.BlockSpec((gfp, kfp), lambda i: (0, 0)), tok, tok, tok]
    if base is not None:
        inputs.append(base)
        in_specs.append(tok)
    scratch_shapes = [pltpu.VMEM((gfp, kfp), jnp.float32)] \
        if tables == "alpha" else []
    return pl.pallas_call(
        functools.partial(_logits_kernel, k=k,
                          meta1=(c.specialized, int(c.stride),
                                 c.base is not None),
                          lane_pad=kfp - kf, mode=tables),
        grid=(np_ // bn,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((nzp, kp), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((nzp, kp), jnp.float32),
        scratch_shapes=scratch_shapes,
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(*inputs)


# ---------------------------------------------------------------------------
# phase 2b: per-child stats from the latent responsibilities
# ---------------------------------------------------------------------------

def _stats_kernel(*refs, k: int, meta1: tuple):
    """refs: r (nzp, kp), values, zmap, tmask[, base], out child stats."""
    specialized, stride, has_base = meta1
    pos = 0
    r_ref, vals_ref, zmi_ref, tm_ref = refs[pos:pos + 4]; pos += 4
    base_ref = None
    if has_base:
        base_ref = refs[pos]; pos += 1
    cref = refs[pos]

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cref[...] = jnp.zeros(cref.shape, cref.dtype)

    oh_z = _onehot(zmi_ref[...], r_ref.shape[0])
    w = _dot(oh_z, r_ref[...])
    base = None if base_ref is None else base_ref[...]
    cref[...] += _child_scatter(w, vals_ref[...], base, tm_ref[...],
                                cref.shape, k, specialized, stride)


def _phase_stats(c: ZChild, r, k: int, kp: int, nzp: int, cdim: tuple,
                 block_n, interpret: bool):
    gf, kf, gfp, kfp = cdim
    bn, _, vals, zmi, tm, base = _phase_inputs(c, kp, nzp, cdim,
                                               "elog", block_n)
    np_ = vals.shape[0]

    tok = pl.BlockSpec((bn, 1), lambda i: (i, 0))
    inputs = [r, vals, zmi, tm]
    in_specs = [pl.BlockSpec((nzp, kp), lambda i: (0, 0)), tok, tok, tok]
    if base is not None:
        inputs.append(base)
        in_specs.append(tok)
    out = pl.pallas_call(
        functools.partial(_stats_kernel, k=k,
                          meta1=(c.specialized, int(c.stride),
                                 c.base is not None)),
        grid=(np_ // bn,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((gfp, kfp), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((gfp, kfp), jnp.float32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(*inputs)
    return out[:gf, :kf]


# ---------------------------------------------------------------------------
# the two-phase substep
# ---------------------------------------------------------------------------

def zstats_zmap(table_prior: jax.Array, prior_rows: jax.Array,
                children: tuple, zmask=None, *, tables: str = "elog",
                block_n: int | None = None, interpret: bool = False):
    """Pallas-backed fused z-substep for segment latents; matches
    ``ref.zstats`` on any child mix where at least one carries a ``zmap``.
    ``tables`` as in ``fused_zstats.zstats``."""
    if all(c.zmap is None for c in children):
        raise ValueError("no zmap children; use fused_zstats.zstats")
    return _two_phase(
        table_prior, prior_rows, children, zmask, tables, block_n,
        functools.partial(_phase_logits, tables=tables, block_n=block_n,
                          interpret=interpret),
        functools.partial(_zstats_call, emit_r=True, interpret=interpret),
        functools.partial(_phase_stats, block_n=block_n,
                          interpret=interpret))


def _two_phase(table_prior, prior_rows, children, zmask, tables, block_n,
               logits, latent, stats):
    """The two-phase substep around its three grids: ``logits(c, k, kp,
    nzp, cdim)`` of a zmap child, ``latent(layout, extra)`` over the
    latent plate, ``stats(c, r, k, kp, nzp, cdim)`` of a zmap child — the
    Pallas kernels, or ``ref.zstats_blocked``'s mirrors of them, which so
    run this same glue."""
    nz = prior_rows.shape[0]
    k, kp, nzp, cdims = _dims(table_prior, children, nz)

    # phase 1: logits accumulated over each zmap child's token plate
    extra = jnp.zeros((nzp, kp), jnp.float32)
    for c, cd in zip(children, cdims):
        if c.zmap is not None:
            extra = extra + logits(c, k, kp, nzp, cd)

    # phase 2a: latent-plate softmax + prior/non-zmap stats (+ emit r)
    nonz = tuple(c for c in children if c.zmap is None)
    lo = _layout(table_prior, prior_rows, nonz, zmask,
                 tables=tables, block_n=block_n)
    if lo.plan.target is not None:
        # a bucketed (streamed-table) latent layout would permute the
        # instances the phase-1 logits and emitted r are matched to
        # positionally — silent corruption, so refuse loudly.  The
        # budget ops.routing applies keeps ops.zstats off this path.
        raise ValueError("segment latents cannot combine with streamed "
                         "tables; use ref.zstats")
    np_lat = lo.nblocks * lo.plan.bn
    ex = extra[:np_lat] if np_lat <= nzp else \
        jnp.pad(extra, ((0, np_lat - nzp), (0, 0)))
    outs = latent(lo, ex)
    lse = outs[0].sum()
    pstats = outs[1][:table_prior.shape[0], :k]
    r = outs[-1][:nz]
    r = jnp.pad(r, ((0, nzp - nz), (0, 0)))

    # phase 2b: zmap child stats from r[zmap]
    nonz_stats = iter(
        cs[:gf, :kf] for cs, (gf, kf, _, _) in
        zip(outs[2:-1], lo.plan.child_dims))
    cstats = []
    for c, cd in zip(children, cdims):
        if c.zmap is None:
            cstats.append(next(nonz_stats))
        else:
            cstats.append(stats(c, r, k, kp, nzp, cd))
    return lse, pstats, tuple(cstats)


__all__ = ["zstats_zmap"]
