"""Pallas TPU kernel: fused token-plate pipeline (gather -> softmax -> stats).

One grid pass over token blocks computes, entirely in VMEM:

    logits_i = elog_prior[prior_rows[i]] + sum_f message_f(i)   (gather)
    r_i      = softmax(logits_i)                                 (z-substep)
    lse_i    = logsumexp(logits_i)
    prior_stats[prior_rows[i]] += r_i                            (scatter)
    child_stats_f += r-weighted count scatter of factor f

emitting only the per-block lse sums and the (G, K) stats accumulators.  The
(N, K) responsibilities and logits never exist in HBM — they are block-local
intermediates — which collapses the z-substep's ~4 full (N, K) HBM round
trips (write logits, read logits, write r, re-read r per stats scatter) to
the irreducible token-stream reads.  See docs/performance.md for the traffic
model.

Implementation notes:

  - Gathers and scatters are expressed as one-hot matmuls so they run on the
    MXU (TPU has no vector gather from VMEM); the one-hot lane dimension is
    the resident extent of the table being gathered.
  - **Streamed tables** (this file's large-vocabulary path): a table whose
    resident footprint exceeds ``_TABLE_BUDGET`` is tiled along its gather
    axis (rows for the prior, the value axis for a specialized child) and
    the tiles are pipelined HBM -> VMEM across the token-block grid.  At
    trace time the tokens are bucketed by table tile (a stable sort plus
    per-tile padding to whole blocks), so every token block gathers only
    from its resident tile; the per-block tile index is fed through
    ``PrefetchScalarGridSpec`` scalar prefetch, and Pallas's grid pipeline
    double-buffers the tile copies (consecutive blocks on the same tile
    skip the copy).  The streamed table's stats accumulator is tiled the
    same way: each tile's accumulator block is initialized at the tile's
    first token block, accumulated across the tile's (contiguous) run of
    blocks, and flushed to HBM once when the grid moves on.
  - **Fused ``dirichlet_expectation``** (``tables="alpha"``): the inputs are
    Dirichlet concentration tables, and E[log theta] is computed in-kernel
    (digamma recurrence + asymptotic series, shared with
    ``kernels/dirichlet_expectation.py``) into a VMEM scratch buffer — once
    at the first grid step for resident tables, once per tile for the
    streamed table.  This drops one full Elog-table materialization (an HBM
    write + re-read) per Dirichlet per VMP step.  For a table streamed
    along its value axis the Dirichlet row sums span all tiles, so the
    per-row ``digamma(sum_k alpha)`` vector is precomputed outside (see
    :func:`rowsum_digamma`, bitwise-matching the standalone kernel).
  - The stats outputs use a constant index map: sequential grid steps
    revisit the same VMEM block, which is the canonical Pallas accumulator
    pattern (initialized at program_id 0, flushed to HBM once at the end).
  - Tables may arrive in bf16 (the engine's ``elog_dtype`` mode);
    accumulation is always f32 (tables are upcast after the VMEM load).

Segment latents (a child with a ``zmap``) take the two-phase kernel in
``kernels/fused_zmap.py``.  Which kernel a call takes is decided in one
place, ``ops.routing``, from :func:`_plan` and the one footprint formula
:func:`_resident_bytes`; this module only lays out and runs the route it
is given.  The per-block math (:func:`_block_step` and friends) is
shared with ``ref.zstats_blocked``, the block-structured oracle that is
the kernels' bitwise parity target.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dirichlet_expectation import COMPILER_PARAMS, _digamma
from .ref import ZChild

_VMEM_BUDGET = 2 * 1024 * 1024        # bytes for the largest per-block tensor
_TABLE_BUDGET = 8 * 1024 * 1024       # resident Elog tables + accumulators
_TILE_BUDGET = 1 * 1024 * 1024        # bytes per streamed-table tile
_LANE = 128
_SUB = 8
_NEG = -1e30


def _pad_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _child_dims(children, kp: int) -> list:
    """Per child ``(gf, kf, gfp, kfp)``: its table's shape and the padded
    shape the table has in VMEM (a specialized child's rows are the
    ``kp`` padded topics)."""
    dims = []
    for c in children:
        gf, kf = c.elog.shape
        gfp = kp if c.specialized else _pad_to(max(gf, 1), _LANE)
        dims.append((gf, kf, gfp, _pad_to(max(kf, 1), _LANE)))
    return dims


def _resident_bytes(table_prior, children, tables: str = "elog",
                    n_latent: Optional[int] = None) -> int:
    """Padded f32 bytes of a ``zstats`` call with every table VMEM-resident:
    each table and its stats accumulator (and its Elog scratch under
    ``tables="alpha"``), plus, for a segment latent (a child with a
    ``zmap``) of ``n_latent`` instances, its ``(n_latent, K)`` logits and
    responsibilities with pipeline slack.  The one footprint formula:
    :func:`_plan`'s budget test, ``ops.routing``'s ``fused-zmap`` test and
    ``RouteInfo.table_bytes`` all read it."""
    kp = _pad_to(max(table_prior.shape[1], 1), _LANE)
    words = _pad_to(max(table_prior.shape[0], 1), _LANE) * kp
    words += sum(gfp * kfp for _, _, gfp, kfp in _child_dims(children, kp))
    byt = (3 if tables == "alpha" else 2) * 4 * words
    if n_latent is not None and any(c.zmap is not None for c in children):
        byt += 4 * 4 * _pad_to(max(n_latent, 1), _LANE) * kp
    return byt


def _block_tokens(block_n: Optional[int], *dims: int) -> int:
    """Tokens per grid block: the largest per-block (bn, max(dims)) f32
    temporary should fit ``_VMEM_BUDGET``.  A whole number of 128-token
    lane tiles, since the scatters transpose the block and put its tokens
    on the lane axis.  The one block-size formula for every kernel in this
    package (flat, streamed, and the zmap phases)."""
    m = max(dims)
    return block_n or max(_LANE, min(512, _VMEM_BUDGET // (4 * m))
                          // _LANE * _LANE)


def _onehot(idx, width: int):
    """(bn, 1) int32 -> (bn, width) f32 one-hot via 2-D iota (TPU-legal)."""
    cols = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], width), 1)
    return (idx == cols).astype(jnp.float32)


def _dot(a, b):
    """f32 matmul at full precision.  The TPU's default f32 matmul rounds
    its operands to bf16; a one-hot operand survives that exactly, but the
    table or responsibilities on the other side would lose 16 bits."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# table resolution: Elog values from either Elog or concentration tables
# ---------------------------------------------------------------------------

def _elog_from_alpha(a, lane_pad: int):
    """E[log theta] of a concentration block whose lane padding holds 1.0:
    the padded row sum minus the pad count is the true row sum (bitwise the
    standalone ``dirichlet_expectation`` kernel's computation)."""
    rs = a.sum(axis=-1, keepdims=True) - float(lane_pad)
    return _digamma(a) - _digamma(rs)


def rowsum_digamma(alpha: jax.Array) -> jax.Array:
    """``digamma(sum_k alpha)`` per row, replicating the standalone Pallas
    kernel's padded-lane row sum op-for-op so the fused ``tables="alpha"``
    path stays bitwise equal to the two-call composition."""
    kf = alpha.shape[1]
    kfp = max(_LANE, _pad_to(kf, _LANE))
    a = jnp.pad(alpha.astype(jnp.float32), ((0, 0), (0, kfp - kf)),
                constant_values=1.0)
    return _digamma(a.sum(axis=-1) - float(kfp - kf))


# ---------------------------------------------------------------------------
# per-block math, shared by the Pallas kernels and ref.zstats_blocked
# ---------------------------------------------------------------------------

def _prior_block(ptab, rows, k: int):
    """Prior gather + padded-lane kill -> (oh_p, lane, logits)."""
    oh_p = _onehot(rows, ptab.shape[0])
    logits = _dot(oh_p, ptab)
    lane = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = logits + jnp.where(lane < k, 0.0, _NEG)
    return oh_p, lane, logits


def _child_message(tab, vals, base, mask, k: int, lane,
                   specialized: bool, stride: int):
    """One child factor's Elog message rows for a token block -> (bn, kp)."""
    oh_v = _onehot(vals, tab.shape[1])
    if specialized:                                # row IS the topic
        e = _dot(oh_v, tab.T)
    else:                                          # row = base + stride*z
        b = base if base is not None else jnp.zeros_like(vals)
        e = jnp.zeros(lane.shape, jnp.float32)
        for kk in range(k):
            oh_r = _onehot(b + stride * kk, tab.shape[0])
            g = _dot(oh_r, tab)
            e = e + jnp.where(lane == kk,
                              (g * oh_v).sum(-1, keepdims=True), 0.0)
    if mask is not None:
        e = e * mask
    return e


def _softmax_block(logits, zm):
    """Masked softmax + summed logsumexp of one block -> (r, lse_sum).
    ``zm`` is the (bn, 1) token validity column."""
    m = logits.max(axis=-1, keepdims=True)
    ex = jnp.exp(logits - m)
    s = ex.sum(axis=-1, keepdims=True)
    r = ex / s * zm
    lse = jnp.sum((m + jnp.log(s)) * zm)
    return r, lse


def _child_scatter(r, vals, base, mask, shape: tuple, k: int,
                   specialized: bool, stride: int):
    """Responsibility-weighted count scatter of one block -> ``shape``."""
    oh_v = _onehot(vals, shape[1])
    w = r if mask is None else r * mask
    if specialized:
        return _dot(w.T, oh_v)
    b = base if base is not None else jnp.zeros_like(vals)
    acc = jnp.zeros(shape, jnp.float32)
    for kk in range(k):
        oh_r = _onehot(b + stride * kk, shape[0])
        acc = acc + _dot(oh_r.T, oh_v * w[:, kk:kk + 1])
    return acc


def _block_step(ptab, tabs, rows, vals, bases, masks, zm, k: int,
                meta: tuple, extra=None):
    """One token block end-to-end: (lse_sum, pstats_delta, cstat_deltas, r).

    All tables arrive resolved to f32 Elog values (full for resident
    tables, the block's tile for a streamed one) and all index streams
    arrive localized to those tables.  ``extra`` optionally adds
    pre-accumulated logits (the zmap kernel's phase-one output).
    """
    oh_p, lane, logits = _prior_block(ptab, rows, k)
    if extra is not None:
        logits = logits + extra
    for tab, v, b, mk, (specialized, stride, _, _) in \
            zip(tabs, vals, bases, masks, meta):
        logits = logits + _child_message(tab, v, b, mk, k, lane,
                                         specialized, stride)
    r, lse = _softmax_block(logits, zm)
    pd = _dot(oh_p.T, r)
    cds = [_child_scatter(r, v, b, mk, tab.shape, k, specialized, stride)
           for tab, v, b, mk, (specialized, stride, _, _) in
           zip(tabs, vals, bases, masks, meta)]
    return lse, pd, cds, r


# ---------------------------------------------------------------------------
# planning: resident budget, streamed-table selection, token bucketing
# ---------------------------------------------------------------------------

class _Plan(NamedTuple):
    """Static layout of one fused zstats call."""
    k: int
    kp: int
    gp: int
    gpp: int                           # prior rows (padded; n_tiles*tl if streamed)
    child_dims: tuple                  # per child (gf, kf, gfp, kfp)
    target: object                     # None | "prior" | child index
    tl: int                            # tile length along the streamed axis
    n_tiles: int
    bn: int                            # tokens per block
    mode: str                          # "elog" | "alpha"


def _plan(table_prior, children, tables: str = "elog",
          block_n: Optional[int] = None) -> Optional[_Plan]:
    """Choose the resident/streamed layout, or ``None`` when not fusable.

    The budget test reads :func:`_resident_bytes`.  At most one
    over-budget table can be streamed, and only along an axis the
    per-token gather indexes directly: the prior's row axis
    (``prior_rows``) or a specialized child's value axis (``values``).
    ``ops.routing`` is the one caller that turns the answer into a route;
    :func:`_layout` asks again for the layout of the route it was given.
    """
    if any(c.zmap is not None for c in children):
        return None
    k = table_prior.shape[1]
    kp = _pad_to(max(k, 1), _LANE)
    gp = table_prior.shape[0]
    gpp = _pad_to(max(gp, 1), _LANE)
    for c in children:
        if c.specialized and c.elog.shape[0] != k:
            raise ValueError(f"specialized child table has "
                             f"{c.elog.shape[0]} rows, expected K={k}")
    child_dims = _child_dims(children, kp)
    total = _resident_bytes(table_prior, children, tables)

    target, tl, n_tiles = None, 0, 1
    if total > _TABLE_BUDGET:
        # the streamable tables, in words: the prior, specialized children
        cands = [("prior", gpp * kp)] + [
            (ci, gfp * kfp) for ci, (c, (_, _, gfp, kfp)) in
            enumerate(zip(children, child_dims)) if c.specialized]
        big = max(cands, key=lambda e: e[1])
        rest = total - (3 if tables == "alpha" else 2) * 4 * big[1]
        # tile double-buffer + tiled accumulator + Elog scratch <= 4 tiles
        if rest > _TABLE_BUDGET - 4 * _TILE_BUDGET:
            return None
        target = big[0]
        if target == "prior":
            tl = _TILE_BUDGET // (4 * kp) // _LANE * _LANE
            if tl < _LANE:             # 128 rows wider than a tile's budget
                return None
            n_tiles = -(-gpp // tl)
            gpp = n_tiles * tl
        else:
            gf, kf, gfp, kfp = child_dims[target]
            tl = _TILE_BUDGET // (4 * gfp) // _LANE * _LANE
            if tl < _LANE:             # one column taller than the budget
                return None
            n_tiles = -(-kfp // tl)
            child_dims[target] = (gf, kf, gfp, n_tiles * tl)

    dims = [kp, tl if target == "prior" else gpp]
    for ci, (_, _, gfp, kfp) in enumerate(child_dims):
        dims += [gfp, tl if target == ci else kfp]
    bn = _block_tokens(block_n, *dims)
    return _Plan(k, kp, gp, gpp, tuple(child_dims), target, tl, n_tiles,
                 bn, tables)


def _bucket(key, n: int, tl: int, n_tiles: int, bn: int, streams=None):
    """Bucket tokens by streamed-table tile, padding each bucket to whole
    ``bn`` blocks (at least one per tile, so every accumulator tile is
    visited and flushed).  Pure trace-time jnp: returns ``(src, slot_tile,
    blk_tile, placed)`` where ``src`` maps padded slots to source tokens
    (-1 = padding), over the static padded length
    ``(ceil(n/bn) + n_tiles)*bn``, and ``placed`` is ``streams`` (an
    ``(R, n)`` int32 stack of token streams) in slot order, with
    unspecified values in the padding slots.

    Every bucket starts on a block boundary, so the tiles are found per
    block, not per slot, and block ``b``'s slots hold a run of ``bn``
    consecutive tokens of the stable sort by tile.  The sort carries the
    streams, so one gather of ``nblocks`` runs (:func:`_runs`) places all
    of them; the tile counts are the sorted tile ids' ``n_tiles + 1``
    boundaries.  The searches are unrolled (no device loop): ``log2`` of
    their sorted operand steps over ``n_tiles + 1`` and ``nblocks``
    queries.
    """
    tid = (key.astype(jnp.int32) // tl).astype(jnp.int32)
    if streams is None:
        streams = jnp.zeros((0, n), jnp.int32)
    tid_s, order, *cols = jax.lax.sort(
        (tid, jnp.arange(n, dtype=jnp.int32), *streams), num_keys=1,
        is_stable=True)
    nblocks = -(-n // bn) + n_tiles
    bounds = jnp.searchsorted(tid_s, jnp.arange(n_tiles + 1, dtype=jnp.int32),
                              method="scan_unrolled").astype(jnp.int32)
    cstart = bounds[:-1]                           # sorted bucket starts
    cnt = bounds[1:] - cstart
    pblk = jnp.maximum(-(-cnt // bn), 1)           # blocks per bucket
    cum_b = jnp.cumsum(pblk)
    blk = jnp.arange(nblocks, dtype=jnp.int32)
    blk_tile = jnp.minimum(
        jnp.searchsorted(cum_b, blk, side="right", method="scan_unrolled"),
        n_tiles - 1).astype(jnp.int32)
    first = (cum_b - pblk)[blk_tile] * bn          # padded bucket starts
    start = cstart[blk_tile] + blk * bn - first    # sorted token of slot b*bn
    end = first + cnt[blk_tile]                    # its bucket's first pad
    placed = _runs(jnp.stack([order, *cols]), start, bn)
    slot = jnp.arange(nblocks * bn, dtype=jnp.int32)
    src = jnp.where(slot < jnp.repeat(end, bn), placed[0], -1)
    return src, jnp.repeat(blk_tile, bn), blk_tile, placed[1:]


def _runs(x, start, width: int):
    """``x[:, s:s + width]`` for every ``s`` in ``start``, side by side:
    ``(R, len(start) * width)``; a run past the end of ``x`` holds
    unspecified values.  Runs at unaligned lane offsets would compile to
    a device loop with one trip per run, so this gathers whole 128-lane
    rows along the untiled leading axis (one gather) and then shifts
    each window left by ``s % 128`` lanes in seven static steps."""
    r, n = x.shape
    w = -(-width // _LANE) + 1                     # rows a run can touch
    rows = -(-n // _LANE) + w
    x = jnp.pad(x, ((0, 0), (0, rows * _LANE - n)))
    x = x.reshape(r, rows, _LANE).transpose(1, 0, 2)
    win = jax.vmap(lambda q: jax.lax.dynamic_slice_in_dim(x, q, w))(
        start // _LANE)
    win = win.transpose(0, 2, 1, 3).reshape(start.shape[0], r, w * _LANE)
    shift = start % _LANE
    for k in range(7):                             # 2**7 == _LANE
        step = 1 << k
        rolled = jnp.concatenate([win[..., step:], win[..., :step]], -1)
        win = jnp.where((shift >> k & 1)[:, None, None] == 1, rolled, win)
    return win[..., :width].transpose(1, 0, 2).reshape(r, -1)


def _bucket_host(key: np.ndarray, n: int, tl: int, n_tiles: int, bn: int):
    """Numpy twin of :func:`_bucket`'s ``(src, slot_tile, blk_tile)``,
    op-for-op (stable sort, identical padding arithmetic; the runs by
    plain indexing), so a bucketing computed once on the host is bitwise
    the one the traced version would produce.  The permutation depends
    only on the observed values, so for a fixed program it never changes
    — computing it here keeps the sort out of the jitted step (where the
    traced version re-sorts on device every iteration)."""
    tid = (key.astype(np.int64) // tl).astype(np.int32)
    order = np.argsort(tid, kind="stable").astype(np.int32)
    tid_s = tid[order]
    nblocks = -(-n // bn) + n_tiles
    bounds = np.searchsorted(tid_s, np.arange(n_tiles + 1))
    cstart = bounds[:-1]
    cnt = bounds[1:] - cstart
    pblk = np.maximum(-(-cnt // bn), 1)
    cum_b = np.cumsum(pblk)
    blk = np.arange(nblocks)
    blk_tile = np.minimum(np.searchsorted(cum_b, blk, side="right"),
                          n_tiles - 1).astype(np.int32)
    first = (cum_b - pblk)[blk_tile] * bn
    start = cstart[blk_tile] + blk * bn - first
    end = first + cnt[blk_tile]
    # a run may read past the end only in padding slots
    runs = np.concatenate([order, np.zeros(bn, np.int32)])
    placed = runs[np.minimum(start, n)[:, None] + np.arange(bn)].reshape(-1)
    slot = np.arange(nblocks * bn)
    src = np.where(slot < np.repeat(end, bn), placed, -1).astype(np.int32)
    return src, np.repeat(blk_tile, bn), blk_tile


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _kernel(*refs, plan: _Plan, meta: tuple, lane_pads: tuple,
            has_extra: bool = False, emit_r: bool = False):
    """meta: per child (specialized, stride, has_base, has_mask).

    Ref layout: ``blk_tile`` (scalar prefetch), prior table, prior rows,
    zmask, per child (table, values[, base][, mask][, dg0]), optional extra
    logits; outputs lse, prior stats, per-child stats, optional r; then in
    ``tables="alpha"`` mode one f32 Elog scratch per table.
    """
    n_child = len(meta)
    pos = 0
    bt_ref = refs[pos]; pos += 1
    ptab_ref = refs[pos]; pos += 1
    prow_ref, zm_ref = refs[pos], refs[pos + 1]; pos += 2
    child_in = []
    for ci, (_, _, has_base, has_mask) in enumerate(meta):
        tab_ref, vals_ref = refs[pos], refs[pos + 1]; pos += 2
        base_ref = mask_ref = dg0_ref = None
        if has_base:
            base_ref = refs[pos]; pos += 1
        if has_mask:
            mask_ref = refs[pos]; pos += 1
        if plan.mode == "alpha" and plan.target == ci:
            dg0_ref = refs[pos]; pos += 1
        child_in.append((tab_ref, vals_ref, base_ref, mask_ref, dg0_ref))
    extra_ref = None
    if has_extra:
        extra_ref = refs[pos]; pos += 1
    lse_ref, pstats_ref = refs[pos], refs[pos + 1]; pos += 2
    cstat_refs = refs[pos:pos + n_child]; pos += n_child
    r_ref = None
    if emit_r:
        r_ref = refs[pos]; pos += 1
    scratch = refs[pos:]

    i = pl.program_id(0)
    cur = bt_ref[i]
    prev = bt_ref[jnp.maximum(i - 1, 0)]
    tile_first = jnp.logical_or(i == 0, prev != cur)

    @pl.when(i == 0)
    def _init_resident():
        if plan.target != "prior":
            pstats_ref[...] = jnp.zeros(pstats_ref.shape, pstats_ref.dtype)
        for ci, cref in enumerate(cstat_refs):
            if plan.target != ci:
                cref[...] = jnp.zeros(cref.shape, cref.dtype)
        if plan.mode == "alpha":
            if plan.target != "prior":
                scratch[0][...] = _elog_from_alpha(
                    ptab_ref[...].astype(jnp.float32), lane_pads[0])
            for ci, (tab_ref, *_) in enumerate(child_in):
                if plan.target != ci:
                    scratch[1 + ci][...] = _elog_from_alpha(
                        tab_ref[...].astype(jnp.float32), lane_pads[1 + ci])

    if plan.target is not None:
        @pl.when(tile_first)
        def _init_tile():
            if plan.target == "prior":
                pstats_ref[...] = jnp.zeros(pstats_ref.shape,
                                            pstats_ref.dtype)
                if plan.mode == "alpha":
                    scratch[0][...] = _elog_from_alpha(
                        ptab_ref[...].astype(jnp.float32), lane_pads[0])
            else:
                ci = plan.target
                cref = cstat_refs[ci]
                cref[...] = jnp.zeros(cref.shape, cref.dtype)
                if plan.mode == "alpha":
                    tab_ref, _, _, _, dg0_ref = child_in[ci]
                    scratch[1 + ci][...] = \
                        _digamma(tab_ref[...].astype(jnp.float32)) \
                        - dg0_ref[...]

    def table(idx, ref):
        if plan.mode == "alpha":
            return scratch[idx][...]
        return ref[...].astype(jnp.float32)

    ptab = table(0, ptab_ref)
    rows = prow_ref[...]
    if plan.target == "prior":
        rows = rows - cur * plan.tl
    tabs, vals, bases, masks = [], [], [], []
    for ci, (tab_ref, vals_ref, base_ref, mask_ref, _) in \
            enumerate(child_in):
        tabs.append(table(1 + ci, tab_ref))
        v = vals_ref[...]
        if plan.target == ci:
            v = v - cur * plan.tl
        vals.append(v)
        bases.append(None if base_ref is None else base_ref[...])
        masks.append(None if mask_ref is None else mask_ref[...])

    extra = None if extra_ref is None else extra_ref[...]
    lse, pd, cds, r = _block_step(ptab, tabs, rows, vals, bases, masks,
                                  zm_ref[...], plan.k, meta, extra)
    lse_ref[...] = jnp.full(lse_ref.shape, lse, jnp.float32)
    pstats_ref[...] += pd
    for cref, cd in zip(cstat_refs, cds):
        cref[...] += cd
    if r_ref is not None:
        r_ref[...] = r


# ---------------------------------------------------------------------------
# layout + call assembly (shared with ref.zstats_blocked)
# ---------------------------------------------------------------------------

class _Layout(NamedTuple):
    """Everything a zstats call (kernel or blocked oracle) consumes:
    padded device inputs, block/tile geometry, and static metadata."""
    plan: _Plan
    meta: tuple                        # per child (spec, stride, base?, mask?)
    lane_pads: tuple                   # per table: lane padding count
    ptab: jax.Array                    # (gpp, kp) padded prior table
    prow: jax.Array                    # (np_, 1) bucketed+padded prior rows
    zm: jax.Array                      # (np_, 1) token validity
    ctabs: tuple                       # per child padded table
    cvals: tuple                       # per child (np_, 1) values
    cbases: tuple                      # per child (np_, 1) base or None
    cmasks: tuple                      # per child (np_, 1) mask or None
    dg0: Optional[jax.Array]           # (kp, 1) streamed-child rowsum digamma
    blk_tile: jax.Array                # (nblocks,) per-block tile index
    nblocks: int


@jax.named_scope("kernels.zstats.layout")
def _layout(table_prior, prior_rows, children, zmask, *,
            tables: str = "elog", block_n: Optional[int] = None,
            bucketing=None) -> _Layout:
    plan = _plan(table_prior, children, tables, block_n)
    if plan is None:
        raise ValueError("not fusable: several over-budget tables, a "
                         "strided over-budget table, or a zmap child — "
                         "use ref.zstats")
    n = prior_rows.shape[0]
    bn = plan.bn
    fill = 1.0 if tables == "alpha" else 0.0

    def pad_table(t, rows, cols):
        return jnp.pad(t, ((0, rows - t.shape[0]), (0, cols - t.shape[1])),
                       constant_values=jnp.asarray(fill, t.dtype))

    # every token stream the kernel reads, one int32 row each (f32 streams
    # as their bit patterns), so that one operation places them all
    def bits(a):
        return jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.int32)

    zm = jnp.ones((n,), jnp.float32) if zmask is None else zmask
    streams = [prior_rows.astype(jnp.int32), bits(zm)]
    for c in children:
        streams.append(c.values.astype(jnp.int32))
        if c.base is not None:
            streams.append(c.base.astype(jnp.int32))
        if c.mask is not None:
            streams.append(bits(c.mask))
    streams = jnp.stack(streams)

    key = None
    if plan.target == "prior":
        key = prior_rows
    elif plan.target is not None:
        key = children[plan.target].values
    if key is None:
        # resident: the tokens in order, then zero padding (every stream's
        # fill) to whole blocks
        np_ = _pad_to(max(n, 1), bn)
        placed = jnp.pad(streams, ((0, 0), (0, np_ - n)))
        pad = slot_tile = None
        blk_tile = jnp.zeros((np_ // bn,), jnp.int32)
    else:
        if bucketing is not None:
            # host-precomputed permutation (ops.host_bucketing): enters the
            # trace as constants, so the per-step device sort disappears
            src, slot_tile, blk_tile = (jnp.asarray(b, jnp.int32)
                                        for b in bucketing)
            expect = (-(-n // bn) + plan.n_tiles) * bn
            if src.shape[0] != expect:
                raise ValueError(
                    f"stale bucketing: {src.shape[0]} padded slots for a "
                    f"layout that needs {expect} (n={n}, bn={bn}, "
                    f"tiles={plan.n_tiles}) — recompute ops.host_bucketing "
                    f"for this program")
            placed = streams[:, jnp.clip(src, 0)]
        else:
            src, slot_tile, blk_tile, placed = _bucket(
                key.astype(jnp.int32), n, plan.tl, plan.n_tiles, bn,
                streams)
        np_ = src.shape[0]
        pad = src < 0
    rows = iter(placed)

    def ptok(fill=0, f32=False):
        """The next token stream in slot order, as a (np_, 1) column: a
        lane-dense (bn, 1) block per grid step (the TPU refuses rank-1
        blocks that are not whole 128-lane tiles).  ``fill`` goes in the
        padding slots."""
        a = next(rows)
        if pad is not None:
            a = jnp.where(pad, fill, a)
        if f32:
            a = jax.lax.bitcast_convert_type(a, jnp.float32)
        return a[:, None]

    prow = ptok(slot_tile * plan.tl if plan.target == "prior" else 0)
    zm = ptok(f32=True)
    lane_pads = [plan.kp - plan.k]
    ctabs, cvals, cbases, cmasks, meta = [], [], [], [], []
    dg0 = None
    for ci, (c, (gf, kf, gfp, kfp)) in enumerate(zip(children,
                                                     plan.child_dims)):
        ctabs.append(pad_table(c.elog, gfp, kfp))
        cvals.append(ptok(slot_tile * plan.tl if plan.target == ci else 0))
        cbases.append(None if c.base is None else ptok())
        cmasks.append(None if c.mask is None else ptok(f32=True))
        meta.append((c.specialized, int(c.stride),
                     c.base is not None, c.mask is not None))
        lane_pads.append(kfp - kf)
        if tables == "alpha" and plan.target == ci:
            d = rowsum_digamma(c.elog.astype(jnp.float32))
            dg0 = jnp.pad(d, (0, plan.kp - d.shape[0]))[:, None]
    return _Layout(plan, tuple(meta), tuple(lane_pads),
                   pad_table(table_prior, plan.gpp, plan.kp),
                   prow, zm, tuple(ctabs), tuple(cvals),
                   tuple(cbases), tuple(cmasks), dg0, blk_tile,
                   np_ // bn)


def _zstats_call(lo: _Layout, extra=None, emit_r: bool = False,
                 interpret: bool = False):
    """Assemble and run the fused kernel over a prepared :class:`_Layout`.

    ``extra`` — optional ``(nblocks*bn, kp)`` pre-accumulated logits added
    after the prior gather (the zmap kernel's phase-one output); ``emit_r``
    appends the block responsibilities as a final ``(nblocks*bn, kp)``
    output.  Returns the ``pallas_call`` outputs
    ``[lse_blocks, pstats, *cstats, r?]`` (padded, unsliced; ``lse_blocks``
    is the ``(nblocks,)`` per-block lse sums).
    """
    plan, bn = lo.plan, lo.plan.bn
    kp, gpp = plan.kp, plan.gpp

    tok_spec = pl.BlockSpec((bn, 1), lambda i, bt: (i, 0))
    inputs = [lo.ptab]
    if plan.target == "prior":
        in_specs = [pl.BlockSpec((plan.tl, kp), lambda i, bt: (bt[i], 0))]
    else:
        in_specs = [pl.BlockSpec((gpp, kp), lambda i, bt: (0, 0))]
    inputs += [lo.prow, lo.zm]
    in_specs += [tok_spec, tok_spec]
    for ci, ((_, _, gfp, kfp), tab) in enumerate(zip(plan.child_dims,
                                                     lo.ctabs)):
        inputs.append(tab)
        if plan.target == ci:
            in_specs.append(pl.BlockSpec((gfp, plan.tl),
                                         lambda i, bt: (0, bt[i])))
        else:
            in_specs.append(pl.BlockSpec((gfp, kfp), lambda i, bt: (0, 0)))
        inputs.append(lo.cvals[ci])
        in_specs.append(tok_spec)
        if lo.cbases[ci] is not None:
            inputs.append(lo.cbases[ci])
            in_specs.append(tok_spec)
        if lo.cmasks[ci] is not None:
            inputs.append(lo.cmasks[ci])
            in_specs.append(tok_spec)
        if lo.dg0 is not None and plan.target == ci:
            inputs.append(lo.dg0)
            in_specs.append(pl.BlockSpec((kp, 1), lambda i, bt: (0, 0)))
    if extra is not None:
        inputs.append(extra)
        in_specs.append(pl.BlockSpec((bn, kp), lambda i, bt: (i, 0)))

    # each block's lse sum fills one (8, 128) tile: a lane-aligned block
    out_shape = [jax.ShapeDtypeStruct((lo.nblocks * _SUB, _LANE),
                                      jnp.float32),
                 jax.ShapeDtypeStruct((gpp, kp), jnp.float32)]
    out_specs = [pl.BlockSpec((_SUB, _LANE), lambda i, bt: (i, 0))]
    if plan.target == "prior":
        out_specs.append(pl.BlockSpec((plan.tl, kp),
                                      lambda i, bt: (bt[i], 0)))
    else:
        out_specs.append(pl.BlockSpec((gpp, kp), lambda i, bt: (0, 0)))
    for ci, (_, _, gfp, kfp) in enumerate(plan.child_dims):
        out_shape.append(jax.ShapeDtypeStruct((gfp, kfp), jnp.float32))
        if plan.target == ci:
            out_specs.append(pl.BlockSpec((gfp, plan.tl),
                                          lambda i, bt: (0, bt[i])))
        else:
            out_specs.append(pl.BlockSpec((gfp, kfp),
                                          lambda i, bt: (0, 0)))
    if emit_r:
        out_shape.append(jax.ShapeDtypeStruct((lo.nblocks * bn, kp),
                                              jnp.float32))
        out_specs.append(pl.BlockSpec((bn, kp), lambda i, bt: (i, 0)))

    scratch_shapes = []
    if plan.mode == "alpha":
        shp = (plan.tl, kp) if plan.target == "prior" else (gpp, kp)
        scratch_shapes.append(pltpu.VMEM(shp, jnp.float32))
        for ci, (_, _, gfp, kfp) in enumerate(plan.child_dims):
            shp = (gfp, plan.tl) if plan.target == ci else (gfp, kfp)
            scratch_shapes.append(pltpu.VMEM(shp, jnp.float32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(lo.nblocks,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    outs = pl.pallas_call(
        functools.partial(_kernel, plan=plan, meta=lo.meta,
                          lane_pads=lo.lane_pads,
                          has_extra=extra is not None, emit_r=emit_r),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(lo.blk_tile, *inputs)
    return [outs[0][::_SUB, 0], *outs[1:]]


def zstats(table_prior: jax.Array, prior_rows: jax.Array, children: tuple,
           zmask=None, *, tables: str = "elog",
           block_n: int | None = None, interpret: bool = False,
           bucketing=None):
    """Pallas-backed fused z-substep; matches ``ref.zstats`` (flat case).

    ``tables="elog"`` gathers from Elog tables as given; ``tables="alpha"``
    treats them as Dirichlet concentrations and fuses the
    ``dirichlet_expectation`` into the gather.  Tables too large for the
    VMEM budget are streamed tile-by-tile (see the module docstring);
    segment latents (zmap) belong to ``fused_zmap.zstats_zmap``.
    ``bucketing`` — an optional ``ops.host_bucketing`` result: the
    streamed path's token permutation, hoisted out of the trace.
    """
    if any(c.zmap is not None for c in children):
        raise ValueError("segment latents (zmap) take the two-phase "
                         "fused_zmap kernel; use ops.zstats")
    return _substep(table_prior, prior_rows, children, zmask, tables,
                    block_n, bucketing,
                    functools.partial(_zstats_call, interpret=interpret))


def _substep(table_prior, prior_rows, children, zmask, tables, block_n,
             bucketing, grid):
    """The flat substep around its one grid: lay the call out, run
    ``grid(layout)`` — the kernel, or ``ref.zstats_blocked``'s mirror of
    it, which so runs this same glue — and cut the padding off."""
    lo = _layout(table_prior, prior_rows, children, zmask,
                 tables=tables, block_n=block_n, bucketing=bucketing)
    outs = grid(lo)
    plan = lo.plan
    lse_blocks, pstats = outs[0], outs[1]
    cstats = tuple(cs[:gf, :kf]
                   for cs, (gf, kf, _, _) in zip(outs[2:], plan.child_dims))
    return lse_blocks.sum(), pstats[:plan.gp, :plan.k], cstats


__all__ = ["ZChild", "zstats", "rowsum_digamma"]
