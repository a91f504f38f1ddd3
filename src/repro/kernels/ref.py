"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics; the kernels must match them (tests sweep shapes and
dtypes and assert allclose in interpret mode).  They are also the production
fallback on non-TPU backends.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.scipy.special import digamma


def dirichlet_expectation(alpha: jax.Array) -> jax.Array:
    """E[log theta] rowwise: digamma(a) - digamma(a.sum(-1))."""
    return digamma(alpha) - digamma(alpha.sum(axis=-1, keepdims=True))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True) -> jax.Array:
    """Oracle for the flash kernel: dense masked attention.
    q/k/v: (BH, S, Dh)."""
    dh = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(float(dh))
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None]
        s = jnp.where(mask, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", w, v.astype(jnp.float32)) \
        .astype(q.dtype)


def zstep(logits: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Fused responsibility update: (softmax(logits), logsumexp(logits)).

    The logsumexp is the per-instance ELBO contribution of a latent at its
    coordinate optimum (see core/vmp.py).
    """
    m = logits.max(axis=-1, keepdims=True)
    e = jnp.exp(logits - m)
    s = e.sum(axis=-1, keepdims=True)
    return e / s, (m + jnp.log(s))[..., 0]


# ---------------------------------------------------------------------------
# fused token-plate substep: gather -> softmax -> sufficient statistics
# ---------------------------------------------------------------------------

class ZChild(NamedTuple):
    """Kernel-level view of one observed child factor of a latent selector.

    The parent Dirichlet row of token ``i`` under topic ``k`` is
    ``base[i] + stride * k`` (``base is None`` means all-zero; ``base is None
    and stride == 1`` is the specialized LDA fast path where the row IS the
    selector value).  ``zmap`` maps tokens to latent instances when the token
    plate is nested below the latent plate (SLDA); ``None`` means identity.
    ``elog`` holds the parent's message table: E[log theta] values under
    the default ``zstats(..., tables="elog")``, or the Dirichlet posterior
    concentrations under ``tables="alpha"`` (the fused-expectation mode).
    """
    elog: jax.Array                    # (G_f, K_f) parent message table
    values: jax.Array                  # (Nt,) observed category per token
    stride: int = 1
    zmap: Optional[jax.Array] = None   # (Nt,) token -> latent instance
    base: Optional[jax.Array] = None   # (Nt,) static row base
    mask: Optional[jax.Array] = None   # (Nt,) 1.0/0.0 token validity

    @property
    def specialized(self) -> bool:
        """LDA fast path: the Dirichlet row IS the selector value (mirrors
        ``compiler.ChildFactor.specialized``)."""
        return self.base is None and self.stride == 1


ZSTATS_CHUNK = 32768                   # token rows per lax.scan chunk


def _child_messages(child: ZChild, vals, base, mask, k: int) -> jax.Array:
    """Per-token Elog message rows of one child factor -> (n, k) f32."""
    if child.specialized:
        e = child.elog[:, vals].T
    else:
        kk = jnp.arange(k, dtype=jnp.int32)
        b = base[:, None] if base is not None else 0
        rows = b + child.stride * kk[None, :]
        e = child.elog[rows, vals[:, None]]
    e = e.astype(jnp.float32)
    if mask is not None:
        e = e * mask[:, None]
    return e


def _child_stats_native(child: ZChild, acc, w, vals, base, mask,
                        k: int) -> jax.Array:
    """Accumulate one chunk's responsibility-weighted counts into ``acc``.

    Specialized children accumulate in the scatter-native (K_f, G_f) layout
    — i.e. (V, K) for LDA — so the per-chunk hot loop is a pure scatter-add;
    the single transpose to the Dirichlet's (G_f, K_f) layout happens once,
    in :func:`_child_stats_finish`, not once per chunk.
    """
    if mask is not None:
        w = w * mask[:, None]
    gf, kf = child.elog.shape
    if child.specialized:
        return acc.at[vals].add(w)                      # (kf, gf) native
    kk = jnp.arange(k, dtype=jnp.int32)
    b = base[:, None] if base is not None else 0
    rows = (b + child.stride * kk[None, :]).astype(jnp.int32)
    flat = rows * kf + vals[:, None]
    s = jax.ops.segment_sum(w.ravel(), flat.ravel(), num_segments=gf * kf)
    return acc + s.reshape(gf, kf)


def _child_stats_init(child: ZChild) -> jax.Array:
    gf, kf = child.elog.shape
    if child.specialized:
        return jnp.zeros((kf, gf), jnp.float32)
    return jnp.zeros((gf, kf), jnp.float32)


def _child_stats_finish(child: ZChild, acc: jax.Array) -> jax.Array:
    if child.specialized:
        return acc.T
    return acc


def _scan_chunks(xs: dict, n: int, chunk: int, init, body):
    """Fold ``body(carry, xs_chunk)`` over ``chunk``-sized row slices of every
    array in ``xs``.  Single-chunk inputs run ``body`` directly (no scan) so
    small problems keep the exact summation order of the unfused path; larger
    ones scan the full chunks and fold the remainder rows with one direct
    tail call — no padding, no synthetic masks, every row is real."""
    if n <= chunk:
        return body(init, xs)
    nc = n // chunk
    head = {name: a[:nc * chunk].reshape((nc, chunk) + a.shape[1:])
            for name, a in xs.items()}
    carry, _ = jax.lax.scan(lambda c, x: (body(c, x), None), init, head)
    if n > nc * chunk:
        carry = body(carry, {name: a[nc * chunk:] for name, a in xs.items()})
    return carry


def _token_xs(child: ZChild, i: int) -> dict:
    xs = {f"values{i}": child.values}
    if child.zmap is not None:
        xs[f"zmap{i}"] = child.zmap
    if child.base is not None:
        xs[f"base{i}"] = child.base
    if child.mask is not None:
        xs[f"mask{i}"] = child.mask
    return xs


def zstats(elog_prior: jax.Array, prior_rows: jax.Array,
           children: tuple, zmask: Optional[jax.Array] = None,
           chunk: int = ZSTATS_CHUNK, *, tables: str = "elog",
           child_stats: bool = True):
    """Fused z-substep semantics: one streaming pass over the token plate.

    Computes, without ever materializing the (N, K) responsibilities or
    logits (they live one chunk at a time):

        logits_i = elog_prior[prior_rows[i]] + sum_f message_f(i)
        r_i, lse_i = softmax/logsumexp(logits_i)          (masked by zmask)
        lse_sum = sum_i lse_i
        prior_stats[prior_rows[i]] += r_i
        child_stats_f = responsibility-weighted count scatter of factor f

    Returns ``(lse_sum, prior_stats, child_stats_tuple)`` — exactly the
    quantities ``core/vmp.py:_step_body`` needs; responsibilities are
    intermediate values, never state.  ``child_stats=False`` leaves the
    child accumulators out of the pass (the tuple is ``()``), so nothing
    of the child tables' size is scattered; ``lse_sum`` and
    ``prior_stats`` come from the same ops in the same order.

    Latents whose children carry a ``zmap`` (segment latents, e.g. SLDA
    sentences) need a cross-token reduction before the softmax, so they
    materialize the (n_latent, K) logits — still dropping the (N_token, K)
    working set, which is the large one.

    ``tables="alpha"`` treats ``elog_prior`` and every child ``elog`` as
    Dirichlet *concentration* tables and computes the expectations here
    (upcast to f32 first — narrow ``elog_dtype`` tables stay narrow only
    in HBM).  This mirrors the Pallas kernels' fused
    ``dirichlet_expectation`` mode; on this pure-jnp path XLA fuses the
    digamma into the gathers anyway, so it is a semantic switch, not an
    optimization.
    """
    if tables == "alpha":
        elog_prior = dirichlet_expectation(elog_prior.astype(jnp.float32))
        children = tuple(
            c._replace(elog=dirichlet_expectation(
                c.elog.astype(jnp.float32))) for c in children)
    k = elog_prior.shape[1]
    if any(c.zmap is not None for c in children):
        return _zstats_segmented(elog_prior, prior_rows, children, zmask,
                                 chunk, k, child_stats)
    return _zstats_flat(elog_prior, prior_rows, children, zmask, chunk, k,
                        child_stats)


def _zstats_flat(elog_prior, prior_rows, children, zmask, chunk, k,
                 child_stats):
    """Token plate == latent plate: a single fused scan, nothing (N, K)."""
    n = prior_rows.shape[0]
    gp = elog_prior.shape[0]

    def body(carry, xs):
        lse_acc, pstats, cstats = carry
        rows = xs["prior_rows"]
        zm = xs.get("zmask")
        logits = elog_prior[rows].astype(jnp.float32)
        for i, c in enumerate(children):
            logits = logits + _child_messages(
                c, xs[f"values{i}"], xs.get(f"base{i}"), xs.get(f"mask{i}"), k)
        r, lse = zstep(logits)
        if zm is not None:
            r = r * zm[:, None]
            lse = lse * zm
        lse_acc = lse_acc + lse.sum()
        pstats = pstats.at[rows].add(r)
        # an empty carry (child_stats=False) zips to no scatter at all
        cstats = tuple(
            _child_stats_native(c, cs, r, xs[f"values{i}"],
                                xs.get(f"base{i}"), xs.get(f"mask{i}"), k)
            for i, (c, cs) in enumerate(zip(children, cstats)))
        return lse_acc, pstats, cstats

    xs = {"prior_rows": prior_rows}
    if zmask is not None:
        xs["zmask"] = zmask
    for i, c in enumerate(children):
        xs.update(_token_xs(c, i))
    init = (jnp.zeros((), jnp.float32),
            jnp.zeros((gp, k), jnp.float32),
            tuple(_child_stats_init(c) for c in children)
            if child_stats else ())
    lse_sum, pstats, cstats = _scan_chunks(xs, n, chunk, init, body)
    return lse_sum, pstats, tuple(_child_stats_finish(c, cs)
                                  for c, cs in zip(children, cstats))


def _zstats_segmented(elog_prior, prior_rows, children, zmask, chunk, k,
                      child_stats):
    """Segment latents: accumulate per-instance logits (cross-token
    reduction), then stream the child token plates against them.  Both
    token passes run under the ``kernels.zstats.segments`` named scope;
    ``child_stats=False`` leaves out the second."""
    nz = prior_rows.shape[0]
    gp = elog_prior.shape[0]
    logits = elog_prior[prior_rows].astype(jnp.float32)

    with jax.named_scope("kernels.zstats.segments"):
        for i, c in enumerate(children):
            if c.zmap is None:
                logits = logits + _child_messages(c, c.values, c.base,
                                                  c.mask, k)
                continue

            def msg_body(acc, xs, c=c, i=i):
                e = _child_messages(c, xs[f"values{i}"], xs.get(f"base{i}"),
                                    xs.get(f"mask{i}"), k)
                return acc + jax.ops.segment_sum(e, xs[f"zmap{i}"],
                                                 num_segments=nz)

            logits = logits + _scan_chunks(
                _token_xs(c, i), c.values.shape[0], chunk,
                jnp.zeros((nz, k), jnp.float32), msg_body)

    r, lse = zstep(logits)
    if zmask is not None:
        r = r * zmask[:, None]
        lse = lse * zmask
    lse_sum = lse.sum()
    pstats = jnp.zeros((gp, k), jnp.float32).at[prior_rows].add(r)
    if not child_stats:
        return lse_sum, pstats, ()

    cstats = []
    with jax.named_scope("kernels.zstats.segments"):
        for i, c in enumerate(children):
            if c.zmap is None:
                s = _child_stats_native(c, _child_stats_init(c), r, c.values,
                                        c.base, c.mask, k)
                cstats.append(_child_stats_finish(c, s))
                continue

            def st_body(cs, xs, c=c, i=i):
                w = r[xs[f"zmap{i}"]]
                return _child_stats_native(c, cs, w, xs[f"values{i}"],
                                           xs.get(f"base{i}"),
                                           xs.get(f"mask{i}"), k)

            s = _scan_chunks(_token_xs(c, i), c.values.shape[0], chunk,
                             _child_stats_init(c), st_body)
            cstats.append(_child_stats_finish(c, s))
    return lse_sum, pstats, tuple(cstats)


# ---------------------------------------------------------------------------
# block-structured oracle: the Pallas kernels' bitwise parity target
# ---------------------------------------------------------------------------

def _one_program(fn, *args):
    """``fn(*args)`` compiled as one XLA program, with the arrays among
    ``args`` as its parameters and everything else static.  The oracle
    runs each kernel's grid this way because the interpret-mode kernel
    runs its grid as one program too.  Run op by op, XLA:CPU computes a
    dot whose operand is transposed (every one-hot scatter here) as a
    transpose and then a dot, and the digamma outside its fusion; both
    sum or round in another order than the compiled grid, 1-2 ulps
    apart."""
    flat, tree = jax.tree.flatten(args)
    dyn = [i for i, x in enumerate(flat) if isinstance(x, jax.Array)]

    def run(*arrays):
        full = list(flat)
        for i, a in zip(dyn, arrays):
            full[i] = a
        return fn(*jax.tree.unflatten(tree, full))

    return jax.jit(run)(*(flat[i] for i in dyn))


def _resolve_table(tab, lane_pad: int, tables: str, dg0=None):
    """Elog values of one padded table, with the kernels' exact ops."""
    from .dirichlet_expectation import _digamma
    from .fused_zstats import _elog_from_alpha
    a = tab.astype(jnp.float32)
    if tables != "alpha":
        return a
    if dg0 is not None:                # streamed along the value axis
        return _digamma(a) - dg0
    return _elog_from_alpha(a, lane_pad)


def _blocked_call(lo, extra=None, emit_r: bool = False):
    """Pure-jnp mirror of ``fused_zstats._zstats_call``: the same blocks in
    the same order with the same one-hot matmuls, accumulated with plain
    adds.  Returns the raw padded ``[lse_blocks, pstats, *cstats, r?]``.
    Run it through :func:`_one_program`, as the kernel's grid runs."""
    from .fused_zstats import _block_step
    plan, bn = lo.plan, lo.plan.bn
    kp, tl = plan.kp, plan.tl

    ptab_full = None if plan.target == "prior" \
        else _resolve_table(lo.ptab, lo.lane_pads[0], plan.mode)
    ctab_full = [
        None if plan.target == ci
        else _resolve_table(tab, lo.lane_pads[1 + ci], plan.mode)
        for ci, tab in enumerate(lo.ctabs)]

    lse = []
    pstats = jnp.zeros((lo.ptab.shape[0], kp), jnp.float32)
    cstats = [jnp.zeros(t.shape, jnp.float32) for t in lo.ctabs]
    rs = []
    for b in range(lo.nblocks):
        sl = slice(b * bn, (b + 1) * bn)
        t = lo.blk_tile[b]
        rows = lo.prow[sl]
        if plan.target == "prior":
            ptab = _resolve_table(
                jax.lax.dynamic_slice(lo.ptab, (t * tl, 0), (tl, kp)),
                lo.lane_pads[0], plan.mode)
            rows = rows - t * tl
        else:
            ptab = ptab_full
        tabs, vals = [], []
        for ci, tab in enumerate(lo.ctabs):
            v = lo.cvals[ci][sl]
            if plan.target == ci:
                tabs.append(_resolve_table(
                    jax.lax.dynamic_slice(tab, (0, t * tl),
                                          (tab.shape[0], tl)),
                    lo.lane_pads[1 + ci], plan.mode, dg0=lo.dg0))
                v = v - t * tl
            else:
                tabs.append(ctab_full[ci])
            vals.append(v)
        bases = [None if a is None else a[sl] for a in lo.cbases]
        masks = [None if a is None else a[sl] for a in lo.cmasks]
        ex = None if extra is None else extra[sl]
        l, pd, cds, r = _block_step(ptab, tabs, rows, vals, bases, masks,
                                    lo.zm[sl], plan.k, lo.meta, ex)
        lse.append(l)
        rs.append(r)
        if plan.target == "prior":
            cur = jax.lax.dynamic_slice(pstats, (t * tl, 0), (tl, kp))
            pstats = jax.lax.dynamic_update_slice(pstats, cur + pd,
                                                  (t * tl, 0))
        else:
            pstats = pstats + pd
        for ci, cd in enumerate(cds):
            if plan.target == ci:
                cur = jax.lax.dynamic_slice(
                    cstats[ci], (0, t * tl), (cstats[ci].shape[0], tl))
                cstats[ci] = jax.lax.dynamic_update_slice(
                    cstats[ci], cur + cd, (0, t * tl))
            else:
                cstats[ci] = cstats[ci] + cd
    outs = [jnp.stack(lse), pstats, *cstats]
    if emit_r:
        outs.append(jnp.concatenate(rs, axis=0))
    return outs


def _blocked_logits(c: ZChild, k: int, kp: int, nzp: int, cdim: tuple,
                    tables: str, block_n):
    """Mirror of ``fused_zmap._phase_logits``: one zmap child's message
    rows, block by block, summed onto its latent instances."""
    from .fused_zmap import _phase_inputs
    from .fused_zstats import _child_message, _dot, _onehot

    def grid(inputs, c):
        bn, tab, vals, zmi, tm, base = inputs
        tabv = _resolve_table(tab, cdim[3] - cdim[1], tables)
        zacc = jnp.zeros((nzp, kp), jnp.float32)
        for b in range(vals.shape[0] // bn):
            sl = slice(b * bn, (b + 1) * bn)
            lane = jax.lax.broadcasted_iota(jnp.int32, (bn, kp), 1)
            e = _child_message(tabv, vals[sl],
                               None if base is None else base[sl], tm[sl],
                               k, lane, c.specialized, int(c.stride))
            zacc = zacc + _dot(_onehot(zmi[sl], nzp).T, e)
        return zacc

    return _one_program(
        grid, _phase_inputs(c, kp, nzp, cdim, tables, block_n), c)


def _blocked_stats(c: ZChild, r, k: int, kp: int, nzp: int, cdim: tuple,
                   block_n):
    """Mirror of ``fused_zmap._phase_stats``: one zmap child's statistics
    from the latent responsibilities ``r[zmap]``."""
    from .fused_zmap import _phase_inputs
    from .fused_zstats import _child_scatter, _dot, _onehot
    gf, kf, gfp, kfp = cdim

    def grid(inputs, r, c):
        bn, _, vals, zmi, tm, base = inputs
        acc = jnp.zeros((gfp, kfp), jnp.float32)
        for b in range(vals.shape[0] // bn):
            sl = slice(b * bn, (b + 1) * bn)
            w = _dot(_onehot(zmi[sl], nzp), r)
            acc = acc + _child_scatter(
                w, vals[sl], None if base is None else base[sl], tm[sl],
                acc.shape, k, c.specialized, int(c.stride))
        return acc

    return _one_program(
        grid, _phase_inputs(c, kp, nzp, cdim, "elog", block_n), r, c
    )[:gf, :kf]


def zstats_blocked(table_prior: jax.Array, prior_rows: jax.Array,
                   children: tuple, zmask: Optional[jax.Array] = None, *,
                   tables: str = "elog", block_n: Optional[int] = None):
    """Oracle for the *block structure* of the fused Pallas kernels.

    Replays the kernels' exact tiling, token bucketing, per-block one-hot
    matmuls, and accumulation order in straight-line jnp (no
    ``pallas_call``), so its outputs are **bitwise equal** to the
    interpret-mode kernels called the same way (both eagerly, as the
    tests call them) — including the HBM-streamed large-table path,
    the two-phase zmap path, and the ``tables="alpha"`` fused
    ``dirichlet_expectation``.  It runs the kernels' own glue
    (``fused_zstats._substep``, ``fused_zmap._two_phase``) with each
    ``pallas_call`` replaced by a mirror of its grid, compiled as one
    program (:func:`_one_program`, which says why).  This validates the
    Pallas plumbing (BlockSpecs, scalar-prefetch index maps, scratch
    accumulators) against plain array code; :func:`zstats` remains the
    *semantic* oracle the kernels must match within float tolerance.
    """
    from .fused_zmap import _two_phase
    from .fused_zstats import _substep
    if not any(c.zmap is not None for c in children):
        return _substep(table_prior, prior_rows, children, zmask, tables,
                        block_n, None,
                        lambda lo: _one_program(_blocked_call, lo))
    return _two_phase(
        table_prior, prior_rows, children, zmask, tables, block_n,
        lambda c, k, kp, nzp, cdim: _blocked_logits(c, k, kp, nzp, cdim,
                                                    tables, block_n),
        lambda lo, extra: _one_program(_blocked_call, lo, extra, True),
        lambda c, r, k, kp, nzp, cdim: _blocked_stats(c, r, k, kp, nzp,
                                                      cdim, block_n))
