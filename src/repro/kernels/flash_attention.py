"""Pallas TPU kernel: flash attention (forward).

The §Perf hillclimb identified attention score-block materialization as the
dominant memory-term contributor for long-context cells (phi3/whisper
prefill+train): XLA cannot keep the (cq, ck) score blocks VMEM-resident
without a custom kernel, so every block pays an HBM write+read.  This kernel
is the structural fix on real TPUs: running max / normalizer / output
accumulator live in VMEM scratch across the kv-block grid dimension, so HBM
traffic is exactly Q+K+V+O.

Grid: (batch*heads, n_q_blocks, n_kv_blocks) — the trailing grid dimension is
sequential on TPU, so the output block is revisited with accumulation and
written once on the last kv block.  Causal masking is positional (blocks are
not skipped; the FLOP skip is a follow-up — the memory win is the point).

Validated in interpret mode against ``ref.flash_attention`` (a pure-jnp
oracle that also backs GQA via kv-head broadcasting) over shape sweeps.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, cq: int, ck: int, nk: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                   # (cq, dh)
    k = k_ref[0].astype(jnp.float32)                   # (ck, dh)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T) * scale                        # (cq, ck) in VMEM
    if causal:
        qi = pl.program_id(1)
        qpos = qi * cq + jax.lax.broadcasted_iota(jnp.int32, (cq, ck), 0)
        kpos = ki * ck + jax.lax.broadcasted_iota(jnp.int32, (cq, ck), 1)
        s = jnp.where(kpos <= qpos, s, _NEG)

    m_prev, l_prev, acc_prev = m_scr[...], l_scr[...], acc_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    m_scr[...] = m_new
    l_scr[...] = l_prev * corr + p.sum(axis=-1)
    acc_scr[...] = acc_prev * corr[:, None] + jnp.dot(p, v)

    @pl.when(ki == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 256,
                    block_k: int = 256, interpret: bool = False) -> jax.Array:
    """q: (BH, Sq, Dh); k/v: (BH, Sk, Dh) — heads pre-flattened (GQA callers
    broadcast kv heads first).  Returns (BH, Sq, Dh).

    Differentiable: the forward runs the Pallas kernel; the backward
    recomputes attention with the (XLA) reference — the standard
    recompute-in-backward flash trade (no O(S^2) residuals saved).
    """
    return _flash_vjp(q, k, v, causal, block_q, block_k, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_vjp(q, k, v, causal, block_q, block_k, interpret):
    return _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret)


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret"))
def _flash_fwd_impl(q, k, v, causal=True, block_q=256, block_k=256,
                    interpret=False):
    if q.ndim != 3 or k.shape != v.shape or q.shape[0] != k.shape[0]:
        raise ValueError("expected (BH, S, Dh) operands")
    bh, sq, dh = q.shape
    sk = k.shape[1]
    cq, ck = min(block_q, sq), min(block_k, sk)
    sq_p = (sq + cq - 1) // cq * cq
    sk_p = (sk + ck - 1) // ck * ck
    qp = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0)))
    # causal masking kills padded kv columns (kpos > qpos for the tail);
    # the non-causal path has no mask, so it requires divisible kv length
    if not causal and sk_p != sk:
        raise ValueError("non-causal flash requires sk % block_k == 0")
    nq, nk = sq_p // cq, sk_p // ck

    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(dh), causal=causal,
                          cq=cq, ck=ck, nk=nk),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, cq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, ck, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, ck, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, cq, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((cq,), jnp.float32),
            pltpu.VMEM((cq,), jnp.float32),
            pltpu.VMEM((cq, dh), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :sq]


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    out = _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v)


def _flash_bwd(causal, block_q, block_k, interpret, res, g):
    # recompute attention through the differentiable reference (the flash
    # backward identity: no residuals beyond q/k/v)
    from . import ref
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: ref.flash_attention(
        q_, k_, v_, causal=causal), q, k, v)
    return vjp(g)


_flash_vjp.defvjp(_flash_fwd, _flash_bwd)
