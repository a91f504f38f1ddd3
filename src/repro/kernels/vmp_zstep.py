"""Pallas TPU kernel: fused latent-Categorical update (the VMP z-substep).

Given summed messages ``logits`` (N, K) this computes, in one VMEM pass:

    r   = softmax(logits, axis=-1)        (the new responsibilities q(z))
    lse = logsumexp(logits, axis=-1)      (the per-instance ELBO term)

N is the token plate (the paper's dominant cost: one z vertex per token);
K is the topic count.  A single fused pass avoids materializing the shifted
exponentials in HBM three times (max, exp, sum) — on TPU this substep is
memory-bound, so the fusion is the whole win.

Tiling: 1-D grid over N blocks, block (block_n, K_padded); K is padded to the
128-lane boundary with -inf (exp -> 0, so softmax and lse are unaffected).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_VMEM_BUDGET = 4 * 1024 * 1024
_LANE = 128
_NEG = -1e30


def _kernel(logits_ref, r_ref, lse_ref):
    x = logits_ref[...]
    m = x.max(axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    s = e.sum(axis=-1, keepdims=True)
    r_ref[...] = e / s
    lse_ref[...] = m[:, 0] + jnp.log(s[:, 0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def zstep(logits: jax.Array, *, interpret: bool = False):
    """Pallas-backed (softmax, logsumexp); matches ref.zstep."""
    if logits.ndim != 2:
        raise ValueError("expected (N, K)")
    n, k = logits.shape
    kp = max(_LANE, (k + _LANE - 1) // _LANE * _LANE)
    # the (block_n,) lse block must be whole 128-lane tiles
    block_n = max(_LANE, min(1024, _VMEM_BUDGET // (kp * 4)) // _LANE * _LANE)
    np_ = (n + block_n - 1) // block_n * block_n

    x = jnp.pad(logits.astype(jnp.float32), ((0, np_ - n), (0, kp - k)),
                constant_values=_NEG)
    r, lse = pl.pallas_call(
        _kernel,
        grid=(np_ // block_n,),
        in_specs=[pl.BlockSpec((block_n, kp), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_n, kp), lambda i: (i, 0)),
                   pl.BlockSpec((block_n,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((np_, kp), jnp.float32),
                   jax.ShapeDtypeStruct((np_,), jnp.float32)],
        interpret=interpret,
    )(x)
    return r[:n, :k].astype(logits.dtype), lse[:n].astype(logits.dtype)
