"""The flash kernel as a drop-in attention path: model forward with
``flash_kernel=True`` must match the default XLA attention path."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, RunConfig
from repro.models import make_model


def test_flash_kernel_path_matches_default():
    cfg = dataclasses.replace(ARCHS["olmo-1b"].reduced(), n_layers=2)
    model = make_model(cfg)
    base = RunConfig(seq_len=32, global_batch=2, dtype="float32")
    flash = dataclasses.replace(base, flash_kernel=True)
    params = model["init"](base, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 32)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (2, 32)),
                                   jnp.int32)}
    l_base = float(model["train_loss"](params, batch, base))
    l_flash = float(model["train_loss"](params, batch, flash))
    np.testing.assert_allclose(l_flash, l_base, rtol=1e-5)


def test_flash_kernel_differentiable():
    """custom_vjp: kernel-forward gradients equal the reference gradients
    (recompute-in-backward, no O(S^2) residuals)."""
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels import ref
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 32, 16)).astype(np.float32))
               for _ in range(3))
    gk = jax.grad(lambda *a: flash_attention(
        *a, causal=True, block_q=16, block_k=16, interpret=True).sum(),
        argnums=(0, 1, 2))(
        q, k, v)
    gr = jax.grad(lambda *a: ref.flash_attention(*a, causal=True).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_flash_train_loss_grad_matches():
    """End-to-end: training gradients through the flash path match the
    default path."""
    cfg = dataclasses.replace(ARCHS["olmo-1b"].reduced(), n_layers=1)
    model = make_model(cfg)
    base = RunConfig(seq_len=16, global_batch=2, dtype="float32")
    flash = dataclasses.replace(base, flash_kernel=True)
    params = model["init"](base, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)),
                                   jnp.int32)}
    g1 = jax.grad(lambda p: model["train_loss"](p, batch, base))(params)
    g2 = jax.grad(lambda p: model["train_loss"](p, batch, flash))(params)
    flat1 = jax.tree_util.tree_leaves(g1)
    flat2 = jax.tree_util.tree_leaves(g2)
    for a, b in zip(flat1, flat2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)


def test_flash_kernel_path_gqa():
    """GQA (kv < heads) routes through the kv-broadcast wrapper."""
    cfg = dataclasses.replace(ARCHS["qwen3-moe-30b-a3b"].reduced(),
                              n_layers=1, n_experts=4, experts_per_tok=2)
    assert cfg.n_kv_heads < cfg.n_heads
    model = make_model(cfg)
    base = RunConfig(seq_len=16, global_batch=2, dtype="float32")
    flash = dataclasses.replace(base, flash_kernel=True)
    params = model["init"](base, jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)),
                                   jnp.int32)}
    np.testing.assert_allclose(
        float(model["train_loss"](params, batch, flash)),
        float(model["train_loss"](params, batch, base)), rtol=1e-5)
