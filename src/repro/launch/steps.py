"""Step builders: jitted train / prefill / decode with explicit shardings.

Every builder returns ``(fn, in_shardings, out_shardings, abstract_inputs)``
so the same artifacts serve three callers: the real trainer/server, the
multi-pod dry-run (.lower().compile() against ShapeDtypeStructs), and the
roofline analyzer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import ArchConfig, RunConfig
from repro.models import make_model
from repro.optim import adamw_init, adamw_update, clip_by_global_norm, lr_schedule
from .shardings import Rules, named


def abstract_train_state(cfg: ArchConfig, run: RunConfig):
    """ShapeDtypeStructs of (params, opt_state) without allocating."""
    model = make_model(cfg)
    params = jax.eval_shape(lambda: model["init"](run, jax.random.PRNGKey(0)))
    opt = jax.eval_shape(adamw_init, params)
    return params, opt


def _install_ctx(mesh):
    from repro.models.sharding_ctx import set_ctx
    from .mesh import data_axes, model_axis
    set_ctx(mesh, data_axes(mesh), model_axis(mesh))


def build_train_step(cfg: ArchConfig, run: RunConfig, mesh):
    model = make_model(cfg)
    _install_ctx(mesh)
    rules = Rules(cfg, run, mesh)
    params_abs, opt_abs = abstract_train_state(cfg, run)
    p_spec = rules.params(params_abs)
    o_spec = rules.opt_state(opt_abs, p_spec)

    def train_step(params, opt_state, batch, step):
        def loss_fn(p, b):
            return model["train_loss"](p, b, run)

        if run.microbatch > 1:
            k = run.microbatch
            resh = jax.tree_util.tree_map(
                lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]), batch)

            def acc_fn(carry, mb):
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                return (carry[0] + l / k,
                        jax.tree_util.tree_map(lambda a, b_: a + b_ / k,
                                               carry[1], g)), None

            zero = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(acc_fn, (0.0, zero), resh)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)

        grads, gnorm = clip_by_global_norm(grads, run.grad_clip)
        lr = lr_schedule(step, run.learning_rate, run.warmup)
        params, opt_state = adamw_update(params, grads, opt_state, lr=lr,
                                         weight_decay=run.weight_decay)
        return params, opt_state, {"loss": loss, "gnorm": gnorm, "lr": lr}

    def batch_specs(batch_abs):
        return rules.batch(batch_abs)

    return {
        "fn": train_step,
        "params_spec": p_spec,
        "opt_spec": o_spec,
        "batch_specs": batch_specs,
        "rules": rules,
        "abstract_state": (params_abs, opt_abs),
        "out_specs": (p_spec, o_spec, {"loss": P(), "gnorm": P(), "lr": P()}),
    }


def build_prefill_step(cfg: ArchConfig, run: RunConfig, mesh):
    model = make_model(cfg)
    _install_ctx(mesh)
    rules = Rules(cfg, run, mesh)
    params_abs, _ = abstract_train_state(cfg, run)
    p_spec = rules.params(params_abs)

    def prefill_step(params, batch):
        return model["prefill"](params, batch, run)

    return {"fn": prefill_step, "params_spec": p_spec, "rules": rules,
            "abstract_params": params_abs}


def build_decode_step(cfg: ArchConfig, run: RunConfig, mesh):
    model = make_model(cfg)
    _install_ctx(mesh)
    rules = Rules(cfg, run, mesh)
    params_abs, _ = abstract_train_state(cfg, run)
    p_spec = rules.params(params_abs)

    def decode_step(params, cache, tokens, pos):
        return model["decode_step"](params, cache, tokens, pos, run)

    return {"fn": decode_step, "params_spec": p_spec, "rules": rules,
            "abstract_params": params_abs}


def jit_train_step(built, mesh, batch_abs):
    b_spec = built["batch_specs"](batch_abs)
    return jax.jit(
        built["fn"],
        in_shardings=(named(mesh, built["params_spec"]),
                      named(mesh, built["opt_spec"]),
                      named(mesh, b_spec),
                      named(mesh, P())),
        out_shardings=(named(mesh, built["params_spec"]),
                       named(mesh, built["opt_spec"]),
                       named(mesh, built["out_specs"][2])),
        donate_argnums=(0, 1))


def jit_prefill_step(built, mesh, batch_abs, cache_abs):
    rules = built["rules"]
    b_spec = rules.batch(batch_abs)
    c_spec = rules.cache(cache_abs)
    logits_spec = P()
    return jax.jit(
        built["fn"],
        in_shardings=(named(mesh, built["params_spec"]),
                      named(mesh, b_spec)),
        out_shardings=(named(mesh, logits_spec), named(mesh, c_spec)))


def jit_decode_step(built, mesh, cache_abs):
    rules = built["rules"]
    c_spec = rules.cache(cache_abs)
    return jax.jit(
        built["fn"],
        in_shardings=(named(mesh, built["params_spec"]),
                      named(mesh, c_spec),
                      named(mesh, P(None, None)),
                      named(mesh, P())),
        out_shardings=(named(mesh, P()), named(mesh, c_spec)),
        donate_argnums=(1,))
