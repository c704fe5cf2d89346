"""Train-step builders: grad accumulation, mixed precision, pjit shardings.

``make_train_step`` returns (step_fn, in_shardings, out_shardings, specs):
exactly what both the real trainer (launch/train.py) and the multi-pod
dry-run (launch/dryrun.py) need.  The step is a pure function

    (params, opt_state, batch) -> (params, opt_state, metrics)

with parameters/optimizer state donated.  Gradient accumulation scans over
microbatches; gradients accumulate in fp32 and are optionally compressed
across the 'pod' axis (grad_compress.compressed_pod_sync).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import RunConfig
from ..models import params as pr
from ..models.lm import LM
from ..parallel.sharding import MeshRules, use_rules
from .optimizer import OptConfig, make_optimizer, state_spec_tree
from . import grad_compress


def cast_tree(tree, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, tree)


def batch_shardings(model: LM, shape, rules: MeshRules, specs: dict):
    out = {}
    axes = model.batch_logical_axes(shape)
    for k, s in specs.items():
        out[k] = rules.act_sharding(axes.get(k, ()), s.shape)
    return out


def make_train_step(model: LM, run: RunConfig, rules: Optional[MeshRules]):
    """Builds the jit-able train step + sharding trees."""
    cfg = model.cfg
    opt_cfg = OptConfig(name=cfg.optimizer, weight_decay=run.weight_decay,
                        grad_clip=run.grad_clip)
    opt_init, opt_update, _ = make_optimizer(cfg.optimizer, opt_cfg)
    n_micro = run.microbatches()

    param_sh_tree = (pr.shardings(model.param_specs(), rules)
                     if rules is not None else None)

    def constrain_like_params(tree):
        """Pin the grad accumulator to the FSDP param layout: without this,
        GSPMD keeps per-microbatch grads replicated on 'data' and emits a
        full-size all-reduce per layer per microbatch; with it the sync is
        a reduce-scatter into the shard (measured 8x collective-byte cut on
        the mamba2 train cell — see EXPERIMENTS.md §Perf)."""
        if param_sh_tree is None:
            return tree
        return jax.tree.map(jax.lax.with_sharding_constraint, tree,
                            param_sh_tree)

    def loss_fn(p, batch):
        loss, metrics = model.loss_fn(p, batch)
        return loss, metrics

    def train_step(params, opt_state, batch):
        with use_rules(rules):
            compute_params = cast_tree(params, jnp.dtype(run.compute_dtype))

            if n_micro == 1:
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(compute_params, batch)
                with jax.named_scope("optimizer"):
                    grads = cast_tree(grads, jnp.float32)
            else:
                def micro(batch_slice, acc):
                    (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                        compute_params, batch_slice)
                    acc = jax.tree.map(
                        lambda a, gg: a + gg.astype(jnp.float32), acc, g)
                    return l, m, constrain_like_params(acc)

                def scan_body(acc, batch_slice):
                    l, m, acc = micro(batch_slice, acc)
                    return acc, (l, m)

                split = jax.tree.map(
                    lambda x: x.reshape((n_micro, x.shape[0] // n_micro)
                                        + x.shape[1:]), batch)
                acc0 = constrain_like_params(jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), compute_params))
                grads, (losses, metricses) = jax.lax.scan(scan_body, acc0, split)
                grads = jax.tree.map(lambda g: g / n_micro, grads)
                loss = jnp.mean(losses)
                metrics = jax.tree.map(jnp.mean, metricses)

            if run.grad_compression == "int8_ef" and rules is not None and \
                    "pod" in rules.mesh.axis_names:
                grads = grad_compress.compressed_pod_sync(grads, rules.mesh)

            from .optimizer import clip_by_global_norm
            with jax.named_scope("optimizer"):
                grads, gnorm = clip_by_global_norm(grads, run.grad_clip)
                new_params, new_opt = opt_update(grads, opt_state, params,
                                                 run.learning_rate)
            out_metrics = {"loss": loss, "grad_norm": gnorm, **metrics}
            return new_params, new_opt, out_metrics

    # ---------------------------------------------------------- shardings
    param_specs = model.param_specs()
    opt_specs = state_spec_tree(cfg.optimizer, param_specs, opt_cfg)
    if rules is not None:
        p_sh = pr.shardings(param_specs, rules)
        o_sh = pr.shardings(opt_specs, rules)
    else:
        p_sh = o_sh = None
    return train_step, param_specs, opt_specs, p_sh, o_sh, opt_init


def make_eval_step(model: LM, run: RunConfig, rules: Optional[MeshRules]):
    def eval_step(params, batch):
        with use_rules(rules):
            compute_params = cast_tree(params, jnp.dtype(run.compute_dtype))
            loss, metrics = model.loss_fn(compute_params, batch)
            return {"loss": loss, **metrics}

    return eval_step
