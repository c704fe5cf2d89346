"""Weights of the hybrid (Zamba2) configuration, made from the seed on the
device in one jitted call.

The Mamba-2 layers, the embedding and the final norm are laid out and
initialised as ``weights.py`` does (Mamba-2's recipe).  The shared block,
each invocation's adapters and its linear are N(0, 0.02), Zamba2's
``initializer_range``, and the norm scales one.  The adapters' B matrices
are drawn like the rest, not zero, so that every adapter shows in the
gradients from the first step.  The names are those of the program's
parameter tree, which the train driver checks leaf for leaf.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import weights
from reference_hybrid import hybrid_ids


def layout(cfg: dict) -> dict:
    """Nested dict of (shape, init, fan_in) leaves for the configuration."""
    sh, d = cfg["shared"], cfg["d_model"]
    a, H, hd = sh["attn_in"], sh["n_heads"], sh["head_dim"]
    r, f, n = sh["adapter_rank"], sh["d_ff"], len(hybrid_ids(cfg))

    def w(*shape):
        return (shape, "normal02", 0)

    def adapter(n_in, *out):
        return {"a": w(n, n_in, r), "b": w(n, r, *out)}

    inv = {"gate_up": adapter(d, 2, f), "linear": w(n, d, d)}
    if sh["attn_adapters"]:
        inv.update(q=adapter(a, H, hd), k=adapter(a, H, hd),
                   v=adapter(a, H, hd))
    return dict(weights.layout(cfg),
                shared={"ln_in": {"scale": ((a,), "ones", 0)},
                        "attn": {"wq": w(a, H, hd), "wk": w(a, H, hd),
                                 "wv": w(a, H, hd), "wo": w(H, hd, d)},
                        "ln_ff": {"scale": ((d,), "ones", 0)},
                        "mlp": {"gate_up": w(d, 2, f), "down": w(f, d)}},
                invocations=inv)


def _leaf(key, shape, init, fan_in):
    if init == "normal02":
        return jax.random.normal(key, shape, jnp.float32) * 0.02
    return weights._leaf(key, shape, init, fan_in)


def make_fn(cfg: dict):
    """A jitted ``f(lo, hi) -> params`` (fp32), with ``(lo, hi)`` the seed's
    two 32-bit words as device scalars: one compile serves every seed."""
    leaves, treedef = jax.tree.flatten(layout(cfg), is_leaf=weights._is_leaf)

    @jax.jit
    def make(lo, hi):
        key = jax.random.fold_in(jax.random.key(lo), hi)
        vals = [_leaf(jax.random.fold_in(key, i), *leaf)
                for i, leaf in enumerate(leaves)]
        return jax.tree.unflatten(treedef, vals)

    return make


seed_args = weights.seed_args


def shapes(cfg: dict) -> dict:
    return jax.tree.map(lambda leaf: jax.ShapeDtypeStruct(leaf[0], jnp.float32),
                        layout(cfg), is_leaf=weights._is_leaf)
