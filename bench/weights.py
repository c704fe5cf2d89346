"""Weights made from the seed, on the device, in one jitted call.

The tree is laid out from the configuration file alone (the names are those
of the model's parameter tree, which the train driver checks leaf for leaf
against the program's own specification).  Both the program under test and
the plain reference are given these weights: neither makes its own.

Initialisation follows Mamba-2's published recipe: fan-in scaled truncated
normals for projections, ``A = -exp(A_log)`` with ``A`` uniform in [1, 16),
``dt = softplus(dt_bias)`` log-uniform in [1e-3, 1e-1], ``D`` and norm
scales one, conv biases zero, embeddings N(0, 0.02).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from traffic import seed_words
from workcount import ssm_dims


def _mixer(cfg: dict, L: int) -> dict:
    m = ssm_dims(cfg)
    d, di, H, K = m["d"], m["di"], m["H"], m["K"]
    gn = m["G"] * m["N"]
    return {
        "wz": ((L, d, di), "normal", d), "wx": ((L, d, di), "normal", d),
        "wB": ((L, d, gn), "normal", d), "wC": ((L, d, gn), "normal", d),
        "wdt": ((L, d, H), "normal", d),
        "conv_x_w": ((L, di, K), "normal", K),
        "conv_x_b": ((L, di), "zeros", 0),
        "conv_B_w": ((L, gn, K), "normal", K),
        "conv_B_b": ((L, gn), "zeros", 0),
        "conv_C_w": ((L, gn, K), "normal", K),
        "conv_C_b": ((L, gn), "zeros", 0),
        "dt_bias": ((L, H), "dt_bias", 0), "A_log": ((L, H), "a_log", 0),
        "D": ((L, H), "ones", 0), "norm": ((L, di), "ones", 0),
        "out_proj": ((L, di, d), "normal", di),
    }


def layout(cfg: dict) -> dict:
    """Nested dict of (shape, init, fan_in) leaves for the configuration."""
    d, V, L = cfg["d_model"], cfg["padded_vocab"], cfg["n_layers"]
    return {"embed": {"table": ((V, d), "embed", 0)},
            "final_norm": {"scale": ((d,), "ones", 0)},
            "layers": {"ln": {"scale": ((L, d), "ones", 0)},
                       "mamba": _mixer(cfg, L)}}


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def _leaf(key, shape, init, fan_in):
    if init == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    if init == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if init == "dt_bias":
        lo, hi = math.log(1e-3), math.log(1e-1)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1
    if init == "embed":
        return jax.random.normal(key, shape, jnp.float32) * 0.02
    std = 1.0 / math.sqrt(fan_in)
    return jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                       jnp.float32) * std


def make_fn(cfg: dict):
    """A jitted ``f(lo, hi) -> params`` (fp32), with ``(lo, hi)`` the seed's
    two 32-bit words as device scalars: one compile serves every seed."""
    tree = layout(cfg)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_leaf)

    @jax.jit
    def make(lo, hi):
        key = jax.random.fold_in(jax.random.key(lo), hi)
        vals = [_leaf(jax.random.fold_in(key, i), *leaf)
                for i, leaf in enumerate(leaves)]
        return jax.tree.unflatten(treedef, vals)

    return make


def seed_args(seed: int):
    lo, hi = seed_words(seed)
    return jnp.uint32(lo), jnp.uint32(hi)


def shapes(cfg: dict) -> dict:
    return jax.tree.map(lambda leaf: jax.ShapeDtypeStruct(leaf[0], jnp.float32),
                        layout(cfg), is_leaf=_is_leaf)
