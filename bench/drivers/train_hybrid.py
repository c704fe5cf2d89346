"""Train cells of the hybrid (Zamba2) configuration: ``train.py``'s loop,
readings and ``correct``, with this configuration's weights
(``weights_hybrid.py``), plain reference (``reference_hybrid.py``) and
program configuration.

``train.py`` is loaded as a module of this driver's own and its three
configuration-bound names are pointed at the hybrid's; every other function
is ``train.py``'s.  Stacked leaves are read slice by slice: the layers, and
each invocation's adapters and linear.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

import reference_hybrid
import spec
import weights_hybrid

from repro.configs import ARCHS
from repro.configs.base import SSMConfig

train = spec.driver("train")
STACKED = ("['layers']", "['invocations']")


def _sizes(mc) -> dict:
    """A ``ModelConfig``'s sizes under the configuration file's keys."""
    ms = mc.ssm
    return {"n_layers": mc.n_layers, "d_model": mc.d_model,
            "padded_vocab": mc.padded_vocab,
            "tie_embeddings": mc.tie_embeddings, "norm_eps": mc.norm_eps,
            "ssm": {"d_state": ms.d_state, "d_conv": ms.d_conv,
                    "expand": ms.expand, "head_dim": ms.head_dim,
                    "chunk": ms.chunk, "n_groups": ms.n_groups},
            "shared": {"every": mc.shared_attn_every, "attn_in": mc.attn_in,
                       "n_heads": mc.n_heads, "head_dim": mc.head_dim,
                       "attn_scale": mc.attn_scale, "d_ff": mc.d_ff,
                       "adapter_rank": mc.adapter_rank,
                       "attn_adapters": mc.attn_adapters,
                       "rope_theta": mc.rope_theta}}


def program_config(cfg: dict):
    """The registry's ``ModelConfig`` with the file's sizes.  Every size the
    file does not list under ``reduced`` has to be the registry's own."""
    base = ARCHS[cfg["registry"]]
    sh = cfg["shared"]
    mc = dataclasses.replace(
        base, n_layers=cfg["n_layers"], d_model=cfg["d_model"],
        vocab_size=cfg["vocab_size"], tie_embeddings=cfg["tie_embeddings"],
        norm_eps=cfg["norm_eps"], remat=cfg["run"]["remat"],
        ssm=SSMConfig(**cfg["ssm"]), shared_attn_every=sh["every"],
        attn_in=sh["attn_in"], n_heads=sh["n_heads"],
        n_kv_heads=sh["n_heads"], d_head=sh["head_dim"],
        attn_scale=sh["attn_scale"], d_ff=sh["d_ff"],
        adapter_rank=sh["adapter_rank"], attn_adapters=sh["attn_adapters"],
        rope_theta=sh["rope_theta"])
    ours, registry = _sizes(mc), _sizes(base)
    for key, value in ours.items():
        if value != cfg[key]:
            raise ValueError(f"{key}: the program builds {value}, the "
                             f"configuration file states {cfg[key]}")
        if key not in cfg["reduced"] and value != registry[key]:
            raise ValueError(f"{key}: the file states {cfg[key]}, the "
                             f"registry's {cfg['registry']} has "
                             f"{registry[key]}, and {key} is not in reduced")
    return mc


def leaf_norms(tree) -> dict:
    """Euclidean norm of every leaf; stacked leaves slice by slice."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        x = x.astype(jnp.float32)
        if name.startswith(STACKED):
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x).reshape(x.shape[0], -1),
                                         axis=1))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))[None]
    return out


train.weights = weights_hybrid
train.reference = reference_hybrid
train.program_config = program_config
train.leaf_norms = leaf_norms

Program = train.Program
reference_readings = train.reference_readings
gaps = train.gaps
run = train.run
_free = train._free
