"""Shared layers: norms, rotary embeddings, MLP variants, embeddings, loss."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import GLU_KINDS, ModelConfig
from ..parallel.sharding import lsc
from .params import P


# ------------------------------------------------------------------- norms
def norm_params(cfg: ModelConfig) -> dict:
    if cfg.norm_kind == "layernorm":
        return {"scale": P((cfg.d_model,), ("embed",), "ones"),
                "bias": P((cfg.d_model,), ("embed",), "zeros")}
    return {"scale": P((cfg.d_model,), ("embed",), "ones")}


def apply_norm(p: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    if "bias" in p:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(dt)


def rms_norm_gated(x: jax.Array, scale: jax.Array, gate: jax.Array,
                   eps: float = 1e-6) -> jax.Array:
    """Mamba2 output norm: RMSNorm(x * silu(gate))."""
    dt = x.dtype
    xf = x.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(dt)


# ------------------------------------------------------------------- rotary
def rope_freqs(head_dim: int, fraction: float, theta: float) -> Optional[jax.Array]:
    rot = int(head_dim * fraction)
    rot -= rot % 2
    if rot == 0:
        return None
    return 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))


def apply_rope(x: jax.Array, positions: jax.Array, fraction: float,
               theta: float, style: str = "interleaved") -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq).

    ``style`` pairs the rotated features: ``interleaved`` rotates (x0, x1),
    (x2, x3), ...; ``half`` rotates x_i with x_{i + rot/2} (rotate-half)."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, fraction, theta)
    if inv is None:
        return x
    rot = inv.shape[0] * 2
    xr, xp = x[..., :rot], x[..., rot:]
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., seq, rot/2)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    if style == "half":
        x1 = xr[..., :rot // 2].astype(jnp.float32)
        x2 = xr[..., rot // 2:].astype(jnp.float32)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1).astype(x.dtype)
        return jnp.concatenate([out, xp], axis=-1)
    x1 = xr[..., 0::2].astype(jnp.float32)
    x2 = xr[..., 1::2].astype(jnp.float32)
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape).astype(x.dtype)
    return jnp.concatenate([out, xp], axis=-1)


# ---------------------------------------------------------------------- MLP
def mlp_params(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.mlp_kind in GLU_KINDS:
        return {
            "wi_gate": P((d, f), ("embed", "mlp")),
            "wi_up": P((d, f), ("embed", "mlp")),
            "wo": P((f, d), ("mlp", "embed")),
        }
    return {"wi": P((d, f), ("embed", "mlp")), "wo": P((f, d), ("mlp", "embed"))}


def _gate_act(kind: str, g: jax.Array) -> jax.Array:
    if kind == "swiglu":
        return jax.nn.silu(g)
    return jax.nn.gelu(g, approximate=kind != "geglu_erf")


def apply_mlp(p: dict, x: jax.Array, kind: str) -> jax.Array:
    if kind in GLU_KINDS:
        g = jnp.einsum("...d,df->...f", x, p["wi_gate"])
        u = jnp.einsum("...d,df->...f", x, p["wi_up"])
        h = _gate_act(kind, g) * u
    else:
        h = jnp.einsum("...d,df->...f", x, p["wi"])
        if kind == "sq_relu":
            h = jnp.square(jax.nn.relu(h))
        else:
            h = jax.nn.gelu(h)
    h = lsc(h, "batch", "rseq", "mlp")
    return jnp.einsum("...f,fd->...d", h, p["wo"])


def shared_mlp_params(cfg: ModelConfig) -> dict:
    """The shared block's gated MLP (zamba2): one gate/up weight (d, 2, f),
    so that each invocation's adapter adds to both halves."""
    d, f = cfg.d_model, cfg.d_ff
    return {"gate_up": P((d, 2, f), ("embed", None, "mlp")),
            "down": P((f, d), ("mlp", "embed"))}


def lora(x: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """The adapter's term ``(x A) B``; B's trailing axes give its output."""
    return jnp.tensordot(jnp.einsum("...i,ir->...r", x, a), b, axes=1)


def apply_shared_mlp(p: dict, adapter: dict, x: jax.Array,
                     kind: str) -> jax.Array:
    """``down(act(g) * u)`` with ``[g, u] = x W + (x A) B``."""
    gu = jnp.einsum("...d,dnf->...nf", x, p["gate_up"]) \
        + lora(x, adapter["a"], adapter["b"])
    h = _gate_act(kind, gu[..., 0, :]) * gu[..., 1, :]
    h = lsc(h, "batch", "rseq", "mlp")
    return jnp.einsum("...f,fd->...d", h, p["down"])


# ----------------------------------------------------------------- embedding
def embed_params(cfg: ModelConfig) -> dict:
    V, d = cfg.padded_vocab, cfg.d_model
    out = {"table": P((V, d), ("vocab", "embed"), "embed")}
    if not cfg.tie_embeddings:
        out["head"] = P((d, V), ("embed", "vocab"))
    return out


def embed_tokens(p: dict, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = jnp.take(p["table"], tokens, axis=0)
    if cfg.name.startswith("paligemma"):  # gemma scales embeddings
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return lsc(x, "batch", "rseq", "embed")


def logits_from_hidden(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        out = jnp.einsum("...d,vd->...v", x, p["table"])
    else:
        out = jnp.einsum("...d,dv->...v", x, p["head"])
    return lsc(out, "batch", "rseq", "vocab")


# --------------------------------------------------------------------- loss
def next_token_loss(logits: jax.Array, tokens: jax.Array,
                    vocab_size: int) -> jax.Array:
    """Mean next-token CE.  logits: (B,S,Vp) for tokens (B,S); padded vocab
    entries are excluded by masking labels >= vocab_size (never produced)."""
    lg = logits[:, :-1].astype(jnp.float32)
    tg = tokens[:, 1:]
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, tg[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)
