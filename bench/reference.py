"""The plain reference: the configuration's training step in float32 jnp.

It imports nothing of the program.  It follows the configuration file and
the published descriptions, and spells each piece out plainly:

* Mamba-2 mixer (arXiv:2405.21060): in projections for z, x, B, C, dt; a
  depthwise causal conv + SiLU on x, B and C; ``dt = softplus(dt + bias)``,
  ``A = -exp(A_log)``; the SSD scan in the paper's own chunked listing
  (``ssd_minimal_discrete``, with its stable ``segsum``); ``y + D x``; the
  gated RMSNorm ``norm(y * silu(z))``; the out projection.
* RMSNorm pre-norms, a final norm, logits against the tied embedding over
  the padded vocabulary, mean next-token cross-entropy;
* AdamW with global-norm clipping, as the configuration's ``optimizer``
  states it.

Matmuls run at full float32 precision (``highest``).  With ``int8_mm`` in
their place the reference is the control: every product one precision step
below the program's single bfloat16 pass, its operands rounded to 8-bit
integers.  Memory: each mixer layer is checkpointed, so the reference fits
one chip at the timed sizes once the program's state is freed.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def highest_mm(spec, *ops):
    return jnp.einsum(spec, *ops, precision=HIGHEST)


def int8(x):
    """``x`` rounded to 8-bit integers on a per-tensor scale (its largest
    magnitude over 127), kept in float32, the gradient passed straight
    through the rounding."""
    scale = jnp.max(jnp.abs(x)) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return x + jax.lax.stop_gradient(jnp.round(x / scale) * scale - x)


def int8_mm(spec, *ops):
    """A product of int8 operands, accumulated exactly in float32."""
    return highest_mm(spec, *map(int8, ops))


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def causal_conv_silu(u, w, b):
    """Depthwise causal conv: y[t] = sum_k u[t - K + 1 + k] w[:, k] + b."""
    S, K = u.shape[1], w.shape[1]
    up = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(up[:, k:k + S] * w[:, k] for k in range(K)) + b
    return jax.nn.silu(y)


def segsum(x):
    """Stable segment sum: out[..., i, j] = sum_{j < k <= i} x[..., k] for
    j <= i, and -inf above the diagonal."""
    T = x.shape[-1]
    x = jnp.repeat(x[..., None], T, axis=-1)          # x[..., i, j] = x_i
    x = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1), x, 0.0)
    xs = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), xs, -jnp.inf)


def ssd(x, dt, A, B, C, chunk, mm=highest_mm):
    """SSD scan, the Mamba-2 paper's minimal chunked listing.

    x (b, l, h, p); dt (b, l, h); A (h,); B, C (b, l, g, n).  Returns y."""
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    c = l // chunk
    X = (x * dt[..., None]).reshape(b, c, chunk, h, p)
    Ad = jnp.transpose((dt * A).reshape(b, c, chunk, h), (0, 3, 1, 2))
    Bh = jnp.repeat(B, h // g, axis=2).reshape(b, c, chunk, h, n)
    Ch = jnp.repeat(C, h // g, axis=2).reshape(b, c, chunk, h, n)
    A_cs = jnp.cumsum(Ad, axis=-1)                     # (b, h, c, l)

    Lm = jnp.exp(segsum(Ad))                           # (b, h, c, l, l)
    y_diag = mm("bclhn,bcshn,bhcls,bcshp->bclhp", Ch, Bh, Lm, X)

    decay_states = jnp.exp(A_cs[..., -1:] - A_cs)
    states = mm("bclhn,bhcl,bclhp->bchpn", Bh, decay_states, X)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    decay_chunk = jnp.exp(segsum(jnp.pad(A_cs[..., -1], ((0, 0), (0, 0),
                                                         (1, 0)))))
    states = mm("bhzc,bchpn->bzhpn", decay_chunk, states)[:, :-1]
    y_off = mm("bclhn,bchpn,bhcl->bclhp", Ch, states, jnp.exp(A_cs))
    return (y_diag + y_off).reshape(b, l, h, p)


def mixer(p, u, cfg, mm=highest_mm):
    s = cfg["ssm"]
    b, l, _ = u.shape
    H = s["expand"] * cfg["d_model"] // s["head_dim"]
    z = mm("bld,de->ble", u, p["wz"])
    x = causal_conv_silu(mm("bld,de->ble", u, p["wx"]), p["conv_x_w"],
                         p["conv_x_b"])
    B = causal_conv_silu(mm("bld,de->ble", u, p["wB"]), p["conv_B_w"],
                         p["conv_B_b"])
    C = causal_conv_silu(mm("bld,de->ble", u, p["wC"]), p["conv_C_w"],
                         p["conv_C_b"])
    dt = jax.nn.softplus(mm("bld,de->ble", u, p["wdt"]) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xh = x.reshape(b, l, H, s["head_dim"])
    gn = (s["n_groups"], s["d_state"])
    y = ssd(xh, dt, A, B.reshape(b, l, *gn), C.reshape(b, l, *gn),
            s["chunk"], mm)
    y = (y + xh * p["D"][:, None]).reshape(b, l, -1)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg["norm_eps"])
    return mm("ble,ed->bld", y, p["out_proj"])


def loss(params, tokens, cfg, mm=highest_mm):
    eps = cfg["norm_eps"]
    h = params["embed"]["table"][tokens]

    @jax.checkpoint
    def layer(h, lp):
        return h + mixer(lp["mamba"], rms_norm(h, lp["ln"]["scale"], eps),
                         cfg, mm)

    h, _ = jax.lax.scan(lambda h, lp: (layer(h, lp), None), h,
                        params["layers"])
    h = rms_norm(h, params["final_norm"]["scale"], eps)
    logits = mm("bld,vd->blv", h, params["embed"]["table"])
    logits, targets = logits[:, :-1], tokens[:, 1:]
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def adamw(params, grads, mu, nu, t, opt):
    """One AdamW step with global-norm clipping.  Returns the new
    (params, mu, nu) and the clipped gradient the moments were fed."""
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(norm, 1e-12))
    g = jax.tree.map(lambda x: x * scale, grads)
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t

    def upd(p, m, v):
        return p - opt["lr"] * ((m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
                                + opt["weight_decay"] * p)

    return jax.tree.map(upd, params, mu, nu), mu, nu, g
