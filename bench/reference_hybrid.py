"""The plain reference of the hybrid (Zamba2) configuration's training step,
in float32 jnp.

It imports nothing of the program; of the benchmark it reuses
``reference.py``'s Mamba-2 mixer, RMSNorm, products and AdamW.  It follows
the configuration file and Zyphra's Zamba2 as the ``transformers``
library's ``modeling_zamba2.py`` writes it:

* ``e`` is the embedding output; every layer is a Mamba-2 layer
  ``h + mixer(rms_norm(x))``, with ``x = h`` except at the hybrid layers
  (every ``shared.every``-th layer from that index on), all in one scan;
* at hybrid layer i, invocation j of the one shared block:
  ``u = rms_norm([h; e])``; ``q = u Wq + (u Aq_j) Bq_j``, and k, v alike;
  rotary embedding (rotate-half) on q and k;
  ``a = softmax_causal(q k^T * scale) v Wo``;
  ``t = down(gelu(g) * up)`` with ``[g, up] = n Wgu + (n Am_j) Bm_j`` and
  ``n = rms_norm(a)``, exact (erf) GELU; then ``x = h + t Wlin_j``;
* a final RMSNorm, logits against the tied embedding, mean next-token
  cross-entropy.

The attention core runs over blocks of query rows, each checkpointed, so a
4,096-token invocation fits one chip once the program's state is freed.
Matmuls run at ``highest``; with ``int8_mm`` the reference is the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# adamw and int8_mm are used through this module: the train driver's loop
# calls ``reference.adamw`` and the calibration passes ``int8_mm``
from reference import adamw, highest_mm, int8_mm, mixer, rms_norm  # noqa: F401

Q_BLOCK = 512


def hybrid_ids(cfg: dict) -> tuple:
    k = cfg["shared"]["every"]
    return tuple(range(k, cfg["n_layers"], k))


def rope(x, theta):
    """Rotate-half rotary embedding; x (b, l, h, d), positions 0..l-1."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    emb = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(emb) + half * jnp.sin(emb)


def causal_attention(q, k, v, scale, mm):
    """softmax(q k^T * scale, causal) v, over blocks of query rows."""
    b, l, h, d = q.shape
    blk = min(Q_BLOCK, l)
    nb = l // blk
    qb = jnp.moveaxis(q.reshape(b, nb, blk, h, d), 1, 0)

    @jax.checkpoint
    def rows(args):
        qi, i = args
        s = mm("bqhd,bkhd->bhqk", qi, k) * scale
        keep = (i * blk + jnp.arange(blk))[:, None] >= jnp.arange(l)[None, :]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return mm("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(rows, (qb, jnp.arange(nb)))
    return jnp.moveaxis(out, 0, 1).reshape(b, l, h, d)


def adapted(u, w, ad, mm):
    """u W + (u A) B, for W (i, ...) and B (r, ...)."""
    out = mm("bli,i...->bl...", u, w)
    if ad is None:
        return out
    return out + mm("blr,r...->bl...", mm("bli,ir->blr", u, ad["a"]),
                    ad["b"])


def shared_block(sp, ip, h, e, cfg, mm=highest_mm):
    """The shared transformer block's output t for invocation weights ip."""
    sh, eps = cfg["shared"], cfg["norm_eps"]
    u = rms_norm(jnp.concatenate([h, e], axis=-1), sp["ln_in"]["scale"], eps)
    at = sp["attn"]
    qkv = [adapted(u, at[w], ip.get(n), mm)
           for w, n in (("wq", "q"), ("wk", "k"), ("wv", "v"))]
    q, k = (rope(x, sh["rope_theta"]) for x in qkv[:2])
    o = causal_attention(q, k, qkv[2], sh["attn_scale"], mm)
    a = mm("blhd,hdo->blo", o, at["wo"])
    n = rms_norm(a, sp["ln_ff"]["scale"], eps)
    gu = adapted(n, sp["mlp"]["gate_up"], ip["gate_up"], mm)
    y = jax.nn.gelu(gu[..., 0, :], approximate=False) * gu[..., 1, :]
    return mm("blf,fd->bld", y, sp["mlp"]["down"])


def hidden(params, tokens, cfg, mm=highest_mm):
    """The last hidden state, after the final norm: one scan over the
    layers; at a hybrid layer a ``cond`` takes the shared block's branch."""
    eps = cfg["norm_eps"]
    e = params["embed"]["table"][tokens]
    ids = hybrid_ids(cfg)
    hyb = jnp.zeros(cfg["n_layers"], bool).at[jnp.asarray(ids, int)].set(True)
    inv = jnp.cumsum(hyb) - 1                     # the layer's invocation
    n_inv = len(ids)

    def link(h, j):
        ip = jax.tree.map(lambda a: a[jnp.clip(j, 0, n_inv - 1)],
                          params["invocations"])
        t = shared_block(params["shared"], ip, h, e, cfg, mm)
        return h + mm("bld,de->ble", t, ip["linear"])

    @jax.checkpoint
    def layer(h, xs):
        lp, is_hybrid, j = xs
        x = jax.lax.cond(is_hybrid, link, lambda h, j: h, h, j)
        return h + mixer(lp["mamba"], rms_norm(x, lp["ln"]["scale"], eps),
                         cfg, mm), None

    h, _ = jax.lax.scan(layer, e, (params["layers"], hyb, inv))
    return rms_norm(h, params["final_norm"]["scale"], eps)


def logits(params, tokens, cfg, mm=highest_mm):
    return mm("bld,vd->blv", hidden(params, tokens, cfg, mm),
              params["embed"]["table"])


def loss(params, tokens, cfg, mm=highest_mm):
    lg, targets = logits(params, tokens, cfg, mm)[:, :-1], tokens[:, 1:]
    picked = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - picked)
