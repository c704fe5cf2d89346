"""GQA attention: train/prefill (blocked, flash-equivalent) + cached decode.

Two implementations share one math definition:

* ``blocked_attention`` — pure-jnp online-softmax over KV blocks (the flash
  algorithm expressed in XLA ops).  This is what the multi-pod dry-run lowers:
  the host platform is CPU, so the Pallas TPU kernel cannot be compiled there;
  the blocked path has the same O(S·block) memory and the same collective
  pattern.  Where ``kernels.ops.flash_fits`` holds (causal self-attention on
  a TPU, outside mesh rules, whole 128-lane heads), ``impl='blocked'`` runs
  the ``kernels.flash_attention`` Pallas kernels instead, forward and
  backward.
* ``decode_attention`` — single-token attention against a KV cache.
"""
from __future__ import annotations

import contextlib
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import ops as kops
from ..parallel.sharding import current_rules, lsc
from .layers import apply_rope, lora
from .params import P


def _attn_seq_axis(q_shape) -> str:
    """'sp_seq' when neither heads nor head_dim can ride the tensor axis
    (e.g. whisper's 20 heads or qwen-32b's 40 on a 16-way mesh): attention
    activations then shard their SEQUENCE instead (Megatron-style sequence
    parallelism) — the §Perf fix for the score-all-reduce disease."""
    rules = current_rules()
    if rules is None:
        return "seq"
    spec = rules.act_spec(("batch", "seq", "heads", "head_dim"), q_shape)
    return "seq" if spec[2] is not None else "sp_seq"

NEG_INF = -1e30


def attn_params(cfg: ModelConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    a = cfg.attn_in_dim
    out = {
        "wq": P((a, h, hd), ("embed", "heads", "head_dim")),
        "wk": P((a, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": P((a, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": P((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        out["bq"] = P((h, hd), ("heads", "head_dim"), "zeros")
        out["bk"] = P((kv, hd), ("kv_heads", "head_dim"), "zeros")
        out["bv"] = P((kv, hd), ("kv_heads", "head_dim"), "zeros")
    return out


def project_qkv(p: dict, x: jax.Array, cfg: ModelConfig):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    return q, k, v


def project_kv(p: dict, x: jax.Array, adapters: Optional[dict] = None):
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if adapters is not None:
        k = k + lora(x, adapters["k"]["a"], adapters["k"]["b"])
        v = v + lora(x, adapters["v"]["a"], adapters["v"]["b"])
    if "bk" in p:
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    return k, v


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def blocked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool, q_offset: int = 0,
                      block: int = 1024,
                      scale: Optional[float] = None,
                      remat: bool = False) -> jax.Array:
    """Online-softmax attention over KV blocks.

    q: (B, Sq, H, D); k, v: (B, Sk, KVH, D); H % KVH == 0.
    Returns (B, Sq, H, D).  fp32 accumulation.  ``scale`` multiplies the
    scores (default ``D ** -0.5``).  With ``remat`` each block's scores are
    recomputed in the backward pass instead of saved: the saved state is
    then the running max, sum and output, not (Sq, block) per head.
    """
    B, Sq, H, D = q.shape
    Sk, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    qg = (q * scale).reshape(B, Sq, KVH, G, D)

    block = min(block, max(Sk, 1))
    kp = _pad_to(k, 1, block)
    vp = _pad_to(v, 1, block)
    nb = kp.shape[1] // block
    # (nb, B, block, KVH, D)
    ks = jnp.moveaxis(kp.reshape(B, nb, block, KVH, D), 1, 0)
    vs = jnp.moveaxis(vp.reshape(B, nb, block, KVH, D), 1, 0)

    qpos = q_offset + jnp.arange(Sq)

    def body(carry, inp):
        m, l, acc = carry
        kb, vb, bidx = inp
        kpos = bidx * block + jnp.arange(block)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kb,
                       preferred_element_type=jnp.float32)
        valid = kpos < Sk
        if causal:
            valid = valid[None, :] & (qpos[:, None] >= kpos[None, :])
            s = jnp.where(valid[None, None, None], s, NEG_INF)
        else:
            s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vb.dtype), vb,
                        preferred_element_type=jnp.float32)
        acc_new = acc * alpha[..., None] + pv
        return (m_new, l_new, acc_new), None

    if remat:
        body = jax.checkpoint(body)
    m0 = jnp.full((B, KVH, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KVH, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KVH, G, Sq, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (ks, vs, jnp.arange(nb)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.moveaxis(out, 3, 1).reshape(B, Sq, H, D)  # (B,Sq,KVH,G,D)->(B,Sq,H,D)
    return out.astype(q.dtype)


def naive_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool, q_offset: int = 0,
                    scale: Optional[float] = None) -> jax.Array:
    """Reference O(S^2)-memory attention (oracle for tests)."""
    B, Sq, H, D = q.shape
    Sk, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    qg = _scaled(q.reshape(B, Sq, KVH, G, D), scale)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32)
    if causal:
        qpos = q_offset + jnp.arange(Sq)
        mask = qpos[:, None] >= jnp.arange(Sk)[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def _scaled(q: jax.Array, scale: Optional[float]) -> jax.Array:
    """q times the softmax scale (default: divided by sqrt(head_dim))."""
    if scale is None:
        return q / math.sqrt(q.shape[-1])
    return q * scale


def quantize_kv(x: jax.Array):
    """Per-(token, head) symmetric int8 quantization of a K/V tensor
    (..., S, KV, HD) -> (int8 tensor, f16 scale (..., S, KV))."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float16)


def decode_attention_q8(q: jax.Array, ck: jax.Array, cv: jax.Array,
                        k_scale: jax.Array, v_scale: jax.Array,
                        length: jax.Array,
                        scale: Optional[float] = None) -> jax.Array:
    """Decode attention over an int8-quantized cache (production serving
    feature; §Perf iteration E).  Exact math: per-(token, head) scales are
    applied to the *scores* and the *probabilities*, so the int8 tensors
    feed the dots directly — on TPU the int8->bf16 convert fuses into the
    MXU operand stream (cost-model rule I-5) and the cache streams at half
    the bf16 bytes."""
    B, _, H, D = q.shape
    Smax, KVH = ck.shape[1], ck.shape[2]
    G = H // KVH
    ck = lsc(ck, "batch", "kvseq", "kv_heads", "head_dim")
    cv = lsc(cv, "batch", "kvseq", "kv_heads", "head_dim")
    qg = _scaled(q.reshape(B, KVH, G, D), scale)
    qg = lsc(qg, "batch", "kv_heads", "q_group", "head_dim")
    s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                   ck.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    s = s * jnp.moveaxis(k_scale.astype(jnp.float32), 1, 2)[:, :, None, :]
    s = lsc(s, "batch", "kv_heads", "q_group", "kvseq")
    valid = jnp.arange(Smax) < length
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = p * jnp.moveaxis(v_scale.astype(jnp.float32), 1, 2)[:, :, None, :]
    p = lsc(p, "batch", "kv_heads", "q_group", "kvseq")
    out = jnp.einsum("bhgk,bkhd->bhgd", p, cv.astype(jnp.float32))
    return out.reshape(B, 1, H, D).astype(q.dtype)


def decode_attention(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                     length: jax.Array,
                     scale: Optional[float] = None) -> jax.Array:
    """q: (B, 1, H, D) against cache (B, Smax, KVH, D); positions >= length
    are masked.  fp32 softmax.

    Decode is sequence-parallel (flash-decode style): the cache stays
    sharded on its *sequence* axis ('kvseq' -> tensor axis), the tiny q is
    replicated across it, and the softmax reductions over the sharded axis
    lower to two small all-reduces.  Without the explicit constraints GSPMD
    resolves the q(heads)-vs-cache(seq) sharding mismatch by materializing
    full per-layer cache copies every step (measured: 0.5 GB/layer copies
    on chatglm3 decode_32k — see EXPERIMENTS.md §Perf)."""
    B, _, H, D = q.shape
    Smax, KVH = cache_k.shape[1], cache_k.shape[2]
    G = H // KVH
    cache_k = lsc(cache_k, "batch", "kvseq", "kv_heads", "head_dim")
    cache_v = lsc(cache_v, "batch", "kvseq", "kv_heads", "head_dim")
    qg = _scaled(q.reshape(B, KVH, G, D), scale)
    qg = lsc(qg, "batch", "kv_heads", "q_group", "head_dim")
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, cache_k,
                   preferred_element_type=jnp.float32)
    s = lsc(s, "batch", "kv_heads", "q_group", "kvseq")
    valid = jnp.arange(Smax) < length
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = lsc(p, "batch", "kv_heads", "q_group", "kvseq")
    out = jnp.einsum("bhgk,bkhd->bhgd", p.astype(cache_v.dtype), cache_v)
    return out.reshape(B, 1, H, D).astype(q.dtype)


def _scope(scope: Optional[str], name: str):
    """``jax.named_scope(f"{scope}.{name}")``, or nothing without a scope."""
    if scope is None:
        return contextlib.nullcontext()
    return jax.named_scope(f"{scope}.{name}")


def attention_block(p: dict, x: jax.Array, cfg: ModelConfig, *,
                    mode: str,
                    positions: Optional[jax.Array] = None,
                    cache: Optional[dict] = None,
                    cache_pos=None,
                    cross_x: Optional[jax.Array] = None,
                    causal: bool = True,
                    impl: str = "blocked",
                    kv_block: int = 1024,
                    adapters: Optional[dict] = None,
                    scope: Optional[str] = None,
                    remat_blocks: bool = False):
    """Full attention sub-block: projections + rope + core + output proj.

    Returns (out, new_cache).  ``cache`` is a dict {k, v} (+ filled length
    tracked by the caller); for cross-attention the cache holds the encoder
    K/V and is never updated after prefill.  ``adapters`` ({q, k, v}: {a,
    b}) add low-rank terms to the projections, before rope.  With
    ``scope``, the projections, the core and the output projection run
    under the named scopes ``<scope>.qkv``, ``<scope>.attn``, ``<scope>.out``.
    ``remat_blocks`` recomputes the jnp blocked core's scores in the
    backward pass (``blocked_attention``'s ``remat``); the flash kernel
    saves no scores, so it has nothing to do there.
    """
    B, S, _ = x.shape
    is_cross = cross_x is not None or (cache is not None and cache.get("cross", False))
    scale = cfg.attn_scale or None

    def rope(t):
        return apply_rope(t, positions, cfg.rope_fraction, cfg.rope_theta,
                          cfg.rope_style)

    with _scope(scope, "qkv"):
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        if adapters is not None:
            q = q + lora(x, adapters["q"]["a"], adapters["q"]["b"])
        if "bq" in p:
            q = q + p["bq"].astype(q.dtype)
        seq_ax = _attn_seq_axis(q.shape)
        q = lsc(q, "batch", seq_ax, "heads", "head_dim")

        if positions is None:
            positions = jnp.arange(S)[None, :]

        if not is_cross and cfg.rope_fraction > 0:
            q = rope(q)
        if not is_cross:
            k, v = project_kv(p, x, adapters)
            if cfg.rope_fraction > 0:
                k = rope(k)

    new_cache = cache
    if is_cross:
        if cross_x is not None:  # prefill: build the cross cache
            k, v = project_kv(p, cross_x)
            new_cache = {"k": k, "v": v, "cross": True}
        else:
            k, v = cache["k"], cache["v"]
        if mode == "decode":
            out = decode_attention(q, k, v, jnp.asarray(k.shape[1]), scale)
        else:
            out = (blocked_attention(q, k, v, causal=False, block=kv_block,
                                     scale=scale)
                   if impl != "naive"
                   else naive_attention(q, k, v, causal=False, scale=scale))
    elif mode == "decode":
        with _scope(scope, "attn"):
            dus = jax.lax.dynamic_update_slice_in_dim
            if "k_scale" in cache:                     # int8-quantized cache
                kq, ks = quantize_kv(k)
                vq, vs = quantize_kv(v)
                ck = dus(cache["k"], kq, cache_pos, axis=1)
                cv = dus(cache["v"], vq, cache_pos, axis=1)
                cks = dus(cache["k_scale"], ks.astype(cache["k_scale"].dtype),
                          cache_pos, axis=1)
                cvs = dus(cache["v_scale"], vs.astype(cache["v_scale"].dtype),
                          cache_pos, axis=1)
                ck = lsc(ck, "batch", "kvseq", "kv_heads", "head_dim")
                cv = lsc(cv, "batch", "kvseq", "kv_heads", "head_dim")
                new_cache = dict(cache, k=ck, v=cv, k_scale=cks, v_scale=cvs)
                out = decode_attention_q8(q, ck, cv, cks, cvs, cache_pos + 1,
                                          scale)
            else:
                ck = dus(cache["k"], k.astype(cache["k"].dtype), cache_pos,
                         axis=1)
                cv = dus(cache["v"], v.astype(cache["v"].dtype), cache_pos,
                         axis=1)
                ck = lsc(ck, "batch", "kvseq", "kv_heads", "head_dim")
                cv = lsc(cv, "batch", "kvseq", "kv_heads", "head_dim")
                new_cache = dict(cache, k=ck, v=cv)
                out = decode_attention(q, ck, cv, cache_pos + 1, scale)
    else:  # train / prefill self-attention
        if mode == "prefill":
            new_cache = {"k": k, "v": v, "cross": False}
        with _scope(scope, "attn"):
            if impl == "naive":
                out = naive_attention(q, k, v, causal=causal, scale=scale)
            elif kops.flash_fits(q.shape, k.shape, causal=causal):
                out = kops.flash_attention(q, k, v, causal=causal,
                                           scale=scale)
            else:
                out = blocked_attention(q, k, v, causal=causal,
                                        block=kv_block, scale=scale,
                                        remat=remat_blocks)

    with _scope(scope, "out"):
        out = lsc(out, "batch", seq_ax, "heads", "head_dim")
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
        return lsc(y, "batch", "rseq", "embed"), new_cache
