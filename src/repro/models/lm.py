"""The language models: one class, six families.

``LM`` builds parameter-spec trees, initializes/abstracts them, and provides
the three entry points every (arch x shape) cell lowers:

* ``loss_fn(params, batch)``            — train_4k
* ``prefill_fn(params, batch)``         — prefill_32k (logits + cache)
* ``decode_fn(params, cache, batch)``   — decode_32k / long_500k (1 new token)

Homogeneous stacks (dense / moe / ssm / whisper enc+dec) are ``lax.scan``-ed
over stacked layer parameters (small HLO, fast SPMD partitioning); the zamba2
hybrid uses a python loop (38 layers, heterogeneous: shared attention block
every 6th layer).
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeConfig
from ..parallel.sharding import lsc, lsc_param
from . import params as pr
from .attention import attn_params, attention_block
from .layers import (
    apply_mlp,
    apply_norm,
    embed_params,
    embed_tokens,
    logits_from_hidden,
    mlp_params,
    next_token_loss,
    norm_params,
)
from .moe import apply_moe, moe_params
from .params import P
from .ssm import apply_mamba, mamba_params


def stack_specs(tree, n: int):
    """Prepend a 'layers' axis to every leaf of a layer spec tree."""
    return pr.tree_map(
        lambda p: P((n,) + p.shape, ("layers",) + p.axes, p.init, p.scale), tree)


def constrain_params(param_tree, spec_tree):
    """Pin a (per-layer) parameter tree to its logical sharding INSIDE the
    scan body.  The forward effect is a no-op (params already arrive FSDP-
    sharded and get gathered for the matmuls); the payoff is the TRANSPOSE:
    ``with_sharding_constraint`` is linear, so each layer's weight cotangent
    is constrained to the same FSDP layout — the per-layer grad partial is
    reduce-scattered into its shard instead of all-reduced at full size
    (measured: 94% collective-byte cut on qwen1.5-110b train_4k — see
    EXPERIMENTS.md §Perf iteration 1)."""
    return jax.tree.map(lambda a, p: lsc_param(a, *p.axes), param_tree,
                        spec_tree)


def _sinusoidal(positions: jax.Array, d: int, dtype) -> jax.Array:
    half = d // 2
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


class LM:
    def __init__(self, cfg: ModelConfig, attn_impl: str = "blocked",
                 kv_block: int = 1024, ssd_impl: str = "jnp",
                 kv_cache_dtype: str = "bf16"):
        self.cfg = cfg
        self.attn_impl = attn_impl
        self.kv_block = kv_block
        self.ssd_impl = ssd_impl
        self.kv_cache_dtype = kv_cache_dtype   # 'bf16' | 'int8' (decode)

    # ------------------------------------------------------------- param specs
    def _dense_layer_specs(self) -> dict:
        cfg = self.cfg
        out = {"ln1": norm_params(cfg), "attn": attn_params(cfg),
               "ln2": norm_params(cfg)}
        if cfg.moe is not None:
            out["moe"] = moe_params(cfg)
        else:
            out["mlp"] = mlp_params(cfg)
        return out

    def _encoder_layer_specs(self) -> dict:
        cfg = self.cfg
        return {"ln1": norm_params(cfg), "attn": attn_params(cfg),
                "ln2": norm_params(cfg), "mlp": mlp_params(cfg)}

    def _decoder_xattn_layer_specs(self) -> dict:
        out = self._encoder_layer_specs()
        out["ln_x"] = norm_params(self.cfg)
        out["xattn"] = attn_params(self.cfg)
        return out

    def param_specs(self) -> dict:
        cfg = self.cfg
        specs: dict[str, Any] = {"embed": embed_params(cfg),
                                 "final_norm": norm_params(cfg)}
        if cfg.family == "ssm":
            layer = {"ln": norm_params(cfg), "mamba": mamba_params(cfg)}
            specs["layers"] = stack_specs(layer, cfg.n_layers)
        elif cfg.family == "hybrid":
            layer = {"ln": norm_params(cfg), "mamba": mamba_params(cfg)}
            specs["layers"] = stack_specs(layer, cfg.n_layers)
            specs["shared_attn"] = {
                "ln1": norm_params(cfg), "attn": attn_params(cfg),
                "ln2": norm_params(cfg), "mlp": mlp_params(cfg),
            }
        elif cfg.family == "audio":
            specs["layers"] = stack_specs(self._decoder_xattn_layer_specs(),
                                          cfg.n_layers)
            specs["encoder"] = {
                "layers": stack_specs(self._encoder_layer_specs(),
                                      cfg.n_encoder_layers),
                "final_norm": norm_params(cfg),
            }
        else:  # dense / moe / vlm
            specs["layers"] = stack_specs(self._dense_layer_specs(),
                                          cfg.n_layers)
        return specs

    def abstract_params(self, dtype=jnp.bfloat16):
        return pr.abstract(self.param_specs(), dtype)

    def init(self, key, dtype=jnp.float32):
        return pr.init(self.param_specs(), key, dtype)

    # --------------------------------------------------------------- caches
    def n_shared_invocations(self) -> int:
        cfg = self.cfg
        if cfg.family != "hybrid":
            return 0
        return len(range(0, cfg.n_layers, cfg.shared_attn_every))

    def cache_specs(self, batch: int, max_seq: int, dtype=jnp.bfloat16) -> dict:
        """Cache tree as P-leaves (shape + logical axes) for dry-run specs."""
        cfg = self.cfg
        L = cfg.n_layers
        kv, hd = cfg.n_kv_heads, cfg.head_dim
        kv_axes = ("layers", "batch", "kvseq", "kv_heads", "head_dim")

        q8 = self.kv_cache_dtype == "int8"

        def kv_leaf(seq):
            return P((L, batch, seq, kv, hd), kv_axes, "zeros",
                     dtype="int8" if q8 else None)

        def scale_leaf(seq):
            return P((L, batch, seq, kv), kv_axes[:-1], "zeros",
                     dtype="float16")

        if cfg.family == "ssm":
            s = cfg.ssm
            di, nh = s.d_inner(cfg.d_model), s.n_heads(cfg.d_model)
            gn = s.n_groups * s.d_state
            return {
                "conv_x": P((L, batch, s.d_conv - 1, di),
                            ("layers", "batch", "kwidth", "inner"), "zeros"),
                "conv_B": P((L, batch, s.d_conv - 1, gn),
                            ("layers", "batch", "kwidth", "state"), "zeros"),
                "conv_C": P((L, batch, s.d_conv - 1, gn),
                            ("layers", "batch", "kwidth", "state"), "zeros"),
                "state": P((L, batch, nh, s.head_dim, s.d_state),
                           ("layers", "batch", "ssm_heads", "head_dim", "state"),
                           "zeros"),
            }
        if cfg.family == "hybrid":
            s = cfg.ssm
            di, nh = s.d_inner(cfg.d_model), s.n_heads(cfg.d_model)
            gn = s.n_groups * s.d_state
            ninv = self.n_shared_invocations()
            return {
                "mamba": {
                    "conv_x": P((L, batch, s.d_conv - 1, di),
                                ("layers", "batch", "kwidth", "inner"), "zeros"),
                    "conv_B": P((L, batch, s.d_conv - 1, gn),
                                ("layers", "batch", "kwidth", "state"), "zeros"),
                    "conv_C": P((L, batch, s.d_conv - 1, gn),
                                ("layers", "batch", "kwidth", "state"), "zeros"),
                    "state": P((L, batch, nh, s.head_dim, s.d_state),
                               ("layers", "batch", "ssm_heads", "head_dim",
                                "state"), "zeros"),
                },
                "shared_k": P((ninv, batch, max_seq, kv, hd), kv_axes, "zeros"),
                "shared_v": P((ninv, batch, max_seq, kv, hd), kv_axes, "zeros"),
            }
        if cfg.family == "audio":
            enc_seq = cfg.n_frames
            return {
                "k": kv_leaf(max_seq), "v": kv_leaf(max_seq),
                "xk": P((L, batch, enc_seq, kv, hd), kv_axes, "zeros"),
                "xv": P((L, batch, enc_seq, kv, hd), kv_axes, "zeros"),
            }
        out = {"k": kv_leaf(max_seq), "v": kv_leaf(max_seq)}
        if q8:
            out["k_scale"] = scale_leaf(max_seq)
            out["v_scale"] = scale_leaf(max_seq)
        return out

    def init_cache(self, batch: int, max_seq: int, dtype=jnp.bfloat16):
        return pr.tree_map(lambda p: jnp.zeros(p.shape, p.dtype or dtype),
                           self.cache_specs(batch, max_seq, dtype))

    # --------------------------------------------------------------- forward
    def _embed_inputs(self, params, batch: dict, mode: str) -> jax.Array:
        cfg = self.cfg
        tokens = batch["tokens"]
        with jax.named_scope("embed"):
            x = embed_tokens(params["embed"], tokens, cfg)
        if cfg.family == "vlm" and mode != "decode":
            img = batch["img_embeds"].astype(x.dtype)
            n_img = img.shape[1]
            x = jnp.concatenate([img, x[:, n_img:]], axis=1)
        if cfg.family == "audio":
            B, S = tokens.shape
            pos0 = batch.get("pos", None)
            start = 0 if pos0 is None else pos0
            positions = start + jnp.arange(S)
            x = x + _sinusoidal(positions, cfg.d_model, x.dtype)[None]
        return x

    def _run_encoder(self, params, frames: jax.Array) -> jax.Array:
        cfg = self.cfg
        x = frames + _sinusoidal(jnp.arange(frames.shape[1]), cfg.d_model,
                                 frames.dtype)[None]
        enc_specs = self._encoder_layer_specs()

        def body(h, lp):
            lp = constrain_params(lp, enc_specs)
            a = apply_norm(lp["ln1"], h)
            a, _ = attention_block(lp["attn"], a, cfg, mode="train",
                                   causal=False, impl=self.attn_impl,
                                   kv_block=self.kv_block)
            h = h + a
            f = apply_norm(lp["ln2"], h)
            f = apply_mlp(lp["mlp"], f, cfg.mlp_kind)
            return h + f, None

        if cfg.remat == "full":
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["encoder"]["layers"])
        return apply_norm(params["encoder"]["final_norm"], x)

    def _dense_stack(self, params, x, mode, cache, pos, cross_x):
        """Scan over homogeneous decoder layers (dense/moe/vlm/audio)."""
        cfg = self.cfg
        has_moe = cfg.moe is not None
        has_xattn = cfg.family == "audio"
        B, S = x.shape[:2]
        positions = (jnp.arange(S)[None, :] if pos is None
                     else pos + jnp.zeros((B, 1), jnp.int32))

        layer_specs = (self._decoder_xattn_layer_specs() if has_xattn
                       else self._dense_layer_specs())

        def body(carry, scanned):
            h, aux = carry
            lp, lc = scanned
            lp = constrain_params(lp, layer_specs)
            a_in = apply_norm(lp["ln1"], h)
            new_lc = {}
            self_cache = None
            if lc is not None:
                self_cache = {k: lc[k] for k in
                              ("k", "v", "k_scale", "v_scale") if k in lc}
                self_cache["cross"] = False
            a, kvout = attention_block(
                lp["attn"], a_in, cfg, mode=mode, positions=positions,
                cache=self_cache,
                cache_pos=pos, impl=self.attn_impl, kv_block=self.kv_block)
            h = h + a
            if kvout is not None and mode != "train":
                for kk in ("k", "v", "k_scale", "v_scale"):
                    if kk in kvout:
                        new_lc[kk] = kvout[kk]
            if has_xattn:
                xa_in = apply_norm(lp["ln_x"], h)
                xa, xkv = attention_block(
                    lp["xattn"], xa_in, cfg, mode=mode,
                    cross_x=(cross_x if mode != "decode" else None),
                    cache=(None if lc is None else
                           {"k": lc["xk"], "v": lc["xv"], "cross": True}),
                    impl=self.attn_impl, kv_block=self.kv_block)
                h = h + xa
                if xkv is not None and mode != "train":
                    new_lc["xk"], new_lc["xv"] = xkv["k"], xkv["v"]
            f_in = apply_norm(lp["ln2"], h)
            if has_moe:
                f, a_loss = apply_moe(lp["moe"], f_in, cfg, mode == "train")
                aux = aux + a_loss
            else:
                f = apply_mlp(lp["mlp"], f_in, cfg.mlp_kind)
            h = lsc(h + f, "batch", "rseq", "embed")
            return (h, aux), new_lc

        if (self.cfg.remat == "full") and mode == "train":
            body = jax.checkpoint(body)

        if mode == "train":
            (x, aux), _ = jax.lax.scan(body, (x, 0.0),
                                       (params["layers"], None))
            return x, aux, None
        if mode == "prefill":
            # caches are emitted per layer (k/v of full prefix)
            (x, aux), caches = jax.lax.scan(body, (x, 0.0),
                                            (params["layers"], None))
            return x, aux, caches
        (x, aux), caches = jax.lax.scan(body, (x, 0.0),
                                        (params["layers"], cache))
        return x, aux, caches

    def _ssm_stack(self, params, x, mode, cache, pos):
        cfg = self.cfg
        layer_specs = {"ln": norm_params(cfg), "mamba": mamba_params(cfg)}

        def body(h, scanned):
            lp, lc = scanned
            lp = constrain_params(lp, layer_specs)
            with jax.named_scope("block_norm"):
                a_in = apply_norm(lp["ln"], h)
            a, new_lc = apply_mamba(lp["mamba"], a_in, cfg, mode=mode,
                                    cache=lc, impl=self.ssd_impl)
            with jax.named_scope("block_norm"):
                h = lsc(h + a, "batch", "rseq", "embed")
            return h, new_lc

        if cfg.remat == "full" and mode == "train":
            body = jax.checkpoint(body)
        with jax.named_scope("layers"):
            x, caches = jax.lax.scan(body, x, (params["layers"], cache))
        return x, 0.0, caches

    def _hybrid_stack(self, params, x, mode, cache, pos):
        """zamba2: python loop; shared attn block every k layers."""
        cfg = self.cfg
        every = cfg.shared_attn_every
        sp = constrain_params(
            params["shared_attn"],
            {"ln1": norm_params(cfg), "attn": attn_params(cfg),
             "ln2": norm_params(cfg), "mlp": mlp_params(cfg)})
        B, S = x.shape[:2]
        positions = (jnp.arange(S)[None, :] if pos is None
                     else pos + jnp.zeros((B, 1), jnp.int32))
        new_cache = {"mamba": {k: [] for k in
                               ("conv_x", "conv_B", "conv_C", "state")},
                     "shared_k": [], "shared_v": []} if mode != "train" else None

        def layer(h, lp, lc, inv_cache, use_attn):
            if use_attn:
                a_in = apply_norm(sp["ln1"], h)
                a, kvout = attention_block(
                    sp["attn"], a_in, cfg, mode=mode, positions=positions,
                    cache=inv_cache, cache_pos=pos, impl=self.attn_impl,
                    kv_block=self.kv_block)
                h = h + a
                f_in = apply_norm(sp["ln2"], h)
                h = h + apply_mlp(sp["mlp"], f_in, cfg.mlp_kind)
            else:
                kvout = None
            m_in = apply_norm(lp["ln"], h)
            m, new_lc = apply_mamba(lp["mamba"], m_in, cfg, mode=mode,
                                    cache=lc, impl=self.ssd_impl)
            return h + m, new_lc, kvout

        if cfg.remat == "full" and mode == "train":
            layer = jax.checkpoint(layer, static_argnums=(4,))

        inv = 0
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda a, i=i: a[i], params["layers"])
            use_attn = (i % every == 0)
            lc = None
            inv_cache = None
            if cache is not None:
                lc = jax.tree.map(lambda a, i=i: a[i], cache["mamba"])
                if use_attn:
                    inv_cache = {"k": cache["shared_k"][inv],
                                 "v": cache["shared_v"][inv], "cross": False}
            elif mode == "prefill":
                lc = None
            x, new_lc, kvout = layer(x, lp, lc, inv_cache, use_attn)
            if new_cache is not None:
                if new_lc is not None:
                    for k in new_cache["mamba"]:
                        new_cache["mamba"][k].append(new_lc[k])
                if use_attn and kvout is not None:
                    new_cache["shared_k"].append(kvout["k"])
                    new_cache["shared_v"].append(kvout["v"])
            if use_attn:
                inv += 1

        if new_cache is not None:
            new_cache["mamba"] = {k: jnp.stack(v) for k, v in
                                  new_cache["mamba"].items()}
            new_cache["shared_k"] = jnp.stack(new_cache["shared_k"])
            new_cache["shared_v"] = jnp.stack(new_cache["shared_v"])
        return x, 0.0, new_cache

    def forward(self, params, batch: dict, mode: str, cache=None,
                pos=None):
        """Returns (logits, aux_loss, new_cache)."""
        cfg = self.cfg
        x = self._embed_inputs(params, dict(batch, pos=pos), mode)
        cross_x = None
        if cfg.family == "audio" and mode != "decode":
            cross_x = self._run_encoder(params, batch["frames"])

        if cfg.family == "ssm":
            x, aux, caches = self._ssm_stack(params, x, mode, cache, pos)
        elif cfg.family == "hybrid":
            x, aux, caches = self._hybrid_stack(params, x, mode, cache, pos)
        else:
            x, aux, caches = self._dense_stack(params, x, mode, cache, pos,
                                               cross_x)
        with jax.named_scope("head"):
            x = apply_norm(params["final_norm"], x)
            logits = logits_from_hidden(params["embed"], x, cfg)
        return logits, aux, caches

    # ------------------------------------------------------------ entry points
    def loss_fn(self, params, batch: dict):
        logits, aux, _ = self.forward(params, batch, "train")
        with jax.named_scope("head"):
            loss = next_token_loss(logits, batch["tokens"],
                                   self.cfg.vocab_size)
        return loss + aux, {"ce": loss, "aux": aux}

    def prefill_fn(self, params, batch: dict, max_seq: Optional[int] = None):
        """Returns (last-position logits, cache sized to the prefix)."""
        logits, _, caches = self.forward(params, batch, "prefill")
        return logits[:, -1], caches

    def decode_fn(self, params, cache, batch: dict):
        """batch: {'tokens': (B,1), 'pos': scalar int32}.  One new token."""
        pos = batch["pos"]
        logits, _, new_cache = self.forward(params, batch, "decode",
                                            cache=cache, pos=pos)
        return logits[:, -1], new_cache

    # ------------------------------------------------------------- input specs
    def input_specs(self, shape: ShapeConfig, dtype=jnp.bfloat16) -> dict:
        """ShapeDtypeStruct stand-ins for every model input of this shape."""
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        tok = jax.ShapeDtypeStruct
        if shape.kind == "decode":
            batch = {"tokens": tok((B, 1), jnp.int32),
                     "pos": tok((), jnp.int32)}
        else:
            batch = {"tokens": tok((B, S), jnp.int32)}
        if cfg.family == "vlm" and shape.kind != "decode":
            batch["img_embeds"] = tok((B, cfg.n_img_tokens, cfg.d_model), dtype)
        if cfg.family == "audio" and shape.kind != "decode":
            batch["frames"] = tok((B, cfg.n_frames, cfg.d_model), dtype)
        return batch

    def batch_logical_axes(self, shape: ShapeConfig) -> dict:
        cfg = self.cfg
        out = {"tokens": ("batch", "seq")}
        if shape.kind == "decode":
            out = {"tokens": ("batch", "seq"), "pos": ()}
        if cfg.family == "vlm" and shape.kind != "decode":
            out["img_embeds"] = ("batch", "seq", "embed")
        if cfg.family == "audio" and shape.kind != "decode":
            out["frames"] = ("batch", "frames", "embed")
        return out


def build_model(cfg: ModelConfig, attn_impl: str = "blocked",
                kv_block: int = 1024, ssd_impl: str = "jnp",
                kv_cache_dtype: str = "bf16") -> LM:
    return LM(cfg, attn_impl=attn_impl, kv_block=kv_block, ssd_impl=ssd_impl,
              kv_cache_dtype=kv_cache_dtype)
