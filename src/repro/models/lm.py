"""The language models: one class, six families.

``LM`` builds parameter-spec trees, initializes/abstracts them, and provides
the three entry points every (arch x shape) cell lowers:

* ``loss_fn(params, batch)``            — train_4k
* ``prefill_fn(params, batch)``         — prefill_32k (logits + cache)
* ``decode_fn(params, cache, batch)``   — decode_32k / long_500k (1 new token)

Homogeneous stacks (dense / moe / ssm / whisper enc+dec) are ``lax.scan``-ed
over stacked layer parameters (small HLO, fast SPMD partitioning).  The
zamba2 hybrid stacks its Mamba-2 layers the same way and scans each run of
them between invocations of its one shared transformer block; the block
reads ``[h; e]`` (``e`` the embedding output) and its output, through the
invocation's own linear, joins that layer's Mamba input (``_hybrid_stack``).
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
    apply_shared_mlp,
    embed_params,
    embed_tokens,
    logits_from_hidden,
    mlp_params,
    next_token_loss,
    norm_params,
    shared_mlp_params,
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


def split_layers(tree, cuts):
    """A stacked tree cut along its layer axis before each index of
    ``cuts``: a list of trees, in order."""
    leaves, treedef = jax.tree.flatten(tree)
    parts = [jnp.split(a, cuts) for a in leaves]
    return [jax.tree.unflatten(treedef, [p[i] for p in parts])
            for i in range(len(cuts) + 1)]


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

    def _shared_specs(self) -> dict:
        """The one shared block (zamba2): the norm over [h; e], attention
        from that width, the pre-MLP norm and the gated MLP."""
        cfg = self.cfg
        return {"ln_in": {"scale": P((cfg.attn_in_dim,), ("embed",), "ones")},
                "attn": attn_params(cfg), "ln_ff": norm_params(cfg),
                "mlp": shared_mlp_params(cfg)}

    def _invocation_specs(self) -> dict:
        """One invocation's own weights: rank-r adapters (x A) B on q, k, v
        (if ``attn_adapters``) and on the MLP's gate/up, and the linear that
        maps the block's output into the Mamba layer's input."""
        cfg = self.cfg
        r, hd, d = cfg.adapter_rank, cfg.head_dim, cfg.d_model

        def adapter(n_in, out, axes):
            return {"a": P((n_in, r), ("embed", None)),
                    "b": P((r,) + out, (None,) + axes)}

        out = {}
        if cfg.attn_adapters:
            a = cfg.attn_in_dim
            out["q"] = adapter(a, (cfg.n_heads, hd), ("heads", "head_dim"))
            out["k"] = adapter(a, (cfg.n_kv_heads, hd),
                               ("kv_heads", "head_dim"))
            out["v"] = adapter(a, (cfg.n_kv_heads, hd),
                               ("kv_heads", "head_dim"))
        out["gate_up"] = adapter(d, (2, cfg.d_ff), (None, "mlp"))
        out["linear"] = P((d, d), ("embed", None))
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
            specs["shared"] = self._shared_specs()
            specs["invocations"] = stack_specs(self._invocation_specs(),
                                               self.n_shared_invocations())
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
        return len(cfg.hybrid_layer_ids) if cfg.family == "hybrid" else 0

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

    def _mamba_body(self, mode):
        """The scan body of a Mamba-2 layer: ``h + Mamba(norm(u))``, with
        ``u = h`` unless the caller gives the layer another input."""
        cfg = self.cfg
        layer_specs = {"ln": norm_params(cfg), "mamba": mamba_params(cfg)}

        def body(h, scanned, u=None):
            lp, lc = scanned
            lp = constrain_params(lp, layer_specs)
            with jax.named_scope("block_norm"):
                a_in = apply_norm(lp["ln"], h if u is None else u,
                                  cfg.norm_eps)
            a, new_lc = apply_mamba(lp["mamba"], a_in, cfg, mode=mode,
                                    cache=lc, impl=self.ssd_impl)
            with jax.named_scope("block_norm"):
                h = lsc(h + a, "batch", "rseq", "embed")
            return h, new_lc

        return body

    def _ssm_stack(self, params, x, mode, cache, pos):
        cfg = self.cfg
        body = self._mamba_body(mode)
        if cfg.remat == "full" and mode == "train":
            body = jax.checkpoint(body)
        with jax.named_scope("layers"):
            x, caches = jax.lax.scan(body, x, (params["layers"], cache))
        return x, 0.0, caches

    def _shared_block(self, sp, ip, h, e, mode, positions, kv_cache, pos):
        """zamba2's shared transformer block on ``[h; e]`` with invocation
        weights ``ip``.  Returns (its output, the attention's new K/V)."""
        cfg = self.cfg
        with jax.named_scope("shared.in"):
            u = apply_norm(sp["ln_in"], jnp.concatenate([h, e], axis=-1),
                           cfg.norm_eps)
        a, kv = attention_block(
            sp["attn"], u, cfg, mode=mode, positions=positions,
            cache=kv_cache, cache_pos=pos, impl=self.attn_impl,
            kv_block=self.kv_block,
            adapters=ip if cfg.attn_adapters else None, scope="shared",
            remat_blocks=mode == "train" and cfg.remat == "full")
        with jax.named_scope("shared.mlp"):
            n = apply_norm(sp["ln_ff"], a, cfg.norm_eps)
            t = apply_shared_mlp(sp["mlp"], ip["gate_up"], n, cfg.mlp_kind)
        return t, kv

    def _hybrid_stack(self, params, x, mode, cache, pos):
        """zamba2: the Mamba-2 layers in runs, each run one ``lax.scan`` of
        ``_mamba_body``; hybrid layer i (``cfg.hybrid_layer_ids``, invocation
        j) computes ``h + Mamba_i(norm(h + shared_j(h, e) W_j))``."""
        cfg = self.cfg
        ids = cfg.hybrid_layer_ids
        e = x
        B, S = x.shape[:2]
        positions = (jnp.arange(S)[None, :] if pos is None
                     else pos + jnp.zeros((B, 1), jnp.int32))
        remat = cfg.remat == "full" and mode == "train"
        body = self._mamba_body(mode)
        scan_body = jax.checkpoint(body) if remat else body
        sp = constrain_params(params["shared"], self._shared_specs())

        def hybrid(h, e, lp, lc, sp, ip, kv_cache):
            t, kv = self._shared_block(sp, ip, h, e, mode, positions,
                                       kv_cache, pos)
            with jax.named_scope("shared.link"):
                u = h + jnp.einsum("bsd,de->bse", t, ip["linear"])
            h, new_lc = body(h, (lp, lc), u)
            return h, new_lc, kv

        if remat:
            hybrid = jax.checkpoint(hybrid)

        # runs of plain layers and single hybrid layers, in order
        cuts = sorted({c for i in ids for c in (i, i + 1)} - {cfg.n_layers})
        n = len(cuts) + 1
        with jax.named_scope("layers"):
            pieces = split_layers(params["layers"], cuts)
            lcs = (split_layers(cache["mamba"], cuts) if cache is not None
                   else [None] * n)
        new_lcs, new_k, new_v = [], [], []
        for start, lp, lc in zip([0] + cuts, pieces, lcs):
            if start not in ids:
                with jax.named_scope("layers"):
                    x, nlc = jax.lax.scan(scan_body, x, (lp, lc))
                new_lcs.append(nlc)
                continue
            j = ids.index(start)
            with jax.named_scope("layers"):
                ip = jax.tree.map(lambda a: a[j], params["invocations"])
                lp, lc = jax.tree.map(lambda a: a[0], (lp, lc))
            kv_cache = None
            if cache is not None:
                kv_cache = {"k": cache["shared_k"][j],
                            "v": cache["shared_v"][j], "cross": False}
            x, nlc, kv = hybrid(x, e, lp, lc, sp, ip, kv_cache)
            if mode != "train":
                new_lcs.append(jax.tree.map(lambda a: a[None], nlc))
                new_k.append(kv["k"])
                new_v.append(kv["v"])
        if mode == "train":
            return x, 0.0, None
        new_cache = {"mamba": jax.tree.map(lambda *a: jnp.concatenate(a),
                                           *new_lcs),
                     "shared_k": jnp.stack(new_k),
                     "shared_v": jnp.stack(new_v)}
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
            x = apply_norm(params["final_norm"], x, cfg.norm_eps)
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
