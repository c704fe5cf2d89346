"""Model-substrate numerics: attention paths, MoE routing, SSD modes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow        # jax-compiling numerics sweeps

from repro.configs import ARCHS, reduced_config
from repro.configs.base import ModelConfig, MoEConfig
from repro.models.attention import (blocked_attention, decode_attention,
                                    naive_attention)
from repro.models.moe import apply_moe, capacity, moe_params
from repro.models import params as pr
from repro.models.layers import apply_mlp
from repro.models.ssm import apply_mamba


# ---------------------------------------------------------------- attention
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("sq,sk,h,kvh,block", [
    (64, 64, 4, 4, 16),
    (64, 64, 4, 1, 64),
    (32, 128, 8, 2, 48),         # block not dividing sk (padding)
    (1, 96, 4, 2, 32),           # single query row
])
def test_blocked_vs_naive(sq, sk, h, kvh, block, causal, key):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (2, sq, h, 32), jnp.float32)
    k = jax.random.normal(k2, (2, sk, kvh, 32), jnp.float32)
    v = jax.random.normal(k3, (2, sk, kvh, 32), jnp.float32)
    off = sk - sq if causal else 0
    out = blocked_attention(q, k, v, causal=causal, q_offset=off, block=block)
    want = naive_attention(q, k, v, causal=causal, q_offset=off)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def _attn_cfg(head_dim):
    return ModelConfig(name="t", family="dense", n_layers=1, d_model=32,
                       n_heads=4, n_kv_heads=2, d_head=head_dim, d_ff=64,
                       vocab_size=64)


@pytest.mark.parametrize("case,runs_kernel", [
    ("train", True),
    ("prefill", True),
    ("cross", False),
    ("decode", False),
    ("not_causal", False),
    ("head_dim_64", False),
    ("mesh_rules", False),
    ("not_tpu", False),
])
def test_blocked_runs_the_flash_kernel_only_where_it_fits(case, runs_kernel,
                                                           monkeypatch, key):
    """attention_block's blocked path calls the flash kernel for causal
    self-attention in train and prefill, on a TPU (patched in here), with
    whole 128-lane heads and no mesh rules; elsewhere the jnp core."""
    from repro.kernels import ops
    from repro.launch.mesh import make_host_mesh
    from repro.models.attention import attention_block, attn_params
    from repro.parallel.sharding import make_rules, use_rules

    monkeypatch.setattr(ops, "_interpret", lambda: case == "not_tpu")
    calls = []

    def kernel(q, k, v, *, causal, scale):
        calls.append(q.shape)
        return naive_attention(q, k, v, causal=causal, scale=scale)

    monkeypatch.setattr(ops, "flash_attention", kernel)
    cfg = _attn_cfg(64 if case == "head_dim_64" else 128)
    p = pr.init(attn_params(cfg), key)
    x = jax.random.normal(key, (1, 16, 32), jnp.float32)
    kw = dict(mode="train", causal=case != "not_causal")
    if case == "prefill":
        kw["mode"] = "prefill"
    elif case == "cross":
        kw["cross_x"] = jax.random.normal(key, (1, 8, 32), jnp.float32)
    elif case == "decode":
        cache = {n: jnp.zeros((1, 16, 2, 128)) for n in ("k", "v")}
        kw.update(mode="decode", cache=cache, cache_pos=jnp.asarray(3))
        x = x[:, :1]
    rules = make_rules(make_host_mesh(1, 1)) if case == "mesh_rules" else None
    with use_rules(rules):
        attention_block(p, x, cfg, **kw)
    assert bool(calls) == runs_kernel


def test_flash_fits_needs_one_length(monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    q = (1, 4096, 32, 128)
    assert ops.flash_fits(q, q, causal=True)
    assert not ops.flash_fits(q, (1, 2048, 32, 128), causal=True)


def test_decode_attention_matches_naive_row(key):
    """decode of position p == row p of causal naive attention."""
    B, S, H, KVH, D = 2, 16, 4, 2, 32
    k1, k2, k3 = jax.random.split(key, 3)
    q_all = jax.random.normal(k1, (B, S, H, D), jnp.float32)
    k = jax.random.normal(k2, (B, S, KVH, D), jnp.float32)
    v = jax.random.normal(k3, (B, S, KVH, D), jnp.float32)
    full = naive_attention(q_all, k, v, causal=True)
    p = 7
    out = decode_attention(q_all[:, p:p + 1], k, v, jnp.asarray(p + 1))
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(full[:, p]),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------- MoE
def _moe_cfg(E=4, top_k=2, cf=2.0, shared=0, kind="swiglu"):
    return ModelConfig(
        name="t", family="moe", n_layers=1, d_model=32, n_heads=2,
        n_kv_heads=2, d_ff=64, vocab_size=64, mlp_kind=kind,
        moe=MoEConfig(n_experts=E, top_k=top_k, capacity_factor=cf,
                      n_shared_experts=shared))


def test_moe_output_finite_and_aux_positive(key):
    cfg = _moe_cfg()
    p = pr.init(moe_params(cfg), key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, 32))
    out, aux = apply_moe(p, x, cfg, train=True)
    assert out.shape == x.shape
    assert jnp.isfinite(out).all()
    assert float(aux) > 0.0


def test_moe_single_expert_equals_dense(key):
    """E=1 top-1 with capacity >= T must equal a plain MLP of that expert."""
    cfg = _moe_cfg(E=1, top_k=1, cf=float(1))
    # capacity rounds to >= T automatically with cf=1, E=1
    p = pr.init(moe_params(cfg), key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, 32))
    out, _ = apply_moe(p, x, cfg, train=False)
    dense_p = {"wi_gate": p["wi_gate"][0], "wi_up": p["wi_up"][0],
               "wo": p["wo"][0]}
    want = apply_mlp(dense_p, x, "swiglu")       # gate prob == 1 for E=1
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_moe_capacity_drops_tokens(key):
    """With tiny capacity, combine weights of dropped tokens are zero —
    output rows for dropped tokens come out as zero (plus shared expert)."""
    cfg = _moe_cfg(E=2, top_k=1, cf=0.1)
    T = 64
    C = capacity(T, cfg)
    assert C < T // 2
    p = pr.init(moe_params(cfg), key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, T, 32))
    out, _ = apply_moe(p, x, cfg, train=False)
    zero_rows = int(jnp.sum(jnp.all(jnp.abs(out[0]) < 1e-9, axis=-1)))
    assert zero_rows >= T - 2 * C


def test_moe_shared_expert_added(key):
    cfg_ns = _moe_cfg(shared=0)
    cfg_sh = _moe_cfg(shared=1)
    p = pr.init(moe_params(cfg_sh), key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, 8, 32))
    out_sh, _ = apply_moe(p, x, cfg_sh, train=False)
    p_ns = {k: v for k, v in p.items() if not k.startswith("shared")}
    out_ns, _ = apply_moe(p_ns, x, cfg_ns, train=False)
    shared = {"wi_gate": p["shared_wi_gate"], "wi_up": p["shared_wi_up"],
              "wo": p["shared_wo"]}
    want = out_ns + apply_mlp(shared, x, "swiglu")
    np.testing.assert_allclose(np.asarray(out_sh), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_moe_decode_single_token_group(key):
    cfg = _moe_cfg()
    p = pr.init(moe_params(cfg), key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 1, 32))
    out, _ = apply_moe(p, x, cfg, train=False)
    assert out.shape == (4, 1, 32)
    assert jnp.isfinite(out).all()


# --------------------------------------------------------------------- SSD
def test_mamba_prefill_then_decode_matches_full(key):
    cfg = reduced_config(ARCHS["mamba2-1.3b"])
    m = pr.init({"m": __import__("repro.models.ssm", fromlist=["mamba_params"]
                                 ).mamba_params(cfg)}, key)["m"]
    B, S = 1, 12
    x = 0.3 * jax.random.normal(jax.random.fold_in(key, 1),
                                (B, S, cfg.d_model), jnp.float32)
    full, _ = apply_mamba(m, x, cfg, mode="train")

    pre, cache = apply_mamba(m, x[:, :8], cfg, mode="prefill")
    np.testing.assert_allclose(np.asarray(pre), np.asarray(full[:, :8]),
                               rtol=2e-3, atol=2e-3)
    outs = []
    for t in range(8, S):
        y, cache = apply_mamba(m, x[:, t:t + 1], cfg, mode="decode",
                               cache=cache)
        outs.append(y[:, 0])
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full[:, 8:]),
                               rtol=5e-3, atol=5e-3)
