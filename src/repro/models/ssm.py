"""Mamba2 / SSD (state-space duality) block [arXiv:2405.21060].

Train/prefill use the chunked SSD algorithm (quadratic within Q-length
chunks, linear state passing across chunks); decode uses the O(1) recurrence.
The pure-jnp chunked path below is the dry-run/lowering path and the oracle
for the ``kernels.ssd_scan`` Pallas kernel.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..parallel.sharding import current_rules, lsc
from .params import P


def ssd_pallas_sharded(x, dt, A, Bm, Cm, chunk, initial_state=None):
    """SSD scan through the Pallas kernel, shard_mapped over the mesh.

    x: (B,S,H,P); dt: (B,S,H); A: (H,); Bm/Cm: (B,S,G,N) at the group count.
    Batch rides ('pod','data'), heads ride 'model'; the sequence stays whole
    per shard (the inter-chunk recurrence is sequential).  B and C are
    replicated on 'model': with one group every shard's heads share it.
    Where heads are split over devices and G > 1, a shard's heads need not
    cover whole groups, so B and C are broadcast to the heads first and
    shard with them.  pallas_call has no SPMD partitioning rule, so
    shard_map supplies the per-device view — the production pattern for
    custom kernels.  Outside a rules context the kernel runs unsharded
    (tests, single-host training).
    """
    from ..kernels import ops as kops

    rules = current_rules()
    if rules is None:
        return kops.ssd_scan(x, dt.astype(x.dtype), A, Bm, Cm, chunk=chunk,
                             initial_state=initial_state)
    mesh = rules.mesh
    x_spec = rules.act_spec(("batch", "seq", "ssm_heads", "head_dim"),
                            x.shape)
    dt_spec = rules.act_spec(("batch", "seq", "ssm_heads"), dt.shape)
    a_spec = rules.act_spec(("ssm_heads",), A.shape)
    heads_on = x_spec[2] or ()
    head_shards = math.prod(mesh.shape[a] for a in (
        (heads_on,) if isinstance(heads_on, str) else heads_on))
    group_axis = None
    if Bm.shape[2] > 1 and head_shards > 1:
        hpg = x.shape[2] // Bm.shape[2]
        Bm, Cm = (jnp.repeat(a, hpg, axis=2) for a in (Bm, Cm))
        group_axis = "ssm_heads"
    b_spec = rules.act_spec(("batch", "seq", group_axis, "state"), Bm.shape)
    st_spec = rules.act_spec(("batch", "ssm_heads", "head_dim", "state"),
                             (x.shape[0], x.shape[2], x.shape[3],
                              Bm.shape[-1]))

    if initial_state is None:
        def run(xl, dtl, al, bl, cl):
            return kops.ssd_scan(xl, dtl, al, bl, cl, chunk=chunk)

        return jax.shard_map(
            run, mesh=mesh,
            in_specs=(x_spec, dt_spec, a_spec, b_spec, b_spec),
            out_specs=(x_spec, st_spec), check_vma=False,
        )(x, dt.astype(x.dtype), A, Bm, Cm)

    def run_init(xl, dtl, al, bl, cl, sl):
        return kops.ssd_scan(xl, dtl, al, bl, cl, chunk=chunk,
                             initial_state=sl)

    return jax.shard_map(
        run_init, mesh=mesh,
        in_specs=(x_spec, dt_spec, a_spec, b_spec, b_spec, st_spec),
        out_specs=(x_spec, st_spec), check_vma=False,
    )(x, dt.astype(x.dtype), A, Bm, Cm, initial_state)


def mamba_params(cfg: ModelConfig) -> dict:
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    gn = s.n_groups * s.d_state
    return {
        "wz": P((d, di), ("embed", "inner")),
        "wx": P((d, di), ("embed", "inner")),
        "wB": P((d, gn), ("embed", "state")),
        "wC": P((d, gn), ("embed", "state")),
        "wdt": P((d, nh), ("embed", "ssm_heads")),
        "conv_x_w": P((di, s.d_conv), ("inner", "kwidth"), "conv"),
        "conv_x_b": P((di,), ("inner",), "zeros"),
        "conv_B_w": P((gn, s.d_conv), ("state", "kwidth"), "conv"),
        "conv_B_b": P((gn,), ("state",), "zeros"),
        "conv_C_w": P((gn, s.d_conv), ("state", "kwidth"), "conv"),
        "conv_C_b": P((gn,), ("state",), "zeros"),
        "dt_bias": P((nh,), ("ssm_heads",), "dt_bias"),
        "A_log": P((nh,), ("ssm_heads",), "a_log"),
        "D": P((nh,), ("ssm_heads",), "ones"),
        "norm": P((di,), ("inner",), "ones"),
        "out_proj": P((di, d), ("inner", "embed")),
    }


def causal_conv(u: jax.Array, w: jax.Array, b: jax.Array,
                cache: Optional[jax.Array] = None):
    """Depthwise causal conv.  u: (B,S,C), w: (C,K).  Returns (y, new_cache)
    where new_cache holds the last K-1 inputs."""
    Bsz, S, C = u.shape
    K = w.shape[1]
    if cache is None:
        up = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        up = jnp.concatenate([cache.astype(u.dtype), u], axis=1)
    y = jnp.zeros_like(u)
    for k in range(K):
        y = y + up[:, k:k + S, :] * w[:, k].astype(u.dtype)
    y = jax.nn.silu(y + b.astype(u.dtype))
    return y, up[:, -(K - 1):, :]


def _segsum(cs: jax.Array) -> jax.Array:
    """cs: (..., Q) inclusive cumsum of dA.  Returns (..., Q, Q) matrix
    T[i, j] = cs[i] - cs[j] for i >= j, -inf otherwise."""
    Q = cs.shape[-1]
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                Cm: jax.Array, chunk: int,
                initial_state: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.

    x: (B, L, H, P); dt: (B, L, H) (post-softplus); A: (H,) negative;
    Bm, Cm: (B, L, G, N) with H % G == 0.
    Returns (y (B, L, H, P), final_state (B, H, P, N)).
    """
    Bsz, L, H, Pd = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    hpg = H // G
    Q = min(chunk, L)
    pad = (-L) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Lp = L + pad
    nc = Lp // Q

    xc = x.reshape(Bsz, nc, Q, H, Pd)
    dtc = dt.reshape(Bsz, nc, Q, H).astype(jnp.float32)
    Bc = Bm.reshape(Bsz, nc, Q, G, N)
    Cc = Cm.reshape(Bsz, nc, Q, G, N)

    dA = dtc * A.astype(jnp.float32)                      # (B,nc,Q,H)
    cs = jnp.cumsum(dA, axis=2)                           # inclusive

    # ---- intra-chunk (quadratic within chunk)
    Lmat = jnp.exp(_segsum(jnp.moveaxis(cs, -1, -2)))     # (B,nc,H,Q,Q)
    scores = jnp.einsum("bcigs,bcjgs->bcgij", Cc, Bc,
                        preferred_element_type=jnp.float32)  # (B,nc,G,Q,Q)
    scores = jnp.repeat(scores, hpg, axis=2)              # (B,nc,H,Q,Q)
    M = scores * Lmat * jnp.moveaxis(dtc, -1, -2)[..., None, :]
    y_diag = jnp.einsum("bchij,bcjhp->bcihp", M.astype(x.dtype), xc)

    # ---- per-chunk end states: sum_j exp(cs_last - cs_j) dt_j B_j (x) x_j
    decay_st = jnp.exp(cs[:, :, -1:, :] - cs) * dtc       # (B,nc,Q,H)
    Bh = jnp.repeat(Bc, hpg, axis=3)                      # (B,nc,Q,H,N)
    S_c = jnp.einsum("bcjh,bcjhn,bcjhp->bchpn",
                     decay_st.astype(x.dtype), Bh.astype(x.dtype), xc)

    # ---- inter-chunk recurrence over nc (linear)
    gamma = jnp.exp(cs[:, :, -1, :])                      # (B,nc,H) chunk decay

    def step(carry, inp):
        s_c, g = inp                                      # (B,H,P,N), (B,H)
        new = carry * g[..., None, None].astype(carry.dtype) + s_c
        return new, carry                                 # emit state ENTERING chunk

    init = (jnp.zeros((Bsz, H, Pd, N), x.dtype) if initial_state is None
            else initial_state.astype(x.dtype))
    final_state, prev_states = jax.lax.scan(
        step, init,
        (jnp.moveaxis(S_c, 1, 0), jnp.moveaxis(gamma, 1, 0).astype(x.dtype)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)         # (B,nc,H,P,N)

    # ---- inter-chunk contribution: exp(cs_i) * C_i . prev_state
    Ch = jnp.repeat(Cc, hpg, axis=3)                      # (B,nc,Q,H,N)
    y_off = jnp.einsum("bcihn,bchpn->bcihp", Ch.astype(x.dtype), prev_states)
    y_off = y_off * jnp.exp(cs)[..., None].astype(x.dtype)

    y = (y_diag + y_off).reshape(Bsz, Lp, H, Pd)[:, :L]
    return y, final_state


def ssd_decode_step(state: jax.Array, x: jax.Array, dt: jax.Array,
                    A: jax.Array, Bm: jax.Array, Cm: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """One-token recurrence.  state: (B,H,P,N); x: (B,H,P); dt: (B,H);
    Bm, Cm: (B,G,N).  Returns (y (B,H,P), new_state)."""
    H = x.shape[1]
    G = Bm.shape[1]
    hpg = H // G
    dtf = dt.astype(jnp.float32)
    dA = jnp.exp(dtf * A.astype(jnp.float32))             # (B,H)
    Bh = jnp.repeat(Bm, hpg, axis=1)                      # (B,H,N)
    Ch = jnp.repeat(Cm, hpg, axis=1)
    upd = (dtf[..., None] * Bh.astype(jnp.float32))[:, :, None, :] \
        * x.astype(jnp.float32)[..., None]                # (B,H,P,N)
    new_state = state.astype(jnp.float32) * dA[..., None, None] + upd
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch.astype(jnp.float32))
    return y.astype(x.dtype), new_state.astype(state.dtype)


def apply_mamba(p: dict, x_in: jax.Array, cfg: ModelConfig, *, mode: str,
                cache: Optional[dict] = None, impl: str = "jnp"):
    """Full Mamba2 mixer.  x_in: (B, S, d).  Returns (out, new_cache).
    ``impl``: 'jnp' (chunked XLA path, the oracle) or 'pallas' (VMEM-tiled
    kernel via shard_map — the §Perf-tuned production path)."""
    from .layers import rms_norm_gated

    s = cfg.ssm
    Bsz, S, d = x_in.shape
    di = s.d_inner(d)
    nh = s.n_heads(d)
    G, N, Pd = s.n_groups, s.d_state, s.head_dim

    with jax.named_scope("mixer.in_proj"):
        z = jnp.einsum("bsd,de->bse", x_in, p["wz"])
        xr = jnp.einsum("bsd,de->bse", x_in, p["wx"])
        Br = jnp.einsum("bsd,de->bse", x_in, p["wB"])
        Cr = jnp.einsum("bsd,de->bse", x_in, p["wC"])
        dt_raw = jnp.einsum("bsd,de->bse", x_in, p["wdt"])
    xr = lsc(xr, "batch", "seq", "inner")

    cx = cache.get("conv_x") if cache else None
    cB = cache.get("conv_B") if cache else None
    cC = cache.get("conv_C") if cache else None
    with jax.named_scope("mixer.conv"):
        xr, ncx = causal_conv(xr, p["conv_x_w"], p["conv_x_b"], cx)
        Br, ncB = causal_conv(Br, p["conv_B_w"], p["conv_B_b"], cB)
        Cr, ncC = causal_conv(Cr, p["conv_C_w"], p["conv_C_b"], cC)
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                             + p["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(p["A_log"].astype(jnp.float32))

    # shard SSD heads on 'model': the (B, nc, H, Q, Q) intra-chunk matrices
    # (the memory hot-spot the Pallas kernel tiles away) ride the tensor axis
    xh = lsc(xr.reshape(Bsz, S, nh, Pd), "batch", "seq", "ssm_heads",
             "head_dim")
    dt = lsc(dt, "batch", "seq", "ssm_heads")
    Bm = Br.reshape(Bsz, S, G, N)
    Cm = Cr.reshape(Bsz, S, G, N)

    if mode == "decode":
        assert S == 1
        y, new_state = ssd_decode_step(
            cache["state"], xh[:, 0], dt[:, 0].astype(x_in.dtype),
            A, Bm[:, 0], Cm[:, 0])
        y = y[:, None]                                     # (B,1,H,P)
        new_cache = dict(cache, conv_x=ncx, conv_B=ncB, conv_C=ncC,
                         state=new_state)
    else:
        init = cache["state"] if cache else None
        if impl == "pallas":
            with jax.named_scope("mixer.ssd_chunk"):
                y, final_state = ssd_pallas_sharded(xh, dt, A, Bm, Cm,
                                                    s.chunk,
                                                    initial_state=init)
        else:
            y, final_state = ssd_chunked(xh, dt, A, Bm, Cm, s.chunk, init)
        new_cache = None
        if mode == "prefill":
            new_cache = {"conv_x": ncx, "conv_B": ncB, "conv_C": ncC,
                         "state": final_state}

    with jax.named_scope("mixer.gate"):
        y = y + xh * p["D"].astype(y.dtype)[:, None]
        y = y.reshape(Bsz, S, di)
        y = rms_norm_gated(y, p["norm"], z, cfg.norm_eps)
    with jax.named_scope("mixer.out_proj"):
        out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    return lsc(out, "batch", "rseq", "embed"), new_cache


def init_mamba_cache(cfg: ModelConfig, batch: int, dtype) -> dict:
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    gn = s.n_groups * s.d_state
    return {
        "conv_x": jnp.zeros((batch, s.d_conv - 1, di), dtype),
        "conv_B": jnp.zeros((batch, s.d_conv - 1, gn), dtype),
        "conv_C": jnp.zeros((batch, s.d_conv - 1, gn), dtype),
        "state": jnp.zeros((batch, nh, s.head_dim, s.d_state), dtype),
    }
