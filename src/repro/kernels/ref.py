"""Pure-jnp oracles for every Pallas kernel (independent formulations)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from .stream import EXPRS, _DTYPES


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        scale: Optional[float] = None) -> jax.Array:
    """Naive O(S^2) attention.  q: (B,H,Sq,D); k,v: (B,KVH,Sk,D); the
    scores scaled by ``scale`` (default ``D ** -0.5``)."""
    B, H, Sq, D = q.shape
    KVH, Sk = k.shape[1], k.shape[2]
    G = H // KVH
    kr = jnp.repeat(k, G, axis=1)
    vr = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32))
    s = s / math.sqrt(D) if scale is None else s * scale
    if causal:
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(Sk)[None, :]
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vr.astype(jnp.float32))
    return out.astype(q.dtype)


def ssd_ref(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
            Cm: jax.Array, initial_state: Optional[jax.Array] = None):
    """Sequential (token-by-token) SSD recurrence — the ground truth the
    chunked algorithm and the Pallas kernel must reproduce.

    x: (B,L,H,P); dt: (B,L,H); A: (H,); Bm,Cm: (B,L,H,N), one per head
    (groups repeated to their heads).
    Returns (y (B,L,H,P), final_state (B,H,P,N)).
    """
    Bsz, L, H, P = x.shape
    N = Bm.shape[-1]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    Bf = Bm.astype(jnp.float32)
    Cf = Cm.astype(jnp.float32)

    def step(state, t):
        xt, dtt, bt, ct = t
        da = jnp.exp(dtt * Af)                              # (B,H)
        upd = (dtt[..., None] * bt)[:, :, None, :] * xt[..., None]
        state = state * da[..., None, None] + upd           # (B,H,P,N)
        y = jnp.einsum("bhpn,bhn->bhp", state, ct)
        return state, y

    init = (jnp.zeros((Bsz, H, P, N), jnp.float32) if initial_state is None
            else initial_state.astype(jnp.float32))
    final, ys = jax.lax.scan(
        step, init,
        (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
         jnp.moveaxis(Bf, 1, 0), jnp.moveaxis(Cf, 1, 0)))
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype)
    return y, final.astype(x.dtype)


def elementwise_ref(name: str, x1: jax.Array, x2: Optional[jax.Array] = None,
                    y0: Optional[jax.Array] = None) -> jax.Array:
    fn, n_in, din, dout = EXPRS[name]
    if x2 is None:
        x2 = x1
    if y0 is None:
        y0 = jnp.zeros(x1.shape, _DTYPES[dout])
    return fn(x1, x2, y0).astype(_DTYPES[dout])


def stream_triad_ref(a: jax.Array, b: jax.Array, scalar: float = 3.0):
    return a + scalar * b
