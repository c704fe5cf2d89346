"""Public jit'd wrappers for the Pallas kernels.

This module is the one place that decides how a kernel runs: through Mosaic
on a TPU, and in interpret mode (the kernel body executes as traced python —
correct semantics, no Mosaic) on any other backend, such as the CPU the
tests run on.  The kernels themselves take ``interpret`` with no default.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..parallel.sharding import current_rules
from . import flash_attention as _fa
from . import ssd_scan as _ssd
from . import stream as _stream


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "scale"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    scale: Optional[float] = None) -> jax.Array:
    """(B, S, H, D)-layout flash attention (matches models.attention), with
    its Pallas backward."""
    return _fa.flash_attention(q, k, v, causal=causal, scale=scale,
                               interpret=_interpret())


def flash_fits(q_shape, k_shape, *, causal: bool) -> bool:
    """Whether attention the models would run blocked runs as the flash
    kernel instead: causal self-attention (q and k of one length; the
    models' self-attention has no query offset) with whole 128-lane heads,
    on a TPU, outside a mesh's sharding rules (``pallas_call`` has no
    partitioning rule, and the kernel has no ``shard_map`` wrapper yet).
    Elsewhere the jnp blocked core runs."""
    return (not _interpret() and current_rules() is None and causal
            and q_shape[1] == k_shape[1] and q_shape[-1] % 128 == 0)


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, chunk: int = 128,
             initial_state: Optional[jax.Array] = None):
    """Full SSD scan = Pallas intra-chunk kernel + jnp inter-chunk recurrence.

    x: (B,L,H,P); dt: (B,L,H) post-softplus; A: (H,); Bm, Cm: (B,L,G,N)
    at the group count, H % G == 0.  Returns (y, final_state (B,H,P,N)).
    x and y pass through the kernel as (B, L, H·P), a free reshape of the
    mixer's arrays, and the chunk states as (nc, B, N, H·P): head h owns
    lanes [h·P, (h+1)·P) of every one of them.
    """
    B, L, H, P = x.shape
    G, N = Bm.shape[-2:]
    hpg = H // G
    Q = min(chunk, L)
    pad = (-L) % Q
    x = x.reshape(B, L, H * P)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Lp = L + pad
    nc = Lp // Q
    dtc = dt.reshape(B, nc, Q, H)
    Bc = Bm.reshape(B, nc, Q, G, N)
    Cc = Cm.reshape(B, nc, Q, G, N)

    dA = dtc.astype(jnp.float32) * A.astype(jnp.float32)
    cs = jnp.cumsum(dA, axis=2)                            # (B,nc,Q,H)
    y_diag, states, e_in = _ssd.ssd_chunk_pallas(
        x, dtc, cs, Bc, Cc, interpret=_interpret())

    # inter-chunk recurrence (linear in nc)
    def step(carry, inp):
        s_c, g = inp                                       # (B,N,H·P), (B,H·P)
        new = carry * g[:, None, :] + s_c
        return new, carry

    with jax.named_scope("mixer.ssd_state"):
        gamma = jnp.repeat(jnp.exp(cs[:, :, -1]), P, axis=-1)  # (B,nc,H·P)
        init = (jnp.zeros((B, N, H * P), jnp.float32) if initial_state is None
                else jnp.transpose(initial_state, (0, 3, 1, 2)).reshape(
                    B, N, H * P).astype(jnp.float32))
        final, prev = jax.lax.scan(step, init,
                                   (states, jnp.moveaxis(gamma, 1, 0)))

        # inter-chunk output: exp(cs_i) * C_i . prev_state, one
        # (Q,N)x(N,hpg·P) product per group, straight into (B, Lp, H·P),
        # scaled by the kernel's e_in = exp(cs_i) over each head's lanes
        y_off = jnp.einsum("bcign,cbngk->bcigk", Cc.astype(jnp.float32),
                           prev.reshape(nc, B, N, G, hpg * P))
        y_off = y_off.reshape(B, Lp, H * P) * e_in

        y = (y_diag.astype(jnp.float32) + y_off)[:, :L].reshape(B, L, H, P)
        final = jnp.transpose(final.reshape(B, N, H, P), (0, 2, 3, 1))
        return y.astype(x.dtype), final.astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("name", "block"))
def elementwise(name: str, x1: jax.Array, x2: Optional[jax.Array] = None,
                y0: Optional[jax.Array] = None, block: int = 2048):
    return _stream.elementwise(name, x1, x2, y0, block=block,
                               interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block",))
def stream_triad(a: jax.Array, b: jax.Array, scalar: float = 3.0,
                 block: int = 8192):
    return _stream.stream_triad(a, b, scalar, block=block,
                                interpret=_interpret())
