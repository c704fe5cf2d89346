"""Flash attention Pallas TPU kernels (online softmax, causal, GQA), forward
and backward.

TPU-native design (vs the CUDA flash-attention algorithm):
* q, k, v and every gradient are passed as (B, S, heads·D), a reshape of
  the models' (B, S, heads, D): head h is the column block h of width D, so
  a (block, D) tile is (8, 128)-aligned for D = 128 and no
  (B, S, H, D) -> (B, H, S, D) transpose surrounds the calls,
* the reduction axis is the innermost grid dimension — on TPU the grid is
  executed sequentially per core, so VMEM scratch (m, l, acc) carries across
  it (the OoO-window analogue gem5 would model; here a software pipeline),
* the (block_q, block_k) scores and probabilities live in VMEM only; the
  forward keeps the per-row log-sum-exp, lane-major as a (1, block_q) row,
  and the backward recomputes the probabilities from it,
* causal tiles wholly above the diagonal are skipped, and their index maps
  are clamped to the last (or first) block needed, so a skipped step issues
  no DMA; the iota mask is applied only on tiles that straddle the diagonal
  or hold padded keys,
* GQA is expressed in the *index maps* (query head h reads KV head h // G),
  and the dK/dV kernel walks a KV head's G query heads as an inner grid axis,
  so their contributions sum in VMEM — no KV repetition through HBM.

The backward is the two-pass flash backward: ``delta = rowsum(dO·O)`` in
jnp, then a dK/dV kernel that walks the query tiles of each key tile (in
transposed form, so the log-sum-exp and delta rows broadcast along lanes),
and a dQ kernel that walks the key tiles of each query tile.  Products run
at JAX's default matmul precision (``ssd_scan._precision``); softmax
statistics and accumulators are float32.

Validated in interpret mode against ``ref.flash_attention_ref`` and the
models' jnp attention on the CPU; ``tests/test_chip_compile.py`` compiles
forward and backward for a v5e.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ssd_scan import _col, _dot, _precision, _row

NEG_INF = -1e30
# scoped VMEM for each kernel call: room for the (512, 1024) f32 score tiles
# and their temporaries; the v5e step was measured with this limit
_VMEM_LIMIT = 64 * 1024 * 1024


def _causal_live(iq, ik, block_q: int, block_k: int):
    """Whether query tile iq sees any key of key tile ik (causal)."""
    return ik * block_k <= iq * block_q + block_q - 1


def _tile_masked(iq, ik, *, causal: bool, block_q: int, block_k: int,
                 seq_k: int):
    """Whether tile (iq, ik) needs the iota mask: it straddles the diagonal,
    or it holds padded keys."""
    masked = False
    if seq_k % block_k:
        masked = (ik + 1) * block_k > seq_k
    if causal:
        masked = jnp.logical_or(
            masked, iq * block_q < ik * block_k + block_k - 1)
    return masked


def _valid(iq, ik, shape, *, causal: bool, block_q: int, block_k: int,
           seq_k: int, transposed: bool):
    """Visible (query, key) pairs of a tile; ``transposed`` tiles are
    (block_k, block_q)."""
    q_ax, k_ax = (1, 0) if transposed else (0, 1)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, k_ax)
    valid = kpos < seq_k
    if causal:
        qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, q_ax)
        valid = valid & (qpos >= kpos)
    return valid


def _run_tile(body, iq, ik, *, causal: bool, block_q: int, block_k: int,
              seq_k: int):
    """``body(masked)`` on a live tile: with the mask where the tile needs
    it, without elsewhere; nothing on a tile above the diagonal."""
    masked = _tile_masked(iq, ik, causal=causal, block_q=block_q,
                          block_k=block_k, seq_k=seq_k)
    if not causal and masked is False:
        body(False)
        return
    live = _causal_live(iq, ik, block_q, block_k) if causal else True
    pl.when(jnp.logical_and(live, masked))(lambda: body(True))
    pl.when(jnp.logical_and(live, jnp.logical_not(masked)))(
        lambda: body(False))


# ------------------------------------------------------------------ forward
def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_ref, l_ref, acc_ref, *, scale: float, causal: bool,
                      block_q: int, block_k: int, seq_k: int, prec):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(masked: bool):
        q = q_ref[0].astype(jnp.float32) * scale               # (Bq, D)
        s = _dot(q, k_ref[0].astype(jnp.float32), ((1,), (1,)), prec)
        if masked:
            s = jnp.where(_valid(iq, ik, s.shape, causal=causal,
                                 block_q=block_q, block_k=block_k,
                                 seq_k=seq_k, transposed=False), s, NEG_INF)
        m_prev = m_ref[...]                                    # (Bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _dot(
            p, v_ref[0].astype(jnp.float32), ((1,), (0,)), prec)
        m_ref[...] = m_new

    _run_tile(body, iq, ik, causal=causal, block_q=block_q, block_k=block_k,
              seq_k=seq_k)

    @pl.when(ik == pl.num_programs(3) - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        eye = (jax.lax.broadcasted_iota(jnp.int32, (block_q, block_q), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (block_q, block_q), 1))
        lse_ref[0, 0] = _row(m_ref[...] + jnp.log(l), eye)


# ------------------------------------------------------------------ backward
def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                      causal: bool, block_q: int, block_k: int, seq_k: int,
                      prec):
    """dK, dV of one key tile of one KV head, summed over the query tiles
    (innermost axis) of each of its G query heads (next axis).  Tiles are
    transposed, (Bk, Bq), so the log-sum-exp and delta rows broadcast."""
    ik, g, iq = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when(jnp.logical_and(g == 0, iq == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(masked: bool):
        q = q_ref[0].astype(jnp.float32) * scale               # (Bq, D)
        k = k_ref[0].astype(jnp.float32)                       # (Bk, D)
        do = do_ref[0].astype(jnp.float32)                     # (Bq, D)
        st = _dot(k, q, ((1,), (1,)), prec)                    # (Bk, Bq)
        if masked:
            st = jnp.where(_valid(iq, ik, st.shape, causal=causal,
                                  block_q=block_q, block_k=block_k,
                                  seq_k=seq_k, transposed=True), st, NEG_INF)
        pt = jnp.exp(st - lse_ref[0, 0])                       # P^T
        dv_acc[...] += _dot(pt, do, ((1,), (0,)), prec)
        dpt = _dot(v_ref[0].astype(jnp.float32), do, ((1,), (1,)), prec)
        dst = pt * (dpt - delta_ref[0, 0])                     # dS^T
        dk_acc[...] += _dot(dst, q, ((1,), (0,)), prec)

    _run_tile(body, iq, ik, causal=causal, block_q=block_q, block_k=block_k,
              seq_k=seq_k)

    @pl.when(jnp.logical_and(g == pl.num_programs(3) - 1,
                             iq == pl.num_programs(4) - 1))
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dq_acc, lse_col, delta_col, *, scale: float,
                     causal: bool, block_q: int, block_k: int, seq_k: int,
                     prec):
    """dQ of one query tile, summed over its key tiles (innermost axis)."""
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        eye = (jax.lax.broadcasted_iota(jnp.int32, (block_q, block_q), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (block_q, block_q), 1))
        lse_col[...] = _col(lse_ref[0, 0], eye)
        delta_col[...] = _col(delta_ref[0, 0], eye)

    def body(masked: bool):
        q = q_ref[0].astype(jnp.float32) * scale               # (Bq, D)
        k = k_ref[0].astype(jnp.float32)                       # (Bk, D)
        s = _dot(q, k, ((1,), (1,)), prec)                     # (Bq, Bk)
        if masked:
            s = jnp.where(_valid(iq, ik, s.shape, causal=causal,
                                 block_q=block_q, block_k=block_k,
                                 seq_k=seq_k, transposed=False), s, NEG_INF)
        p = jnp.exp(s - lse_col[...])
        dp = _dot(do_ref[0].astype(jnp.float32),
                  v_ref[0].astype(jnp.float32), ((1,), (1,)), prec)
        ds = p * (dp - delta_col[...])
        dq_acc[...] += _dot(ds, k, ((1,), (0,)), prec)

    _run_tile(body, iq, ik, causal=causal, block_q=block_q, block_k=block_k,
              seq_k=seq_k)

    @pl.when(ik == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


# ------------------------------------------------------------------ calls
class _Tiling:
    """Block sizes and grid extents of one call's (padded) shapes."""

    def __init__(self, q, k, H, block_q, block_k, causal):
        B, Sqp, HD = q.shape
        self.B, self.H, self.D = B, H, HD // H
        self.KVH = k.shape[2] // self.D
        self.G = H // self.KVH
        self.bq, self.bk = block_q, block_k
        self.nq, self.nk = Sqp // block_q, k.shape[1] // block_k
        self.causal = causal

    def last_k(self, iq):
        """The key tile a query tile's index map stops at (causal)."""
        if not self.causal:
            return self.nk - 1
        return jnp.minimum((iq * self.bq + self.bq - 1) // self.bk,
                           self.nk - 1)

    def first_q(self, ik):
        """The query tile a key tile's index map starts at (causal)."""
        if not self.causal:
            return 0
        return jnp.minimum((ik * self.bk) // self.bq, self.nq - 1)


def _compiler_params(n_parallel: int, n_reduced: int):
    """The grid's leading axes are independent, the trailing ones carry a
    VMEM accumulator."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel
        + ("arbitrary",) * n_reduced,
        vmem_limit_bytes=_VMEM_LIMIT)


def _fwd_call(q, k, v, t: _Tiling, scale, seq_k, interpret):
    bq, bk, D, G = t.bq, t.bk, t.D, t.G

    def kv_map(b, h, iq, ik):
        return b, jnp.minimum(ik, t.last_k(iq)), h // G

    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=t.causal, block_q=bq,
        block_k=bk, seq_k=seq_k, prec=_precision())
    return pl.pallas_call(
        kernel,
        grid=(t.B, t.H, t.nq, t.nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, h, iq, ik: (b, iq, h)),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, h, iq, ik: (b, iq, h)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, h, iq, ik: (b, h, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((t.B, t.H, 1, q.shape[1]), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=_compiler_params(3, 1),
        interpret=interpret,
    )(q, k, v)


def _dkv_call(q, k, v, do, lse, delta, t: _Tiling, scale, seq_k, interpret):
    bq, bk, D, G = t.bq, t.bk, t.D, t.G

    def q_map(b, j, ik, g, iq):
        return b, jnp.maximum(iq, t.first_q(ik)), j * G + g

    def row_map(b, j, ik, g, iq):
        return b, j * G + g, 0, jnp.maximum(iq, t.first_q(ik))

    def kv_map(b, j, ik, g, iq):
        return b, ik, j

    kernel = functools.partial(
        _flash_dkv_kernel, scale=scale, causal=t.causal, block_q=bq,
        block_k=bk, seq_k=seq_k, prec=_precision())
    return pl.pallas_call(
        kernel,
        grid=(t.B, t.KVH, t.nk, G, t.nq),
        in_specs=[
            pl.BlockSpec((1, bq, D), q_map),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bq, D), q_map),
            pl.BlockSpec((1, 1, 1, bq), row_map),
            pl.BlockSpec((1, 1, 1, bq), row_map),
        ],
        out_specs=[pl.BlockSpec((1, bk, D), kv_map)] * 2,
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32)] * 2,
        compiler_params=_compiler_params(3, 2),
        interpret=interpret,
    )(q, k, v, do, lse, delta)


def _dq_call(q, k, v, do, lse, delta, t: _Tiling, scale, seq_k, interpret):
    bq, bk, D, G = t.bq, t.bk, t.D, t.G

    def q_map(b, h, iq, ik):
        return b, iq, h

    def row_map(b, h, iq, ik):
        return b, h, 0, iq

    def kv_map(b, h, iq, ik):
        return b, jnp.minimum(ik, t.last_k(iq)), h // G

    kernel = functools.partial(
        _flash_dq_kernel, scale=scale, causal=t.causal, block_q=bq,
        block_k=bk, seq_k=seq_k, prec=_precision())
    return pl.pallas_call(
        kernel,
        grid=(t.B, t.H, t.nq, t.nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), q_map),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bq, D), q_map),
            pl.BlockSpec((1, 1, 1, bq), row_map),
            pl.BlockSpec((1, 1, 1, bq), row_map),
        ],
        out_specs=pl.BlockSpec((1, bq, D), q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        compiler_params=_compiler_params(3, 1),
        interpret=interpret,
    )(q, k, v, do, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_flat(q, k, v, H, causal, scale, block_q, block_k, seq_k,
                interpret):
    """(B, Sq_p, H·D), (B, Sk_p, KVH·D) padded layouts -> (B, Sq_p, H·D)."""
    t = _Tiling(q, k, H, block_q, block_k, causal)
    return _fwd_call(q, k, v, t, scale, seq_k, interpret)[0]


def _flash_flat_fwd(q, k, v, H, causal, scale, block_q, block_k, seq_k,
                    interpret):
    t = _Tiling(q, k, H, block_q, block_k, causal)
    o, lse = _fwd_call(q, k, v, t, scale, seq_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_flat_bwd(H, causal, scale, block_q, block_k, seq_k, interpret,
                    res, do):
    q, k, v, o, lse = res
    t = _Tiling(q, k, H, block_q, block_k, causal)
    B, Sqp, _ = q.shape
    delta = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32))
                    .reshape(B, Sqp, H, t.D), axis=-1)
    delta = jnp.transpose(delta, (0, 2, 1))[:, :, None, :]   # (B, H, 1, Sq)
    do = do.astype(q.dtype)
    dk, dv = _dkv_call(q, k, v, do, lse, delta, t, scale, seq_k, interpret)
    dq = _dq_call(q, k, v, do, lse, delta, t, scale, seq_k, interpret)
    return dq, dk, dv


_flash_flat.defvjp(_flash_flat_fwd, _flash_flat_bwd)


def _pad_rows(x: jax.Array, mult: int) -> jax.Array:
    pad = (-x.shape[1]) % mult
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 1024,
                    interpret: bool) -> jax.Array:
    """q: (B, Sq, H, D); k, v: (B, Sk, KVH, D), H % KVH == 0.  Returns
    (B, Sq, H, D).  ``scale`` multiplies the scores (default ``D ** -0.5``);
    causal masks key j > query i (no offset).  Differentiable: a custom VJP
    runs the Pallas backward kernels."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    assert H % k.shape[2] == 0
    scale = 1.0 / math.sqrt(D) if scale is None else float(scale)
    block_q, block_k = min(block_q, Sq), min(block_k, Sk)
    qf = _pad_rows(q.reshape(B, Sq, H * D), block_q)
    kf, vf = (_pad_rows(x.reshape(B, Sk, -1), block_k) for x in (k, v))
    out = _flash_flat(qf, kf, vf, H, causal, scale, block_q, block_k, Sk,
                      interpret)
    return out[:, :Sq].reshape(B, Sq, H, D)
