"""Flash attention Pallas TPU kernel (online softmax, causal, GQA).

TPU-native design (vs the CUDA flash-attention algorithm):
* the KV axis is the innermost grid dimension — on TPU the grid is executed
  sequentially per core, so VMEM scratch (m, l, acc) carries across KV steps
  (the OoO-window analogue gem5 would model; here it is a software pipeline),
* BlockSpec tiles are (Bq, D) / (Bk, D) with D kept whole — MXU-aligned
  (D in {64, 128, 192, 256}; Bq, Bk multiples of 128 lanes),
* GQA is expressed in the *index map* (query head h reads KV head h // G) —
  no KV repetition through HBM.

Validated in interpret mode against ``ref.flash_attention_ref`` on the CPU;
``tests/test_chip_compile.py`` compiles it for a v5e at chatglm3-6b widths.
Forward only: ``pallas_call`` has no autodiff rule and this kernel defines no
VJP, so it serves prefill, not training.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  seq_k: int):
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale           # (Bq, D)
    k = k_ref[0, 0].astype(jnp.float32)                   # (Bk, D)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Bq, Bk)

    iq = pl.program_id(2)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    valid = kpos < seq_k
    if causal:
        qpos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        valid = valid & (qpos >= kpos)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, block_q: int = 128,
                         block_k: int = 128,
                         interpret: bool) -> jax.Array:
    """q: (B, H, Sq, D); k, v: (B, KVH, Sk, D).  Returns (B, H, Sq, D)."""
    B, H, Sq, D = q.shape
    KVH, Sk = k.shape[1], k.shape[2]
    assert H % KVH == 0
    G = H // KVH
    scale = 1.0 / math.sqrt(D)

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = qp.shape[2] // block_q
    nk = kp.shape[2] // block_k

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_k=Sk)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :Sq]
