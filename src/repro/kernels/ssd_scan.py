"""Mamba2 SSD intra-chunk Pallas TPU kernel.

Computes, per (batch, head, chunk) grid cell, entirely in VMEM:
  * the intra-chunk quadratic contribution
    ``y[i] = sum_{j<=i} (C_i . B_j) exp(cs_i - cs_j) dt_j x_j``,
  * the per-chunk end state ``S = sum_j exp(cs_last - cs_j) dt_j B_j (x) x_j``.

The cumulative decay ``cs = cumsum(dt * A)``, the chunk decay
``gamma = exp(cs_last)``, the O(nc) inter-chunk recurrence and the rank-1
inter-chunk output correction stay in jnp (``ops.ssd_scan`` composes them):
they are tiny and XLA fuses them well, and JAX differentiates them, so the
kernels never see ``A`` — matching the paper's division of labour between
the simulated pipeline (hot loop) and the surrounding infrastructure.

Block shapes: (Q, P) and (Q, N) tiles with Q=chunk (128/256) — MXU-aligned
on the (Q, Q) score matmul and the (N, P) state outer product.  Per-position
vectors (``dt``, ``cs`` and their cotangents) travel lane-major as (1, Q)
rows, so every block's last two dimensions are (8, 128)-aligned or whole,
as Mosaic requires.  Inside the kernel a (Q, 1) column is read off the
diagonal of the broadcast row (exact: it only adds zeros).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _precision():
    """In-kernel dots follow JAX's default matmul precision: full f32 under
    ``jax.default_matmul_precision("highest")``, Mosaic's default otherwise."""
    prec = jax.config.jax_default_matmul_precision
    return jax.lax.Precision.HIGHEST if prec in ("highest", "float32") \
        else None


def _dot(a, b, dims, prec):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=prec,
                               preferred_element_type=jnp.float32)


def _masks(chunk: int):
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return ii == jj, ii >= jj


def _col(row, eye):
    """(1, Q) row -> (Q, 1) column, read off the diagonal."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col, eye):
    """(Q, 1) column -> (1, Q) row, read off the diagonal."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _last(row, chunk: int):
    """(1, 1) last entry of a (1, Q) row (a masked lane reduction: Mosaic
    cannot broadcast a sliced-out lane)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    return jnp.sum(jnp.where(lane == chunk - 1, row, 0.0), axis=1,
                   keepdims=True)


def _decay(cs_r, cs_c, tril):
    """Γ[i, j] = exp(cs_i - cs_j) for j <= i, else 0 (the exponent is
    masked first, so the upper triangle never overflows)."""
    return jnp.where(tril, jnp.exp(jnp.where(tril, cs_c - cs_r, 0.0)), 0.0)


def _ssd_chunk_kernel(x_ref, dt_ref, cs_ref, b_ref, c_ref, y_ref, state_ref,
                      *, chunk: int, prec):
    # blocks: x (1,1,Q,P), dt/cs (1,1,1,Q), b/c (1,1,Q,N)
    x = x_ref[0, 0].astype(jnp.float32)                   # (Q, P)
    dt_r = dt_ref[0, 0].astype(jnp.float32)               # (1, Q)
    cs_r = cs_ref[0, 0].astype(jnp.float32)               # (1, Q)
    Bm = b_ref[0, 0].astype(jnp.float32)                  # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)                  # (Q, N)
    eye, tril = _masks(chunk)
    cs_c = _col(cs_r, eye)                                # (Q, 1)
    dt_c = _col(dt_r, eye)

    # intra-chunk: M[i,j] = (C_i.B_j) * exp(cs_i - cs_j) * dt_j, j <= i
    scores = _dot(Cm, Bm, ((1,), (1,)), prec)             # (Q, Q)
    M = scores * _decay(cs_r, cs_c, tril) * dt_r
    y_ref[0, 0] = _dot(M, x, ((1,), (0,)), prec).astype(y_ref.dtype)

    # chunk end state: sum_j exp(cs_last - cs_j) dt_j B_j (x) x_j -> (N, P)
    w_c = jnp.exp(_last(cs_r, chunk) - cs_c) * dt_c       # (Q, 1)
    state = _dot(Bm * w_c, x, ((0,), (0,)), prec)
    state_ref[0, 0] = state.astype(state_ref.dtype)


def _ssd_chunk_bwd_kernel(x_ref, dt_ref, cs_ref, b_ref, c_ref,
                          dy_ref, dstate_ref,
                          dx_ref, ddt_ref, dcs_ref, db_ref, dc_ref, *,
                          chunk: int, prec):
    """Intra-chunk SSD backward, entirely in VMEM per (b, c·h) block.

    Recomputes Γ/s/M (flash-attention-style recompute-in-bwd), then forms
    the five cotangents with ~8 (Q,Q)/(Q,N)/(Q,P) matmuls.  The cumsum
    that makes ``cs``, the inter-chunk scan and the y_off term are
    differentiated by JAX outside (they are jnp code in ops.ssd_scan)."""
    x = x_ref[0, 0].astype(jnp.float32)                   # (Q, P)
    dt_r = dt_ref[0, 0].astype(jnp.float32)               # (1, Q)
    cs_r = cs_ref[0, 0].astype(jnp.float32)               # (1, Q)
    Bm = b_ref[0, 0].astype(jnp.float32)                  # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)
    dy = dy_ref[0, 0].astype(jnp.float32)                 # (Q, P)
    dstate = dstate_ref[0, 0].astype(jnp.float32)         # (N, P)
    eye, tril = _masks(chunk)
    cs_c = _col(cs_r, eye)
    dt_c = _col(dt_r, eye)

    G = _decay(cs_r, cs_c, tril)                          # Γ
    s = _dot(Cm, Bm, ((1,), (1,)), prec)
    K = s * G                                             # s∘Γ
    M = K * dt_r

    dM = _dot(dy, x, ((1,), (1,)), prec)                  # (Q, Q)
    dx = _dot(M, dy, ((0,), (0,)), prec)                  # M^T dy

    U = dM * K                                            # for ddt (÷dt form)
    T1 = U * dt_r                                         # dM∘M
    # Γ path: +row sums at i, -column sums at j
    dcs_c = jnp.sum(T1, axis=1, keepdims=True)            # (Q, 1)
    dcs_r = -jnp.sum(T1, axis=0, keepdims=True)           # (1, Q)
    ddt_r = jnp.sum(U, axis=0, keepdims=True)             # dt_j factor of M

    V = dM * G * dt_r                                     # ds
    dc = _dot(V, Bm, ((1,), (0,)), prec)
    db = _dot(V, Cm, ((0,), (0,)), prec)

    # ---- state path: state = B^T diag(w) X, w = exp(cs_last - cs)·dt
    expw = jnp.exp(_last(cs_r, chunk) - cs_c)             # (Q, 1)
    w = expw * dt_c
    R = _dot(Bm, dstate, ((1,), (0,)), prec)              # (Q, P)
    dx = dx + w * R
    dw = jnp.sum(R * x, axis=1, keepdims=True)            # (Q, 1)
    dww = dw * w
    db = db + _dot(w * x, dstate, ((1,), (1,)), prec)
    dcs_c = dcs_c - dww                                   # cs_j path
    ddt_c = dw * expw                                     # dt_j path
    # cs_last path: every w_j grows with cs_last
    last = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    dcs_r = dcs_r + _row(dcs_c, eye) + jnp.where(last, jnp.sum(dww), 0.0)

    dx_ref[0, 0] = dx.astype(dx_ref.dtype)
    ddt_ref[0, 0] = (ddt_r + _row(ddt_c, eye)).astype(ddt_ref.dtype)
    dcs_ref[0, 0] = dcs_r.astype(dcs_ref.dtype)
    db_ref[0, 0] = db.astype(db_ref.dtype)
    dc_ref[0, 0] = dc.astype(dc_ref.dtype)


def _specs(Q, P, N, kinds):
    """BlockSpecs over the (B, CH) grid: 'qp'/'qn'/'np' tiles and 'row'
    (1, Q) per-position vectors."""
    shapes = {"qp": (Q, P), "qn": (Q, N), "np": (N, P), "row": (1, Q)}
    return [pl.BlockSpec((1, 1) + shapes[k], lambda b, ch: (b, ch, 0, 0))
            for k in kinds]


def _chunks_fwd_impl(xt, dtt, cst, bt, ct, interpret: bool):
    B, CH, Q, P = xt.shape
    N = bt.shape[-1]
    kernel = functools.partial(_ssd_chunk_kernel, chunk=Q, prec=_precision())
    return pl.pallas_call(
        kernel,
        grid=(B, CH),
        in_specs=_specs(Q, P, N, ("qp", "row", "row", "qn", "qn")),
        out_specs=_specs(Q, P, N, ("qp", "np")),
        out_shape=[
            jax.ShapeDtypeStruct((B, CH, Q, P), xt.dtype),
            jax.ShapeDtypeStruct((B, CH, N, P), jnp.float32),
        ],
        interpret=interpret,
    )(xt, dtt, cst, bt, ct)


def ssd_chunk_bwd_pallas(xt, dtt, cst, bt, ct, dy, dstate, *,
                         interpret: bool):
    """Backward pass over (B, CH) blocks.  Layouts match ssd_chunk_pallas's
    internal (B, CH, Q, -) form.  Returns (dx, ddt, dcs, db, dc)."""
    B, CH, Q, P = xt.shape
    N = bt.shape[-1]
    kernel = functools.partial(_ssd_chunk_bwd_kernel, chunk=Q,
                               prec=_precision())
    return pl.pallas_call(
        kernel,
        grid=(B, CH),
        in_specs=_specs(Q, P, N, ("qp", "row", "row", "qn", "qn", "qp",
                                  "np")),
        out_specs=_specs(Q, P, N, ("qp", "row", "row", "qn", "qn")),
        out_shape=[
            jax.ShapeDtypeStruct((B, CH, Q, P), xt.dtype),
            jax.ShapeDtypeStruct((B, CH, 1, Q), jnp.float32),
            jax.ShapeDtypeStruct((B, CH, 1, Q), jnp.float32),
            jax.ShapeDtypeStruct((B, CH, Q, N), jnp.float32),
            jax.ShapeDtypeStruct((B, CH, Q, N), jnp.float32),
        ],
        interpret=interpret,
    )(xt, dtt, cst, bt, ct, dy, dstate)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def ssd_chunks_flat(xt, dtt, cst, bt, ct, interpret):
    """(B, CH=nc·H, Q, -) layout intra-chunk pass with a Pallas backward
    (pallas_call has no autodiff rule; the custom VJP recomputes Γ/M in
    VMEM, flash-attention-style)."""
    return _chunks_fwd_impl(xt, dtt, cst, bt, ct, interpret)


def _chunks_fwd(xt, dtt, cst, bt, ct, interpret):
    out = _chunks_fwd_impl(xt, dtt, cst, bt, ct, interpret)
    return out, (xt, dtt, cst, bt, ct)


def _chunks_bwd(interpret, res, cts):
    xt, dtt, cst, bt, ct = res
    dy, dstates = cts
    dx, ddt, dcs, db, dc = ssd_chunk_bwd_pallas(
        xt, dtt, cst, bt, ct, dy.astype(xt.dtype),
        dstates.astype(jnp.float32), interpret=interpret)
    return (dx.astype(xt.dtype), ddt.astype(dtt.dtype), dcs.astype(cst.dtype),
            db.astype(bt.dtype), dc.astype(ct.dtype))


ssd_chunks_flat.defvjp(_chunks_fwd, _chunks_bwd)


def ssd_chunk_pallas(x: jax.Array, dt: jax.Array, cs: jax.Array,
                     Bm: jax.Array, Cm: jax.Array, *, interpret: bool):
    """Intra-chunk SSD pass.

    x: (B, nc, Q, H, P); dt: (B, nc, Q, H) (post-softplus, fp32-ok);
    cs: (B, nc, Q, H) inclusive cumsum of dt·A within each chunk;
    Bm, Cm: (B, nc, Q, H, N) (already broadcast from groups).
    Returns (y_diag (B,nc,Q,H,P), states (B,nc,H,N,P)).
    Differentiable (custom VJP -> Pallas backward kernel).
    """
    B, nc, Q, H, P = x.shape
    N = Bm.shape[-1]
    # rearrange to put (Q, feature) in the last two dims per (b, c, h) cell
    xt = jnp.transpose(x, (0, 1, 3, 2, 4)).reshape(B, nc * H, Q, P)
    dtt = jnp.transpose(dt, (0, 1, 3, 2)).reshape(B, nc * H, 1, Q)
    cst = jnp.transpose(cs, (0, 1, 3, 2)).reshape(B, nc * H, 1, Q)
    bt = jnp.transpose(Bm, (0, 1, 3, 2, 4)).reshape(B, nc * H, Q, N)
    ct = jnp.transpose(Cm, (0, 1, 3, 2, 4)).reshape(B, nc * H, Q, N)

    y, states = ssd_chunks_flat(xt, dtt, cst, bt, ct, interpret)
    y = jnp.transpose(y.reshape(B, nc, H, Q, P), (0, 1, 3, 2, 4))
    return y, states.reshape(B, nc, H, N, P)
