"""Mamba2 SSD intra-chunk Pallas TPU kernel.

Computes, per (batch, chunk, block of heads) grid cell, entirely in VMEM:
  * the intra-chunk quadratic contribution
    ``y[i] = sum_{j<=i} (C_i . B_j) exp(cs_i - cs_j) dt_j x_j``,
  * the per-chunk end state ``S = sum_j exp(cs_last - cs_j) dt_j B_j (x) x_j``.

The cumulative decay ``cs = cumsum(dt * A)``, the chunk decay
``gamma = exp(cs_last)``, the O(nc) inter-chunk recurrence and the rank-1
inter-chunk output correction stay in jnp (``ops.ssd_scan`` composes them):
they are tiny and XLA fuses them well, and JAX differentiates them, so the
kernels never see ``A`` — matching the paper's division of labour between
the simulated pipeline (hot loop) and the surrounding infrastructure.

Layout: x, y and their cotangents are the mixer's own (B, L, H·P) arrays,
head h in lanes [h·P, (h+1)·P); the chunk states are (nc, B, N, H·P) in the
same lane order.  A block covers ``hb`` heads (``_heads_per_block``): a
(Q, hb·P) tile, 128 lanes wide at mamba2's P = 64, so no array is padded
from P to 128 lanes and nothing is transposed into a per-head form.  Inside
the block a static loop visits its heads: head j's (Q, Q) x (Q, hb·P)
products run over the whole tile, which costs the 128-wide MXU what a
(Q, P) one does, and its own lanes are kept (``_pick``); only the
contraction over lanes that makes dM masks dy to them.  The forward also
writes e = exp(cs) over each head's lanes, the factor of the inter-chunk
output, which XLA cannot spread over a head's lanes without a transposing
copy.  Per-position vectors (``dt``, ``cs`` and their cotangents) travel
lane-major as (1, Q) rows, hb of them a block, so every block's last two
dimensions are (8, 128)-aligned or whole, as Mosaic requires.  Inside the
kernel a (Q, 1) column is read off the diagonal of the broadcast row
(exact: it only adds zeros).

B and C stay at their group count G (H % G == 0): the head-block axis is
the grid's innermost, and a block reads the groups of its heads (one group,
or several whole ones), so consecutive blocks of a group hit the same B/C
block and the pipeline fetches it once.  The scores C·Bᵀ are computed once
per group, and the backward forms each group's dB and dC once, from the ds
summed over its heads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


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


def _heads_per_block(H: int, P: int, hpg: int) -> int:
    """Heads in one block: the fewest whose lanes fill whole 128-lane rows
    (hb·P % 128 == 0) and whose block lies inside one B/C group or covers
    whole ones (hb | hpg or hpg | hb); else all H (a block as wide as the
    array, which Mosaic always accepts)."""
    for hb in range(1, H + 1):
        if H % hb == 0 and hb * P % 128 == 0 and (hpg % hb == 0
                                                  or hb % hpg == 0):
            return hb
    return H


def _head_lanes(hb: int, P: int):
    """(1, hb·P) index of the head each lane of a block belongs to."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, hb * P), 1) // P


def _pick(parts, owner):
    """The (rows, hb·P) array whose lanes owned by k come from parts[k]
    (``owner``: each lane's head, or group); a part may be a (rows, 1)
    column, spread over its owner's lanes."""
    out = parts[0]
    for k in range(1, len(parts)):
        out = jnp.where(owner == k, parts[k], out)
    return jnp.broadcast_to(out, (out.shape[0], owner.shape[1]))


def _ssd_chunk_kernel(x_ref, dt_ref, cs_ref, b_ref, c_ref, y_ref, state_ref,
                      e_ref, scores_ref, *, chunk: int, hb: int, P: int,
                      hpg: int, prec):
    # blocks: x (1,Q,hb·P), the block's heads side by side; dt/cs
    # (1,hb,1,Q); b/c (1,gb,Q,N), the groups of the block's heads;
    # scores_ref: (gb, Q, Q) VMEM scratch, carried across a group's blocks.
    # Each head's product runs over the whole block and keeps its own lanes
    x = x_ref[0].astype(jnp.float32)                      # (Q, hb·P)
    eye, tril = _masks(chunk)
    heads = _head_lanes(hb, P)
    gb = scores_ref.shape[0]

    @pl.when(pl.program_id(2) * hb % hpg == 0)            # a group's first
    def _():
        for g in range(gb):
            scores_ref[g] = _dot(c_ref[0, g].astype(jnp.float32),
                                 b_ref[0, g].astype(jnp.float32),
                                 ((1,), (1,)), prec)

    ys, ws, es = [], [], []
    for j in range(hb):
        dt_r = dt_ref[0, j].astype(jnp.float32)           # (1, Q)
        cs_r = cs_ref[0, j].astype(jnp.float32)           # (1, Q)
        cs_c = _col(cs_r, eye)                            # (Q, 1)
        dt_c = _col(dt_r, eye)

        # intra-chunk: M[i,j] = (C_i.B_j) * exp(cs_i - cs_j) * dt_j, j <= i
        M = scores_ref[j // hpg] * _decay(cs_r, cs_c, tril) * dt_r
        ys.append(_dot(M, x, ((1,), (0,)), prec))
        # chunk end state weights exp(cs_last - cs_j) dt_j
        ws.append(jnp.exp(_last(cs_r, chunk) - cs_c) * dt_c)
        es.append(jnp.exp(cs_c))                          # exp(cs_i)
    y_ref[0] = _pick(ys, heads).astype(y_ref.dtype)
    e_ref[0] = _pick(es, heads).astype(e_ref.dtype)

    # chunk end state: sum_j w_j B_j (x) x_j, one product per group
    wx = _pick(ws, heads) * x
    state = _pick([_dot(b_ref[0, g].astype(jnp.float32), wx, ((0,), (0,)),
                        prec) for g in range(gb)], heads // hpg)
    state_ref[0, 0] = state.astype(state_ref.dtype)       # (N, hb·P)


def _ssd_chunk_bwd_kernel(x_ref, dt_ref, cs_ref, b_ref, c_ref,
                          dy_ref, dstate_ref, de_ref,
                          dx_ref, ddt_ref, dcs_ref, db_ref, dc_ref,
                          s_ref, v_ref, *, chunk: int, hb: int, P: int,
                          hpg: int, prec):
    """Intra-chunk SSD backward, entirely in VMEM per (b, c, head block).

    Recomputes Γ/M (flash-attention-style recompute-in-bwd) and forms each
    head's dx, ddt, dcs.  The scores ``s = C·Bᵀ`` are computed once per
    group (``s_ref``); ``V = ds`` is summed over the group's heads
    (``v_ref``) and turned into dC = ΣV·B and dB's intra part ΣVᵀ·C at the
    group's last block.  dB's state-path term accumulates into the group's
    dB block, which stays resident across its blocks.  ``de`` is the
    cotangent of the forward's e = exp(cs).  The cumsum that makes ``cs``,
    the inter-chunk scan and the y_off product are differentiated by JAX
    outside (they are jnp code in ops.ssd_scan)."""
    h = pl.program_id(2)
    x = x_ref[0].astype(jnp.float32)                      # (Q, hb·P)
    dy = dy_ref[0].astype(jnp.float32)                    # (Q, hb·P)
    dstate = dstate_ref[0, 0].astype(jnp.float32)         # (N, hb·P)
    de = de_ref[0].astype(jnp.float32)                    # (Q, hb·P)
    eye, tril = _masks(chunk)
    heads = _head_lanes(hb, P)
    groups = heads // hpg
    gb = s_ref.shape[0]
    last = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1

    @pl.when(h * hb % hpg == 0)                           # a group's first
    def _():
        for g in range(gb):
            s_ref[g] = _dot(c_ref[0, g].astype(jnp.float32),
                            b_ref[0, g].astype(jnp.float32),
                            ((1,), (1,)), prec)
        v_ref[...] = jnp.zeros_like(v_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    # ---- state path: state = B^T diag(w) X, w = exp(cs_last - cs)·dt;
    # R = B·dstate keeps each head's lanes
    R = _pick([_dot(b_ref[0, g].astype(jnp.float32), dstate, ((1,), (0,)),
                    prec) for g in range(gb)], groups)    # (Q, hb·P)
    Rx = R * x
    dxs, ws = [], []
    for j in range(hb):
        g = j // hpg
        on = heads == j                                   # head j's lanes
        dt_r = dt_ref[0, j].astype(jnp.float32)           # (1, Q)
        cs_r = cs_ref[0, j].astype(jnp.float32)           # (1, Q)
        cs_c = _col(cs_r, eye)
        dt_c = _col(dt_r, eye)

        G = _decay(cs_r, cs_c, tril)                      # Γ
        K = s_ref[g] * G                                  # s∘Γ
        M = K * dt_r

        dM = _dot(jnp.where(on, dy, 0.0), x, ((1,), (1,)), prec)  # (Q, Q)
        dxs.append(_dot(M, dy, ((0,), (0,)), prec))       # M^T dy

        U = dM * K                                        # for ddt (÷dt form)
        T1 = U * dt_r                                     # dM∘M
        # Γ path: +row sums at i, -column sums at j
        dcs_c = jnp.sum(T1, axis=1, keepdims=True)        # (Q, 1)
        dcs_r = -jnp.sum(T1, axis=0, keepdims=True)       # (1, Q)
        ddt_r = jnp.sum(U, axis=0, keepdims=True)         # dt_j factor of M

        v_ref[g] += dM * G * dt_r                         # ds, summed

        expw = jnp.exp(_last(cs_r, chunk) - cs_c)         # (Q, 1)
        w = expw * dt_c
        ws.append(w)
        dw = jnp.sum(jnp.where(on, Rx, 0.0), axis=1, keepdims=True)
        dww = dw * w
        dcs_c = dcs_c - dww                               # cs_j path
        # the exp(cs_i) factor of the inter-chunk output
        dcs_c = dcs_c + jnp.exp(cs_c) * jnp.sum(jnp.where(on, de, 0.0),
                                                axis=1, keepdims=True)
        ddt_c = dw * expw                                 # dt_j path
        # cs_last path: every w_j grows with cs_last
        dcs_r = dcs_r + _row(dcs_c, eye) + jnp.where(last, jnp.sum(dww), 0.0)

        ddt_ref[0, j] = (ddt_r + _row(ddt_c, eye)).astype(ddt_ref.dtype)
        dcs_ref[0, j] = dcs_r.astype(dcs_ref.dtype)
    W = _pick(ws, heads)                                  # (Q, hb·P)
    dx_ref[0] = (_pick(dxs, heads) + W * R).astype(dx_ref.dtype)
    wx = W * x
    for g in range(gb):
        wx_g = wx if gb == 1 else jnp.where(groups == g, wx, 0.0)
        db_ref[0, g] += _dot(wx_g, dstate, ((1,), (1,)), prec)

    @pl.when((h + 1) * hb % hpg == 0)                     # a group's last
    def _():
        for g in range(gb):
            V = v_ref[g]
            dc_ref[0, g] = _dot(V, b_ref[0, g].astype(jnp.float32),
                                ((1,), (0,)), prec)
            db_ref[0, g] += _dot(V, c_ref[0, g].astype(jnp.float32),
                                 ((0,), (0,)), prec)


class _Shape:
    """The sizes of one call, read off its operands x (B, Lp, H·P) and B/C
    (B, nc·G, Q, N)."""

    def __init__(self, x, bt, H):
        self.B, Lp, HP = x.shape
        self.Q, self.N = bt.shape[-2:]
        self.H, self.P, self.nc = H, HP // H, Lp // self.Q
        self.G = bt.shape[1] // self.nc
        self.hpg = H // self.G
        self.hb = _heads_per_block(H, self.P, self.hpg)
        self.gb = max(1, self.hb // self.hpg)             # groups a block

    def grid(self):
        return (self.B, self.nc, self.H // self.hb)

    def specs(self, kinds):
        """BlockSpecs over the (B, nc, H / hb) grid: 'x' (Q, hb·P) tiles of a
        (B, Lp, H·P) array; 'state' (N, hb·P) tiles of (nc, B, N, H·P);
        'row' hb (1, Q) rows of (B, nc·H, 1, Q); 'qn' the gb (Q, N) tiles
        of the block's groups in (B, nc·G, Q, N) (a group's blocks share
        one fetch)."""
        Q, N, P, hb, gb = self.Q, self.N, self.P, self.hb, self.gb
        nhb, ngb, hpg = self.H // hb, self.G // gb, self.hpg
        maps = {
            "x": ((1, Q, hb * P), lambda b, c, h: (b, c, h)),
            "state": ((1, 1, N, hb * P), lambda b, c, h: (c, b, 0, h)),
            "row": ((1, hb, 1, Q), lambda b, c, h: (b, c * nhb + h, 0, 0)),
            "qn": ((1, gb, Q, N),
                   lambda b, c, h: (b, c * ngb + h * hb // hpg // gb, 0, 0)),
        }
        return [pl.BlockSpec(*maps[k]) for k in kinds]

    def kernel(self, body):
        return functools.partial(body, chunk=self.Q, hb=self.hb, P=self.P,
                                 hpg=self.hpg, prec=_precision())

    def params(self, n_scratch: int):
        """(gb, Q, Q) f32 scratch and the grid's semantics, shared by both
        calls: the head-block axis is innermost and sequential, so a
        group's scratch and its dB, dC blocks carry from one of its blocks
        to the next."""
        return dict(
            scratch_shapes=[pltpu.VMEM((self.gb, self.Q, self.Q),
                                       jnp.float32)] * n_scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")))


def _chunks_fwd_impl(x, dtt, cst, bt, ct, H, interpret: bool):
    sh = _Shape(x, bt, H)
    return pl.pallas_call(
        sh.kernel(_ssd_chunk_kernel),
        grid=sh.grid(),
        in_specs=sh.specs(("x", "row", "row", "qn", "qn")),
        out_specs=sh.specs(("x", "state", "x")),
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((sh.nc, sh.B, sh.N, x.shape[-1]),
                                 jnp.float32),
            jax.ShapeDtypeStruct(x.shape, jnp.float32),
        ],
        **sh.params(1),                   # scores
        interpret=interpret,
    )(x, dtt, cst, bt, ct)


def ssd_chunk_bwd_pallas(x, dtt, cst, bt, ct, dy, dstate, de, H, *,
                         interpret: bool):
    """Backward pass over (B, nc, H / hb) blocks.  Layouts match
    ssd_chunks_flat's.  Returns (dx, ddt, dcs, db, dc), with db and dc at
    the group count."""
    sh = _Shape(x, bt, H)
    return pl.pallas_call(
        sh.kernel(_ssd_chunk_bwd_kernel),
        grid=sh.grid(),
        in_specs=sh.specs(("x", "row", "row", "qn", "qn", "x", "state",
                           "x")),
        out_specs=sh.specs(("x", "row", "row", "qn", "qn")),
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(dtt.shape, jnp.float32),
            jax.ShapeDtypeStruct(cst.shape, jnp.float32),
            jax.ShapeDtypeStruct(bt.shape, jnp.float32),
            jax.ShapeDtypeStruct(ct.shape, jnp.float32),
        ],
        **sh.params(2),                   # scores, summed ds
        interpret=interpret,
    )(x, dtt, cst, bt, ct, dy, dstate, de)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def ssd_chunks_flat(x, dtt, cst, bt, ct, H, interpret):
    """Intra-chunk pass on x (B, Lp, H·P), rows (B, nc·H, 1, Q) and B/C
    (B, nc·G, Q, N), with a Pallas backward (pallas_call has no autodiff
    rule; the custom VJP recomputes Γ/M in VMEM, flash-attention-style).
    Returns (y (B, Lp, H·P), states (nc, B, N, H·P), e (B, Lp, H·P))."""
    return _chunks_fwd_impl(x, dtt, cst, bt, ct, H, interpret)


def _chunks_fwd(x, dtt, cst, bt, ct, H, interpret):
    out = _chunks_fwd_impl(x, dtt, cst, bt, ct, H, interpret)
    return out, (x, dtt, cst, bt, ct)


def _chunks_bwd(H, interpret, res, cts):
    x, dtt, cst, bt, ct = res
    dy, dstates, de = cts
    dx, ddt, dcs, db, dc = ssd_chunk_bwd_pallas(
        x, dtt, cst, bt, ct, dy.astype(x.dtype),
        dstates.astype(jnp.float32), de.astype(jnp.float32), H,
        interpret=interpret)
    return (dx.astype(x.dtype), ddt.astype(dtt.dtype), dcs.astype(cst.dtype),
            db.astype(bt.dtype), dc.astype(ct.dtype))


ssd_chunks_flat.defvjp(_chunks_fwd, _chunks_bwd)


def ssd_chunk_pallas(x: jax.Array, dt: jax.Array, cs: jax.Array,
                     Bm: jax.Array, Cm: jax.Array, *, interpret: bool):
    """Intra-chunk SSD pass.

    x: (B, Lp, H·P), the mixer's layout, Lp = nc·Q; dt: (B, nc, Q, H)
    (post-softplus, fp32-ok); cs: (B, nc, Q, H) inclusive cumsum of dt·A
    within each chunk; Bm, Cm: (B, nc, Q, G, N) at the group count,
    H % G == 0 (head h reads group h // (H // G)).
    Returns (y_diag (B, Lp, H·P), states (nc, B, N, H·P), e (B, Lp, H·P)),
    each head's state in its own P lanes, and e = exp(cs), the factor of
    the inter-chunk output, over each head's lanes.
    Differentiable (custom VJP -> Pallas backward kernel).
    """
    B, nc, Q, H = dt.shape
    G, N = Bm.shape[-2:]
    # per-position rows: (Q,) lane-major per (b, c, h) cell; with one
    # group, B and C only move a unit axis (a free reshape)
    dtt = jnp.transpose(dt, (0, 1, 3, 2)).reshape(B, nc * H, 1, Q)
    cst = jnp.transpose(cs, (0, 1, 3, 2)).reshape(B, nc * H, 1, Q)
    bt = jnp.transpose(Bm, (0, 1, 3, 2, 4)).reshape(B, nc * G, Q, N)
    ct = jnp.transpose(Cm, (0, 1, 3, 2, 4)).reshape(B, nc * G, Q, N)
    return ssd_chunks_flat(x, dtt, cst, bt, ct, H, interpret)
