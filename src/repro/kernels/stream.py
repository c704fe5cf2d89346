"""The paper's evaluation kernels as Pallas TPU kernels.

Table 1's 28 single-core kernels (basic arithmetic / type conversion /
numeric / mathematical) plus Stream Triad (§5.2).  Each is a blocked
elementwise Pallas kernel with explicit VMEM tiling — the TPU analogue of
the paper's 8-wide SVE SIMD loops (here the VPU's (8, 128) vregs).

These serve three roles:
 1. paper-faithful reproduction of the evaluation workload (Figs 3-5),
 2. calibration targets for ``core.calibrate`` (simulator vs measured, the
    paper's test-chip comparison),
 3. simple, sweep-friendly kernels for the per-kernel allclose test suite.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

C0 = 1.6180339887  # the paper's scalar constant (value irrelevant)
LOG2_10 = 3.321928094887362


def _exp10(x):
    return jnp.exp2(x * LOG2_10)


# name -> (fn(x1, x2, y), n_inputs, in_dtype, out_dtype)
# Fortran semantics: aint=trunc, nint=round-to-int, anint=round-to-float,
# sign(a,b)=|a|*sgn(b), mod(a,b)=a-int(a/b)*b (Fortran MOD, not modulo).
EXPRS: dict[str, tuple[Callable, int, str, str]] = {
    "add":   (lambda a, b, y: a + b, 2, "f8", "f8"),
    "sub":   (lambda a, b, y: a - b, 2, "f8", "f8"),
    "mul":   (lambda a, b, y: a * b, 2, "f8", "f8"),
    "fma":   (lambda a, b, y: y + C0 * a, 1, "f8", "f8"),
    "div":   (lambda a, b, y: a / b, 2, "f8", "f8"),
    "rev":   (lambda a, b, y: 1.0 / a, 1, "f8", "f8"),
    "sqrt":  (lambda a, b, y: jnp.sqrt(a), 1, "f8", "f8"),
    "f2d":   (lambda a, b, y: a.astype(jnp.float64), 1, "f4", "f8"),
    "i2d":   (lambda a, b, y: a.astype(jnp.float64), 1, "i4", "f8"),
    "d2f":   (lambda a, b, y: a.astype(jnp.float32), 1, "f8", "f4"),
    "d2i":   (lambda a, b, y: a.astype(jnp.int32), 1, "f8", "i4"),
    "aint":  (lambda a, b, y: jnp.trunc(a), 1, "f8", "f8"),
    "nint":  (lambda a, b, y: jnp.rint(a).astype(jnp.int32), 1, "f8", "i4"),
    "anint": (lambda a, b, y: jnp.rint(a), 1, "f8", "f8"),
    "abs":   (lambda a, b, y: jnp.abs(a), 1, "f8", "f8"),
    "max":   (lambda a, b, y: jnp.maximum(a, b), 2, "f8", "f8"),
    "min":   (lambda a, b, y: jnp.minimum(a, b), 2, "f8", "f8"),
    "mod":   (lambda a, b, y: a - jnp.trunc(a / b) * b, 2, "f8", "f8"),
    "sign":  (lambda a, b, y: jnp.copysign(jnp.abs(a), b), 2, "f8", "f8"),
    "atan":  (lambda a, b, y: jnp.arctan(a), 1, "f8", "f8"),
    "atan2": (lambda a, b, y: jnp.arctan2(a, b), 2, "f8", "f8"),
    "cos":   (lambda a, b, y: jnp.cos(a), 1, "f8", "f8"),
    "sin":   (lambda a, b, y: jnp.sin(a), 1, "f8", "f8"),
    "exp":   (lambda a, b, y: jnp.exp(a), 1, "f8", "f8"),
    "exp10": (lambda a, b, y: _exp10(a), 1, "f8", "f8"),
    "log":   (lambda a, b, y: jnp.log(a), 1, "f8", "f8"),
    "log10": (lambda a, b, y: jnp.log10(a), 1, "f8", "f8"),
    "pwr":   (lambda a, b, y: jnp.exp(b * jnp.log(a)), 2, "f8", "f8"),
}

_DTYPES = {"f8": jnp.float64, "f4": jnp.float32, "i4": jnp.int32,
           "bf16": jnp.bfloat16}


def dtypes_for(name: str):
    fn, n_in, din, dout = EXPRS[name]
    return _DTYPES[din], _DTYPES[dout]


def _ew_kernel(x1_ref, x2_ref, yin_ref, y_ref, *, fn):
    y_ref[...] = fn(x1_ref[...], x2_ref[...], yin_ref[...]).astype(y_ref.dtype)


def elementwise(name: str, x1: jax.Array, x2: Optional[jax.Array] = None,
                y0: Optional[jax.Array] = None, *, block: int = 2048,
                interpret: bool) -> jax.Array:
    """Run one Table-1 kernel.  1-D inputs; blocked over ``block`` lanes."""
    fn, n_in, din, dout = EXPRS[name]
    n = x1.shape[0]
    block = min(block, n)
    assert n % block == 0, (n, block)
    grid = (n // block,)
    if x2 is None:
        x2 = x1
    if y0 is None:
        y0 = jnp.zeros(n, _DTYPES[dout])

    spec = pl.BlockSpec((block,), lambda i: (i,))
    out = pl.pallas_call(
        functools.partial(_ew_kernel, fn=fn),
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n,), _DTYPES[dout]),
        interpret=interpret,
    )(x1, x2, y0)
    return out


def _triad_kernel(a_ref, b_ref, y_ref, *, scalar: float):
    y_ref[...] = a_ref[...] + scalar * b_ref[...]


def stream_triad(a: jax.Array, b: jax.Array, scalar: float = 3.0, *,
                 block: int = 8192, interpret: bool) -> jax.Array:
    """y = a + scalar * b (STREAM Triad), blocked HBM->VMEM tiles."""
    n = a.shape[0]
    block = min(block, n)
    assert n % block == 0
    spec = pl.BlockSpec((block,), lambda i: (i,))
    return pl.pallas_call(
        functools.partial(_triad_kernel, scalar=scalar),
        grid=(n // block,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n,), a.dtype),
        interpret=interpret,
    )(a, b)
