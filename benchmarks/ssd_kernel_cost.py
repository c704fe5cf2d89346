"""§Perf iteration C evidence: SSD hot-loop cost, jnp lowering vs the
Pallas kernel's analytic TPU cost.

    PYTHONPATH=src python -m benchmarks.ssd_kernel_cost

Method: lower the per-device-local SSD computation (fwd + bwd, the exact
subgraph a mamba2-1.3b train_4k device executes per layer per microbatch)
through the jnp chunked path, parse its HBM traffic with the same cost
parser the dry-run uses; then compute the Pallas kernel's traffic
analytically from its BlockSpecs (grid x block bytes — on TPU each block
moves HBM->VMEM exactly once; intermediates live in VMEM).  The interpret-
mode lowering cannot stand in for Mosaic here: it emulates the grid as a
while loop with full-buffer copies per step.

The analytic block accounting is VALIDATED against the kernels' declared
BlockSpecs (the same shapes the interpret tests execute), and the kernel's
numerics are validated against the jnp oracle in tests/test_kernels.py.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.core.hlo import parse_program
from repro.core.hwspec import TPU_V5E

OUT = Path("experiments/bench")

# mamba2-1.3b train_4k per-device-local SSD shapes (single-pod mesh,
# microbatch 32): B = 32/16 data shards = 2, H = 64/16 model shards = 4
B, S, H, P, N, CHUNK = 2, 4096, 4, 64, 128, 256
L_LAYERS, N_MICRO = 48, 8


def jnp_ssd_traffic() -> dict:
    from repro.models.ssm import ssd_chunked

    def loss(x, dt, A, Bm, Cm):
        y, st = ssd_chunked(x, dt, A, Bm, Cm, CHUNK)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(st.astype(jnp.float32))

    grad = jax.grad(loss, argnums=(0, 1, 2, 3, 4))
    args = (
        jax.ShapeDtypeStruct((B, S, H, P), jnp.bfloat16),
        jax.ShapeDtypeStruct((B, S, H), jnp.float32),
        jax.ShapeDtypeStruct((H,), jnp.float32),
        jax.ShapeDtypeStruct((B, S, 1, N), jnp.bfloat16),
        jax.ShapeDtypeStruct((B, S, 1, N), jnp.bfloat16),
    )
    compiled = jax.jit(grad).lower(*args).compile()
    prog = parse_program(compiled.as_text())
    return {
        "bytes": prog.bytes_normalized("bf16"),
        "flops": prog.flops,
    }


def kernel_analytic_traffic() -> dict:
    """Grid x block-boundary bytes for the fwd and bwd kernels (each block
    is DMA'd HBM->VMEM once; Q x Q intermediates never leave VMEM)."""
    nc = S // CHUNK
    grid = B * nc * H
    bf2, f4 = 2, 4
    q = CHUNK
    fwd_block = (q * P * bf2            # x in
                 + 2 * q * f4           # dt, cs
                 + 2 * q * N * bf2      # B, C
                 + q * P * bf2          # y out
                 + N * P * f4)          # state out
    bwd_block = (q * P * bf2 * 2        # x, dy
                 + 2 * q * f4           # dt, cs
                 + 2 * q * N * bf2      # B, C
                 + N * P * f4           # dstate in
                 + q * P * bf2          # dx out
                 + 2 * q * f4           # ddt, dcs out
                 + 2 * q * N * f4)      # dB, dC out
    # jnp-side residue: cumsum, gamma, inter-chunk scan + y_off (per
    # device, fwd+bwd ~3x)
    residue = 3 * (B * nc * H * (N * P + 1) * f4        # states, gamma
                   + B * S * H * f4                     # cs
                   + B * S * H * (N + P) * bf2)         # y_off C/x traffic
    flops_block = (2 * q * q * N        # C B^T
                   + 2 * q * q * P      # M X
                   + 2 * q * N * P)     # state outer product
    bwd_flops_block = 4 * flops_block   # ~8 matmuls of the same shapes
    return {
        "bytes": grid * (fwd_block + bwd_block) + residue,
        "flops": grid * (flops_block + bwd_flops_block),
    }


def main() -> int:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jnp_t = jnp_ssd_traffic()
    ker_t = kernel_analytic_traffic()
    scale = L_LAYERS * N_MICRO
    rows = {}
    for name, t in (("jnp_chunked", jnp_t), ("pallas_kernel", ker_t)):
        mem_s = t["bytes"] * scale / TPU_V5E.hbm_read_bw
        comp_s = t["flops"] * scale / TPU_V5E.peak_flops["bf16"]
        rows[name] = {"bytes_per_layer_mb": t["bytes"] / 2**20,
                      "flops_per_layer_gf": t["flops"] / 1e9,
                      "memory_term_s": mem_s, "compute_term_s": comp_s}
        print(f"{name:<16s} bytes/layer·mb {t['bytes'] / 2**20:9.1f} MiB  "
              f"flops {t['flops'] / 1e9:7.1f} GF  -> step memory term "
              f"{mem_s:7.3f} s  compute {comp_s:6.3f} s")
    cut = 1 - ker_t["bytes"] / jnp_t["bytes"]
    print(f"\nSSD hot-loop HBM traffic cut by the kernel: {100 * cut:.1f}%")
    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / "ssd_kernel_cost.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
