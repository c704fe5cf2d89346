"""Chip smoke: the paper's test-chip loop, run once on one TPU chip.

    python chip_smoke.py

1. Device gate: JAX's first device must be a TPU.  There is no CPU fallback.
2. Test-chip run: mamba2-1.3b training at its published widths (d_model
   2048, 64 SSD heads x 64, d_state 128, chunk 256, vocab 50,432 padded),
   fp32 parameters and AdamW, the SSD mixer through its Pallas kernel
   (``ssd_impl="pallas"``).  Depth and batch are cut to fit one 16 GB chip
   (``LAYERS``, ``BATCH``, ``SEQ``).  Compiles through
   ``launch.train.build_training``, checks the compiled step holds the
   kernel as a ``tpu_custom_call`` (Mosaic, not the interpreter), times
   warmed-up steps, and checks the first step of the Pallas path against
   the jnp path at full matmul precision.
3. The simulator on the chip's own program: ``simulate()`` prices the
   compiled step with the ``TPU_V5E`` parameter file; the estimate is set
   beside the measured median, unvalidated.
4. The simulator's device backend: the A64FX node sweep of the zoo
   (``schedule_node_sweep``, ``backend="jax"``) runs on the chip over that
   program and is checked against the numpy backend.

Findings go to stdout; the last line is a JSON object naming the device.
Any failure exits non-zero and prints no such line.  One process holds the
chip and starts no other.
"""
from __future__ import annotations

import dataclasses
import json
import math
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, RunConfig, ShapeConfig
from repro.core.hwspec import A64FX_CORE, TPU_V5E
from repro.core.node import compile_node, schedule_node_sweep
from repro.core.simulate import simulate
from repro.core.zoo import DEFAULT_CORE_COUNTS, zoo_o3_knobs
from repro.data.synthetic import SyntheticLMDataset
from repro.launch.compile_cache import use_compile_cache
from repro.launch.train import build_training
from repro.models import params as pr
from repro.models.lm import build_model

ARCH = "mamba2-1.3b"
# 16 of 48 layers (one of three pipeline stages): params + AdamW moments are
# 6.2 GB of fp32; batch 2 x 2048 adds 6.9 GB of temporaries, most of them
# the full-vocab fp32 logits and their gradient (v5e compiler's
# memory_analysis).  Batch 4 or 8 at this depth does not fit 16 GB.
LAYERS, BATCH, SEQ = 16, 2, 2048
CHECK_BATCH = 1          # the jnp path's (B, nc, H, Q, Q) intermediates
TIMED_STEPS = 6
SEED = 0
# Pallas vs jnp, both at full f32 matmul precision: the same math in a
# different summation order (and the kernel's diagonal row/column algebra),
# so per-op rounding of ~1e-6 compounds over 16 layers forward and back.
# An algebra error in the kernel moves both numbers by far more than these.
LOSS_RTOL = 1e-4
GNORM_RTOL = 1e-3       # a sum over 517M squared partials, exp() inside
# the parsed program's FLOPs over 6·N·D: full remat recomputes the forward
# (about 8/6); a layer scan counted once would give about 0.25
FLOP_RATIO_FLOOR = 0.5
NODE_RTOL = 1e-9         # the jax-backend tests' tolerance vs numpy


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke FAILED: {msg}")


def _entries(cache: Path) -> int:
    return len(list(cache.glob("*"))) if cache.is_dir() else 0


def device_gate():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"JAX's first device is {dev.platform} ({dev.device_kind}), "
             "not a TPU; this script measures the chip and has no fallback")
    return dev


def smoke_config(layers: int = LAYERS):
    return dataclasses.replace(ARCHS[ARCH], n_layers=layers)


def _training(cfg, impl: str, batch: int, seq: int):
    model = build_model(cfg, ssd_impl=impl)
    run = RunConfig(model=cfg, shape=ShapeConfig("chip_smoke", seq, batch,
                                                 "train"),
                    param_dtype="float32", compute_dtype="float32")
    jitted, init_state, _ = build_training(model, run)
    data = SyntheticLMDataset(vocab_size=cfg.vocab_size, seq_len=seq,
                              global_batch=batch, seed=SEED)
    return model, jitted, init_state, data


def _batch(data, step: int) -> dict:
    return {"tokens": jnp.asarray(data.batch(step)["tokens"])}


def train_phase(cfg, batch: int = BATCH, seq: int = SEQ,
                timed_steps: int = TIMED_STEPS) -> dict:
    """Compile the Pallas-path step, warm it up, time ``timed_steps``."""
    model, jitted, init_state, data = _training(cfg, "pallas", batch, seq)
    params, opt = init_state(SEED)
    batches = [_batch(data, s) for s in range(timed_steps + 1)]
    t0 = time.perf_counter()
    compiled = jitted.lower(params, opt, batches[0]).compile()
    compile_s = time.perf_counter() - t0
    losses, times = [], []
    for b in batches:                         # step 0 is the warm-up
        t0 = time.perf_counter()
        params, opt, m = compiled(params, opt, b)
        jax.block_until_ready((params, opt, m))
        times.append(time.perf_counter() - t0)
        losses.append(float(m["loss"]))
    del params, opt
    return {"compiled": compiled, "compile_s": compile_s,
            "n_params": pr.count(model.param_specs()), "tokens": batch * seq,
            "losses": losses, "step_s": times[1:]}


def first_step(cfg, impl: str, batch: int = CHECK_BATCH,
               seq: int = SEQ) -> tuple:
    """(loss, grad_norm) of the first train step of one SSD path."""
    _, jitted, init_state, data = _training(cfg, impl, batch, seq)
    params, opt = init_state(SEED)
    _, _, m = jitted(params, opt, _batch(data, 0))
    return float(m["loss"]), float(m["grad_norm"])


def simulate_phase(compiled, n_params: int, tokens: int) -> dict:
    six_nd = 6.0 * n_params * tokens
    rep = simulate(compiled, hw=TPU_V5E, n_chips=1, compute_dtype="f32",
                   model_flops_global=six_nd)
    return {"program": rep.program, "t_est": rep.engine.t_est,
            "bound_by": rep.engine.bound_by,
            "flop_ratio": rep.program.flops / six_nd}


def node_backend_phase(prog, device) -> dict:
    """The zoo's A64FX node sweep over ``prog``: numpy on the host, then
    the jax backend with its arrays placed on ``device``."""
    hw = A64FX_CORE
    nc = compile_node(prog, hw, compute_dtype="f32")
    knobs = zoo_o3_knobs(hw)

    def sweep(backend):
        t0 = time.perf_counter()
        t = schedule_node_sweep(nc, hw, knobs, DEFAULT_CORE_COUNTS,
                                topology=hw.topology, partition="shard",
                                backend=backend)
        return t, time.perf_counter() - t0

    ref, numpy_s = sweep("numpy")
    with jax.default_device(device):
        got, jax_first_s = sweep("jax")      # includes the jit compile
        again, jax_s = sweep("jax")
    return {"ref": ref, "got": got, "again": again, "numpy_s": numpy_s,
            "jax_first_s": jax_first_s, "jax_s": jax_s,
            "n_ops": len(prog.ops), "grid": knobs.batch}


def main() -> int:
    t_start = time.perf_counter()
    dev = device_gate()
    cache = use_compile_cache()
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
        f"jax {jax.__version__}")
    log(f"compile cache: {cache} ({_entries(cache)} entries at start)")

    cfg = smoke_config()
    log(f"\n== phase 2: {ARCH} train step, {LAYERS}/{ARCHS[ARCH].n_layers} "
        f"layers, batch {BATCH} x seq {SEQ}, fp32 + AdamW, ssd_impl=pallas")
    tr = train_phase(cfg)
    text = tr["compiled"].as_text()
    n_kernels = text.count("tpu_custom_call")
    log(f"params {tr['n_params']:,}  compile {tr['compile_s']:.3f} s  "
        f"tpu_custom_call x{n_kernels}")
    if n_kernels == 0:
        fail("the compiled step holds no tpu_custom_call: the SSD kernel "
             "did not go through Mosaic")
    for i, loss in enumerate(tr["losses"]):
        log(f"  step {i}{' (warm-up)' if i == 0 else ''}  loss {loss!r}")
    if not all(math.isfinite(x) for x in tr["losses"]):
        fail("non-finite loss")
    median = statistics.median(tr["step_s"])
    log(f"step times (s): {tr['step_s']}")
    log(f"median step {median!r} s  ({tr['tokens'] / median:.1f} tokens/s)")
    peak = dev.memory_stats().get("peak_bytes_in_use")
    ma = tr["compiled"].memory_analysis()
    log(f"peak_bytes_in_use {peak}  (compiler: arguments "
        f"{ma.argument_size_in_bytes} + temporaries {ma.temp_size_in_bytes})")

    log(f"\n== phase 2b: first step, pallas vs jnp, batch {CHECK_BATCH} x "
        f"seq {SEQ}, matmul precision highest")
    with jax.default_matmul_precision("highest"):
        lp, gp = first_step(cfg, "pallas")
        lj, gj = first_step(cfg, "jnp")
    dl, dg = abs(lp - lj) / abs(lj), abs(gp - gj) / abs(gj)
    log(f"loss   pallas {lp!r}  jnp {lj!r}  rel diff {dl:.3e} "
        f"(tol {LOSS_RTOL:g})")
    log(f"gnorm  pallas {gp!r}  jnp {gj!r}  rel diff {dg:.3e} "
        f"(tol {GNORM_RTOL:g})")
    if not (dl <= LOSS_RTOL and dg <= GNORM_RTOL):
        fail("the Pallas path disagrees with the jnp path")

    log("\n== phase 3: simulate() on the chip-compiled step (TPU_V5E file)")
    t0 = time.perf_counter()
    sim = simulate_phase(tr["compiled"], tr["n_params"], tr["tokens"])
    log(f"parsed {len(sim['program'].ops)} ops in "
        f"{time.perf_counter() - t0:.3f} s")
    log(f"t_est {sim['t_est']!r} s  bound_by {sim['bound_by']}  measured "
        f"median {median!r} s  t_est/measured "
        f"{sim['t_est'] / median:.4f}  (unvalidated)")
    log(f"parsed FLOPs / 6ND {sim['flop_ratio']:.4f} "
        f"(floor {FLOP_RATIO_FLOOR})")
    if not (math.isfinite(sim["t_est"]) and sim["t_est"] > 0):
        fail("t_est is not a positive finite time")
    if sim["flop_ratio"] < FLOP_RATIO_FLOOR:
        fail("the parsed program counts too few FLOPs")

    log("\n== phase 4: schedule_node_sweep backend=jax on the chip "
        "(A64FX node, zoo knob grid, cores "
        f"{DEFAULT_CORE_COUNTS})")
    nb = node_backend_phase(sim["program"], dev)
    log(f"{nb['n_ops']} ops x {nb['grid']} knobs x "
        f"{len(DEFAULT_CORE_COUNTS)} core counts; jax backend on {dev}")
    log(f"numpy {nb['numpy_s']:.3f} s  jax first {nb['jax_first_s']:.3f} s "
        f"(with compile)  jax again {nb['jax_s']:.3f} s")
    err = float(np.max(np.abs(nb["got"] - nb["ref"]) / np.abs(nb["ref"])))
    log(f"max rel diff jax vs numpy {err:.3e} (tol {NODE_RTOL:g})")
    np.testing.assert_allclose(nb["got"], nb["ref"], rtol=NODE_RTOL)
    np.testing.assert_allclose(nb["again"], nb["ref"], rtol=NODE_RTOL)

    log(f"\nwall {time.perf_counter() - t_start:.1f} s; compile cache "
        f"{_entries(cache)} entries at end")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
