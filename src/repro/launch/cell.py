"""Cell builder: one (architecture x input-shape x mesh) dry-run unit.

``build_cell`` assembles everything needed to lower one cell:
the step function (train_step / prefill / serve_step per the shape's kind),
abstract input trees (ShapeDtypeStruct — no device allocation), and the
in/out sharding trees resolved against the mesh.  It is shared by the
multi-pod dry-run, the roofline benchmarks and the §Perf iterations, so a
perf experiment is exactly "rebuild the cell with one knob changed".
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..configs import ARCHS, SHAPES, RunConfig, shapes_for
from ..configs.base import ModelConfig, ShapeConfig
from ..models import params as pr
from ..models.lm import LM, build_model
from ..parallel.sharding import MeshRules, make_rules
from ..serve.engine import make_decode_step, make_prefill_step
from ..serve.kvcache import cache_abstract, cache_shardings
from ..train.trainer import make_train_step


# Per-arch training policy: microbatch size (0 = whole batch in one shot).
# Set so the per-microbatch activation footprint fits 16 GiB/chip on the
# single-pod mesh (validated by the dry-run's memory_analysis).
TRAIN_MICROBATCH = {
    "nemotron-4-340b": 32,      # §Perf iteration G: frac 0.654 -> 0.716
    "qwen1.5-110b": 32,
    "grok-1-314b": 32,
    "llama4-scout-17b-a16e": 64,
    "qwen1.5-32b": 64,
    "mamba2-1.3b": 32,
    "zamba2-1.2b": 32,
}

# Archs whose q/kv-head counts do not divide the 16-way tensor axis run
# attention (and the residual stream) sequence-parallel instead of
# head-parallel — §Perf iteration A.  whisper: 20 heads; qwen-32b: 40;
# paligemma: 8 q / 1 kv; llama4-scout: 40 q heads.
SP_ARCHS = {"whisper-large-v3", "qwen1.5-32b", "paligemma-3b",
            "llama4-scout-17b-a16e"}

# int8 KV cache for decode (§Perf iteration E): qwen1.5-32b is full MHA
# (40 KV heads), the only arch whose bf16 32k-cache genuinely exceeds
# per-chip HBM on the single-pod mesh.
KV_INT8_ARCHS = {"qwen1.5-32b"}
SP_ACT_RULES = {"sp_seq": ("model",), "rseq": ("model",)}


@dataclass
class Cell:
    arch: str
    shape: ShapeConfig
    kind: str                      # train | prefill | decode
    model: LM
    run: RunConfig
    rules: MeshRules
    fn: Callable
    args: Tuple[Any, ...]          # abstract inputs (ShapeDtypeStructs)
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    donate_argnums: Tuple[int, ...]
    static_argnums: Tuple[int, ...] = ()

    @property
    def name(self) -> str:
        return f"{self.arch}__{self.shape.name}"

    def lower(self):
        jitted = jax.jit(self.fn, in_shardings=self.in_shardings,
                         out_shardings=self.out_shardings,
                         donate_argnums=self.donate_argnums)
        with self.rules.mesh:
            return jitted.lower(*self.args)


def default_run_config(cfg: ModelConfig, shape: ShapeConfig,
                       **overrides) -> RunConfig:
    mb = TRAIN_MICROBATCH.get(cfg.name, 0) if shape.kind == "train" else 0
    base = RunConfig(model=cfg, shape=shape, microbatch=mb)
    return dataclasses.replace(base, **overrides) if overrides else base


def batch_abstract(model: LM, shape: ShapeConfig, dtype=jnp.bfloat16) -> dict:
    return model.input_specs(shape, dtype)


def batch_shardings(model: LM, shape: ShapeConfig, rules: MeshRules,
                    dtype=jnp.bfloat16) -> dict:
    axes = model.batch_logical_axes(shape)
    specs = model.input_specs(shape, dtype)
    return {k: rules.act_sharding(axes.get(k, ()), s.shape)
            for k, s in specs.items()}


def build_cell(arch: str, shape_name: str, mesh: Mesh, *,
               run_overrides: Optional[dict] = None,
               act_rule_overrides: Optional[dict] = None) -> Cell:
    cfg = ARCHS[arch]
    shape = SHAPES[shape_name]
    if shape not in shapes_for(cfg):
        raise ValueError(f"{shape_name} is skipped for {arch} "
                         "(see DESIGN.md §Arch-applicability)")
    run = default_run_config(cfg, shape, **(run_overrides or {}))
    if act_rule_overrides is None and arch in SP_ARCHS \
            and shape.kind != "decode":
        act_rule_overrides = SP_ACT_RULES
    rules = make_rules(mesh, act_overrides=act_rule_overrides)
    kv_dtype = ("int8" if arch in KV_INT8_ARCHS and shape.kind == "decode"
                else "bf16")
    model = build_model(cfg, kv_cache_dtype=kv_dtype)
    pdt = jnp.dtype(run.param_dtype)

    param_specs = model.param_specs()
    p_abs = pr.abstract(param_specs, pdt)
    p_sh = pr.shardings(param_specs, rules)
    b_abs = batch_abstract(model, shape, pdt)
    b_sh = batch_shardings(model, shape, rules, pdt)
    repl = NamedSharding(mesh, PartitionSpec())

    if shape.kind == "train":
        step, _, opt_specs, p_sh2, o_sh, _ = make_train_step(model, run, rules)
        o_abs = pr.abstract(opt_specs, jnp.dtype(run.optimizer_dtype))
        return Cell(arch=arch, shape=shape, kind="train", model=model,
                    run=run, rules=rules, fn=step,
                    args=(p_abs, o_abs, b_abs),
                    in_shardings=(p_sh, o_sh, b_sh),
                    out_shardings=(p_sh, o_sh, None),
                    donate_argnums=(0, 1))

    if shape.kind == "prefill":
        step = make_prefill_step(model, rules)
        c_sh = cache_shardings(model, shape.global_batch, shape.seq_len, rules)
        return Cell(arch=arch, shape=shape, kind="prefill", model=model,
                    run=run, rules=rules, fn=step,
                    args=(p_abs, b_abs),
                    in_shardings=(p_sh, b_sh),
                    out_shardings=(None, c_sh),
                    donate_argnums=())

    # decode: one new token against a seq_len-deep cache (serve_step)
    step = make_decode_step(model, rules)
    c_abs = cache_abstract(model, shape.global_batch, shape.seq_len, pdt)
    c_sh = cache_shardings(model, shape.global_batch, shape.seq_len, rules)
    return Cell(arch=arch, shape=shape, kind="decode", model=model,
                run=run, rules=rules, fn=step,
                args=(p_abs, c_abs, b_abs),
                in_shardings=(p_sh, c_sh, b_sh),
                out_shardings=(None, c_sh),
                donate_argnums=(1,))


def all_cells() -> list[tuple[str, str]]:
    """Every (arch, shape) pair that runs (32 cells; skips documented)."""
    out = []
    for name, cfg in ARCHS.items():
        for s in shapes_for(cfg):
            out.append((name, s.name))
    return out


def model_flops_for(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """MODEL_FLOPS = 6*N_active*D (train) / 2*N_active*D (inference fwd)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n * shape.tokens
    return 2.0 * n * shape.global_batch          # decode: one token per seq
