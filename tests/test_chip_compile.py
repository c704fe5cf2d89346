"""The main path's kernels and programs, compiled for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler is installed alongside JAX and
compiles for a topology that is described, not attached.  It refuses what the
chip would refuse (block shapes off the (8, 128) tiling, too much fast
memory) and costs about two seconds a case.  Every chip compile of the test
suite lives in this one file: the topology is described inside a fixture,
never at import, so parallel test workers collect the same tests and only
the worker given this file loads the TPU library.
"""
import base64
import json
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.hwspec import TPU_V5E
from repro.core.simulate import simulate
from repro.kernels import flash_attention as fa
from repro.kernels import ssd_scan

# mamba2-1.3b SSD widths: chunk Q, head dim P, state N, heads H
Q, P, N, H = 256, 64, 128, 64
# chatglm3-6b attention widths: 32 query heads x 128, 2 KV heads, seq 4096
FA_H, FA_KVH, FA_D, FA_S = 32, 2, 128, 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                     # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(one_chip, dtype, *shapes):
    return [jax.ShapeDtypeStruct(s, dtype, sharding=one_chip) for s in shapes]


def _ssd_args(one_chip, nc=2):
    return _shapes(one_chip, jnp.float32, (1, nc, Q, H, P), (1, nc, Q, H),
                   (1, nc, Q, H), (1, nc, Q, H, N), (1, nc, Q, H, N))


def test_ssd_forward_compiles_for_v5e(one_chip):
    def fwd(*args):
        return ssd_scan.ssd_chunk_pallas(*args, interpret=False)

    text = jax.jit(fwd).lower(*_ssd_args(one_chip)).compile().as_text()
    assert text.count("tpu_custom_call") == 1


def test_ssd_forward_backward_compiles_for_v5e(one_chip):
    def loss(*args):
        y, s = ssd_scan.ssd_chunk_pallas(*args, interpret=False)
        return jnp.sum(jnp.sin(y)) + jnp.sum(s * s)

    step = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))
    text = jax.jit(step).lower(*_ssd_args(one_chip)).compile().as_text()
    assert text.count("tpu_custom_call") == 2          # forward + backward


def _mosaic_bodies(text):
    """The serialized Mosaic module of each ``tpu_custom_call``."""
    bodies = []
    for cfg in re.findall(r'custom_call_target="tpu_custom_call".*?'
                          r'backend_config=(\{.*)$', text, re.M):
        obj, _ = json.JSONDecoder().raw_decode(cfg)
        bodies.append(base64.b64decode(obj["custom_call_config"]["body"]))
    return bodies


def test_ssd_groups_reach_the_kernel_unbroadcast(one_chip, monkeypatch):
    """mamba2-1.3b's one B/C group, through ops.ssd_scan forward and
    backward: two kernel calls, no B/C (or dB/dC) array at the per-head
    shape anywhere in the compiled step, and the kernels keep the body
    names the benchmark finds them by."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    nc = 2
    x, dt, Bm, Cm = _shapes(one_chip, jnp.float32, (1, nc * Q, H, P),
                            (1, nc * Q, H), (1, nc * Q, 1, N),
                            (1, nc * Q, 1, N))
    A, = _shapes(one_chip, jnp.float32, (H,))

    def loss(*args):
        y, s = ops.ssd_scan(*args, chunk=Q)
        return jnp.sum(jnp.sin(y)) + jnp.sum(s * s)

    step = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))
    text = jax.jit(step).lower(x, dt, A, Bm, Cm).compile().as_text()
    assert text.count("tpu_custom_call") == 2          # forward + backward
    per_head = re.compile(rf"f32\[[\d,]*\b({H},{Q}|{Q},{H}),{N}\]")
    assert not per_head.findall(text)
    bodies = _mosaic_bodies(text)
    assert len(bodies) == 2
    assert {n for b in bodies for n in (b"_ssd_chunk_kernel",
                                        b"_ssd_chunk_bwd_kernel")
            if re.search(rb"\b" + n + rb"\b", b)} == {
        b"_ssd_chunk_kernel", b"_ssd_chunk_bwd_kernel"}


def test_flash_attention_forward_compiles_for_v5e(one_chip):
    q, k, v = _shapes(one_chip, jnp.bfloat16, (1, FA_S, FA_H, FA_D),
                      (1, FA_S, FA_KVH, FA_D), (1, FA_S, FA_KVH, FA_D))

    def fwd(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, interpret=False)

    text = jax.jit(fwd).lower(q, k, v).compile().as_text()
    assert "tpu_custom_call" in text


FLASH_BODIES = (b"_flash_fwd_kernel", b"_flash_dkv_kernel",
                b"_flash_dq_kernel")


def _bodies_named(text):
    """The flash body names found in the step's Mosaic modules."""
    return [n for b in _mosaic_bodies(text) for n in FLASH_BODIES
            if re.search(rb"\b" + n + rb"\b", b)]


def test_flash_attention_forward_backward_compiles_for_v5e(one_chip):
    """zamba2-1.2b's shared attention core, (1, 4096, 32, 128) f32: the
    forward, dK/dV and dQ kernels, under the body names the benchmark
    finds them by."""
    q, k, v = _shapes(one_chip, jnp.float32, *[(1, 4096, 32, 128)] * 3)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, scale=0.125,
                                 interpret=False)
        return jnp.sum(jnp.sin(out))

    step = jax.value_and_grad(loss, argnums=(0, 1, 2))
    text = jax.jit(step).lower(q, k, v).compile().as_text()
    assert sorted(_bodies_named(text)) == sorted(FLASH_BODIES)


def test_tpu_compiled_mlp_gradient_is_priced_on_the_mxu(one_chip):
    """The TPU compiler writes dots as convolutions inside fusions; the
    parser must still class them matmul, count their FLOPs, and the
    occupancy estimate must be bound by the MXU."""
    n = 2048
    w1, w2, x = _shapes(one_chip, jnp.bfloat16, (n, n), (n, n), (n, n))

    def loss(w1, w2, x):
        h = jax.nn.relu(jnp.dot(x, w1))
        return jnp.sum(jnp.dot(h, w2).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        w1, w2, x).compile()
    rep = simulate(compiled, hw=TPU_V5E, n_chips=1)
    big = [o for o in rep.program.ops if o.flops * o.count > 1e9]
    assert big and all(o.opclass == "matmul" for o in big)
    # x@w1 forward plus the two weight gradients: three 2048^3 matmuls
    assert sum(o.flops * o.count for o in big) >= 3 * 2 * n ** 3
    assert rep.engine.bound_by == "mxu"


def test_train_step_regions_survive_the_v5e_compiler(one_chip, monkeypatch):
    """Two Mamba-2 layers at mamba2-1.3b's widths (one would unroll the
    layer loop), their train step compiled for the v5e with the Pallas SSD
    kernel: the forward, recomputed and backward kernel calls sit in
    ``mixer.ssd_chunk``, every matmul in a region, and the simulator's
    sections split the step's serial time."""
    import dataclasses

    from repro.configs import ARCHS, RunConfig, ShapeConfig
    from repro.core.hlo import op_names
    from repro.core.stats import REGIONS, SHARED_REGIONS, region_of
    from repro.kernels import ops
    from repro.launch.train import build_training
    from repro.models.lm import build_model
    from repro.train.optimizer import OptConfig, adamw_init

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mc = dataclasses.replace(ARCHS["mamba2-1.3b"], n_layers=2, remat="full")
    model = build_model(mc, ssd_impl="pallas")
    run = RunConfig(model=mc, shape=ShapeConfig("t", 512, 1, "train"),
                    param_dtype="float32", compute_dtype="float32")
    jitted, _, _ = build_training(model, run)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    opt = jax.eval_shape(lambda: adamw_init(params, OptConfig()))
    batch = on_chip({"tokens": jax.ShapeDtypeStruct((1, 512), jnp.int32)})
    compiled = jitted.lower(on_chip(params), on_chip(opt), batch).compile()
    text = compiled.as_text()
    names = op_names(text)
    calls = [n for n, line in ((m.group(1), m.group(0)) for m in re.finditer(
        r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .*tpu_custom_call.*$", text, re.M))]
    assert sorted(region_of(names[n]) for n in calls) == [
        ("mixer.ssd_chunk", "backward"), ("mixer.ssd_chunk", "forward"),
        ("mixer.ssd_chunk", "recompute")]
    rep = simulate(compiled, hw=TPU_V5E, n_chips=1, compute_dtype="f32")
    matmuls = [o for o in rep.program.ops if o.opclass == "matmul"]
    assert matmuls and all(region_of(o.op_name)[0] for o in matmuls)
    assert {region_of(o.op_name)[0] for o in rep.program.ops} >= \
        set(REGIONS) - set(SHARED_REGIONS)
    s = rep.sections
    assert sum(s.get("t_serial_s", p) for p in s.sections()) == \
        pytest.approx(rep.engine.t_serial, rel=1e-12)


def test_hybrid_train_step_runs_attention_in_the_flash_kernel(one_chip,
                                                               monkeypatch):
    """zamba2-1.2b at its widths, 7 layers (one shared-block invocation),
    full remat, its train step compiled for the v5e on the blocked path:
    the flash forward, its recompute and the two backward kernels all sit
    in ``shared.attn``, and no (..., 4096, 1024) f32 score block is left."""
    import dataclasses

    from repro.configs import ARCHS, RunConfig, ShapeConfig
    from repro.core.hlo import op_names
    from repro.core.stats import region_of
    from repro.kernels import ops
    from repro.launch.train import build_training
    from repro.models.lm import build_model
    from repro.train.optimizer import OptConfig, adamw_init

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mc = dataclasses.replace(ARCHS["zamba2-1.2b"], n_layers=7, remat="full")
    model = build_model(mc, ssd_impl="pallas", attn_impl="blocked")
    run = RunConfig(model=mc, shape=ShapeConfig("t", 4096, 1, "train"),
                    param_dtype="float32", compute_dtype="float32")
    jitted, _, _ = build_training(model, run)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    opt = jax.eval_shape(lambda: adamw_init(params, OptConfig()))
    batch = on_chip({"tokens": jax.ShapeDtypeStruct((1, 4096), jnp.int32)})
    text = jitted.lower(on_chip(params), on_chip(opt),
                        batch).compile().as_text()
    names = op_names(text)
    flash = {}
    for m in re.finditer(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .*"
                         r"custom_call_target=\"tpu_custom_call\".*?"
                         r"backend_config=(\{.*)$", text, re.M):
        found = _bodies_named(m.group(0))
        if found:
            flash[m.group(1)] = found[0]
    assert sorted((region_of(names[n]), body) for n, body in flash.items()) \
        == sorted([(("shared.attn", "forward"), b"_flash_fwd_kernel"),
                   (("shared.attn", "recompute"), b"_flash_fwd_kernel"),
                   (("shared.attn", "backward"), b"_flash_dkv_kernel"),
                   (("shared.attn", "backward"), b"_flash_dq_kernel")])
    assert not re.search(r"f32\[[\d,]*4096,1024\]", text)
