"""The main path's kernels and programs, compiled for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler is installed alongside JAX and
compiles for a topology that is described, not attached.  It refuses what the
chip would refuse (block shapes off the (8, 128) tiling, too much fast
memory) and costs about two seconds a case.  Every chip compile of the test
suite lives in this one file: the topology is described inside a fixture,
never at import, so parallel test workers collect the same tests and only
the worker given this file loads the TPU library.
"""
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


def test_flash_attention_forward_compiles_for_v5e(one_chip):
    q, k, v = _shapes(one_chip, jnp.bfloat16, (1, FA_H, FA_S, FA_D),
                      (1, FA_KVH, FA_S, FA_D), (1, FA_KVH, FA_S, FA_D))

    def fwd(q, k, v):
        return fa.flash_attention_bhsd(q, k, v, causal=True, interpret=False)

    text = jax.jit(fwd).lower(q, k, v).compile().as_text()
    assert "tpu_custom_call" in text


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
