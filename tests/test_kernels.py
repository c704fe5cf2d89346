"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow        # every test here compiles through jax

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.stream import EXPRS, elementwise, stream_triad

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# ------------------------------------------------------------ flash attention
def _bhsd(x):
    """(B, S, H, D) <-> (B, H, S, D), for the (B, H, S, D) oracle."""
    return jnp.transpose(x, (0, 2, 1, 3))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("sq,sk,h,kvh,d", [
    (128, 128, 4, 4, 64),        # MHA, single block
    (256, 256, 4, 1, 64),        # MQA, multi-block
    (128, 384, 8, 2, 32),        # GQA, sk > sq (prefix decode style)
    (100, 200, 4, 2, 64),        # ragged (padding path)
    (200, 200, 4, 1, 128),       # GQA at D = 128, ragged
])
def test_flash_attention_vs_ref(sq, sk, h, kvh, d, causal, dtype, key):
    # causal with offset-free q over longer k: q token i attends k <= i
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (2, sq, h, d), dtype)
    k = jax.random.normal(k2, (2, sk, kvh, d), dtype)
    v = jax.random.normal(k3, (2, sk, kvh, d), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                          interpret=True)
    want = _bhsd(ref.flash_attention_ref(_bhsd(q), _bhsd(k), _bhsd(v),
                                         causal=causal))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def _attn_inputs(key, S, H, G, D):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    q = jax.random.normal(k1, (1, S, H, D), jnp.float32)
    k = jax.random.normal(k2, (1, S, H // G, D), jnp.float32)
    v = jax.random.normal(k3, (1, S, H // G, D), jnp.float32)
    w = jax.random.normal(k4, (1, S, H, D), jnp.float32)
    return q, k, v, w


def _value_and_grads(attn, q, k, v, w):
    """attn's output and the gradients of sum(w * output) in q, k, v."""
    def loss(q, k, v):
        out = attn(q, k, v)
        return jnp.sum(w * out), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return (out,) + grads


@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("scale", [None, 0.125])
@pytest.mark.parametrize("G", [1, 4])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_grads_vs_jnp(causal, G, scale, D, key):
    """Output and dq, dk, dv of the differentiable kernel against the
    models' naive and blocked jnp attention, at a length (136) that is a
    multiple of neither block, with query and key blocks of different
    sizes, so the causal index maps clamp."""
    from repro.models.attention import blocked_attention, naive_attention

    q, k, v, w = _attn_inputs(key, 136, 4, G, D)
    got = _value_and_grads(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, scale=scale, block_q=32, block_k=64,
        interpret=True), q, k, v, w)
    for oracle in (
            lambda q, k, v: naive_attention(q, k, v, causal=causal,
                                            scale=scale),
            lambda q, k, v: blocked_attention(q, k, v, causal=causal,
                                              block=64, scale=scale)):
        want = _value_and_grads(oracle, q, k, v, w)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)


def test_flash_attention_masked_tiles_contribute_nothing(key):
    """Causal: keys and values past the first two 64-query tiles (whole
    masked tiles for them) are perturbed; those queries' outputs and every
    gradient of a loss over them stay bit for bit the same."""
    q, k, v, w = _attn_inputs(key, 256, 4, 4, 64)
    w = w.at[:, 128:].set(0.0)
    noise = 10.0 * jax.random.normal(jax.random.fold_in(key, 1), k.shape)
    beyond = (jnp.arange(256) >= 128)[None, :, None, None]

    def run(k, v):
        return _value_and_grads(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=64, block_k=64, interpret=True),
            q, k, v, w)

    base = run(k, v)
    moved = run(jnp.where(beyond, k + noise, k),
                jnp.where(beyond, v - noise, v))
    for a, b in zip(base, moved):
        np.testing.assert_array_equal(np.asarray(a[:, :128]),
                                      np.asarray(b[:, :128]))


@pytest.mark.parametrize("block_q,block_k", [(64, 64), (128, 256)])
def test_flash_attention_block_shape_invariance(block_q, block_k, key):
    q = jax.random.normal(key, (1, 256, 2, 64), jnp.float32)
    out_a = flash_attention(q, q, q, causal=True, block_q=block_q,
                            block_k=block_k, interpret=True)
    out_b = _bhsd(ref.flash_attention_ref(_bhsd(q), _bhsd(q), _bhsd(q),
                                          causal=True))
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_ops_layout(key):
    """ops.flash_attention uses (B, S, H, D) layout like the models."""
    q = jax.random.normal(key, (2, 128, 4, 64), jnp.float32)
    out = ops.flash_attention(q, q, q, causal=True)
    want = _bhsd(ref.flash_attention_ref(_bhsd(q), _bhsd(q), _bhsd(q),
                                         causal=True))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------------ SSD scan
def _groups(G, H):
    """The B/C group count of a case: a number, or "H" for one per head."""
    return H if G == "H" else G


def _heads(a, H):
    """(B, L, G, N) at the group count -> (B, L, H, N), for the reference."""
    return jnp.repeat(a, H // a.shape[2], axis=2)


@pytest.mark.parametrize("G", [1, 2, "H"])
@pytest.mark.parametrize("L,H,P,N,chunk", [
    (64, 2, 16, 16, 16),
    (128, 4, 32, 32, 32),
    (96, 2, 16, 8, 32),          # L not a multiple of chunk*2
])
def test_ssd_scan_vs_sequential_ref(L, H, P, N, chunk, G, key):
    G = _groups(G, H)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    B = 2
    x = jax.random.normal(k1, (B, L, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(k2, (B, L, H), jnp.float32))
    A = -jnp.exp(jax.random.normal(k3, (H,), jnp.float32) * 0.5)
    Bm = jax.random.normal(k4, (B, L, G, N), jnp.float32) * 0.5
    Cm = jax.random.normal(k1, (B, L, G, N), jnp.float32) * 0.5
    y, state = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    y_ref, state_ref = ref.ssd_ref(x, dt, A, _heads(Bm, H), _heads(Cm, H))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(state), np.asarray(state_ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("G", [1, 2, "H"])
def test_ssd_scan_initial_state(G, key):
    """Chunked scan over [x1; x2] == scan x1 then scan x2 from its state."""
    B, L, H, P, N = 1, 64, 4, 16, 16
    G = _groups(G, H)
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (B, L, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(k2, (B, L, H), jnp.float32))
    A = -jnp.ones((H,), jnp.float32)
    Bm = jax.random.normal(k1, (B, L, G, N), jnp.float32) * 0.3
    Cm = jax.random.normal(k2, (B, L, G, N), jnp.float32) * 0.3
    y_full, s_full = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=16)
    y1, s1 = ops.ssd_scan(x[:, :32], dt[:, :32], A, Bm[:, :32], Cm[:, :32],
                          chunk=16)
    y2, s2 = ops.ssd_scan(x[:, 32:], dt[:, 32:], A, Bm[:, 32:], Cm[:, 32:],
                          chunk=16, initial_state=s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               rtol=2e-3, atol=2e-3)


# ------------------------------------------------- the paper's kernel suite
@pytest.mark.parametrize("name", sorted(EXPRS))
def test_elementwise_kernel_vs_ref(name, key):
    with jax.enable_x64(True):
        fn, n_in, din, dout = EXPRS[name]
        n = 4096
        from repro.kernels.stream import _DTYPES
        if din == "i4":
            x1 = jax.random.randint(key, (n,), -1000, 1000, jnp.int32)
        else:
            x1 = jnp.abs(jax.random.normal(key, (n,), _DTYPES[din])) + 0.5
        x2 = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (n,),
                                       _DTYPES["f8" if din == "i4" else din])
                     ) + 0.5
        if din != "i4":
            x2 = x2.astype(_DTYPES[din])
        y0 = jnp.zeros((n,), _DTYPES[dout])
        out = elementwise(name, x1, x2, y0, block=512, interpret=True)
        want = ref.elementwise_ref(name, x1, x2, y0)
        np.testing.assert_allclose(np.asarray(out, np.float64),
                                   np.asarray(want, np.float64),
                                   rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("n,block", [(1 << 14, 4096), (3 * 4096, 4096)])
def test_stream_triad_kernel(n, block, key):
    a = jax.random.normal(key, (n,), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(key, 1), (n,), jnp.float32)
    out = stream_triad(a, b, 3.0, block=block, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.stream_triad_ref(a, b, 3.0)),
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------ SSD backward (custom VJP)
@pytest.mark.parametrize("G", [1, 2, "H"])
def test_ssd_kernel_gradients_match_reference(G, key):
    """jax.grad through the Pallas fwd+bwd kernels == grad of the
    sequential jnp recurrence; dB and dC come out at the group shape, equal
    to the reference's per-head gradients summed over each group's heads."""
    B, L, H, P, N, chunk = 2, 64, 4, 16, 16, 16
    G = _groups(G, H)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    x = jax.random.normal(k1, (B, L, H, P), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(k2, (B, L, H), jnp.float32))
    A = -jnp.exp(jax.random.normal(k3, (H,)) * 0.3)
    Bm = jax.random.normal(k4, (B, L, G, N), jnp.float32) * 0.4
    Cm = jax.random.normal(k5, (B, L, G, N), jnp.float32) * 0.4

    def loss_kernel(*args):
        y, s = ops.ssd_scan(*args, chunk=chunk)
        return jnp.sum(jnp.sin(y)) + jnp.sum(s * s)

    def loss_ref(x, dt, A, Bm, Cm):
        y, s = ref.ssd_ref(x, dt, A, _heads(Bm, H), _heads(Cm, H))
        return jnp.sum(jnp.sin(y)) + jnp.sum(s * s)

    g_k = jax.grad(loss_kernel, argnums=(0, 1, 2, 3, 4))(x, dt, A, Bm, Cm)
    g_r = jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4))(x, dt, A, Bm, Cm)
    assert g_k[3].shape == g_k[4].shape == (B, L, G, N)
    for a, b in zip(g_k, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_apply_mamba_pallas_matches_jnp(key):
    """apply_mamba(impl='pallas') == apply_mamba(impl='jnp') in fwd and
    grad (no mesh: the shard_map wrapper falls through to the kernel)."""
    from repro.configs import ARCHS, reduced_config
    from repro.models import params as pr
    from repro.models.ssm import apply_mamba, mamba_params

    cfg = reduced_config(ARCHS["mamba2-1.3b"])
    p = pr.init(mamba_params(cfg), key)
    x = 0.3 * jax.random.normal(jax.random.fold_in(key, 1),
                                (2, 32, cfg.d_model), jnp.float32)

    def loss(p, impl):
        out, _ = apply_mamba(p, x, cfg, mode="train", impl=impl)
        return jnp.sum(out * out), out

    (l_j, out_j), g_j = jax.value_and_grad(loss, has_aux=True)(p, "jnp")
    (l_p, out_p), g_p = jax.value_and_grad(loss, has_aux=True)(p, "pallas")
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_j),
                               rtol=2e-3, atol=2e-3)
    for kk in g_j:
        np.testing.assert_allclose(np.asarray(g_p[kk]), np.asarray(g_j[kk]),
                                   rtol=5e-3, atol=5e-3, err_msg=kk)


_SHARDED_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
import jax.numpy as jnp
from jax.sharding import AxisType
from repro.models.ssm import ssd_pallas_sharded
from repro.parallel.sharding import make_rules, use_rules

mesh = jax.make_mesh((2, 2), ("data", "model"), (AxisType.Auto,) * 2)
B, L, H, P, N = 2, 64, 4, 16, 16
k = jax.random.split(jax.random.PRNGKey(0), 5)
x = jax.random.normal(k[0], (B, L, H, P))
dt = jax.nn.softplus(jax.random.normal(k[1], (B, L, H)))
A = -jnp.exp(jax.random.normal(k[2], (H,)) * 0.3)


def loss(*args):
    y, s = ssd_pallas_sharded(*args, 16)
    return jnp.sum(jnp.sin(y)) + jnp.sum(s * s)


gaps = {}
for G in (1, 2, H):
    Bm = jax.random.normal(k[3], (B, L, G, N)) * 0.4
    Cm = jax.random.normal(k[4], (B, L, G, N)) * 0.4
    grad = jax.value_and_grad(loss, argnums=(0, 1, 3, 4))
    want = grad(x, dt, A, Bm, Cm)
    with use_rules(make_rules(mesh)):
        got = jax.jit(grad)(x, dt, A, Bm, Cm)
    gaps[G] = max(float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
                  for a, b in zip(jax.tree.leaves(got),
                                  jax.tree.leaves(want)))
print(json.dumps(gaps))
"""


def test_ssd_pallas_sharded_matches_unsharded():
    """The shard_mapped SSD (batch on 'data', heads on 'model') equals the
    unsharded kernel in value and gradient (largest gap over the largest
    entry, leaf by leaf), with one group replicated to
    every head shard, two groups (broadcast to heads first) and one group
    per head.  Four CPU devices, so in a process of its own."""
    env = dict(os.environ, PYTHONPATH=os.path.abspath("src"))
    out = subprocess.run([sys.executable, "-c", _SHARDED_CHILD], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    gaps = json.loads(out.stdout.strip().splitlines()[-1])
    assert sorted(gaps) == ["1", "2", "4"]
    assert all(g < 2e-5 for g in gaps.values()), gaps
