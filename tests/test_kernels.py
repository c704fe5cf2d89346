"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow        # every test here compiles through jax

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.stream import EXPRS, elementwise, stream_triad

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("sq,sk,h,kvh,d", [
    (128, 128, 4, 4, 64),        # MHA, single block
    (256, 256, 4, 1, 64),        # MQA, multi-block
    (128, 384, 8, 2, 32),        # GQA, sk > sq (prefix decode style)
    (100, 200, 4, 2, 64),        # ragged (padding path)
])
def test_flash_attention_vs_ref(sq, sk, h, kvh, d, causal, dtype, key):
    if sq != sk and causal:
        # causal with offset-free q over longer k: q token i attends k <= i
        pass
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (2, h, sq, d), dtype)
    k = jax.random.normal(k2, (2, kvh, sk, d), dtype)
    v = jax.random.normal(k3, (2, kvh, sk, d), dtype)
    out = flash_attention_bhsd(q, k, v, causal=causal, block_q=128,
                               block_k=128, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("block_q,block_k", [(64, 64), (128, 256)])
def test_flash_attention_block_shape_invariance(block_q, block_k, key):
    q = jax.random.normal(key, (1, 2, 256, 64), jnp.float32)
    out_a = flash_attention_bhsd(q, q, q, causal=True, block_q=block_q,
                                 block_k=block_k, interpret=True)
    out_b = ref.flash_attention_ref(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_ops_layout(key):
    """ops.flash_attention uses (B, S, H, D) layout like the models."""
    q = jax.random.normal(key, (2, 128, 4, 64), jnp.float32)
    out = ops.flash_attention(q, q, q, causal=True)
    want = jnp.transpose(
        ref.flash_attention_ref(*(jnp.transpose(x, (0, 2, 1, 3))
                                  for x in (q, q, q)), causal=True),
        (0, 2, 1, 3))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------------ SSD scan
@pytest.mark.parametrize("L,H,P,N,chunk", [
    (64, 2, 16, 16, 16),
    (128, 4, 32, 32, 32),
    (96, 2, 16, 8, 32),          # L not a multiple of chunk*2
])
def test_ssd_scan_vs_sequential_ref(L, H, P, N, chunk, key):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    B = 2
    x = jax.random.normal(k1, (B, L, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(k2, (B, L, H), jnp.float32))
    A = -jnp.exp(jax.random.normal(k3, (H,), jnp.float32) * 0.5)
    Bm = jax.random.normal(k4, (B, L, H, N), jnp.float32) * 0.5
    Cm = jax.random.normal(k1, (B, L, H, N), jnp.float32) * 0.5
    y, state = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    y_ref, state_ref = ref.ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(state), np.asarray(state_ref),
                               rtol=2e-3, atol=2e-3)


def test_ssd_scan_initial_state(key):
    """Chunked scan over [x1; x2] == scan x1 then scan x2 from its state."""
    B, L, H, P, N = 1, 64, 2, 16, 16
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (B, L, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(k2, (B, L, H), jnp.float32))
    A = -jnp.ones((H,), jnp.float32)
    Bm = jax.random.normal(k1, (B, L, H, N), jnp.float32) * 0.3
    Cm = jax.random.normal(k2, (B, L, H, N), jnp.float32) * 0.3
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
def test_ssd_kernel_gradients_match_reference(key):
    """jax.grad through the Pallas fwd+bwd kernels == grad of the
    sequential jnp recurrence."""
    B, L, H, P, N, chunk = 2, 64, 2, 16, 16, 16
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    x = jax.random.normal(k1, (B, L, H, P), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(k2, (B, L, H), jnp.float32))
    A = -jnp.exp(jax.random.normal(k3, (H,)) * 0.3)
    Bm = jax.random.normal(k4, (B, L, H, N), jnp.float32) * 0.4
    Cm = jax.random.normal(k5, (B, L, H, N), jnp.float32) * 0.4

    def loss_kernel(*args):
        y, s = ops.ssd_scan(*args, chunk=chunk)
        return jnp.sum(jnp.sin(y)) + jnp.sum(s * s)

    def loss_ref(*args):
        y, s = ref.ssd_ref(*args)
        return jnp.sum(jnp.sin(y)) + jnp.sum(s * s)

    g_k = jax.grad(loss_kernel, argnums=(0, 1, 2, 3, 4))(x, dt, A, Bm, Cm)
    g_r = jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4))(x, dt, A, Bm, Cm)
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
