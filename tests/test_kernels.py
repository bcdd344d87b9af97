"""Per-kernel allclose vs the pure-jnp oracles, swept over shapes/dtypes.
Every call passes ``interpret=True``: the Pallas interpreter executes the
kernel bodies on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd.ops import ssd_chunked_pallas
from repro.kernels.ssd.ref import ssd_ref
from repro.kernels.grouped_matmul.ops import grouped_matmul
from repro.kernels.grouped_matmul.ref import grouped_matmul_ref


@pytest.mark.parametrize("s,h,kv,d,win,cap", [
    (128, 4, 4, 32, 0, 0.0),          # MHA
    (192, 4, 2, 64, 0, 0.0),          # GQA, non-multiple seq (padding path)
    (128, 4, 2, 32, 48, 0.0),         # sliding window
    (128, 2, 2, 64, 0, 30.0),         # logit softcap (gemma2)
    (96, 8, 1, 32, 32, 50.0),         # MQA + window + cap
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_ref(s, h, kv, d, win, cap, dtype):
    k = jax.random.PRNGKey(0)
    b = 2
    q = jax.random.normal(k, (b, s, h, d)).astype(dtype)
    kk = jax.random.normal(jax.random.PRNGKey(1), (b, s, kv, d)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kv, d)).astype(dtype)
    out = flash_attention(q, kk, v, window=win, softcap=cap,
                          block_q=64, block_kv=64, interpret=True)
    g = h // kv
    kr = jnp.repeat(kk, g, 2).transpose(0, 2, 1, 3)
    vr = jnp.repeat(v, g, 2).transpose(0, 2, 1, 3)
    ref = attention_ref(q.transpose(0, 2, 1, 3), kr, vr, window=win,
                        softcap=cap).transpose(0, 2, 1, 3)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("s,nh,hd,ds,ch", [
    (64, 2, 16, 8, 16),
    (128, 4, 32, 16, 32),
    (128, 4, 32, 16, 64),     # chunk-size invariance
])
def test_ssd_kernel_vs_ref(s, nh, hd, ds, ch):
    k = jax.random.PRNGKey(0)
    b = 2
    x = jax.random.normal(k, (b, s, nh, hd)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(3), (b, s, nh)))
    A = -jnp.exp(jnp.linspace(0.0, 1.0, nh))
    B = jax.random.normal(jax.random.PRNGKey(4), (b, s, ds)) * 0.3
    C = jax.random.normal(jax.random.PRNGKey(5), (b, s, ds)) * 0.3
    y, st = ssd_chunked_pallas(x, dt, A, B, C, chunk=ch, interpret=True)
    yr, str_ = ssd_ref(x, dt, A, B, C)
    np.testing.assert_allclose(y, yr, atol=5e-4, rtol=5e-3)
    np.testing.assert_allclose(st, str_, atol=5e-4, rtol=5e-3)


@pytest.mark.parametrize("e,c,d,f,bc,bf,bd", [
    (2, 64, 64, 64, 64, 64, 64),
    (4, 96, 160, 192, 64, 64, 64),    # non-multiples (padding path)
    (8, 32, 128, 96, 32, 32, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul_vs_ref(e, c, d, f, bc, bf, bd, dtype):
    k = jax.random.PRNGKey(0)
    x = (jax.random.normal(k, (e, c, d)) * 0.3).astype(dtype)
    w = (jax.random.normal(jax.random.PRNGKey(1), (e, d, f)) * 0.3).astype(dtype)
    g = grouped_matmul(x, w, block_c=bc, block_f=bf, block_d=bd,
                       interpret=True)
    gr = grouped_matmul_ref(x, w)
    tol = 3e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(g, np.float32),
                               np.asarray(gr, np.float32), atol=tol, rtol=tol)
