"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import block_size, flash_attention
from repro.kernels.ops import attention_op, rglru_op, ssd_op
from repro.kernels.rglru_scan import rglru_scan_kernel
from repro.kernels.ssd_scan import ssd_scan

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("b,h,s,d", [(1, 1, 128, 64), (2, 4, 256, 64), (1, 2, 512, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window,cap", [
    (True, None, None), (False, None, None), (True, 128, None), (True, None, 50.0),
])
def test_flash_attention_sweep(b, h, s, d, dtype, causal, window, cap):
    k = jax.random.PRNGKey(b * 1000 + h)
    ks = jax.random.split(k, 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    kk = jax.random.normal(ks[1], (b, h, s, d), dtype)
    v = jax.random.normal(ks[2], (b, h, s, d), dtype)
    out = flash_attention(q, kk, v, causal=causal, window=window, softcap=cap,
                          interpret=True)
    want = ref.mha(q, kk, v, causal=causal, window=window, softcap=cap)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=TOL[dtype], rtol=TOL[dtype]
    )


@pytest.mark.parametrize("s,block", [(128, 128), (384, 128), (768, 256), (1024, 512)])
def test_flash_attention_block_shapes(s, block):
    """Blocks follow the sequence: the largest of 512, 256, 128 dividing it."""
    assert block_size(s) == block
    k = jax.random.PRNGKey(0)
    ks = jax.random.split(k, 3)
    q = jax.random.normal(ks[0], (1, 2, s, 64))
    kk = jax.random.normal(ks[1], (1, 2, s, 64))
    v = jax.random.normal(ks[2], (1, 2, s, 64))
    out = flash_attention(q, kk, v, interpret=True)
    want = ref.mha(q, kk, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


def _scaled_err(got, want) -> float:
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    return float(np.abs(g - w).max() / max(np.abs(w).max(), 1.0))


@pytest.mark.parametrize("s", [256, 512])
@pytest.mark.parametrize("h,kv", [(2, 2), (8, 2), (8, 1)])  # GQA ratios 1, 4, 8
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_grads(s, h, kv, causal, dtype):
    """Forward and the gradients of q, k and v against ``ref.mha`` on
    K/V repeated to every query head; the kernel reads them grouped."""
    ks = jax.random.split(jax.random.PRNGKey(s + 10 * h + kv), 4)
    q = jax.random.normal(ks[0], (1, h, s, 128), dtype)
    kk = jax.random.normal(ks[1], (1, kv, s, 128), dtype)
    v = jax.random.normal(ks[2], (1, kv, s, 128), dtype)
    dout = jax.random.normal(ks[3], (1, h, s, 128), dtype)

    def kernel(q, kk, v):
        return flash_attention(q, kk, v, causal=causal, interpret=True)

    def oracle(q, kk, v):
        rep = h // kv
        return ref.mha(q, jnp.repeat(kk, rep, 1), jnp.repeat(v, rep, 1), causal=causal)

    out, kernel_vjp = jax.vjp(kernel, q, kk, v)
    want, oracle_vjp = jax.vjp(oracle, q, kk, v)
    tol = TOL[dtype]
    assert _scaled_err(out, want) < tol
    for name, got, exp in zip("qkv", kernel_vjp(dout), oracle_vjp(dout)):
        assert got.shape == exp.shape and got.dtype == exp.dtype, name
        assert _scaled_err(got, exp) < tol, (name, _scaled_err(got, exp))


@pytest.mark.parametrize("b,h,s,p,n,g,chunk", [
    (1, 2, 128, 32, 64, 1, 32),
    (2, 4, 256, 64, 128, 2, 64),
    (1, 4, 64, 16, 32, 4, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(b, h, s, p, n, g, chunk, dtype):
    k = jax.random.PRNGKey(h * 31 + s)
    ks = jax.random.split(k, 6)
    x = jax.random.normal(ks[0], (b, h, s, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, h, s)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    Bm = jax.random.normal(ks[3], (b, h, s, n), dtype)
    Cm = jax.random.normal(ks[4], (b, h, s, n), dtype)
    st0 = jax.random.normal(ks[5], (b, h, n, p))
    y, st = ssd_scan(x, dt, A, Bm, Cm, st0, chunk=chunk, interpret=True)
    yr, str_ = ref.ssd(x, dt, A, Bm, Cm, st0)
    # error scale-relative to the tensor's magnitude (bf16 accumulations
    # over N=128 produce O(100) values; element-wise rtol misfires on the
    # near-zero entries)
    tol = 1e-3 if dtype == jnp.float32 else 2e-2
    for got, want in ((y, yr), (st, str_)):
        g = np.asarray(got, np.float32)
        w = np.asarray(want, np.float32)
        scale = max(np.abs(w).max(), 1.0)
        assert np.abs(g - w).max() / scale < tol, np.abs(g - w).max() / scale


@pytest.mark.parametrize("b,s,c,t", [
    (1, 128, 64, 32), (2, 256, 128, 64), (3, 64, 256, 64),
    (2, 64, 1024, 32),  # two 512-lane channel blocks, each carried on its own
])
def test_rglru_scan_sweep(b, s, c, t):
    k = jax.random.PRNGKey(s + c)
    ks = jax.random.split(k, 3)
    x = jax.random.normal(ks[0], (b, s, c))
    log_a = -jnp.abs(jax.random.normal(ks[1], (b, s, c))) * 0.3
    h0 = jax.random.normal(ks[2], (b, c))
    h, hl = rglru_scan_kernel(x, log_a, h0, t_block=t, interpret=True)
    hr, hlr = ref.rglru(x, log_a, h0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(hlr), atol=1e-5, rtol=1e-5)


def test_attention_op_gqa():
    """Model-layout wrapper hands grouped KV to the kernel unrepeated."""
    k = jax.random.PRNGKey(7)
    ks = jax.random.split(k, 3)
    q = jax.random.normal(ks[0], (2, 128, 8, 32))
    kk = jax.random.normal(ks[1], (2, 128, 2, 32))
    v = jax.random.normal(ks[2], (2, 128, 2, 32))
    out = attention_op(q, kk, v)
    kr = jnp.repeat(jnp.moveaxis(kk, 1, 2), 4, axis=1)
    vr = jnp.repeat(jnp.moveaxis(v, 1, 2), 4, axis=1)
    want = jnp.moveaxis(ref.mha(jnp.moveaxis(q, 1, 2), kr, vr), 1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_ssd_op_matches_model_layer():
    """Kernel wrapper == the model's chunked XLA implementation."""
    from repro.models.ssm import ssd_chunked

    k = jax.random.PRNGKey(3)
    ks = jax.random.split(k, 5)
    b, s, h, p, n, g = 2, 128, 4, 16, 32, 2
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    Bm = jax.random.normal(ks[3], (b, s, g, n))
    Cm = jax.random.normal(ks[4], (b, s, g, n))
    y_kernel, st_kernel = ssd_op(x, dt, A, Bm, Cm, chunk=32)
    y_model, st_model = ssd_chunked(x, dt, A, Bm, Cm, chunk=32)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(st_kernel), np.asarray(st_model), atol=2e-4, rtol=2e-4)


def test_rglru_op_matches_model_layer():
    from repro.models.rglru import rglru_scan as model_scan

    k = jax.random.PRNGKey(4)
    ks = jax.random.split(k, 2)
    x = jax.random.normal(ks[0], (2, 64, 32))
    log_a = -jnp.abs(jax.random.normal(ks[1], (2, 64, 32))) * 0.2
    h_kernel, hl_kernel = rglru_op(x, log_a, t_block=16)
    h_model, hl_model = model_scan(x, log_a)
    np.testing.assert_allclose(np.asarray(h_kernel), np.asarray(h_model), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(hl_kernel), np.asarray(hl_model), atol=1e-5, rtol=1e-5)
