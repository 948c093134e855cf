"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs
pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import encoder_lstm as net
from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_ref)
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.lstm_cell import lstm_cell, lstm_cell_ref
from repro.kernels.mamba_scan import mamba_scan, mamba_scan_ref
from repro.kernels.moe_router import moe_router, moe_router_ref

jax.config.update("jax_platform_name", "cpu")


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ------------------------------ flash attention ---------------------------

FLASH_SWEEP = [
    # (b, h, hkv, s, d, causal)
    (1, 4, 4, 128, 64, True),     # MHA
    (1, 4, 2, 256, 64, True),     # GQA 2:1
    (2, 8, 1, 128, 128, True),    # MQA
    (1, 2, 2, 192, 64, False),    # non-causal, non-pow2 seq
    (1, 4, 2, 100, 128, True),    # padding path
]


@pytest.mark.parametrize("b,h,hkv,s,d,causal", FLASH_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, h, hkv, s, d, causal, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    out = flash_attention(q, k, v, causal)
    ref = attention_ref(q, k, v, causal=causal)
    assert out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


def test_flash_attention_grad_matches_ref():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 4, 128, 64))
    k = jax.random.normal(ks[1], (1, 2, 128, 64))
    v = jax.random.normal(ks[2], (1, 2, 128, 64))
    g1 = jax.grad(lambda q_: flash_attention(q_, k, v, True).sum())(q)
    g2 = jax.grad(lambda q_: attention_ref(q_, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-4)


# ------------------------------ decode attention --------------------------

DECODE_SWEEP = [
    # (b, h, hkv, s, d, kv_len)
    (1, 4, 4, 512, 64, 512),
    (2, 8, 2, 1024, 128, 700),    # masked tail
    (1, 16, 2, 512, 128, 512),
    (1, 4, 1, 300, 64, 300),      # padding path
]


@pytest.mark.parametrize("b,h,hkv,s,d,kvlen", DECODE_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, h, hkv, s, d, kvlen, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    out = decode_attention(q, k, v, kv_len=kvlen)
    ref = decode_attention_ref(q, k, v, kv_len=kvlen)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


def test_decode_matches_flash_last_row():
    """Decode of the last position == causal flash attention's last row."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    b, h, hkv, s, d = 1, 4, 2, 128, 64
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, hkv, s, d))
    v = jax.random.normal(ks[2], (b, hkv, s, d))
    full = flash_attention(q, k, v, True)
    dec = decode_attention(q[:, :, -1], k, v, kv_len=s)
    np.testing.assert_allclose(dec, full[:, :, -1], rtol=1e-5, atol=1e-5)


# -------------------------------- mamba scan ------------------------------

MAMBA_SWEEP = [
    # (b, l, d, n)
    (1, 64, 128, 16),
    (2, 128, 64, 16),     # d below block -> padding path
    (1, 96, 256, 8),      # non-pow2 length
]


@pytest.mark.parametrize("b,l,d,n", MAMBA_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mamba_scan_sweep(b, l, d, n, dtype):
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    u = jax.random.normal(ks[0], (b, l, d), dtype)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (b, l, d), dtype))
    a = -jnp.exp(jax.random.normal(ks[2], (d, n)))
    bmat = jax.random.normal(ks[3], (b, l, n), dtype)
    cmat = jax.random.normal(ks[4], (b, l, n), dtype)
    skip = jax.random.normal(ks[5], (d,))
    out = mamba_scan(u, delta, a, bmat, cmat, skip)
    ref = mamba_scan_ref(u, delta, a, bmat, cmat, skip)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               **(dict(rtol=5e-2, atol=5e-2)
                                  if dtype == jnp.bfloat16 else
                                  dict(rtol=1e-4, atol=1e-4)))


def test_mamba_scan_grad_finite():
    b, l, d, n = 1, 32, 64, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    u = jax.random.normal(ks[0], (b, l, d))
    delta = jax.nn.softplus(jax.random.normal(ks[1], (b, l, d)))
    a = -jnp.exp(jax.random.normal(ks[2], (d, n)))
    bmat = jax.random.normal(ks[3], (b, l, n))
    cmat = jax.random.normal(ks[4], (b, l, n))
    skip = jax.random.normal(ks[5], (d,))
    g = jax.grad(lambda u_: mamba_scan(u_, delta, a, bmat, cmat,
                                       skip).sum())(u)
    assert bool(jnp.isfinite(g).all())


# --------------------------------- lstm cell ------------------------------

LSTM_SWEEP = [
    # (batch, n_in, hidden)
    (8, 32, 32),      # the paper's encoder-LSTM geometry
    (130, 32, 32),    # padding path
    (64, 128, 64),
]


@pytest.mark.parametrize("bsz,nin,hid", LSTM_SWEEP)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lstm_cell_sweep(bsz, nin, hid, dtype):
    ks = jax.random.split(jax.random.PRNGKey(6), 6)
    x = jax.random.normal(ks[0], (bsz, nin), dtype)
    h = jax.random.normal(ks[1], (bsz, hid), dtype)
    c = jax.random.normal(ks[2], (bsz, hid), dtype)
    wx = jax.random.normal(ks[3], (nin, 4 * hid), dtype) * 0.2
    wh = jax.random.normal(ks[4], (hid, 4 * hid), dtype) * 0.2
    b = jax.random.normal(ks[5], (4 * hid,), dtype) * 0.1
    h2, c2 = lstm_cell(x, h, c, wx, wh, b, interpret=True)
    hr, cr = lstm_cell_ref(x, h, c, wx, wh, b)
    np.testing.assert_allclose(np.asarray(h2, np.float32),
                               np.asarray(hr, np.float32), **tol(dtype))
    np.testing.assert_allclose(np.asarray(c2, np.float32),
                               np.asarray(cr, np.float32), **tol(dtype))


def test_lstm_kernel_matches_core_network_cell():
    """The kernel implements exactly the core encoder_lstm cell."""
    layer = net._lstm_init(jax.random.PRNGKey(7), 32, 32)
    x = jax.random.normal(jax.random.PRNGKey(8), (16, 32))
    h = jnp.zeros((16, 32))
    c = jnp.zeros((16, 32))
    h1, c1 = net.lstm_cell_apply(layer, h, c, x)
    h2, c2 = lstm_cell(x, h, c, layer["wx"], layer["wh"], layer["b"],
                       interpret=True)
    np.testing.assert_allclose(h1, h2, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(c1, c2, rtol=1e-5, atol=1e-6)


# --------------------------------- moe router -----------------------------

ROUTER_SWEEP = [
    # (tokens, experts, k)
    (256, 8, 2),
    (512, 128, 8),     # qwen3-moe geometry
    (300, 256, 8),     # deepseek-v3 geometry + padding path
    (64, 16, 2),       # jamba geometry
]


@pytest.mark.parametrize("t,e,k", ROUTER_SWEEP)
def test_moe_router_sweep(t, e, k):
    logits = jax.random.normal(jax.random.PRNGKey(9), (t, e))
    w, idx = moe_router(logits, k)
    wr, idxr = moe_router_ref(logits, k)
    # weight sets must match (order may differ on ties; none expected with
    # random floats)
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(idxr, -1))
    np.testing.assert_allclose(np.sort(w, -1), np.sort(wr, -1),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-5)


def test_moe_router_weights_positive_topk():
    logits = jax.random.normal(jax.random.PRNGKey(10), (128, 32))
    w, idx = moe_router(logits, 4)
    assert (np.asarray(w) > 0).all()
    assert (np.asarray(idx) >= 0).all() and (np.asarray(idx) < 32).all()
    # indices unique per row
    assert all(len(set(row)) == 4 for row in np.asarray(idx))
