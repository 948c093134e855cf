"""Flash-decode attention kernel: one new token vs. a long KV cache.

TPU-native layout: queries are reshaped (B, H, D) -> (B, Hkv, G, D) so each
grid cell computes a (G x block_k) score matrix on the MXU for one KV head's
whole GQA group (G = H/Hkv query heads share the KV block already resident
in VMEM). grid = (B, Hkv, kv_blocks) with the kv dimension sequential; the
online-softmax state (m, l, acc) persists in VMEM scratch across kv blocks.
This is the serving hot loop for decode_32k / long_500k shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANES = 128
NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                   sm_scale: float, block_k: int, kv_len: int,
                   num_k_blocks: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)               # (G, d)
    k = k_ref[0, 0].astype(jnp.float32)               # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)               # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    kpos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    s = jnp.where(kpos < kv_len, s, NEG_INF)

    m_prev = m_scr[...][:, :1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_next = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - m_next)
    p = jnp.where(kpos < kv_len, p, 0.0)
    l_scr[...] = jnp.broadcast_to(
        alpha * l_scr[...][:, :1] + jnp.sum(p, axis=1, keepdims=True),
        l_scr.shape)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...][:, :1], 1e-30)
                       ).astype(o_ref.dtype)


def decode_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                            sm_scale: float | None = None,
                            block_k: int = 512, kv_len: int | None = None,
                            interpret: bool = True) -> jax.Array:
    """q: (B, H, D); k, v: (B, Hkv, S, D). Returns (B, H, D)."""
    b, h, d = q.shape
    _, hkv, sk, _ = k.shape
    assert h % hkv == 0
    g = h // hkv
    assert sk % block_k == 0
    sm_scale = sm_scale if sm_scale is not None else d ** -0.5
    kv_len = kv_len if kv_len is not None else sk
    nk = sk // block_k
    qg = q.reshape(b, hkv, g, d)

    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale,
                               block_k=block_k, kv_len=kv_len,
                               num_k_blocks=nk)
    out = pl.pallas_call(
        kernel,
        grid=(b, hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, hk, ik: (b_, hk, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, hk, ik: (b_, hk, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, hk, ik: (b_, hk, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda b_, hk, ik: (b_, hk, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, LANES), jnp.float32),
            pltpu.VMEM((g, LANES), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qg, k, v)
    return out.reshape(b, h, d)
