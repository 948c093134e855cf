"""Blocked online-softmax (flash) attention for TPU via Pallas.

TPU-native design (see DESIGN.md §6):
  * grid = (batch, q_heads, q_blocks, kv_blocks); the kv dimension is
    innermost and sequential ("arbitrary"), so the (m, l, acc) running
    softmax state lives in VMEM scratch across kv iterations — the classic
    TPU flash layout (state never round-trips to HBM).
  * BlockSpecs tile Q/K/V into (block_q|block_k, head_dim) VMEM tiles;
    head_dim and block sizes are MXU-aligned (multiples of 128 / the fp32
    (8,128) tile).
  * GQA: the K/V index_map divides the query-head index by the group size,
    so a KV block is fetched once per group and reused from VMEM.
  * Causal masking skips fully-masked kv blocks via pl.when (a production
    grid would also shrink the kv extent per q block; we keep the full grid
    and predicate, as jax's reference TPU kernel does).

Scratch (m, l) are kept (block_q, LANES)-shaped: TPU vector registers are
(8, 128) tiles, so a (block_q,) vector would be padded anyway; broadcasting
across lanes keeps every op tile-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANES = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  sm_scale: float, causal: bool, block_q: int, block_k: int,
                  kv_len: int, num_k_blocks: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip kv blocks entirely in the causal future of this q block
    if causal:
        run = (iq + 1) * block_q > ik * block_k
    else:
        run = True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (bq, bk)
        kpos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = kpos < kv_len                          # padded keys
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = mask & (qpos >= kpos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...][:, :1]                    # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)     # (bq, 1)
        m_next = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_next)              # (bq, 1)
        p = jnp.exp(s - m_next)                       # (bq, bk)
        p = jnp.where(mask, p, 0.0)
        l_prev = l_scr[...][:, :1]
        l_next = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_next, l_scr.shape)

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        l = l_scr[...][:, :1]
        out = acc_scr[...] / jnp.maximum(l, 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, sm_scale: float | None = None,
                           block_q: int = 128, block_k: int = 128,
                           kv_len: int | None = None,
                           interpret: bool = True) -> jax.Array:
    """q: (B, H, Sq, D); k, v: (B, Hkv, Sk, D) with H % Hkv == 0.

    Sq/Sk must be multiples of block_q/block_k (ops.py pads); ``kv_len``
    masks out padded keys.
    """
    b, h, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert h % hkv == 0, (h, hkv)
    group = h // hkv
    assert sq % block_q == 0 and sk % block_k == 0
    sm_scale = sm_scale if sm_scale is not None else d ** -0.5
    kv_len = kv_len if kv_len is not None else sk
    nq, nk = sq // block_q, sk // block_k

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, kv_len=kv_len, num_k_blocks=nk)

    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, iq, ik, g=group: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, iq, ik, g=group: (b_, h_ // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
