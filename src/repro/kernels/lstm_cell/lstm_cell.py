"""Fused Encoder-LSTM cell kernel — the paper's own compute hot-spot.

START runs Encoder-LSTM inference for EVERY active job EVERY interval
(thousands of jobs x T steps). Unfused, one LSTM cell step is ~12 XLA ops
(2 matmuls, add, bias, 4 splits, 3 sigmoids, 2 tanh, 2 FMAs) each
round-tripping HBM. This kernel fuses the whole cell for a batch block:

    z = x @ Wx + h @ Wh + b ;  i,f,g,o = split(z)
    c' = sigma(f)*c + sigma(i)*tanh(g) ;  h' = sigma(o)*tanh(c')

grid = (batch_blocks,); weights are broadcast into VMEM once per block
(index_map pins them to block 0); gate width 4H = 128 for the paper's
H = 32 — exactly one MXU tile. fp32 accumulation, I/O in input dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lstm_kernel(x_ref, h_ref, c_ref, wx_ref, wh_ref, b_ref, h_out, c_out):
    x = x_ref[...].astype(jnp.float32)
    h = h_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    z = (jax.lax.dot(x, wx_ref[...].astype(jnp.float32),
                     preferred_element_type=jnp.float32)
         + jax.lax.dot(h, wh_ref[...].astype(jnp.float32),
                       preferred_element_type=jnp.float32)
         + b_ref[...].astype(jnp.float32))
    hid = h.shape[-1]
    i = jax.nn.sigmoid(z[:, :hid])
    f = jax.nn.sigmoid(z[:, hid:2 * hid])
    g = jnp.tanh(z[:, 2 * hid:3 * hid])
    o = jax.nn.sigmoid(z[:, 3 * hid:])
    c_new = f * c + i * g
    h_new = o * jnp.tanh(c_new)
    h_out[...] = h_new.astype(h_out.dtype)
    c_out[...] = c_new.astype(c_out.dtype)


def lstm_cell_pallas(x: jax.Array, h: jax.Array, c: jax.Array,
                     wx: jax.Array, wh: jax.Array, b: jax.Array, *,
                     block_b: int = 128,
                     interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """x: (B, In); h, c: (B, H); wx: (In, 4H); wh: (H, 4H); b: (4H,)."""
    bsz, n_in = x.shape
    hid = h.shape[1]
    assert wx.shape == (n_in, 4 * hid) and wh.shape == (hid, 4 * hid)
    assert bsz % block_b == 0
    nb = bsz // block_b
    b2 = b.reshape(1, 4 * hid)

    out_shape = (jax.ShapeDtypeStruct((bsz, hid), h.dtype),
                 jax.ShapeDtypeStruct((bsz, hid), c.dtype))
    h_new, c_new = pl.pallas_call(
        _lstm_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_b, n_in), lambda ib: (ib, 0)),
            pl.BlockSpec((block_b, hid), lambda ib: (ib, 0)),
            pl.BlockSpec((block_b, hid), lambda ib: (ib, 0)),
            pl.BlockSpec((n_in, 4 * hid), lambda ib: (0, 0)),
            pl.BlockSpec((hid, 4 * hid), lambda ib: (0, 0)),
            pl.BlockSpec((1, 4 * hid), lambda ib: (0, 0)),
        ],
        out_specs=(pl.BlockSpec((block_b, hid), lambda ib: (ib, 0)),
                   pl.BlockSpec((block_b, hid), lambda ib: (ib, 0))),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, h, c, wx, wh, b2)
    return h_new, c_new
