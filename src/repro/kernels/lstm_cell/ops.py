"""jit'd wrapper for the fused LSTM cell (batch padding + dispatch).

``lstm_cell`` is differentiable: ``pallas_call`` defines no AD rule, so
the public op carries a ``custom_vjp`` whose forward runs the fused
kernel and whose backward rematerializes the reference cell and applies
jax's own VJP to it.  In interpret mode the kernel's forward is
bitwise-equal to the reference (tested on CPU), so gradients routed
through the Pallas cell are exactly those of the reference cell.

``interpret`` selects the Pallas interpreter (CPU tests) instead of the
compiled Mosaic kernel; it defaults to compiled, which is the only mode
a TPU should run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.lstm_cell.lstm_cell import lstm_cell_pallas
from repro.kernels.lstm_cell.ref import lstm_cell_ref


def _lstm_cell_fwd_impl(x, h, c, wx, wh, b, interpret, block_b=128):
    """Pad batch to the block size, run the fused kernel, unpad."""
    bsz = x.shape[0]
    bb = min(block_b, max(8, 1 << (bsz - 1).bit_length()))
    pad = (-bsz) % bb
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        h = jnp.pad(h, ((0, pad), (0, 0)))
        c = jnp.pad(c, ((0, pad), (0, 0)))
    h2, c2 = lstm_cell_pallas(x, h, c, wx, wh, b, block_b=bb,
                              interpret=interpret)
    return h2[:bsz], c2[:bsz]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def lstm_cell(x, h, c, wx, wh, b, interpret=False):
    """Public API; pads batch to the block size and unpads outputs."""
    return _lstm_cell_fwd_impl(x, h, c, wx, wh, b, interpret)


def _lstm_cell_fwd(x, h, c, wx, wh, b, interpret):
    return (_lstm_cell_fwd_impl(x, h, c, wx, wh, b, interpret),
            (x, h, c, wx, wh, b))


def _lstm_cell_bwd(interpret, residuals, cotangents):
    # rematerialize the reference graph and use jax's own VJP of it
    _, vjp = jax.vjp(lstm_cell_ref, *residuals)
    return vjp(cotangents)


lstm_cell.defvjp(_lstm_cell_fwd, _lstm_cell_bwd)


__all__ = ["lstm_cell", "lstm_cell_ref"]
