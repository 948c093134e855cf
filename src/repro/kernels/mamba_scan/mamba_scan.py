"""Selective-scan (Mamba-1) Pallas kernel, chunked for TPU.

Recurrence per channel d with state size N:
    h_t = exp(delta_t[d] * A[d]) * h_{t-1} + (delta_t[d] * u_t[d]) * B_t
    y_t[d] = <C_t, h_t> + D[d] * u_t[d]

TPU adaptation (the original is a CUDA kernel with warp-level scans):
  * grid = (batch, d_blocks, l_chunks); the time dimension is innermost and
    sequential — the (block_d, N) state h persists in VMEM scratch across
    chunks, so the recurrence never leaves VMEM.
  * channels are blocked to the 128-lane register width; the per-step math
    is (block_d, N) elementwise FMAs + an N-reduction, which the VPU
    vectorizes across the channel block (no MXU needed — the op is
    bandwidth-bound, so the win is VMEM residency, not systolic compute).
  * within a chunk we iterate timesteps with fori_loop + dynamic stores
    (a chunk-parallel associative scan is a further optimization documented
    in EXPERIMENTS.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, skip_ref, o_ref,
                 h_scr, *, chunk: int):
    il = pl.program_id(2)

    @pl.when(il == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[...].astype(jnp.float32)          # (bd, N)
    skip = skip_ref[...].astype(jnp.float32)    # (1, bd)

    def step(t, h):
        row = (slice(None), pl.ds(t, 1), slice(None))
        u_t = u_ref[row][0, 0].astype(jnp.float32)     # (bd,)
        dt_t = dt_ref[row][0, 0].astype(jnp.float32)   # (bd,)
        b_t = b_ref[row][0, 0].astype(jnp.float32)     # (N,)
        c_t = c_ref[row][0, 0].astype(jnp.float32)     # (N,)
        decay = jnp.exp(dt_t[:, None] * a)          # (bd, N)
        h = decay * h + (dt_t * u_t)[:, None] * b_t[None, :]
        y = jnp.sum(h * c_t[None, :], axis=1) + skip[0] * u_t  # (bd,)
        o_ref[row] = y[None, None].astype(o_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk, step, h_scr[...])


def mamba_scan_pallas(u: jax.Array, delta: jax.Array, a: jax.Array,
                      b: jax.Array, c: jax.Array, skip: jax.Array, *,
                      block_d: int = 128, chunk: int = 64,
                      interpret: bool = True) -> jax.Array:
    """u, delta: (B, L, D); a: (D, N); b, c: (B, L, N); skip: (D,).

    L must divide by ``chunk`` and D by ``block_d`` (ops.py pads).
    """
    bsz, ell, d = u.shape
    n = a.shape[1]
    assert ell % chunk == 0 and d % block_d == 0
    nd, nl = d // block_d, ell // chunk
    skip2 = skip.reshape(1, d)

    kernel = functools.partial(_scan_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(bsz, nd, nl),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d),
                         lambda b_, id_, il: (b_, il, id_)),
            pl.BlockSpec((1, chunk, block_d),
                         lambda b_, id_, il: (b_, il, id_)),
            pl.BlockSpec((block_d, n), lambda b_, id_, il: (id_, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, id_, il: (b_, il, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, id_, il: (b_, il, 0)),
            pl.BlockSpec((1, block_d), lambda b_, id_, il: (0, id_)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_d),
                               lambda b_, id_, il: (b_, il, id_)),
        out_shape=jax.ShapeDtypeStruct((bsz, ell, d), u.dtype),
        scratch_shapes=[pltpu.VMEM((block_d, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(u, delta, a, b, c, skip2)
