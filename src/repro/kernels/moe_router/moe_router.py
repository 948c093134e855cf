"""Fused top-k softmax MoE router kernel.

Per token-block: row softmax over E experts (fp32, max-subtracted), then k
sequential argmax+mask passes selecting the top-k experts, renormalizing
the selected probabilities (Qwen3 `norm_topk_prob` semantics; DeepSeek-V3's
sigmoid+bias variant shares the same dispatch shape — see models/moe.py).

grid = (token_blocks,); block (block_t, E) fits VMEM for E <= 512 at
block_t = 256. Outputs: weights (T, k) fp32 and indices (T, k) int32 —
the int32 index matrix feeds the all-to-all dispatch in the EP runtime.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _router_kernel(logits_ref, w_ref, idx_ref, *, k: int):
    x = logits_ref[...].astype(jnp.float32)           # (bt, E)
    m = jnp.max(x, axis=1, keepdims=True)
    p = jnp.exp(x - m)
    p = p / jnp.sum(p, axis=1, keepdims=True)          # softmax
    bt, e = p.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (bt, e), 1)
    masked = p
    for j in range(k):
        best = jnp.argmax(masked, axis=1).astype(jnp.int32)   # (bt,)
        wj = jnp.max(masked, axis=1)                           # (bt,)
        idx_ref[:, j] = best
        w_ref[:, j] = wj
        masked = jnp.where(cols == best[:, None], NEG_INF, masked)
    # renormalize the selected top-k weights
    total = jnp.zeros((bt,), jnp.float32)
    for j in range(k):
        total = total + w_ref[:, j]
    for j in range(k):
        w_ref[:, j] = w_ref[:, j] / jnp.maximum(total, 1e-20)


def moe_router_pallas(logits: jax.Array, k: int, *, block_t: int = 256,
                      interpret: bool = True
                      ) -> tuple[jax.Array, jax.Array]:
    """logits: (T, E) -> (weights (T, k) f32, indices (T, k) i32)."""
    t, e = logits.shape
    assert t % block_t == 0
    nt = t // block_t
    kernel = functools.partial(_router_kernel, k=k)
    return pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[pl.BlockSpec((block_t, e), lambda it: (it, 0))],
        out_specs=(pl.BlockSpec((block_t, k), lambda it: (it, 0)),
                   pl.BlockSpec((block_t, k), lambda it: (it, 0))),
        out_shape=(jax.ShapeDtypeStruct((t, k), jnp.float32),
                   jax.ShapeDtypeStruct((t, k), jnp.int32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(logits)
