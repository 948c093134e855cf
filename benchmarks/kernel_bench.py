"""Kernel microbenchmarks.

Two surfaces:

  * :func:`rows` — jitted XLA-oracle wall time on CPU for the scaffold's
    CSV contract (the Pallas kernels are TPU-targeted; interpret mode is
    a correctness harness, not a timing one — see DESIGN.md), consumed
    by ``benchmarks/run.py``;
  * :func:`main` — the fused Pallas **LSTM cell** benchmark (forward +
    custom-VJP backward, vs the jnp reference cell), written to
    ``BENCH_kernel.json`` for the CI perf-smoke lane.  On a CPU backend
    it runs the kernel in **interpret mode** (Pallas emulated op by op —
    the number is a correctness-path cost, expected to be much slower
    than the XLA reference); on a TPU both legs run the compiled Mosaic
    kernel.  The artifact carries a host fingerprint and the backend, so
    ``check_perf.py``-style consumers never compare across hardware.

    PYTHONPATH=src python benchmarks/kernel_bench.py [--repeats N]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from common import host_fingerprint  # noqa: E402

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import encoder_lstm as net
from repro.kernels.decode_attention import decode_attention_xla
from repro.kernels.flash_attention import attention_xla
from repro.kernels.lstm_cell import lstm_cell, lstm_cell_ref
from repro.kernels.mamba_scan import mamba_scan_xla
from repro.kernels.moe_router import moe_router_xla

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _time(fn, *args, repeats=5, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e6


def rows() -> list[list]:
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    out = []

    b, h, hkv, s, d = 1, 8, 2, 512, 64
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.bfloat16)
    us = _time(attention_xla, q, k, v)
    flops = 4 * b * h * s * s * d
    out.append(["flash_attention_xla_512", round(us, 1),
                f"{flops / us * 1e-3:.1f}GF/s"])

    qd = jax.random.normal(ks[3], (4, h, d), jnp.bfloat16)
    kc = jax.random.normal(ks[4], (4, hkv, 2048, d), jnp.bfloat16)
    us = _time(decode_attention_xla, qd, kc, kc)
    out.append(["decode_attention_xla_2k", round(us, 1),
                f"kv_bytes={kc.nbytes * 2}"])

    bl, ell, dm, n = 1, 256, 256, 16
    u = jax.random.normal(ks[5], (bl, ell, dm), jnp.bfloat16)
    delta = jax.nn.softplus(jax.random.normal(ks[6], (bl, ell, dm),
                                              jnp.bfloat16))
    a = -jnp.exp(jax.random.normal(ks[7], (dm, n)))
    bm = jax.random.normal(ks[5], (bl, ell, n), jnp.bfloat16)
    cm = jax.random.normal(ks[6], (bl, ell, n), jnp.bfloat16)
    us = _time(mamba_scan_xla, u, delta, a, bm, cm, jnp.ones(dm))
    out.append(["mamba_scan_xla_256", round(us, 1), f"L={ell} D={dm}"])

    logits = jax.random.normal(ks[0], (2048, 128))
    us = _time(moe_router_xla, logits, 8)
    out.append(["moe_router_xla_2k_128e", round(us, 1), "top8"])

    # the paper's own hot loop: batched encoder-LSTM inference
    params = net.init_params(jax.random.PRNGKey(0), input_dim=490)
    xs = jax.random.normal(jax.random.PRNGKey(1), (5, 256, 490))
    us = _time(net.predict_sequence, params, xs)
    out.append(["encoder_lstm_predict_256jobs", round(us, 1), "T=5"])
    return out


# ------------------- fused Pallas LSTM cell -> BENCH_kernel.json ------------


def _median_us(fn, *args, repeats: int = 20) -> float:
    out = fn(*args)
    jax.block_until_ready(out)        # compile outside the timed region
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6


def _cell_args(batch: int, hidden: int, n_in: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(batch, n_in)), jnp.float32)
    h = jnp.asarray(rng.normal(size=(batch, hidden)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(batch, hidden)), jnp.float32)
    layer = net._lstm_init(jax.random.PRNGKey(seed), n_in, hidden)
    return x, h, c, layer["wx"], layer["wh"], layer["b"]


def bench_lstm_cell(repeats: int = 20, interpret: bool | None = None
                    ) -> dict:
    """Time the fused LSTM cell (forward + custom-VJP backward) against
    the jnp reference at model-relevant shapes.

    ``interpret=None`` resolves from the backend: interpret mode on a
    CPU, compiled Mosaic on a TPU.
    """
    backend = jax.default_backend()
    if interpret is None:
        interpret = backend != "tpu"

    def pallas_fwd(x, h, c, wx, wh, b):
        return lstm_cell(x, h, c, wx, wh, b, interpret=interpret)

    def grad_of(cell):
        def loss(x, h, c, wx, wh, b):
            h2, c2 = cell(x, h, c, wx, wh, b)
            return (h2 * h2 + c2).sum()
        return jax.grad(loss, argnums=(3, 4, 5))

    results = []
    # (batch, hidden) — hidden 32 is the model's LSTM_HIDDEN; 128 the
    # block-padded serving shape; 64/256 headroom points
    for batch, hidden in ((128, 32), (256, 32), (256, 64)):
        n_in = hidden  # encoder output feeds the cell at ENC_OUT == H
        args = _cell_args(batch, hidden, n_in)
        row = {"batch": batch, "hidden": hidden, "n_in": n_in}
        row["ref_fwd_us"] = round(_median_us(
            jax.jit(lstm_cell_ref), *args, repeats=repeats), 1)
        row["pallas_fwd_us"] = round(_median_us(
            jax.jit(pallas_fwd), *args, repeats=repeats), 1)
        row["ref_vjp_us"] = round(_median_us(
            jax.jit(grad_of(lstm_cell_ref)), *args, repeats=repeats), 1)
        row["pallas_vjp_us"] = round(_median_us(
            jax.jit(grad_of(pallas_fwd)), *args, repeats=repeats), 1)
        # correctness cross-check rides along: bitwise in interpret mode
        # (tested); compiled, the matmul passes may differ from XLA's
        h_ref, c_ref = jax.jit(lstm_cell_ref)(*args)
        h_pal, c_pal = jax.jit(pallas_fwd)(*args)
        row["bitwise_fwd"] = bool(
            np.array_equal(np.asarray(h_ref), np.asarray(h_pal))
            and np.array_equal(np.asarray(c_ref), np.asarray(c_pal)))
        row["max_abs_err_fwd"] = float(max(
            np.abs(np.asarray(h_ref) - np.asarray(h_pal)).max(),
            np.abs(np.asarray(c_ref) - np.asarray(c_pal)).max()))
        results.append(row)

    return {
        "host": host_fingerprint(),
        "backend": backend,
        "interpret": bool(interpret),
        "mode": "interpret" if interpret else "compiled",
        "repeats": repeats,
        "jax": jax.__version__,
        "cells": results,
    }


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--out", default=os.path.join(REPO_ROOT,
                                                  "BENCH_kernel.json"))
    args = ap.parse_args(argv)
    out = bench_lstm_cell(repeats=args.repeats)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")
    print(json.dumps(out, indent=1, sort_keys=True))
    return out


if __name__ == "__main__":
    sys.exit(0 if main() else 1)
