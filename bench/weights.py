"""Encoder-LSTM weights from ``--seed``, made on the device in one jitted call.

The benchmark makes the weights itself (and the reference makes the same
ones again), so the reference takes nothing the program made.  The
pytree layout is the one ``StragglerPredictor.params`` holds; its widths
come from the configuration file's ``network`` block.  Weights are
untrained: normal, scaled by ``gain / sqrt(fan-in)`` (the configuration's
``init`` block gives one gain for the first encoder layer and one for
the layers after it, up to the head); biases are small and nonzero, so
a dropped bias shows in the comparison.
"""
from __future__ import annotations

import functools

from bench.common import seed_int


def _shapes(input_dim: int, net: dict) -> dict:
    h, e, lh = net["enc_hidden"], net["enc_out"], net["lstm_hidden"]
    enc = [(input_dim, h), (h, h), (h, h), (h, e)]
    lstm = [(e if i == 0 else lh, lh) for i in range(net["lstm_layers"])]
    return {"enc": enc, "lstm": lstm, "head": (lh, 2)}


@functools.lru_cache(maxsize=None)
def _maker(input_dim: int, net_items: tuple, gain_first: float,
           gain_rest: float, bias_std: float):
    import jax
    import jax.numpy as jnp
    shapes = _shapes(input_dim, dict(net_items))

    def normal(key, shape, gain):
        return (gain / jnp.sqrt(jnp.float32(shape[0]))) * jax.random.normal(
            key, shape, jnp.float32)

    def bias(key, n):
        return bias_std * jax.random.normal(key, (n,), jnp.float32)

    def make(key):
        keys = iter(jax.random.split(key, 32))
        enc = [{"w": normal(next(keys), s, gain_first if i == 0
                            else gain_rest),
                "b": bias(next(keys), s[1])}
               for i, s in enumerate(shapes["enc"])]
        lstm = [{"wx": normal(next(keys), (n_in, 4 * hid), gain_rest),
                 "wh": normal(next(keys), (hid, 4 * hid), gain_rest),
                 "b": bias(next(keys), 4 * hid)}
                for n_in, hid in shapes["lstm"]]
        head = {"w": normal(next(keys), shapes["head"], 1.0),
                "b": bias(next(keys), shapes["head"][1])}
        return {"enc": enc, "lstm": lstm, "head": head}

    return jax.jit(make)


def make_params(seed: int, input_dim: int, net: dict, init: dict):
    """The weights of ``seed`` as device arrays (float32)."""
    import jax
    key = jax.random.PRNGKey(seed_int(seed, "weights"))
    dims = tuple(sorted((k, net[k]) for k in
                        ("enc_hidden", "enc_out", "lstm_hidden",
                         "lstm_layers")))
    return _maker(input_dim, dims, float(init["gain_first"]),
                  float(init["gain_rest"]), float(init["bias_std"]))(key)


def host_params(seed: int, input_dim: int, net: dict, init: dict) -> dict:
    """The same weights, copied to the host as numpy arrays."""
    import jax
    import numpy as np
    return jax.tree_util.tree_map(
        np.asarray, make_params(seed, input_dim, net, init))
