"""Encoder-LSTM straggler-prediction network (paper §3.2, Fig. 4) in pure JAX.

Architecture (faithful to the paper):
  - Encoder: 4 fully-connected layers, softplus activations:
        input(|M_H| + |M_T|) -> 128 -> 128 -> 32
    (the first "layer" in the paper is the input layer with softplus applied;
    we apply softplus after each of the four affine maps).
  - LSTM: 2 layers, hidden size 32. eta_0 = 0.
  - Head: FC(2); alpha = relu(o0) + 1 (so the Pareto mean exists),
    beta = relu(o1) + BETA_EPS (strictly positive scale).
  - Inputs are EMA-smoothed with weight EMA_W = 0.8 on the newest matrices
    (paper cites [36]); the cell is iterated every I seconds for T seconds.

Params are plain dict pytrees; everything is jit/vmap-friendly. The fused
Pallas kernel in ``repro.kernels.lstm_cell`` implements the same cell; tests
assert exact agreement with ``lstm_cell_apply`` below in the Pallas
interpreter (compiled on a TPU it agrees within the Tier-1 bound).
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

EMA_W = 0.8          # weight of the *latest* resource matrix (paper §3.2)
BETA_EPS = 1e-3      # strictly-positive Pareto scale
ENC_HIDDEN = 128
ENC_OUT = 32
LSTM_HIDDEN = 32
LSTM_LAYERS = 2

#: Every matmul of the network — the Pallas cell's included — runs at
#: full f32 precision.  XLA on a TPU otherwise runs f32 dots in fewer
#: bf16 passes, and the fused (Tier-1) and reference (Tier-0) programs,
#: which split and fuse the dots differently, then drift 1.9e-5 apart on
#: a v5e: past the Tier-1 bound of 1e-5.  On the CPU it changes nothing.
MATMUL_PRECISION = "highest"

Params = dict  # pytree


def _mm(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.matmul(a, b, precision=MATMUL_PRECISION)


def _dense_init(key, n_in, n_out, scale=None):
    scale = scale if scale is not None else (1.0 / jnp.sqrt(n_in))
    wkey, _ = jax.random.split(key)
    return {
        "w": jax.random.normal(wkey, (n_in, n_out), jnp.float32) * scale,
        "b": jnp.zeros((n_out,), jnp.float32),
    }


def _lstm_init(key, n_in, hidden):
    k1, k2 = jax.random.split(key)
    s_in = 1.0 / jnp.sqrt(n_in)
    s_h = 1.0 / jnp.sqrt(hidden)
    return {
        # gates packed as [i, f, g, o] along the last dim
        "wx": jax.random.normal(k1, (n_in, 4 * hidden), jnp.float32) * s_in,
        "wh": jax.random.normal(k2, (hidden, 4 * hidden), jnp.float32) * s_h,
        "b": jnp.zeros((4 * hidden,), jnp.float32),
    }


def init_params(key: jax.Array, input_dim: int,
                enc_hidden: int = ENC_HIDDEN, enc_out: int = ENC_OUT,
                lstm_hidden: int = LSTM_HIDDEN,
                lstm_layers: int = LSTM_LAYERS) -> Params:
    keys = jax.random.split(key, 4 + lstm_layers + 1)
    enc = [
        _dense_init(keys[0], input_dim, enc_hidden),
        _dense_init(keys[1], enc_hidden, enc_hidden),
        _dense_init(keys[2], enc_hidden, enc_hidden),
        _dense_init(keys[3], enc_hidden, enc_out),
    ]
    lstm = []
    n_in = enc_out
    for i in range(lstm_layers):
        lstm.append(_lstm_init(keys[4 + i], n_in, lstm_hidden))
        n_in = lstm_hidden
    head = _dense_init(keys[4 + lstm_layers], lstm_hidden, 2)
    return {"enc": enc, "lstm": lstm, "head": head}


def encoder_apply(params: Params, x: jax.Array) -> jax.Array:
    """4-layer softplus MLP (paper's Encoder network)."""
    h = x
    for layer in params["enc"]:
        h = jax.nn.softplus(_mm(h, layer["w"]) + layer["b"])
    return h


def lstm_cell_apply(layer: Params, h: jax.Array, c: jax.Array,
                    x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One LSTM cell step; gates packed [i, f, g, o]."""
    z = _mm(x, layer["wx"]) + _mm(h, layer["wh"]) + layer["b"]
    i, f, g, o = jnp.split(z, 4, axis=-1)
    c_new = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
    return h_new, c_new


def _cell_apply(layer: Params, h: jax.Array, c: jax.Array, x: jax.Array,
                use_pallas: bool | str = False
                ) -> tuple[jax.Array, jax.Array]:
    """Dispatch one cell step to the jnp cell or the fused Pallas kernel
    (``repro.kernels.lstm_cell``).  ``use_pallas=True`` runs the compiled
    Mosaic kernel (TPU); ``"interpret"`` runs it in the Pallas
    interpreter (CPU tests)."""
    if not use_pallas:
        return lstm_cell_apply(layer, h, c, x)
    from repro.kernels.lstm_cell import lstm_cell
    batch = h.shape[:-1]
    hid = h.shape[-1]
    with jax.default_matmul_precision(MATMUL_PRECISION):
        h2, c2 = lstm_cell(x.reshape(-1, x.shape[-1]), h.reshape(-1, hid),
                           c.reshape(-1, hid), layer["wx"], layer["wh"],
                           layer["b"], interpret=use_pallas == "interpret")
    return h2.reshape(*batch, hid), c2.reshape(*batch, hid)


class LSTMState(NamedTuple):
    h: jax.Array  # (layers, ..., hidden)
    c: jax.Array


def init_state(params: Params, batch_shape: tuple = ()) -> LSTMState:
    layers = len(params["lstm"])
    hidden = params["lstm"][0]["wh"].shape[0]
    z = jnp.zeros((layers, *batch_shape, hidden), jnp.float32)
    return LSTMState(h=z, c=z)


def step_decoded(params: Params, state: LSTMState, lam: jax.Array,
                 use_pallas: bool = False) -> tuple[LSTMState, jax.Array]:
    """LSTM + head over an already-encoded input (the recurrent half of
    :func:`step`).  Factored out so Tier-1 callers can hoist the encoder
    out of the scan entirely (:func:`decode_sequence`) — the op graph
    here is byte-identical to the tail of the historical ``step``."""
    hs, cs = [], []
    inp = lam
    for li, layer in enumerate(params["lstm"]):
        h_new, c_new = _cell_apply(layer, state.h[li], state.c[li], inp,
                                   use_pallas=use_pallas)
        hs.append(h_new)
        cs.append(c_new)
        inp = h_new
    new_state = LSTMState(h=jnp.stack(hs), c=jnp.stack(cs))
    out = _mm(inp, params["head"]["w"]) + params["head"]["b"]
    # positivity head: the paper uses ReLU (+1 on alpha); we use softplus —
    # same constraint, but a ReLU alpha-head that initializes negative is
    # DEAD (alpha pinned to 1.0 -> E_S ~ 0 -> START never mitigates).
    # Deviation noted in DESIGN.md.
    alpha = jax.nn.softplus(out[..., 0]) + 1.0
    beta = jax.nn.softplus(out[..., 1]) + BETA_EPS
    return new_state, jnp.stack([alpha, beta], axis=-1)


def step(params: Params, state: LSTMState, x: jax.Array,
         use_pallas: bool = False) -> tuple[LSTMState, jax.Array]:
    """One inference step: encoder -> stacked LSTM -> (alpha, beta) head."""
    return step_decoded(params, state, encoder_apply(params, x),
                        use_pallas=use_pallas)


def ema_smooth(seq: jax.Array, w: float = EMA_W) -> jax.Array:
    """Exponential moving average along axis 0 with weight w on the newest
    element (paper §3.2): s_t = w*x_t + (1-w)*s_{t-1}, s_0 = x_0."""

    def f(carry, x):
        s = w * x + (1.0 - w) * carry
        return s, s

    _, out = jax.lax.scan(f, seq[0], seq)
    return out.at[0].set(seq[0])


# --------------------------- Tier-1 fast path ------------------------------
#
# The functions below restructure the emission for speed and are governed
# by the repo's Tier-1 determinism contract (documented relative/ulp
# tolerance vs the bitwise reference path; see README "Performance" and
# tests/tolerance.py).  ``predict_sequence`` below stays the bitwise
# Tier-0-compatible reference — do not restructure it.


def encoder_hoisted(params: Params, mh_ema: jax.Array,
                    mt: jax.Array) -> jax.Array:
    """Encoder over a (T, host_dim) shared host block + (nb, task_dim)
    per-job task block, hoisted out of the recurrent scan.

    Two restructurings relative to ``encoder_apply`` over the assembled
    (T, nb, input_dim) batch, both Tier-1 (ulp-level drift, never
    bitwise-pinned):

      * the first layer's matmul is split at the host/task column
        boundary — the shared host product ``mh_ema @ W[:host_dim]`` is
        computed once per step instead of once per job (host_dim
        dominates input_dim for real cluster sizes), and the task
        product once per job instead of once per (step, job).  Summing
        two partial dots changes the reduction order of the full-width
        dot by a few ulps.
      * ``mt`` is used raw instead of EMA-smoothed: the task block is
        constant across the horizon, and the EMA of a constant sequence
        is the constant itself (s_t = w*x + (1-w)*x = x, exactly in
        real arithmetic, within 1 ulp in float32).

    Returns the (T, nb, ENC_OUT) encodings for :func:`decode_sequence`.
    """
    l0 = params["enc"][0]
    host_dim = mh_ema.shape[-1]
    lam_h = _mm(mh_ema, l0["w"][:host_dim])         # (T, E) — once per step
    lam_t = _mm(mt, l0["w"][host_dim:]) + l0["b"]   # (nb, E) — once per job
    return _encoder_rest(params, lam_h[:, None, :] + lam_t[None, :, :])


def encoder_segmented(params: Params, mh_ema: jax.Array, mt: jax.Array,
                      seg: jax.Array) -> jax.Array:
    """:func:`encoder_hoisted` for job rows of many host blocks (the
    tenant batch): ``mh_ema`` is (T, G, host_dim), one EMA-smoothed host
    window per tenant, ``seg`` (nb,) the block of each of the (nb,
    task_dim) task rows.  Each block's host product is computed once per
    step and gathered to its rows, so no row carries a copy of a host
    window.  Returns the (T, nb, ENC_OUT) encodings."""
    l0 = params["enc"][0]
    host_dim = mh_ema.shape[-1]
    lam_h = _mm(mh_ema, l0["w"][:host_dim])         # (T, G, E)
    lam_t = _mm(mt, l0["w"][host_dim:]) + l0["b"]   # (nb, E)
    return _encoder_rest(params, jnp.take(lam_h, seg, axis=1) + lam_t[None])


def _encoder_rest(params: Params, pre: jax.Array) -> jax.Array:
    """The first layer's activation over its summed pre-activation, then
    the encoder's later layers."""
    h = jax.nn.softplus(pre)
    for layer in params["enc"][1:]:
        h = jax.nn.softplus(_mm(h, layer["w"]) + layer["b"])
    return h


def decode_sequence(params: Params, lam: jax.Array, unroll: int = 1,
                    use_pallas: bool = False) -> jax.Array:
    """Scan the LSTM + head over precomputed (T, ..., ENC_OUT) encodings.

    ``unroll`` forwards to ``lax.scan`` — unrolling the (tiny, typically
    T=5) emission loop lets XLA fuse across steps instead of paying the
    while-loop machinery per step.  Different unroll factors compile
    different fusions whose rounding may differ by ulps: Tier-1.
    Callers embed this in their own jitted programs (it is not jitted
    here), so each (shape, unroll) pair is one cache entry there.
    """
    state = init_state(params, lam.shape[1:-1])

    def f(state, x):
        return step_decoded(params, state, x, use_pallas=use_pallas)

    _, outs = jax.lax.scan(f, state, lam, unroll=unroll)
    return outs[-1]


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def predict_sequence(params: Params, xs: jax.Array,
                     use_pallas: bool = False) -> jax.Array:
    """Run the net over a (T, ..., input_dim) EMA-smoothed feature sequence.

    Returns the final-step (alpha, beta), shape (..., 2). This is the paper's
    "send matrices for T seconds every I seconds; read (alpha, beta) at the
    end" loop, with T = xs.shape[0] steps.

    Compiles once per (shape, use_pallas) signature — callers in the
    simulator hot path pad the batch axis to power-of-two buckets
    (``repro.core.predictor``) so the compile count is bounded by the
    bucket set, not the number of distinct job counts.
    """
    xs = ema_smooth(xs)
    batch_shape = xs.shape[1:-1]
    state = init_state(params, batch_shape)

    def f(state, x):
        state, out = step(params, state, x, use_pallas=use_pallas)
        return state, out

    _, outs = jax.lax.scan(f, state, xs)
    return outs[-1]


# ------------------------------- training ---------------------------------


def mse_loss(params: Params, xs: jax.Array, targets: jax.Array,
             use_pallas: bool = False) -> jax.Array:
    """MSE between predicted (alpha, beta) and MLE-fitted targets (paper §4.4:
    'trained using Mean-Square-Error Loss between the values based on the
    predicted distribution and the actual data')."""
    pred = predict_sequence(params, xs, use_pallas=use_pallas)
    return jnp.mean((pred - targets) ** 2)


class AdamState(NamedTuple):
    step: jax.Array
    mu: Any
    nu: Any


def adam_init(params: Params) -> AdamState:
    z = jax.tree_util.tree_map(jnp.zeros_like, params)
    return AdamState(step=jnp.zeros((), jnp.int32), mu=z,
                     nu=jax.tree_util.tree_map(jnp.zeros_like, params))


def adam_update(params: Params, grads: Params, state: AdamState,
                lr: float = 1e-5, b1: float = 0.9, b2: float = 0.999,
                eps: float = 1e-8) -> tuple[Params, AdamState]:
    """Adam (paper §4.4 uses Adam with lr 1e-5)."""
    t = state.step + 1
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                                state.mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                state.nu, grads)
    tf = t.astype(jnp.float32)
    bc1 = 1 - b1 ** tf
    bc2 = 1 - b2 ** tf
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps),
        params, mu, nu)
    return params, AdamState(step=t, mu=mu, nu=nu)


@functools.partial(jax.jit, static_argnames=("lr", "use_pallas"))
def train_step(params: Params, opt: AdamState, xs: jax.Array,
               targets: jax.Array, lr: float = 1e-5,
               use_pallas: bool = False
               ) -> tuple[Params, AdamState, jax.Array]:
    loss, grads = jax.value_and_grad(mse_loss)(params, xs, targets,
                                               use_pallas)
    params, opt = adam_update(params, grads, opt, lr=lr)
    return params, opt, loss
