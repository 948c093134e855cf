"""Plain reference of START's decision: features -> Encoder-LSTM -> Pareto E_S.

Written from the paper (arXiv:2111.10241 §3.2, Fig. 3-4) and imports
nothing of the program.  It runs on the host in numpy, in float64, with
the straightforward (un-hoisted, un-fused) loop: EMA over the whole
feature sequence, every step through the full encoder, the LSTM stack,
the head, then ``E_S = q * (K / beta) ** -alpha`` with
``K = k * alpha * beta / (alpha - 1)``.

``precision="f32"`` is the same loop in plain float32: the rounding a
sound float32 computation shows, the scale the check's ratio divides by.

:func:`e_s_device` is the same loop once more, in float32 on JAX's
default device, with the device's own activations (a TPU's float32
``log``, ``log1p`` and ``tanh`` err by 1e-4 where the host's libm errs
by 1e-7): every matmul at ``highest``, six bfloat16 passes, is the
reference the program is compared with; at ``high``, three passes, the
step below the configuration's ``highest``, it is the control.
"""
from __future__ import annotations

import numpy as np

HOST_FEATURES = 11
TASK_FEATURES = 5
#: E_S gaps are relative to max(|reference|, this): below it an E_S is
#: zero for every decision, and the gap is absolute.
REL_FLOOR = 1e-6


# ------------------------------ features ------------------------------

def host_matrix(util, cap, cost, power_max, n_tasks) -> np.ndarray:
    """M_H (n_hosts, 11): utilization (clipped to [0, 2]), capacities
    over the fleet's column maxima, cost, peak power and task count over
    their maxima."""
    f = np.float64
    util = np.clip(np.asarray(util, f), 0.0, 2.0)
    cap = np.asarray(cap, f)
    cap_n = cap / np.maximum(cap.max(axis=0, keepdims=True), 1e-8)
    cost = np.asarray(cost, f)
    p = np.asarray(power_max, f)
    nt = np.asarray(n_tasks, f)
    return np.concatenate([
        util, cap_n, (cost / max(cost.max(), 1e-8))[:, None],
        (p / max(p.max(), 1e-8))[:, None],
        (nt / max(nt.max(), 1.0))[:, None]], axis=1)


def task_matrices(req, host_col, counts, n_hosts, max_tasks) -> np.ndarray:
    """M_T (jobs, max_tasks, 5): each job's tasks in order, requirement
    columns then ``(host + 1) / n_hosts``; rows past the job's count are
    zero.  ``req``/``host_col`` hold every job's tasks back to back."""
    mt = np.zeros((len(counts), max_tasks, TASK_FEATURES))
    lo = 0
    for j, c in enumerate(counts):
        mt[j, :c, :4] = req[lo:lo + c]
        mt[j, :c, 4] = (np.asarray(host_col[lo:lo + c], np.float64)
                        + 1.0) / n_hosts
        lo += c
    return mt


def start_k(util, reserved: float, k_lo: float, k_hi: float) -> float:
    """START's straggler parameter from the mean CPU utilization over
    the fleet, less the reserved floor (paper §4.3, adaptive k)."""
    raw = float(np.clip(np.asarray(util, np.float64)[:, 0].mean(), 0, 1))
    return k_lo + (k_hi - k_lo) * float(np.clip(raw - reserved, 0, 1))


# ------------------------------ network -------------------------------

def _softplus(x):
    return np.logaddexp(x, x.dtype.type(0))


def _sigmoid(x):
    one = x.dtype.type(1)
    with np.errstate(over="ignore"):
        return one / (one + np.exp(-x))


def e_s(params: dict, mh_rows, mt, q, k: float, net: dict,
        precision: str = "f64") -> np.ndarray:
    """E_S of each job.

    Args:
        params: the weights as numpy arrays (``bench.weights`` layout).
        mh_rows: (T, n_hosts, 11) host matrices, oldest first.
        mt: (jobs, max_tasks, 5) task matrices.
        q: (jobs,) task counts.
        k: the straggler parameter of this decision.
        net: the configuration's ``network`` block.
        precision: ``"f64"`` (the reference) or ``"f32"`` (the same in
            plain float32).
    """
    if precision not in ("f64", "f32"):
        raise ValueError(precision)
    dt = np.float64 if precision == "f64" else np.float32
    mm = np.matmul
    p = {"enc": [{n: np.asarray(v, dt) for n, v in layer.items()}
                 for layer in params["enc"]],
         "lstm": [{n: np.asarray(v, dt) for n, v in layer.items()}
                  for layer in params["lstm"]],
         "head": {n: np.asarray(v, dt) for n, v in params["head"].items()}}
    mh = np.asarray(mh_rows, dt).reshape(len(mh_rows), -1)
    mt = np.asarray(mt, dt).reshape(len(mt), -1)
    n = mt.shape[0]
    t_steps = mh.shape[0]
    x = np.concatenate([np.broadcast_to(mh[:, None, :], (t_steps, n,
                                                         mh.shape[1])),
                        np.broadcast_to(mt[None], (t_steps, *mt.shape))],
                       axis=-1)
    w = dt(net["ema_w"])
    s = x[0]
    hid = net["lstm_hidden"]
    h = [np.zeros((n, hid), dt) for _ in p["lstm"]]
    c = [np.zeros((n, hid), dt) for _ in p["lstm"]]
    for t in range(t_steps):
        if t:
            s = w * x[t] + (dt(1) - w) * s
        a = s
        for layer in p["enc"]:
            a = _softplus(mm(a, layer["w"]) + layer["b"])
        for li, layer in enumerate(p["lstm"]):
            z = mm(a, layer["wx"]) + mm(h[li], layer["wh"]) + layer["b"]
            zi, zf, zg, zo = np.split(z, 4, axis=-1)
            c[li] = _sigmoid(zf) * c[li] + _sigmoid(zi) * np.tanh(zg)
            h[li] = _sigmoid(zo) * np.tanh(c[li])
            a = h[li]
    out = mm(a, p["head"]["w"]) + p["head"]["b"]
    alpha = _softplus(out[:, 0]) + dt(1)
    beta = (_softplus(out[:, 1]) + dt(net["beta_eps"])) * dt(
        net["beta_scale"])
    thr = dt(k) * (alpha * beta / (alpha - dt(1)))
    return np.asarray(q, dt) * (thr / beta) ** (-alpha)


# --------------------- the same, in float32 on a device ---------------------

#: The device reference pads its batch to this many rows: one program per
#: precision, whatever a decision's job count.
DEVICE_ROWS = 256


#: The compiler's precision setting for each number of bfloat16 passes.
PRECISION = {6: "highest", 3: "high"}


def _mm_passes(a, b, passes: int):
    """``a @ b`` in float32 at ``passes`` bfloat16 passes: on a TPU the
    compiler's own (``highest`` six, ``high`` three).  Where the compiler
    ignores the setting (the CPU computes every float32 product in
    full), three passes are built by hand: ``hi*hi + hi*lo + lo*hi`` of a
    bfloat16 split of each operand."""
    import jax
    import jax.numpy as jnp
    if passes == 6 or jax.default_backend() == "tpu":
        return jnp.matmul(a, b, precision=PRECISION[passes])

    def dot(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    ah, bh = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    al = (a - ah.astype(jnp.float32)).astype(jnp.bfloat16)
    bl = (b - bh.astype(jnp.float32)).astype(jnp.bfloat16)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


def _device_program(passes: int, net_items: tuple):
    import jax
    import jax.numpy as jnp
    net = dict(net_items)

    def mm(a, b):
        return _mm_passes(a, b, passes)

    def run(p, mh, mt, q, k):
        t_steps, n = mh.shape[0], mt.shape[0]
        x = jnp.concatenate([jnp.broadcast_to(mh[:, None, :],
                                              (t_steps, n, mh.shape[1])),
                             jnp.broadcast_to(mt[None], (t_steps, *mt.shape))],
                            axis=-1)
        w = jnp.float32(net["ema_w"])
        hid = net["lstm_hidden"]
        zeros = jnp.zeros((len(p["lstm"]), n, hid), jnp.float32)

        def step(carry, xt):
            s, h, c = carry
            s = w * xt + (1 - w) * s
            a = s
            for layer in p["enc"]:
                a = jax.nn.softplus(mm(a, layer["w"]) + layer["b"])
            hs, cs = [], []
            for li, layer in enumerate(p["lstm"]):
                z = mm(a, layer["wx"]) + mm(h[li], layer["wh"]) + layer["b"]
                zi, zf, zg, zo = jnp.split(z, 4, axis=-1)
                cn = jax.nn.sigmoid(zf) * c[li] + jax.nn.sigmoid(zi) * jnp.tanh(zg)
                hn = jax.nn.sigmoid(zo) * jnp.tanh(cn)
                hs.append(hn)
                cs.append(cn)
                a = hn
            return (s, jnp.stack(hs), jnp.stack(cs)), a

        # s_0 = x_0: the first step's EMA with itself is x_0 exactly
        (_, _, _), a = jax.lax.scan(step, (x[0], zeros, zeros), x)
        out = mm(a[-1], p["head"]["w"]) + p["head"]["b"]
        alpha = jax.nn.softplus(out[:, 0]) + 1
        beta = (jax.nn.softplus(out[:, 1]) + net["beta_eps"]) * net["beta_scale"]
        thr = k * (alpha * beta / (alpha - 1))
        return q * (thr / beta) ** (-alpha)

    return jax.jit(run)


_DEVICE = {}


def e_s_device(params: dict, mh_rows, mt, q, k: float, net: dict,
               passes: int = 6) -> np.ndarray:
    """E_S of each job as :func:`e_s` computes it, in float32 on JAX's
    default device: the straightforward loop again, every matmul at
    ``passes`` bfloat16 passes (6: the reference; 3: the control), with
    the device's own activations.  The batch is padded to
    :data:`DEVICE_ROWS`, so one program serves every decision."""
    import jax.numpy as jnp
    key = (passes, tuple(sorted((n, v) for n, v in net.items()
                                if not isinstance(v, (list, dict)))))
    if key not in _DEVICE:
        _DEVICE[key] = _device_program(passes, key[1])
    n = len(q)
    if n > DEVICE_ROWS:
        raise ValueError(f"{n} jobs, the device reference holds {DEVICE_ROWS}")
    mt = np.asarray(mt, np.float32).reshape(n, -1)
    mt_pad = np.zeros((DEVICE_ROWS, mt.shape[1]), np.float32)
    mt_pad[:n] = mt
    q_pad = np.ones(DEVICE_ROWS, np.float32)
    q_pad[:n] = q
    f32 = np.float32
    out = _DEVICE[key](
        {"enc": [{m: jnp.asarray(v, f32) for m, v in l.items()}
                 for l in params["enc"]],
         "lstm": [{m: jnp.asarray(v, f32) for m, v in l.items()}
                  for l in params["lstm"]],
         "head": {m: jnp.asarray(v, f32) for m, v in params["head"].items()}},
        jnp.asarray(np.asarray(mh_rows, f32).reshape(len(mh_rows), -1)),
        jnp.asarray(mt_pad), jnp.asarray(q_pad), jnp.float32(k))
    return np.asarray(out, np.float64)[:n]


def rel_gap(got, want) -> float:
    """Widest relative gap of ``got`` from ``want``; a non-finite value
    on either side reads as infinitely far."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    if got.size == 0:
        return 0.0
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf")
    return float((np.abs(got - want)
                  / np.maximum(np.abs(want), REL_FLOOR)).max())
