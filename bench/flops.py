"""Operations and bytes of START's device programs, from their shapes.

Only matrix products are counted (2 * m * k * n each); the elementwise
work (EMA, activations, the Pareto tail) is a few operations per output
element and is left out.  Bytes are the least a program has to move:
every weight read once, the inputs read once and the outputs written
once, in float32.
"""
from __future__ import annotations

F32 = 4


def _dims(net: dict) -> tuple[int, int, int, int]:
    return (net["enc_hidden"], net["enc_out"], net["lstm_hidden"],
            net["lstm_layers"])


def param_count(input_dim: int, net: dict) -> int:
    e, o, h, layers = _dims(net)
    enc = (input_dim * e + e) + 2 * (e * e + e) + (e * o + o)
    lstm = sum((o if i == 0 else h) * 4 * h + h * 4 * h + 4 * h
               for i in range(layers))
    return enc + lstm + h * 2 + 2


def _per_row_step(net: dict) -> int:
    """Matmul flops of one (step, job) row after the first encoder layer,
    head excluded."""
    e, o, h, layers = _dims(net)
    enc = 2 * (e * e + e * e + e * o)
    lstm = sum(2 * ((o if i == 0 else h) * 4 * h + h * 4 * h)
               for i in range(layers))
    return enc + lstm


def fused_step_flops(nb: int, host_dim: int, task_dim: int,
                     net: dict) -> int:
    """``_fused_step`` at batch ``nb`` as the program runs it: the host
    block through the first layer once per step, the task block once per
    job, every later layer per (step, job), and the head every step."""
    e, _, h, _ = _dims(net)
    t = net["horizon"]
    return (2 * t * host_dim * e + 2 * nb * task_dim * e
            + t * nb * (_per_row_step(net) + 2 * h * 2))


def fused_step_bytes(nb: int, host_dim: int, task_dim: int,
                     net: dict) -> int:
    """Weights, the M_H ring read and written back, the staged vector
    (2 scalars, a host row, q and M_T) and the E_S read back."""
    t = net["horizon"]
    packed = 2 + host_dim + nb * (1 + task_dim)
    return F32 * (param_count(host_dim + task_dim, net)
                  + 2 * t * host_dim + packed + nb)


def decision_flops(n_jobs: int, host_dim: int, task_dim: int,
                   net: dict) -> int:
    """What one decision over ``n_jobs`` real jobs needs of the model:
    the shared host block once per step, each job's task block once,
    every later layer per (step, job), and the head once per job."""
    if n_jobs <= 0:
        return 0
    e, _, h, _ = _dims(net)
    t = net["horizon"]
    return (2 * t * host_dim * e + 2 * n_jobs * task_dim * e
            + t * n_jobs * _per_row_step(net) + n_jobs * 2 * h * 2)
