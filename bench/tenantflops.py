"""Operations and bytes of the service's tenant step, from its shapes.

As ``bench.flops`` counts ``_fused_step``: matrix products only, and the
least bytes the program has to move, in float32.  The first layer's host
half runs once per step for each tenant of the tick (its window is
encoded once, whatever its job count), everything else per job row.
"""
from __future__ import annotations

from bench import flops


def tenant_step_flops(g: int, nb: int, host_dim: int, task_dim: int,
                      net: dict) -> int:
    """The tenant step over ``g`` tenants and ``nb`` dispatched rows:
    ``_fused_step``'s count at ``nb`` rows, with the host block's first
    layer once per tenant in place of once."""
    e = net["enc_hidden"]
    return (flops.fused_step_flops(nb, host_dim, task_dim, net)
            + 2 * net["horizon"] * (g - 1) * host_dim * e)


def tenant_step_bytes(slots: int, g: int, nb: int, host_dim: int,
                      task_dim: int, net: dict) -> int:
    """Weights, the ``g`` present tenants' rings read and written back,
    the staged vector (2 scalars, each slot's flag and host row, and q,
    the slot index and M_T of each row) and the E_S read back."""
    t = net["horizon"]
    packed = 2 + slots * (1 + host_dim) + nb * (2 + task_dim)
    return flops.F32 * (flops.param_count(host_dim + task_dim, net)
                        + 2 * g * t * host_dim + packed + nb)
