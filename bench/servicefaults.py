"""Faults planted in the service's tenant step, to see its cell's check
come out false.

Each is a context manager that breaks the program underneath the
benchmark and mends it on exit:

* ``crossed``: answers crossed between tenants, each tenant's job rows
  given the next tenant's slot (the row -> slot index permuted);
* ``stale``: each slot rolls in its tenant's row of the interval before,
  so every window is one interval stale;
* ``host_half``: the host half of the first layer dropped for every
  tenant but the first of the tick.

``bench/tests/test_bench_service.py`` plants each on the CPU.
"""
from __future__ import annotations

import numpy as np

from bench.faults import _patched


def crossed():
    from repro.core.predictor import StragglerPredictor, tenant_layout
    orig = StragglerPredictor.pack_tenants

    def pack_tenants(self, group):
        buf, nb, n = orig(self, group)
        _, _, o_seg, o_rows, _, _ = tenant_layout(
            len(self._slot_rows), nb, self.host_dim, self.task_dim)
        seg = buf[o_seg:o_seg + n]
        slots = np.unique(seg)
        seg[:] = np.roll(slots, 1)[np.searchsorted(slots, seg)]
        return buf, nb, n

    return _patched((StragglerPredictor, "pack_tenants", pack_tenants))


def stale():
    from repro.core.predictor import StragglerPredictor
    orig = StragglerPredictor._sync_slot

    def sync(self, slot, rows_seen, hist):
        row = orig(self, slot, rows_seen, hist)
        return list(hist)[-2] if len(hist) > 1 else row

    return _patched((StragglerPredictor, "_sync_slot", sync))


def host_half():
    import jax
    import jax.numpy as jnp
    from repro.core import encoder_lstm as net
    from repro.core import predictor
    orig = net.encoder_segmented

    def encoder_segmented(params, mh_ema, mt, seg):
        first = jnp.arange(mh_ema.shape[1]) == seg[0]
        return orig(params, mh_ema * first[None, :, None], mt, seg)

    body = predictor._fused_step_tenants.__wrapped__

    def tenant_step(*args, **kw):
        return body(*args, **kw)

    # a new function, so its jit traces the broken encoder afresh
    step = jax.jit(tenant_step, donate_argnums=(1,),
                   static_argnames=("nb", "task_dim", "use_pallas",
                                    "per_task", "unroll"))
    return _patched((net, "encoder_segmented", encoder_segmented),
                    (predictor, "_fused_step_tenants", step))


FAULTS = {"crossed": crossed, "stale": stale, "host_half": host_half}
