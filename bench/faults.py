"""Faults planted in the timed path, to see the check come out false.

Each is a context manager that breaks the program underneath the
benchmark and mends it on exit:

* ``stale``: the fused step returns its ring unchanged and the catch-up
  roll does nothing, so every decision sees the warm-up's history;
* ``half``: half of the jobs are predicted, the rest given their mean;
* ``altered``: the first E_S of every decision is altered by 0.1% where
  it is produced.

``bench/control.py --fault <name>`` reads each at a cell's own size on
the chip; ``bench/tests/test_bench_sim.py`` plants each on the CPU.
"""
from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def _patched(*patches):
    saved = [(obj, name, getattr(obj, name)) for obj, name, _ in patches]
    try:
        for obj, name, new in patches:
            setattr(obj, name, new)
        yield
    finally:
        for obj, name, old in saved:
            setattr(obj, name, old)


def stale():
    import jax.numpy as jnp
    from repro.core import predictor
    fused, roll = predictor._fused_step, predictor._ring_roll

    def step(params, ring, packed, **kw):
        return jnp.array(ring), fused(params, ring, packed, **kw)[1]

    def stay(ring, row):
        return jnp.array(ring)

    # the program's compile counter reads each entry's jit cache
    step._cache_size, stay._cache_size = fused._cache_size, roll._cache_size
    return _patched((predictor, "_fused_step", step),
                    (predictor, "_ring_roll", stay))


def half():
    from repro.core.predictor import StragglerPredictor
    orig = StragglerPredictor.predict_interval

    def predict_interval(self, m_t, q, per_task=False):
        n = len(q)
        h = max(1, n // 2)
        out = orig(self, m_t[:h], q[:h], per_task=per_task)
        return np.concatenate([out, np.full(n - h, out.mean(), out.dtype)])

    return _patched((StragglerPredictor, "predict_interval",
                     predict_interval))


def altered():
    from repro.core.predictor import StragglerPredictor
    orig = StragglerPredictor.predict_interval

    def predict_interval(self, m_t, q, per_task=False):
        out = np.array(orig(self, m_t, q, per_task=per_task))
        out[0] *= np.float32(1.001)
        return out

    return _patched((StragglerPredictor, "predict_interval",
                     predict_interval))


FAULTS = {"stale": stale, "half": half, "altered": altered}
