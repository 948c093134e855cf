"""Shared pytest configuration for the tier-1 suite.

Three jobs:
  * register the ``slow`` marker (used by the distributed tests and the CI
    fast lane's ``-m "not slow"`` filter);
  * the ``sanction_uploads`` fixture of the zero-transfer tests;
  * make ``hypothesis`` optional: when the real package is missing (it is a
    dev-only dependency, see requirements-dev.txt), install a minimal stub
    into ``sys.modules`` BEFORE test modules import it, so collection never
    hard-errors and the property tests still run as fixed-example
    parametrizations instead of being skipped wholesale.
"""
from __future__ import annotations

import sys
import types

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, excluded from the fast CI lane "
        "(deselect with -m \"not slow\")")


@pytest.fixture
def sanction_uploads(monkeypatch):
    """Returns a function that exempts the predictor's two upload funnels
    from a surrounding ``jax.transfer_guard_host_to_device('disallow')``
    and returns a dict counting their calls from then on: ``_launch``
    (the fused step, whose own argument is a warm interval's one upload)
    and ``_stage`` (every other upload).  ``_launch`` is let through only
    once its other operands, every leaf of the params and the ring, are
    ``jax.Array`` s on one device, so the staging vector is all it can
    upload.  Any other host->device transfer under the guard raises."""
    import jax
    from repro.core.predictor import StragglerPredictor

    def on_device(self, ring):
        leaves = jax.tree_util.tree_leaves(self.params) + [ring]
        bad = [type(x).__name__ for x in leaves
               if not isinstance(x, jax.Array)]
        assert not bad, f"launch would upload host operands: {bad}"
        devices = {d for x in leaves for d in x.devices()}
        assert len(devices) == 1, f"launch operands span {devices}"

    def install():
        calls = {"_launch": 0, "_stage": 0}

        def sanctioned(name):
            orig = getattr(StragglerPredictor, name)

            def call(self, *args, **kwargs):
                calls[name] += 1
                if name == "_launch":
                    on_device(self, args[0])
                with jax.transfer_guard_host_to_device("allow"):
                    return orig(self, *args, **kwargs)
            return call

        for name in calls:
            monkeypatch.setattr(StragglerPredictor, name, sanctioned(name))
        return calls
    return install


def _install_hypothesis_stub() -> None:
    """Degraded-mode ``hypothesis``: @given draws a handful of boundary +
    midpoint examples per strategy and parametrizes over them."""

    class _Strategy:
        def __init__(self, examples):
            self.examples = list(examples)

    def floats(lo, hi):
        return _Strategy([lo, hi, (lo + hi) / 2.0])

    def integers(lo, hi):
        mid = (lo + hi) // 2
        return _Strategy([lo, hi, mid])

    def sampled_from(xs):
        return _Strategy(list(xs))

    def settings(*a, **kw):
        def deco(fn):
            return fn
        return deco

    def given(**kw):
        keys = sorted(kw)
        n = max(len(kw[k].examples) for k in keys)
        cases = [tuple(kw[k].examples[i % len(kw[k].examples)]
                       for k in keys) for i in range(n)]
        if len(keys) == 1:  # parametrize wants scalars for one argname
            cases = [c[0] for c in cases]

        def deco(fn):
            return pytest.mark.parametrize(",".join(keys), cases)(fn)
        return deco

    mod = types.ModuleType("hypothesis")
    mod.given = given
    mod.settings = settings
    mod.__is_stub__ = True
    st_mod = types.ModuleType("hypothesis.strategies")
    st_mod.floats = floats
    st_mod.integers = integers
    st_mod.sampled_from = sampled_from
    mod.strategies = st_mod
    sys.modules["hypothesis"] = mod
    sys.modules["hypothesis.strategies"] = st_mod


try:
    import hypothesis  # noqa: F401
except ImportError:
    _install_hypothesis_stub()
