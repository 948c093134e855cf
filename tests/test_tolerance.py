"""Tests of the Tier-1 tolerance contract itself (tests/tolerance.py).

Three jobs:
  * the helper's semantics: ulp arithmetic, non-finite handling, and —
    property-tested — that the bound is *tight*: perturbations beyond
    ``TIER1_REL`` fail, perturbations comfortably inside pass;
  * the shape-sweep regression: every Tier-1 optimization (split-encoder
    hoisting + fused Pareto tail, exact-shape batches, the tenant step's
    segmented encoder at each scan unroll) pinned against the Tier-0
    reference at every swept shape, with the worst observed ulp drift pinned so growth is
    visible in review;
  * the Tier-0 firewall: the bitwise path (engine, sweep, golden
    fixture) must never import the tolerance helper — Tier-0 has no
    tolerances.
"""
import collections
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import features
from repro.core.predictor import StragglerPredictor

from tolerance import (TIER1_MAX_ULP, TIER1_REL, assert_tier1, drift,
                       sweep_drift, ulp_diff)

# ------------------------------ helper semantics ----------------------------


def test_ulp_diff_basics():
    a = np.float32(1.0)
    assert ulp_diff(a, a) == 0
    assert ulp_diff(a, np.nextafter(a, np.float32(2.0), dtype=np.float32)) \
        == 1
    # well-defined across the zero crossing: -min_denormal and
    # +min_denormal are 2 ulps apart (one step to each side of 0)
    tiny = np.float32(1e-45)
    assert ulp_diff(-tiny, tiny) == 2
    assert ulp_diff(np.float32(0.0), tiny) == 1


def test_assert_tier1_passes_bitwise_and_returns_drift():
    x = np.linspace(0.1, 5.0, 64, dtype=np.float32)
    d = assert_tier1(x, x)
    assert d == {"max_rel": 0.0, "max_abs": 0.0, "max_ulp": 0}


def test_assert_tier1_nonfinite_must_match_exactly():
    x = np.array([1.0, np.inf, np.nan], np.float32)
    assert_tier1(x, x.copy())  # matching non-finites pass
    y = x.copy()
    y[1] = -np.inf
    with pytest.raises(AssertionError, match="non-finite"):
        assert_tier1(y, x)
    z = x.copy()
    z[2] = 1.0
    with pytest.raises(AssertionError, match="non-finite"):
        assert_tier1(z, x)


def test_drift_shape_mismatch_is_an_error():
    with pytest.raises(AssertionError, match="shape"):
        drift(np.zeros(3, np.float32), np.zeros(4, np.float32))


@settings(max_examples=50, deadline=None)
@given(scale=st.floats(1e-3, 1e3), factor=st.floats(2.0, 100.0))
def test_tier1_bound_is_tight(scale, factor):
    """Property: the bound rejects anything beyond TIER1_REL and accepts
    anything comfortably within it — there is no dead zone where a real
    regression could hide inside the tolerance."""
    x = (np.linspace(0.5, 2.0, 32) * scale).astype(np.float32)
    # beyond the bound: relative error = factor * TIER1_REL > TIER1_REL
    bad = (x.astype(np.float64) * (1.0 + factor * TIER1_REL)).astype(
        np.float32)
    with pytest.raises(AssertionError, match="out of tolerance"):
        assert_tier1(bad, x)
    # comfortably inside: factor/10 >= 0.2, <= 10 -> rel error well under
    # the bound after float32 rounding at these magnitudes
    good = (x.astype(np.float64) * (1.0 + TIER1_REL / 20.0)).astype(
        np.float32)
    assert_tier1(good, x)


def test_sweep_drift_aggregates_worst_pair():
    x = np.ones(8, np.float32)
    y = x.copy()
    y[0] = np.nextafter(y[0], np.float32(2.0), dtype=np.float32)
    worst = sweep_drift([(x, x), (y, x)])
    assert worst["max_ulp"] == 1
    assert worst["max_rel"] > 0


# --------------------------- shape-sweep regression -------------------------
#
# Every Tier-1 optimization vs the Tier-0 reference, across a job-count
# sweep covering exact-shape hits (5, 9), padded buckets and bucket
# boundaries.  The asserts are two-level: assert_tier1 gates at TIER1_REL
# (a real bug fails loudly), and the final ulp pin keeps the observed
# drift trajectory visible — if a future rewrite pushes past it, the pin
# must be consciously re-blessed alongside TIER1_MAX_ULP.

_COUNTS = (1, 2, 3, 5, 8, 9, 12, 16)


def _fused_vs_reference(exact_shapes: bool):
    """Warm fused intervals vs predict_features at every swept count;
    ``exact_shapes`` toggles the exact-shape batch policy so its drift
    contribution is isolated from the hoisting + fused-tail rewrite."""
    n_hosts, max_tasks = 6, 5
    pred = StragglerPredictor(
        n_hosts=n_hosts, max_tasks=max_tasks,
        exact_shape_waste=0.25 if exact_shapes else 1.0)
    rng = np.random.default_rng(7)
    t = pred.horizon
    rows = [rng.uniform(0, 1, (n_hosts, features.HOST_FEATURES))
            .astype(np.float32) for _ in range(t)]
    for r in rows:
        pred.push_host_row(r)
    pairs = []
    for n in _COUNTS:
        mt = rng.uniform(0, 1, (n, max_tasks, features.TASK_FEATURES)) \
            .astype(np.float32)
        q = rng.integers(1, max_tasks + 1, n).astype(np.float32)
        e_fused = pred.predict_interval(mt, q)
        ref = pred.predict_features(np.stack(rows[-t:]), mt, q)
        pairs.append((e_fused, np.asarray(ref.e_s)))
        # per-task head drifts identically or less (same upstream math)
        rows.append(rng.uniform(0, 1, (n_hosts, features.HOST_FEATURES))
                    .astype(np.float32))
        pred.push_host_row(rows[-1])
        e_pt, scores = pred.predict_interval(mt, q, per_task=True)
        ref_es, ref_scores = pred.predict_features(
            np.stack(rows[-t:]), mt, q, per_task=True)
        pairs.append((e_pt, ref_es))
        pairs.append((scores.ravel(), ref_scores.ravel()))
        rows.append(rng.uniform(0, 1, (n_hosts, features.HOST_FEATURES))
                    .astype(np.float32))
        pred.push_host_row(rows[-1])
    return sweep_drift(pairs)


def test_shape_sweep_fused_step_within_bound():
    """The full fused program (split-encoder hoisting + unroll + fused
    Pareto tail, padding disabled from the exact-shape policy) vs the
    Tier-0 reference at every swept shape — the acceptance criterion's
    fused == unfused proof."""
    worst = _fused_vs_reference(exact_shapes=False)
    assert worst["max_ulp"] <= TIER1_MAX_ULP


def test_shape_sweep_exact_shapes_within_bound():
    """Same sweep with exact-shape batches enabled: counts 5 and 9 run at
    their exact widths instead of buckets 8/16, exercising the
    batch-width drift source on top of the fused rewrite."""
    worst = _fused_vs_reference(exact_shapes=True)
    assert worst["max_ulp"] <= TIER1_MAX_ULP


def _tenant_step_vs_reference(unroll: int):
    """Ticks of the tenant step (three tenants in slots 0, 2, 3 of four,
    one new host row each per tick, their rows summing to each swept
    count) vs each tenant's Tier-0 prediction from its own window, left
    padded while it holds fewer than ``horizon`` rows."""
    n_hosts, max_tasks = 6, 5
    pred = StragglerPredictor(n_hosts=n_hosts, max_tasks=max_tasks,
                              unroll=unroll)
    pred.open_slab(4)
    rng = np.random.default_rng(11)
    t = pred.horizon
    hists = {slot: collections.deque(maxlen=t) for slot in (0, 2, 3)}
    pairs = []
    for tick, n in enumerate(_COUNTS):
        group = []
        for (slot, hist), c in zip(hists.items(),
                                   (n - 2 * (n // 3), n // 3, n // 3)):
            hist.append(rng.uniform(0, 1, (n_hosts, features.HOST_FEATURES))
                        .astype(np.float32))
            mt = rng.uniform(0, 1, (c, max_tasks, features.TASK_FEATURES)) \
                .astype(np.float32)
            q = rng.integers(1, max_tasks + 1, c).astype(np.float32)
            group.append((slot, tick + 1, hist, mt, q))
        buf, nb, m = pred.pack_tenants(group)
        out = pred.predict_tenants(buf, nb, m, len(group))
        lo = 0
        for _, _, hist, mt, q in group:
            if len(q):
                win = [hist[0]] * (t - len(hist)) + list(hist)
                ref = pred.predict_features(np.stack(win), mt, q)
                pairs.append((out[lo:lo + len(q)], np.asarray(ref.e_s)))
            lo += len(q)
    return sweep_drift(pairs)


@pytest.mark.parametrize("unroll", [1, 2, 5])
def test_shape_sweep_tenant_step_within_bound(unroll):
    """The service's tenant step (segmented encoder over the slab's
    windows, unrolled scan, fused tail) vs per-tenant reference
    predictions, at every swept row count and each unroll factor."""
    worst = _tenant_step_vs_reference(unroll)
    assert worst["max_ulp"] <= TIER1_MAX_ULP


# ------------------------------ Tier-0 firewall -----------------------------


def test_tier0_path_never_imports_tolerance():
    """The golden-fixture import closure (engine, sweep, techniques,
    START controller, predictor) must not pull in the tolerance helper:
    Tier-0 is bitwise and has no tolerances to consult.  Run in a clean
    subprocess so this test's own imports don't contaminate the check."""
    code = (
        "import sys\n"
        "import repro.sim.sweep, repro.sim.engine, repro.sim.techniques\n"
        "import repro.core.start, repro.core.predictor\n"
        "bad = [m for m in sys.modules if 'tolerance' in m.lower()]\n"
        "assert not bad, f'Tier-0 closure imported {bad}'\n"
    )
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
