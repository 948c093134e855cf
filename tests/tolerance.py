"""The Tier-1 determinism contract: tolerance bounds + comparison helpers.

The repo's determinism guarantees are tiered (README "Performance"):

  * **Tier-0 (bitwise)** — the engine, sweep serial == parallel, and the
    golden determinism fixture.  Nothing in this module applies there;
    Tier-0 comparisons use ``np.testing.assert_array_equal`` and the
    golden-fixture path must never import this file (guarded by
    ``test_tolerance.py::test_tier0_path_never_imports_tolerance``).

  * **Tier-1 (tolerance-bounded)** — the fused interval step and the
    serving batch path.  They restructure the Encoder-LSTM emission for
    speed (encoder hoisted out of the scan, scan unrolled, Pareto tail
    fused into the same program, exact-shape batches), which shifts
    float32 rounding by a few ulps at some shapes.  Tier-1 paths must
    agree with the bitwise reference within the bounds below at EVERY
    shape; each path is still fully deterministic run-to-run for a fixed
    (shape, unroll, platform).

The bounds are deliberately tight: the shape sweep in
``test_tolerance.py`` pins the *observed* drift per optimization at
roughly 5e-7 relative (~4 float32 ulps); ``TIER1_REL`` leaves ~20x
headroom for platform variation without ever accepting a real numeric
bug (a wrong sign, a dropped term, a swapped operand all blow past 1e-5
immediately).
"""
from __future__ import annotations

import numpy as np

#: Maximum relative error |a - b| / max(|b|, TIER1_ABS_FLOOR) a Tier-1
#: path may show against the Tier-0 reference, at any shape.
TIER1_REL = 1e-5

#: Denominator floor for the relative error: below this magnitude the
#: comparison degrades to an absolute bound of TIER1_REL * TIER1_ABS_FLOOR
#: (E_S values this small are zero for every downstream decision).
TIER1_ABS_FLOOR = 1e-6

#: Maximum float32 ulp distance observed across the committed shape
#: sweeps, re-pinned whenever a new Tier-1 optimization lands.  The shape
#: sweeps in test_tolerance.py assert it, to keep the drift trajectory
#: visible; the numeric gate is TIER1_REL.
TIER1_MAX_ULP = 64


def ulp_diff(a, b) -> np.ndarray:
    """Elementwise distance in float32 ulps (units in the last place).

    Implemented as the difference of the IEEE-754 bit patterns mapped to
    a monotonic integer line (sign-magnitude -> offset binary), so 0 ulp
    means bitwise-equal, 1 ulp means adjacent representable floats, and
    the measure is well-defined across the zero crossing.
    """
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)

    def key(x):
        bits = x.view(np.int32).astype(np.int64)
        return np.where(bits < 0, np.int64(-0x80000000) - bits, bits)

    return np.abs(key(a) - key(b))


def drift(actual, desired) -> dict:
    """Max drift of ``actual`` vs ``desired`` as a dict of scalars:
    ``{"max_rel", "max_abs", "max_ulp"}``.  Used by the shape-sweep
    tests and, through :func:`assert_tier1`, by ``chip_smoke.py``'s
    Tier-1 check on the chip."""
    actual = np.asarray(actual, np.float32)
    desired = np.asarray(desired, np.float32)
    if actual.shape != desired.shape:
        raise AssertionError(
            f"shape mismatch: {actual.shape} vs {desired.shape}")
    abs_err = np.abs(actual.astype(np.float64) - desired.astype(np.float64))
    denom = np.maximum(np.abs(desired.astype(np.float64)), TIER1_ABS_FLOOR)
    # ulp distance is only meaningful above the absolute floor — below it
    # the contract is an absolute bound and ulp counts at denormal scale
    # are astronomically large for negligible absolute error
    ulp = ulp_diff(actual, desired)
    ulp = ulp[np.abs(desired) >= TIER1_ABS_FLOOR]
    return {
        "max_rel": float((abs_err / denom).max()) if actual.size else 0.0,
        "max_abs": float(abs_err.max()) if actual.size else 0.0,
        "max_ulp": int(ulp.max()) if ulp.size else 0,
    }


def assert_tier1(actual, desired, rel: float = TIER1_REL,
                 context: str = "") -> dict:
    """Assert a Tier-1 path agrees with the Tier-0 reference within the
    contract bound; returns the measured :func:`drift` so sweeps can
    aggregate it.  Non-finite values must match exactly (a NaN in one
    path but not the other is a real bug, not rounding)."""
    actual = np.asarray(actual, np.float32)
    desired = np.asarray(desired, np.float32)
    fin_a, fin_d = np.isfinite(actual), np.isfinite(desired)
    nf_ok = (fin_a == fin_d).all()
    if nf_ok and (~fin_a).any():
        a_nf, d_nf = actual[~fin_a], desired[~fin_a]
        nf_ok = bool(((np.isnan(a_nf) & np.isnan(d_nf))
                      | (a_nf == d_nf)).all())
    if not nf_ok:
        raise AssertionError(
            f"Tier-1 {context or 'comparison'}: non-finite mismatch "
            f"(actual finite {fin_a.sum()}/{fin_a.size}, "
            f"desired finite {fin_d.sum()}/{fin_d.size})")
    d = drift(np.where(fin_a, actual, 0), np.where(fin_d, desired, 0))
    if d["max_rel"] > rel:
        raise AssertionError(
            f"Tier-1 {context or 'comparison'} out of tolerance: "
            f"max_rel {d['max_rel']:.3e} > bound {rel:.1e} "
            f"(max_abs {d['max_abs']:.3e}, max_ulp {d['max_ulp']})")
    return d


def sweep_drift(pairs) -> dict:
    """Aggregate :func:`assert_tier1` over ``(actual, desired)`` pairs —
    the shape-sweep harness: every pair must individually pass, and the
    worst drift across the sweep comes back for pinning/recording."""
    worst = {"max_rel": 0.0, "max_abs": 0.0, "max_ulp": 0}
    for i, (actual, desired) in enumerate(pairs):
        d = assert_tier1(actual, desired, context=f"sweep pair {i}")
        for k in worst:
            worst[k] = max(worst[k], d[k])
    return worst
