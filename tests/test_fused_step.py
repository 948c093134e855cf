"""Fused per-interval decision step + sweep scheduling tests.

Pins the fused path's contracts under the tiered determinism model:

  * the fused device program (ring-buffer M_H history + on-device feature
    assembly + hoisted-encoder Encoder-LSTM + in-program Pareto tail in
    one donated-buffer jit) is **Tier-1**: it agrees with the unfused
    Tier-0 reference within the documented tolerance bound
    (tests/tolerance.py) at every batch shape, and is itself fully
    deterministic — a full planetlab x start cell reproduces bitwise
    across runs and across pickling;
  * a warm interval performs **zero XLA retraces and zero host->device
    transfers** beyond its single upload, the packed staging vector that
    the launch takes as its own argument (that guarantee is hard, not
    toleranced);
  * the sweep's parent-pretrain broadcast and the parent-participating
    scheduler preserve serial == parallel bitwise while removing the
    per-worker duplicate pretraining.
"""
import dataclasses
import functools
import pickle

import jax
import numpy as np
import pytest

from repro.core import encoder_lstm as net
from repro.core import features
from repro.core.predictor import (StragglerPredictor, _fused_step,
                                  _stage_put, fused_compile_count)
from repro.core.start import STARTController
from repro.sim import sweep
from repro.sim.engine import Simulation
from repro.sim.sweep import SweepSpec, deterministic_summary

from tolerance import assert_tier1

jax.config.update("jax_platform_name", "cpu")


def _cell_spec(**kw):
    base = dict(techniques=("start",), seeds=(0,), scenarios=("planetlab",),
                n_hosts=16, n_intervals=30, arrival_rate=0.8,
                max_workers=1, pretrain_epochs=2)
    base.update(kw)
    return SweepSpec(**base)


@pytest.fixture(scope="module")
def trained_start_bytes():
    spec = _cell_spec()
    cfg = spec.cell_config("planetlab", 0)
    return pickle.dumps(
        sweep.make_technique("start", cfg, pretrain_epochs=2)), cfg


# ---------------------- fused == unfused within Tier-1 ----------------------

def test_fused_cell_is_deterministic_across_runs(trained_start_bytes):
    """Tier-1 relaxes cross-path bitwise equality, NOT determinism: the
    whole planetlab x start cell must reproduce bitwise when the fused
    pipeline runs twice from the same pretrained bytes."""
    tech_bytes, cfg = trained_start_bytes
    a = pickle.loads(tech_bytes)
    assert a._controller.use_fused_step   # the default
    s_a = Simulation(cfg, technique=a).run()
    b = pickle.loads(tech_bytes)
    s_b = Simulation(cfg, technique=b).run()

    assert deterministic_summary(s_a) == deterministic_summary(s_b)
    # and the fused path actually ran: one staged upload per predicted
    # interval, nothing else
    pred = a._controller.predictor
    assert pred.h2d_stages > 0
    # the unfused route still works end to end (service degraded mode,
    # cold second-predicts) — no equality demanded at cell granularity:
    # per-interval ulp drift compounds through placement decisions
    c = pickle.loads(tech_bytes)
    c.use_fused_step = False      # forwards to the controller
    assert not c._controller.use_fused_step
    s_c = Simulation(cfg, technique=c).run()
    assert deterministic_summary(s_c)["tasks_total"] > 0


def test_fused_predict_interval_matches_predict_features():
    """Direct predictor-level equivalence across batch sizes within the
    Tier-1 bound, including the idle-interval catch-up roll (observe
    without predict).  The fused program restructures the emission
    (hoisted split encoder, unrolled scan, in-program Pareto tail, exact
    shapes for counts 5 and 9), so agreement is toleranced, not bitwise."""
    rng = np.random.default_rng(0)
    n_hosts, max_tasks = 6, 5
    pred_f = StragglerPredictor(n_hosts=n_hosts, max_tasks=max_tasks)
    pred_u = StragglerPredictor(n_hosts=n_hosts, max_tasks=max_tasks)
    hist = []
    for step, n in enumerate([1, 3, 0, 0, 2, 8, 5, 0, 9]):
        row = rng.uniform(0, 1, (n_hosts, features.HOST_FEATURES)) \
            .astype(np.float32)
        hist.append(row)
        pred_f.push_host_row(row)
        if n == 0:
            continue  # idle interval: history advances, no prediction
        m_t = rng.uniform(0, 1, (n, max_tasks, features.TASK_FEATURES)) \
            .astype(np.float32)
        q = rng.integers(1, max_tasks, n).astype(np.float32)
        # unfused reference uses the deque semantics (last horizon rows,
        # left-padded with the oldest)
        seq = list(hist[-pred_u.horizon:])
        while len(seq) < pred_u.horizon:
            seq.insert(0, seq[0])
        want = np.asarray(
            pred_u.predict_features(np.stack(seq), m_t, q).e_s)
        got = pred_f.predict_interval(m_t, q)
        assert_tier1(got, want, context=f"step {step}")


def test_fused_predictor_survives_pickling_mid_run():
    """The device ring is a cache: pickling drops it and the next predict
    rebuilds from the staged host rows with identical results."""
    rng = np.random.default_rng(1)
    n_hosts, max_tasks = 4, 4
    ctrl = STARTController(n_hosts=n_hosts, max_tasks=max_tasks)
    assert ctrl.use_fused_step
    for _ in range(3):
        ctrl.observe_hosts(rng.uniform(
            0, 1, (n_hosts, features.HOST_FEATURES)).astype(np.float32))
        m_t = rng.uniform(0, 1, (2, max_tasks, features.TASK_FEATURES))
        ctrl.predictor.predict_interval(
            np.asarray(m_t, np.float32), np.full(2, 4.0, np.float32))
    clone = pickle.loads(pickle.dumps(ctrl))
    row = rng.uniform(0, 1, (n_hosts, features.HOST_FEATURES)) \
        .astype(np.float32)
    m_t = np.asarray(rng.uniform(
        0, 1, (3, max_tasks, features.TASK_FEATURES)), np.float32)
    q = np.full(3, 4.0, np.float32)
    ctrl.observe_hosts(row)
    clone.observe_hosts(row)
    np.testing.assert_array_equal(
        clone.predictor.predict_interval(m_t, q),
        ctrl.predictor.predict_interval(m_t, q))


# ------------------- zero retraces / zero transfers warm -------------------

def test_warm_intervals_zero_retraces_and_zero_transfers(
        trained_start_bytes, sanction_uploads):
    """After a cell has warmed every bucket, further cells must (a) never
    recompile a prediction program and (b) perform no host->device
    transfer per interval beyond the fused step's one upload, which is the
    launch's own staging argument — pinned by running a whole warm cell
    under ``jax.transfer_guard_host_to_device('disallow')`` with only the
    predictor's funnels exempted: ``_launch`` (the warm interval) and
    ``_stage`` (ring rebuilds and catch-up rows, never a warm interval's
    upload)."""
    tech_bytes, cfg = trained_start_bytes
    warm = pickle.loads(tech_bytes)
    Simulation(cfg, technique=warm).run()          # warm all buckets

    calls = sanction_uploads()
    tech = pickle.loads(tech_bytes)
    compiles_before = (net.predict_sequence._cache_size()
                       + fused_compile_count())
    sim = Simulation(cfg, technique=tech)
    with jax.transfer_guard_host_to_device("disallow"):
        sim.run()
    grew = (net.predict_sequence._cache_size() + fused_compile_count()
            - compiles_before)
    assert grew == 0, "warm cell retraced a prediction program"
    pred = tech._controller.predictor
    # one upload per predicted interval, carried by its launch (ring
    # rebuilds after unpickling add their one-time upload through _stage)
    assert pred.h2d_stages <= cfg.n_intervals + 1
    assert calls["_launch"] == pred.fused_calls > 0
    assert calls["_stage"] == pred.ring_rebuilds + pred.catchup_rolls
    assert pred.h2d_stages == calls["_launch"] + calls["_stage"]
    assert pred.h2d_stages > 0


# ------------- the staging vector as the launch's own argument -------------

def _staged_predictor(n, rng, n_hosts=4, max_tasks=3):
    pred = StragglerPredictor(n_hosts=n_hosts, max_tasks=max_tasks)
    for _ in range(pred.horizon):
        pred.push_host_row(rng.uniform(
            0, 1, (n_hosts, features.HOST_FEATURES)).astype(np.float32))
    m_t = rng.uniform(0, 1, (n, max_tasks, features.TASK_FEATURES)) \
        .astype(np.float32)
    q = rng.integers(1, max_tasks + 1, n).astype(np.float32)
    return pred, m_t, q


@pytest.mark.parametrize("per_task", [False, True])
@pytest.mark.parametrize("n", [1, 6])
def test_launch_argument_is_bitwise_the_staged_upload(n, per_task):
    """Handing the packed numpy staging vector to ``_fused_step`` as its
    own argument is the same program on the same bytes as uploading it
    first through ``_stage_put``: E_S, the per-task scores and the new
    ring are bitwise equal."""
    pred, m_t, q = _staged_predictor(n, np.random.default_rng(n))
    pred.predict_interval(m_t, q)                 # builds the ring
    nb = pred.batch_size(n)
    buf = pred._pack(pred._row_hist[-1], m_t, q, n, nb).copy()
    ring = np.asarray(pred._ring)
    kw = dict(nb=nb, task_dim=pred.task_dim, per_task=per_task,
              use_pallas=pred.use_pallas_cell, unroll=pred._unroll(nb))
    got = _fused_step(pred.params, _stage_put(ring), buf, **kw)
    want = _fused_step(pred.params, _stage_put(ring), _stage_put(buf), **kw)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("operand", ["params", "ring"])
def test_sanctioned_launch_refuses_host_operands(operand, sanction_uploads):
    """The zero-transfer tests let ``_launch`` upload under the guard, so
    the sanction itself must refuse a launch whose params or ring sit in
    host memory (a numpy leaf would be uploaded on every interval)."""
    rng = np.random.default_rng(3)
    pred, m_t, q = _staged_predictor(2, rng)
    pred.predict_interval(m_t, q)                 # warm: ring on device
    pred.push_host_row(rng.uniform(
        0, 1, (pred.n_hosts, features.HOST_FEATURES)).astype(np.float32))
    if operand == "params":
        pred.params = jax.tree_util.tree_map(np.asarray, pred.params)
    else:
        pred._ring = np.asarray(pred._ring)
    calls = sanction_uploads()
    with jax.transfer_guard_host_to_device("disallow"):
        with pytest.raises(AssertionError, match="host operands"):
            pred.predict_interval(m_t, q)
    assert calls["_launch"] == 1


# --------------------- pallas-cell training route exact ---------------------

def test_lstm_cell_gradients_exact_match_reference():
    """The fused Pallas cell is differentiable (custom VJP: kernel
    forward, rematerialized-reference backward) and under jit — the only
    way training ever runs — its gradients are bitwise-identical to
    differentiating the reference cell.  (Eager per-op dispatch compiles
    slightly different transpose sequences and lands within an ulp; the
    jitted whole-graph comparison is the contract.)"""
    from repro.kernels.lstm_cell import lstm_cell, lstm_cell_ref
    lstm_cell = functools.partial(lstm_cell, interpret=True)
    rng = np.random.default_rng(3)
    layer = net._lstm_init(jax.random.PRNGKey(3), 32, 32)
    x, h, c = (np.asarray(rng.normal(size=(8, 32)), np.float32)
               for _ in range(3))

    def loss(cell_fn, layer):
        h2, c2 = cell_fn(x, h, c, layer["wx"], layer["wh"], layer["b"])
        return (h2 * h2 + c2).sum()

    g_ref = jax.jit(jax.grad(lambda p: loss(lstm_cell_ref, p)))(layer)
    g_pal = jax.jit(jax.grad(lambda p: loss(lstm_cell, p)))(layer)
    for k in g_ref:
        np.testing.assert_array_equal(np.asarray(g_ref[k]),
                                      np.asarray(g_pal[k]), err_msg=k)


def test_fit_through_pallas_cell_reproduces_reference_training():
    """StragglerPredictor.fit(use_pallas_cell=...) routes every train
    step through the fused cell.  The isolated cell gradient is bitwise
    exact (test above); inside the full train-step graph XLA may fuse
    the surrounding network differently per path, so whole-training
    params are pinned to ulp-level agreement rather than bit equality."""
    rng = np.random.default_rng(0)
    ref = StragglerPredictor(n_hosts=2, max_tasks=3)
    pal = StragglerPredictor(n_hosts=2, max_tasks=3)
    dim = ref.input_dim
    xs = rng.normal(size=(5, 8, dim)).astype(np.float32)
    ys = np.abs(rng.normal(size=(8, 2))).astype(np.float32) + 1.0
    l_ref = ref.fit(xs, ys, epochs=2, lr=1e-3)
    l_pal = pal.fit(xs, ys, epochs=2, lr=1e-3,
                    use_pallas_cell="interpret")
    np.testing.assert_allclose(l_ref, l_pal, rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-8),
        ref.params, pal.params)


# ----------------------- sweep scheduling / broadcast -----------------------

def test_pretrain_payload_broadcast_matches_local_training():
    """A technique built from the parent's broadcast bytes must equal one
    the worker would have trained locally (same fixed seeds)."""
    spec = _cell_spec()
    cfg = spec.cell_config("planetlab", 0)
    payload = sweep.pretrain_payload(spec, "planetlab", "start")
    assert payload is not None
    via_payload = sweep.make_technique("start", cfg, pretrain_epochs=2,
                                       pretrained=payload)
    local = sweep.make_technique("start", cfg, pretrain_epochs=2)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        via_payload._controller.predictor.params,
        local._controller.predictor.params)
    # techniques that do not pretrain have no payload
    assert sweep.pretrain_payload(spec, "planetlab", "none") is None


def test_schedule_units_group_by_technique_and_cover_grid():
    spec = SweepSpec(techniques=("none", "sgc"), seeds=(0, 1, 2),
                     scenarios=("planetlab", "heavy-tail"),
                     n_hosts=8, n_intervals=10)
    units = sweep._schedule_units(spec, n_workers=2)
    flat = [c for u in units for c in u]
    assert sorted(flat) == sorted(spec.cells())      # exact cover
    for u in units:  # affinity: one (technique, scenario) per unit
        assert len({(c[1], c[0]) for c in u}) == 1


def test_parallel_run_with_pretrained_technique_bitwise_equals_serial():
    spec = _cell_spec(seeds=(0, 1), scenarios=("planetlab", "heavy-tail"),
                      n_hosts=8, n_intervals=12, max_workers=2)
    serial = sweep.run(dataclasses.replace(spec, max_workers=1))
    parallel = sweep.run(spec)
    assert [(c.scenario, c.technique, c.seed) for c in parallel.cells] \
        == spec.cells()
    for a, b in zip(serial.cells, parallel.cells):
        assert deterministic_summary(a.summary) \
            == deterministic_summary(b.summary)
    sweep.shutdown_pool()


def test_warm_pool_reports_spawn_and_pool_is_ready():
    sweep.shutdown_pool()
    spawn_s = sweep.warm_pool(2)
    assert spawn_s > 0
    assert all(f.done() for f in sweep._POOL_READY)
    # warming an already-warm pool is ~free
    assert sweep.warm_pool(2) < spawn_s
    sweep.shutdown_pool()
