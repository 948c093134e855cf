"""Per-task predicted-straggler trigger (the late-trigger-gap fix).

Pins the PR's contracts:

  * the predictor's per-task score head agrees between the fused device
    step and the historical unfused path within the Tier-1 tolerance
    bound (tests/tolerance.py) at every batch shape — the fused program
    restructures the emission, so cross-path equality is toleranced —
    and scores decompose the job-level E_S exactly;
  * with the per-task head enabled the fused warm path still performs
    zero XLA retraces and zero host->device transfers beyond its single
    staged upload;
  * non-finite E_S from the network can neither crash the controller
    nor force-fire its trigger (clamped to [0, q], non-finite -> 0);
  * on a seeded ``overload`` cell, legacy ``start`` emits zero
    mitigation actions before the first job-completion milestone while
    ``start-eager`` acts strictly earlier, and over >= 5 seeds
    ``start-eager`` improves both SLA-violation rate and execution time
    over legacy ``start`` AND ``none``;
  * the eager technique exists on both substrates (sim registry entry +
    the pod policy translating to backup-shard/evict).
"""
import dataclasses
import pickle
import types

import jax
import numpy as np
import pytest

from repro.core import encoder_lstm as net
from repro.core import features
from repro.core.predictor import StragglerPredictor, fused_compile_count
from repro.core.start import JobView, STARTController
from repro.sim.engine import Simulation
from repro.sim.sweep import SweepSpec
from repro.sim.techniques.start_tech import START, STARTEager, pretrain
from repro.sim import sweep

from tolerance import assert_tier1

jax.config.update("jax_platform_name", "cpu")

OVERLOAD = dict(scenarios=("overload",), n_hosts=16, n_intervals=40,
                arrival_rate=0.8, max_workers=1, pretrain_epochs=2)


@pytest.fixture(scope="module")
def overload_ctrl_bytes():
    """One pretrained controller for the overload cells — START and
    STARTEager pretrain identically (same seed-7 warmup, same fit), so
    both techniques are built from clones of this single controller."""
    spec = SweepSpec(techniques=("start",), seeds=(0,), **OVERLOAD)
    cfg = spec.cell_config("overload", 0)
    return pickle.dumps(
        pretrain(dataclasses.replace(cfg, seed=7), epochs=2, lr=1e-3)), spec


# ----------------------- per-task score head: equality ----------------------

def test_per_task_scores_fused_equals_unfused_per_shape():
    """(e_s, scores) must agree within the Tier-1 bound between the fused
    device step and the unfused path across batch shapes, including idle
    intervals (observe without predict)."""
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
            continue
        m_t = rng.uniform(0, 1, (n, max_tasks, features.TASK_FEATURES)) \
            .astype(np.float32)
        q = rng.integers(1, max_tasks, n).astype(np.float32)
        seq = list(hist[-pred_u.horizon:])
        while len(seq) < pred_u.horizon:
            seq.insert(0, seq[0])
        want_es, want_s = pred_u.predict_features(
            np.stack(seq), m_t, q, per_task=True)
        got_es, got_s = pred_f.predict_interval(m_t, q, per_task=True)
        assert_tier1(got_es, want_es, context=f"e_s step {step}")
        assert_tier1(got_s, want_s, context=f"scores step {step}")
        assert got_s.shape == (n, max_tasks)


def test_per_task_scores_decompose_es():
    """Scores are the demand-share decomposition of E_S: non-negative,
    summing to the job's E_S over its real tasks, zero on padded slots;
    an all-zero-demand job falls back to uniform E_S / q."""
    rng = np.random.default_rng(1)
    n_hosts, max_tasks = 4, 6
    pred = StragglerPredictor(n_hosts=n_hosts, max_tasks=max_tasks)
    pred.push_host_row(rng.uniform(
        0, 1, (n_hosts, features.HOST_FEATURES)).astype(np.float32))
    m_t = rng.uniform(0, 1, (3, max_tasks, features.TASK_FEATURES)) \
        .astype(np.float32)
    q = np.array([6, 3, 4], np.float32)
    m_t[1, 3:] = 0.0          # job 1: only 3 real tasks, rest padded
    m_t[2, :, :4] = 0.0       # job 2: zero resource demand everywhere
    e_s, scores = pred.predict_interval(m_t, q, per_task=True)
    assert np.all(scores >= 0.0)
    np.testing.assert_allclose(scores.sum(axis=1), e_s, rtol=1e-5)
    assert np.all(scores[1, 3:] == 0.0)          # padded slots score 0
    np.testing.assert_allclose(                  # uniform fallback
        scores[2, :4], np.full(4, e_s[2] / 4.0), rtol=1e-5)
    assert np.all(scores[2, 4:] == 0.0)


# ------------------- warm path: zero retraces / zero H2D --------------------

def test_warm_per_task_cell_zero_retraces_and_zero_transfers(
        overload_ctrl_bytes, sanction_uploads):
    """A warm start-eager cell — the per-task head enabled on every
    predicted interval — must never recompile a prediction program and
    must perform no host->device transfer beyond the fused step's one
    upload, which is the launch's own staging argument (``_launch``);
    ``_stage`` uploads only ring rebuilds and catch-up rows."""
    ctrl_bytes, spec = overload_ctrl_bytes
    cfg = spec.cell_config("overload", 0)
    warm = STARTEager(controller=pickle.loads(ctrl_bytes))
    Simulation(cfg, technique=warm).run()          # warm all buckets

    calls = sanction_uploads()
    tech = STARTEager(controller=pickle.loads(ctrl_bytes))
    compiles_before = (net.predict_sequence._cache_size()
                       + fused_compile_count())
    sim = Simulation(cfg, technique=tech)
    with jax.transfer_guard_host_to_device("disallow"):
        sim.run()
    grew = (net.predict_sequence._cache_size() + fused_compile_count()
            - compiles_before)
    assert grew == 0, "warm per-task cell retraced a prediction program"
    pred = tech._controller.predictor
    assert pred.h2d_stages > 0
    assert pred.h2d_stages <= cfg.n_intervals + 1
    assert calls["_launch"] == pred.fused_calls > 0
    assert calls["_stage"] == pred.ring_rebuilds + pred.catchup_rolls
    assert pred.h2d_stages == calls["_launch"] + calls["_stage"]


# ------------------------- non-finite E_S guard -----------------------------

def test_sanitize_es_clamps_and_zeroes_nonfinite():
    got = STARTController._sanitize_es(
        np.array([np.nan, np.inf, -np.inf, -1.0, 2.5, 99.0]),
        np.array([4.0, 4.0, 4.0, 4.0, 4.0, 4.0]))
    np.testing.assert_array_equal(got, [0.0, 0.0, 0.0, 0.0, 2.5, 4.0])


@pytest.mark.parametrize("bad", [np.nan, np.inf])
def test_nonfinite_es_cannot_fire_or_crash_either_trigger(bad):
    """A NaN/inf network output used to flow into np.floor and either
    crash ``decide`` or permanently force-fire ``decide_arrays``; it
    must now read as 'no predicted stragglers' on both paths."""
    for trigger in ("milestone", "per_task"):
        ctrl = STARTController(n_hosts=4, max_tasks=3, trigger=trigger,
                               hysteresis=1, use_fused_step=False)
        ctrl.observe_hosts(np.zeros((4, features.HOST_FEATURES),
                                    np.float32))
        ctrl.predictor.predict_features = types.MethodType(
            lambda self, *a, **kw:
            (np.full(2, bad), np.full((2, 3), bad)) if kw.get("per_task")
            else types.SimpleNamespace(e_s=np.full(2, bad)),
            ctrl.predictor)
        m_t = np.zeros((2, 3, features.TASK_FEATURES), np.float32)
        acts = ctrl.decide_arrays(
            np.array([0, 1]), m_t, np.array([3.0, 3.0]),
            np.array([1, 1]), np.array([True, False]),
            lambda job: ([0], [0], [0]))
        assert acts == []
        assert ctrl.es_total([0, 1]) == 0.0
    # JobView path: int(np.floor(nan)) used to raise ValueError
    ctrl = STARTController(n_hosts=4, max_tasks=3, use_fused_step=False)
    ctrl.observe_hosts(np.zeros((4, features.HOST_FEATURES), np.float32))
    ctrl.predictor.predict_features = types.MethodType(
        lambda self, *a, **kw: types.SimpleNamespace(e_s=np.full(1, bad)),
        ctrl.predictor)
    jv = JobView(job_id=0, q=3, deadline_oriented=True,
                 incomplete_task_ids=[0], task_hosts=[0],
                 task_matrix=np.zeros((3, features.TASK_FEATURES),
                                      np.float32))
    assert ctrl.decide([jv]) == []


# ----------------------- per-task trigger unit behavior ---------------------

def _scripted_controller(es_value, n_tasks=3, **kw):
    """Controller whose prediction is scripted: E_S fixed, scores
    concentrated on slot 0."""
    ctrl = STARTController(n_hosts=4, max_tasks=n_tasks,
                           trigger="per_task", use_fused_step=False, **kw)
    ctrl.observe_hosts(np.zeros((4, features.HOST_FEATURES), np.float32))
    scores = np.zeros((1, n_tasks))
    scores[0, 0] = es_value

    def scripted(self, *a, **kwargs):
        if kwargs.get("per_task"):
            return np.full(1, es_value), scores
        return types.SimpleNamespace(e_s=np.full(1, es_value))

    ctrl.predictor.predict_features = types.MethodType(
        scripted, ctrl.predictor)
    return ctrl


def _step(ctrl):
    ctrl.observe_hosts(np.zeros((4, features.HOST_FEATURES), np.float32))
    return ctrl.decide_arrays(
        np.array([7]), np.zeros((1, 3, features.TASK_FEATURES),
                                np.float32),
        np.array([3.0]), np.array([3]), np.array([True]),
        lambda job: ([10, 11, 12], [0, 1, 2], [0, 1, 2]))


def test_per_task_hysteresis_then_cooldown():
    """The top-scored task fires exactly after ``hysteresis``
    consecutive in-set intervals, then not again until ``cooldown``
    intervals passed."""
    ctrl = _scripted_controller(1.4, hysteresis=3, cooldown=4,
                                score_on=0.1)
    fired = [len(_step(ctrl)) for _ in range(10)]
    # fires on the 3rd interval (hysteresis=3); the streak keeps
    # building through the cooldown, so the re-fire lands exactly
    # ``cooldown`` intervals later, then cools again
    assert fired == [0, 0, 1, 0, 0, 0, 1, 0, 0, 0]
    acts = []
    ctrl2 = _scripted_controller(1.4, hysteresis=3, cooldown=4,
                                 score_on=0.1)
    for _ in range(3):
        acts = _step(ctrl2)
    assert [a.task_id for a in acts] == [10]     # the top-scored task


def test_per_task_streak_resets_when_set_empties():
    ctrl = _scripted_controller(1.4, hysteresis=3, cooldown=4,
                                score_on=0.1)
    assert _step(ctrl) == [] and _step(ctrl) == []
    ctrl.score_on = 10.0                         # set goes empty
    assert _step(ctrl) == []
    ctrl.score_on = 0.1                          # streak must restart
    assert [len(_step(ctrl)) for _ in range(3)] == [0, 0, 1]


def test_per_task_load_gate_defers_fire_on_idle_host():
    """With host_load given, a set member on a below-median-load host
    defers its fire until its host is contended (streak preserved)."""
    ctrl = _scripted_controller(1.4, hysteresis=2, cooldown=4,
                                score_on=0.1)
    idle = np.array([0.0, 1.0, 1.0, 1.0])       # task 10 lives on host 0
    busy = np.array([2.0, 1.0, 1.0, 1.0])

    def step(load):
        ctrl.observe_hosts(np.zeros((4, features.HOST_FEATURES),
                                    np.float32))
        return ctrl.decide_arrays(
            np.array([7]), np.zeros((1, 3, features.TASK_FEATURES),
                                    np.float32),
            np.array([3.0]), np.array([3]), np.array([True]),
            lambda job: ([10, 11, 12], [0, 1, 2], [0, 1, 2]),
            host_load=load)

    assert step(idle) == [] and step(idle) == [] and step(idle) == []
    assert [a.task_id for a in step(busy)] == [10]


def test_milestone_trigger_unchanged_by_extended_incomplete_fn():
    """Legacy milestone controllers accept (and ignore) the per-task
    slot element, so one policy-side callback serves both modes."""
    ctrl = STARTController(n_hosts=4, max_tasks=3, use_fused_step=False)
    ctrl.observe_hosts(np.zeros((4, features.HOST_FEATURES), np.float32))
    ctrl.predictor.predict_features = types.MethodType(
        lambda self, *a, **kw: types.SimpleNamespace(
            e_s=np.full(1, 2.0)), ctrl.predictor)
    acts = ctrl.decide_arrays(
        np.array([7]), np.zeros((1, 3, features.TASK_FEATURES),
                                np.float32),
        np.array([3.0]), np.array([2]), np.array([True]),
        lambda job: ([10, 11], [0, 1], [0, 1]))
    assert sorted(a.task_id for a in acts) == [10, 11]


# ------------------------ the late-trigger gap itself -----------------------

@pytest.mark.slow
def test_start_waits_for_milestone_while_eager_acts_before_it(
        overload_ctrl_bytes):
    """The seeded overload cell: legacy start emits zero mitigation
    actions before the first job-completion milestone (on this cell it
    never fires at all), while start-eager emits its first action
    strictly earlier than the first completion."""
    ctrl_bytes, spec = overload_ctrl_bytes
    cfg = spec.cell_config("overload", 0)

    def run(cls):
        tech = cls(controller=pickle.loads(ctrl_bytes))
        fires = []
        orig = type(tech).decide

        def wrapped(self, view):
            acts = orig(self, view)
            if acts:
                fires.append(int(view.t))
            return acts

        tech.decide = types.MethodType(wrapped, tech)
        sim = Simulation(cfg, technique=tech)
        sim.run()
        done_ts = [r["t"] for r in sim.snapshot().completed_jobs]
        return fires, (min(done_ts) if done_ts else None)

    start_fires, start_done = run(START)
    eager_fires, eager_done = run(STARTEager)
    assert start_done is not None and eager_done is not None
    # legacy start: nothing before the first completion milestone
    assert not [t for t in start_fires if t < start_done]
    # eager: first action strictly before any job completed
    assert eager_fires and eager_fires[0] < eager_done
    # and strictly before legacy start's first action (if it ever fired)
    if start_fires:
        assert eager_fires[0] < start_fires[0]


@pytest.mark.slow
def test_eager_strictly_improves_overload_over_start_and_none(
        overload_ctrl_bytes):
    """The PR's acceptance cell: mean SLA-violation rate AND mean
    execution time over 5 seeds, start-eager < start and < none."""
    ctrl_bytes, _ = overload_ctrl_bytes
    spec = SweepSpec(techniques=("none", "start", "start-eager"),
                     seeds=(0, 1, 2, 3, 4), **OVERLOAD)

    def run_cells(make_tech):
        sla, ex = [], []
        for seed in spec.seeds:
            cfg = spec.cell_config("overload", seed)
            s = Simulation(cfg, technique=make_tech(cfg)).run()
            sla.append(s["sla_violation_rate"])
            ex.append(s["avg_execution_time_s"])
        return float(np.mean(sla)), float(np.mean(ex))

    res = {
        "none": run_cells(lambda cfg: sweep.make_technique("none", cfg)),
        "start": run_cells(
            lambda cfg: START(controller=pickle.loads(ctrl_bytes))),
        "start-eager": run_cells(
            lambda cfg: STARTEager(controller=pickle.loads(ctrl_bytes))),
    }
    eager = res["start-eager"]
    for other in ("start", "none"):
        assert eager[0] < res[other][0], \
            f"sla_violation_rate: eager {eager[0]} vs {other} " \
            f"{res[other][0]}"
        assert eager[1] < res[other][1], \
            f"avg_execution_time_s: eager {eager[1]} vs {other} " \
            f"{res[other][1]}"


# ----------------------------- both substrates ------------------------------

def test_eager_registered_on_both_substrates():
    from repro import policy
    import repro.distributed.straggler_runtime  # noqa: F401  (registers)
    import repro.sim.techniques as T
    assert "start-eager" in policy.names("sim")
    assert "start-eager" in policy.names("pod")
    assert "start-eager-pod" in policy.names("pod")
    assert "start-eager-pod" not in policy.names("sim")
    assert "start-eager" in T.FIELD


def test_eager_pod_policy_backups_after_hysteresis_with_cooldown():
    """One chronically slow host: the eager pod policy backs up its
    shard only after ``hysteresis`` consecutive straggler steps, then
    rests ``cooldown`` steps; the runtime translates and picks a backup
    host."""
    from repro.distributed.straggler_runtime import (
        ActionKind, RuntimeConfig, StartEagerPodPolicy, StragglerRuntime)
    rt = StragglerRuntime(
        RuntimeConfig(n_hosts=8, evict_after=100),
        policy=StartEagerPodPolicy(hysteresis=3, cooldown=4))
    backups = []
    for t in range(10):
        times = np.full(8, 1.0)
        times[5] = 4.0                       # persistent straggler
        rt.observe_step(times)
        acts = rt.decide()
        backups.append([a.host for a in acts
                        if ActionKind(a.kind) is ActionKind.BACKUP_SHARD])
        for a in acts:
            assert ActionKind(a.kind) is ActionKind.BACKUP_SHARD
            assert a.backup is not None and a.backup != a.host
    fired = [t for t, b in enumerate(backups) if b == [5]]
    assert fired and fired[0] == 2           # 3rd straggler step
    assert all(not b for t, b in enumerate(backups) if t not in fired)
    assert len(fired) >= 2 and fired[1] - fired[0] == 4  # cooldown held
