"""The program's profiler spans and the predictor's counters.

A short START episode traced by the JAX profiler holds every span of the
decision path, each inside its parent and inside one interval's
``sim.step``; with no profiler running ``span`` hands back one shared
no-op; the predictor's counters match a hand count over a scripted
sequence of host rows and predictions.
"""
import collections
import pathlib
import sys

import jax
import numpy as np
import pytest

from repro import trace
from repro.core import features
from repro.core.predictor import StragglerPredictor
from repro.core.start import STARTController
from repro.sim.config import SimConfig
from repro.sim.engine import Simulation
from repro.sim.techniques.start_tech import START

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import programspans  # noqa: E402

jax.config.update("jax_platform_name", "cpu")

# every span of one decision, with the span it nests in
PARENT = {
    "sim.step": None,
    "sim.arrivals": "sim.step", "sim.submit": "sim.step",
    "sim.place": "sim.step", "sim.faults": "sim.step",
    "sim.policy": "sim.step", "sim.progress": "sim.step",
    "sim.record": "sim.step",
    "start.observe": "sim.policy", "start.host_features": "start.observe",
    "start.decide": "sim.policy", "start.task_features": "start.decide",
    "start.trigger": "start.decide", "start.guard": "start.decide",
    "predictor.interval": "start.decide",
    "predictor.sync_ring": "predictor.interval",
    "predictor.pack": "predictor.interval",
    "predictor.dispatch": "predictor.interval",
    "predictor.readback": "predictor.interval",
}


def test_span_is_the_shared_noop_without_a_profiler():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert trace.span("sim.step", t=3) is trace.OFF
    assert trace.span("predictor.pack", t=3, n=2, nb=2) is trace.OFF
    with trace.span("sim.step", t=0):
        pass


@pytest.fixture(scope="module")
def episode_spans(tmp_path_factory):
    cfg = SimConfig(n_hosts=20, n_intervals=12, seed=3)
    ctrl = STARTController(cfg.n_hosts, cfg.max_tasks, k=cfg.k)
    sim = Simulation(cfg, technique=START(controller=ctrl))
    out = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        for _ in range(cfg.n_intervals):
            sim.step()
    finally:
        jax.profiler.stop_trace()
    from bench import tracereduce
    spans = programspans.load(tracereduce.find_xplane(out))
    return cfg, ctrl.predictor, spans


def _innermost_parent(sp, spans):
    around = [o for o in spans if o is not sp and o[3] == sp[3]
              and o[1] <= sp[1] and sp[2] <= o[2]
              and (o[1], -o[2]) < (sp[1], -sp[2])]
    return min(around, key=lambda o: o[2] - o[1]) if around else None


def test_episode_writes_every_span_nested_in_its_parent(episode_spans):
    cfg, pred, spans = episode_spans
    names = collections.Counter(sp[0] for sp in spans)
    assert set(names) == set(PARENT), set(PARENT) ^ set(names)
    assert names["sim.step"] == cfg.n_intervals
    for name in PARENT:
        if name.startswith("sim."):
            assert names[name] == cfg.n_intervals, name
    assert names["predictor.interval"] == pred.fused_calls > 0
    for sp in spans:
        parent = _innermost_parent(sp, spans)
        if PARENT[sp[0]] is None:
            assert parent is None, sp
        else:
            assert parent is not None and parent[0] == PARENT[sp[0]], sp


def test_one_readback_per_prediction_and_one_t_per_interval(episode_spans):
    cfg, pred, spans = episode_spans
    calls = [sp for sp in spans if sp[0] == "predictor.interval"]
    for call in calls:
        inside = [sp for sp in spans if sp[0] == "predictor.readback"
                  and call[1] <= sp[1] and sp[2] <= call[2]]
        assert len(inside) == 1
        for sp in spans:
            if sp[0].startswith("predictor.") and \
                    call[1] <= sp[1] and sp[2] <= call[2]:
                assert (sp[4]["n"], sp[4]["nb"]) == \
                    (call[4]["n"], call[4]["nb"])
        assert call[4]["nb"] >= call[4]["n"] >= 1
    assert sum(c[4]["n"] for c in calls) == pred.rows_real
    assert sum(c[4]["nb"] for c in calls) == pred.rows_dispatched
    steps = sorted(sp for sp in spans if sp[0] == "sim.step")
    assert [sp[4]["t"] for sp in steps] == list(range(cfg.n_intervals))
    # the engine's and the policy's spans carry their interval; the
    # trigger's and the predictor's take it from the step they nest in
    for sp in spans:
        around = [st for st in steps if st[1] <= sp[1] and sp[2] <= st[2]]
        assert len(around) == 1, sp
        if sp[0].startswith(("sim.", "start.")) \
                and sp[0] != "start.trigger":
            assert sp[4]["t"] == around[0][4]["t"], sp
        else:
            assert "t" not in sp[4], sp
    for step in steps:
        assert sum(step[1] <= sp[1] and sp[2] <= step[2]
                   for sp in spans) <= 19


def test_predictor_counters_match_a_hand_count():
    pred = StragglerPredictor(n_hosts=4, max_tasks=3, horizon=3)
    rng = np.random.default_rng(0)

    def row():
        return rng.random((4, features.HOST_FEATURES), np.float32)

    def predict(n):
        m_t = rng.random((n, 3, features.TASK_FEATURES), np.float32)
        return pred.predict_interval(m_t, np.full(n, 3, np.float32))

    def counters():
        return (pred.fused_calls, pred.catchup_rolls, pred.ring_rebuilds,
                pred.rows_real, pred.rows_dispatched, pred.h2d_stages)

    assert counters() == (0, 0, 0, 0, 0, 0)
    pred.push_host_row(row())
    predict(3)              # cold start: rebuild, then a 4-row bucket
    assert counters() == (1, 0, 1, 3, 4, 2)
    pred.push_host_row(row())
    predict(5)              # 8 would waste 3/8: the exact shape 5
    assert counters() == (2, 0, 1, 8, 9, 3)
    pred.push_host_row(row())
    pred.push_host_row(row())
    predict(1)              # an idle interval: one catch-up roll
    assert counters() == (3, 1, 1, 9, 10, 5)
    for _ in range(4):
        pred.push_host_row(row())
    predict(2)              # fell a whole horizon behind: a rebuild
    assert counters() == (4, 1, 2, 11, 12, 7)
    assert pred.compile_count > 0   # the other counters' readers stay
    assert pred.buckets_used == {4, 5, 1, 2}
