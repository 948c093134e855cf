"""Array-native hot-path tests: batched placement bitwise-equals the
sequential scheduler loop (including edge cases), task-matrix features use
the previous-host field, the jitted predictor compiles at most once per
batch bucket, the Pallas LSTM-cell route is exact, predictor.fit keeps one
minibatch shape, and sweep-result lookups are indexed."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import encoder_lstm as net
from repro.core import features, predictor
from repro.core.predictor import StragglerPredictor, bucket_size
from repro.sim import Simulation, small, sweep
from repro.sim.cluster import Cluster
from repro.sim.scheduler import RandomScheduler, UtilizationAwareScheduler
from repro.sim.sweep import CellResult, SweepResult, SweepSpec
from repro.sim.techniques.start_tech import _task_matrix

jax.config.update("jax_platform_name", "cpu")


def _cluster(n_hosts=12, seed=0, **kw):
    cfg = small(n_hosts=n_hosts, **kw)
    rng = np.random.default_rng(seed)
    c = Cluster(cfg, rng)
    # a non-trivial utilization/task profile for the scorer
    c.util = np.abs(np.random.default_rng(seed + 1)
                    .normal(0.3, 0.2, c.util.shape))
    c.n_tasks = np.random.default_rng(seed + 2).integers(
        0, 7, n_hosts).astype(np.int64)
    return c


def _sequential_reference(sched, cluster, reqs, rng, exclude):
    """The engine's historical per-task loop: place with exclusion, then
    re-place without it if the chosen host is down."""
    out = np.empty(len(reqs), np.int64)
    for i, req in enumerate(reqs):
        ex = int(exclude[i]) if exclude[i] >= 0 else None
        h = sched.place(cluster, req, rng, exclude=ex)
        if cluster.downtime[h] > 0:
            h = sched.place(cluster, req, rng)
        out[i] = h
    return out


# --------------------------- place_batch ≡ place ----------------------------

@pytest.mark.parametrize("sched_cls", [UtilizationAwareScheduler,
                                       RandomScheduler])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_place_batch_bitwise_equals_sequential_place(sched_cls, seed):
    """Randomized workload: batched placement must reproduce the
    sequential loop exactly (hosts AND rng stream)."""
    c = _cluster(n_hosts=14, seed=seed)
    rng = np.random.default_rng(seed + 10)
    n = 64
    reqs = rng.uniform(0.02, 0.6, (n, 4))
    exclude = rng.integers(-1, c.n, n)
    c.downtime[rng.integers(0, c.n, 4)] = 2  # some hosts down

    sched = sched_cls()
    ref_rng = np.random.default_rng(99)
    got_rng = np.random.default_rng(99)
    want = _sequential_reference(sched, c, reqs, ref_rng, exclude)
    got = sched.place_batch(c, reqs, got_rng, exclude=exclude)
    np.testing.assert_array_equal(got, want)
    # randomized schedulers must leave the rng stream in the same state
    assert ref_rng.integers(0, 1 << 30) == got_rng.integers(0, 1 << 30)


def test_place_batch_all_hosts_offline():
    """Every host down: placement still returns a host (the engine keeps
    the task nominally placed; progress is zero while the host is down)."""
    c = _cluster(n_hosts=6)
    c.downtime[:] = 3
    reqs = np.full((5, 4), 0.2)
    exclude = np.array([-1, 2, 0, -1, 5])
    sched = UtilizationAwareScheduler()
    rng = np.random.default_rng(0)
    want = _sequential_reference(sched, c, reqs, rng, exclude)
    got = sched.place_batch(c, reqs, rng, exclude=exclude)
    np.testing.assert_array_equal(got, want)
    assert ((got >= 0) & (got < c.n)).all()


def test_place_batch_exclude_with_single_online_host():
    """One host online and it's the excluded one: the exclusion is waived
    (exclusions only apply with >1 online host) and the task lands there."""
    c = _cluster(n_hosts=5)
    c.downtime[:] = 2
    c.downtime[3] = 0
    reqs = np.full((3, 4), 0.1)
    exclude = np.array([3, 3, -1])
    sched = UtilizationAwareScheduler()
    rng = np.random.default_rng(0)
    got = sched.place_batch(c, reqs, rng, exclude=exclude)
    np.testing.assert_array_equal(got, [3, 3, 3])
    np.testing.assert_array_equal(
        got, _sequential_reference(sched, c, reqs, rng, exclude))


def test_engine_survives_all_hosts_offline_interval():
    cfg = small(n_hosts=6, n_intervals=10, fault_host_rate=0.0)
    sim = Simulation(cfg)
    sim.step()
    sim.cluster.downtime[:] = 4  # blackout: every later placement is forced
    for _ in range(4):
        sim.step()
    s = sim.summary()
    assert s["tasks_total"] >= 0  # no crash, bookkeeping intact
    for job in range(sim.jobs.n):
        tids = sim.jobs.task_ids(job)
        open_n = int((sim.tasks.state[tids] <= 1).sum())
        assert sim.jobs.open_count[job] == open_n


# ------------------------- feature-matrix twins -----------------------------

def test_host_matrix_np_matches_jax_twin_bitwise():
    rng = np.random.default_rng(3)
    n = 9
    util = rng.uniform(0, 1.4, (n, 4))
    cap = rng.uniform(1, 8, (n, 4))
    cost = rng.uniform(1, 5, n)
    pmax = rng.uniform(100, 300, n)
    ntasks = rng.integers(0, 9, n)
    a = features.host_matrix_np(util, cap, cost, pmax, ntasks)
    b = np.asarray(features.host_matrix(util, cap, cost, pmax, ntasks))
    assert a.dtype == np.float32
    np.testing.assert_array_equal(a, b)


def test_task_matrix_batch_np_matches_jax_twin_bitwise():
    rng = np.random.default_rng(4)
    n_hosts, max_tasks = 7, 10
    counts = np.array([2, 10, 5])
    rows = np.repeat(np.arange(3), counts)
    cols = np.concatenate([np.arange(c) for c in counts])
    req = rng.uniform(0.02, 0.9, (counts.sum(), 4))
    prev = rng.integers(-1, n_hosts, counts.sum())
    batch = features.task_matrix_batch_np(req, prev, rows, cols, 3,
                                          n_hosts, max_tasks)
    assert batch.shape == (3, max_tasks, features.TASK_FEATURES)
    off = 0
    for j, c in enumerate(counts):
        want = np.asarray(features.task_matrix(
            req[off:off + c], prev[off:off + c], n_hosts, max_tasks))
        np.testing.assert_array_equal(batch[j], want)
        off += c


def test_task_matrix_prev_host_feature_uses_previous_host_for_restarts():
    """Regression: a restarted (unplaced) task must report the host it ran
    on before the restart, not -1/'never placed'."""
    cfg = small(n_hosts=8, n_intervals=6, fault_host_rate=0.0,
                fault_task_rate=0.0, fault_vm_creation_rate=0.0)
    sim = Simulation(cfg)
    for _ in range(3):
        sim.step()
    tt = sim.tasks
    run = np.nonzero(tt.active_mask())[0]
    assert run.size > 0
    i = int(run[0])
    old_host = int(tt.host[i])
    sim._restart(i)          # fault-style restart: pending, unplaced
    assert tt.host[i] == -1 and tt.prev_host[i] == old_host
    mt = _task_matrix(sim.snapshot(), [i])
    expected = np.float32(old_host + 1.0) / np.float32(cfg.n_hosts)
    assert mt[0, 4] == expected
    # never-restarted running tasks keep reporting their current host
    j = int(run[1])
    mt_j = _task_matrix(sim.snapshot(), [j])
    assert mt_j[0, 4] == np.float32(int(tt.host[j]) + 1.0) \
        / np.float32(cfg.n_hosts)


# ----------------------- bucketed jit, no retraces --------------------------

def test_predict_sequence_compiles_once_per_bucket():
    """Sweeping the active-job count must not retrace per count: the
    predictor pads to power-of-two buckets, so the jit cache grows by at
    most one entry per distinct bucket and not at all on repeats."""
    pred = StragglerPredictor(n_hosts=3, max_tasks=4)
    rng = np.random.default_rng(0)
    mh = rng.uniform(0, 1, (5, 3, features.HOST_FEATURES)).astype(np.float32)

    def run_counts(counts):
        for n in counts:
            mt = rng.uniform(0, 1, (n, 4, features.TASK_FEATURES)) \
                .astype(np.float32)
            out = pred.predict_features(mh, mt, np.full(n, 4.0, np.float32))
            assert out.e_s.shape == (n,)

    before = net.predict_sequence._cache_size()
    run_counts([1, 2, 3, 4, 5, 6, 7, 8, 9, 12, 16])
    grew = net.predict_sequence._cache_size() - before
    assert pred.buckets_used == {1, 2, 4, 8, 16}
    assert grew <= len(pred.buckets_used)
    # repeats of already-seen counts (and new counts in seen buckets)
    # compile nothing
    mid = net.predict_sequence._cache_size()
    run_counts([1, 3, 5, 7, 9, 11, 13, 15, 16, 2, 10])
    assert net.predict_sequence._cache_size() == mid


def test_bucket_size():
    assert [bucket_size(n) for n in (0, 1, 2, 3, 4, 5, 8, 9, 17)] \
        == [1, 1, 2, 4, 4, 8, 8, 16, 32]


def test_batch_size_exact_shape_policy_and_budget(monkeypatch):
    """The Tier-1 exact-shape policy: counts whose power-of-two bucket
    wastes more than ``PAD_WASTE_LIMIT`` run at their exact width, up
    to ``EXACT_SHAPES_LIMIT`` distinct shapes; decisions replay
    deterministically and the budget bounds the steady-state compile
    count of a long-lived process."""
    pred = StragglerPredictor(n_hosts=3, max_tasks=4)
    assert pred.batch_size(3) == 4    # waste 1/4 == threshold: pads
    assert pred.batch_size(6) == 8    # waste 2/8 == threshold: pads
    assert pred.batch_size(5) == 5    # waste 3/8 > threshold: exact
    assert pred.batch_size(9) == 9    # waste 7/16: exact
    assert pred.batch_size(8) == 8    # exact power of two: unchanged
    assert pred.batch_size(5) == 5    # replay is deterministic

    monkeypatch.setattr(predictor, "EXACT_SHAPES_LIMIT", 2)
    tight = StragglerPredictor(n_hosts=3, max_tasks=4)
    assert tight.batch_size(5) == 5
    assert tight.batch_size(9) == 9
    assert tight.batch_size(17) == 32   # budget spent: new counts pad
    assert tight.batch_size(5) == 5     # seen exact shapes stay exact

    monkeypatch.setattr(predictor, "PAD_WASTE_LIMIT", 1.0)
    off = StragglerPredictor(n_hosts=3, max_tasks=4)
    assert off.batch_size(5) == 8       # policy disabled: pure po2

    # the Tier-0 reference path is NOT subject to the policy: its batch
    # shaping stays pure power-of-two bucketing (bucket_size above)
    rng = np.random.default_rng(0)
    mh = rng.uniform(0, 1, (5, 3, features.HOST_FEATURES)) \
        .astype(np.float32)
    mt = rng.uniform(0, 1, (5, 4, features.TASK_FEATURES)) \
        .astype(np.float32)
    off2 = StragglerPredictor(n_hosts=3, max_tasks=4)
    out = off2.predict_features(mh, mt, np.full(5, 4.0, np.float32))
    assert out.e_s.shape == (5,)
    assert off2.buckets_used == {8}     # padded, not exact


def test_start_cell_run_stays_within_bucket_compiles():
    """End to end: a multi-interval START run retraces at most once per
    bucket the run actually used."""
    from repro.sim.techniques.start_tech import START
    before = net.predict_sequence._cache_size()
    sim = Simulation(small(n_hosts=10, n_intervals=25, seed=3),
                     technique=START())
    sim.run()
    tech = sim.technique
    grew = net.predict_sequence._cache_size() - before
    assert grew <= len(tech._controller.predictor.buckets_used)


# ------------------------- Pallas cell route exact --------------------------

def test_predict_sequence_pallas_route_is_exact():
    """The fused Pallas LSTM cell behind ``use_pallas`` must reproduce the
    jnp cell bit-for-bit through the full network (Pallas interpreter on
    the CPU; the compiled kernel is checked on the chip by
    ``chip_smoke.py``)."""
    params = net.init_params(jax.random.PRNGKey(0), input_dim=24)
    xs = jax.random.normal(jax.random.PRNGKey(1), (5, 6, 24), jnp.float32)
    ref = net.predict_sequence(params, xs)
    pal = net.predict_sequence(params, xs, use_pallas="interpret")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))
    # and via the predictor flag
    pred = StragglerPredictor(n_hosts=2, max_tasks=4,
                              use_pallas_cell="interpret")
    mh = np.zeros((5, 2, features.HOST_FEATURES), np.float32)
    mt = np.zeros((3, 4, features.TASK_FEATURES), np.float32)
    out = pred.predict_features(mh, mt, np.full(3, 4.0, np.float32))
    assert np.isfinite(out.e_s).all()


# --------------------------- predictor.fit shapes ---------------------------

def test_fit_drops_partial_batch_and_records_epoch_mean_loss():
    rng = np.random.default_rng(0)
    pred = StragglerPredictor(n_hosts=2, max_tasks=3)
    dim = pred.input_dim
    xs = rng.normal(size=(5, 10, dim)).astype(np.float32)
    ys = np.abs(rng.normal(size=(10, 2))).astype(np.float32) + 1.0
    before = net.train_step._cache_size()
    losses = pred.fit(xs, ys, epochs=3, lr=1e-3, batch=4)
    # n=10, batch=4 -> two full batches per epoch, partial batch dropped:
    # exactly one train_step shape, so at most one new compile
    assert net.train_step._cache_size() - before <= 1
    assert len(losses) == 3
    assert all(np.isfinite(v) for v in losses)
    # n <= batch keeps the whole set as the single batch
    pred2 = StragglerPredictor(n_hosts=2, max_tasks=3)
    losses2 = pred2.fit(xs, ys, epochs=2, lr=1e-3, batch=64)
    assert len(losses2) == 2 and all(np.isfinite(v) for v in losses2)


# --------------------------- sweep result index -----------------------------

def test_sweep_result_cell_lookup_is_indexed():
    spec = SweepSpec(techniques=("none",), seeds=(0, 1),
                     scenarios=("planetlab",), metrics=("m",))
    cells = [CellResult("planetlab", "none", s, {"m": float(s)}, 0.0)
             for s in (0, 1)]
    res = SweepResult(spec=spec, cells=cells, wall_s=0.0, n_workers=1)
    assert res.cell("planetlab", "none", 1).summary["m"] == 1.0
    assert "_index" in res.__dict__          # built lazily, then reused
    assert res.cell("planetlab", "none", 0) is cells[0]
    with pytest.raises(KeyError):
        res.cell("planetlab", "none", 7)
    # the index tracks late-appended cells instead of going stale
    res.cells.append(CellResult("planetlab", "none", 7, {"m": 7.0}, 0.0))
    assert res.cell("planetlab", "none", 7).summary["m"] == 7.0


# ------------------------ persistent pool plumbing --------------------------

def test_persistent_pool_is_reused_across_runs():
    spec = SweepSpec(techniques=("none", "sgc"), seeds=(0,),
                     scenarios=("planetlab",), n_hosts=8, n_intervals=10,
                     arrival_rate=0.8, max_workers=2)
    r1 = sweep.run(spec)
    pool1 = sweep._POOL
    assert pool1 is not None
    r2 = sweep.run(dataclasses.replace(spec, seeds=(1,)))
    assert sweep._POOL is pool1              # same workers, caches warm
    assert len(r1.cells) == len(r2.cells) == 2
    sweep.shutdown_pool()
    assert sweep._POOL is None
