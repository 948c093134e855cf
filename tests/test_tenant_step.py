"""The service's tenant step: many tenants' intervals in one launch over a
device slab of tenant rings (``StragglerPredictor.pack_tenants`` /
``predict_tenants``, ``PredictionService`` ticks of several tenants).

It is a Tier-1 path: its E_S and per-task scores agree with the plain
float64 reference (``bench/reference.py``) and with the Tier-0
``predict_features`` within ``tests/tolerance.py``'s bound, tenant by
tenant.  Its slots are caches of each tenant's host history, rebuilt
exactly when they fall behind, and a warm tick uploads only its staging
vector, with no host window per job row.
"""
import collections
import pathlib
import sys

import jax
import numpy as np
import pytest

from repro.core import features
from repro.core.predictor import StragglerPredictor, fused_compile_count, \
    tenant_layout
from repro.policy import wire
from repro.service import PredictionService, Profile, ServiceConfig

from tolerance import assert_tier1

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from bench import reference  # noqa: E402

N_HOSTS, MAX_TASKS, HORIZON = 8, 4, 5
#: job rows of the five tenants of a tick
COUNTS = (0, 1, 3, 7, 12)


def _rows(rng, n):
    return rng.uniform(0, 1, (n, N_HOSTS, features.HOST_FEATURES)) \
        .astype(np.float32)


def _jobs(rng, n):
    """``n`` jobs' task matrices (zero past each job's q) and q."""
    q = rng.integers(1, MAX_TASKS + 1, n)
    mt = rng.uniform(0, 1, (n, MAX_TASKS, features.TASK_FEATURES)) \
        .astype(np.float32)
    mt[np.arange(MAX_TASKS)[None, :] >= q[:, None]] = 0.0
    return mt, q.astype(np.float32)


def _window(hist):
    """The controller's window: left-padded with the oldest row."""
    hist = list(hist)
    return np.stack([hist[0]] * (HORIZON - len(hist)) + hist)


@pytest.mark.parametrize("per_task", [False, True])
def test_tenant_step_matches_reference_per_tenant(per_task):
    """Five tenants of uneven job counts, one of them (slot 1) holding
    fewer than ``horizon`` rows: each tenant's answer agrees with the
    float64 reference and with Tier-0 ``predict_features`` computed from
    its own window alone."""
    pred = StragglerPredictor(n_hosts=N_HOSTS, max_tasks=MAX_TASKS,
                              horizon=HORIZON, beta_scale=300.0)
    pred.open_slab(6)
    rng = np.random.default_rng(3)
    seen = [7, 2, 9, 5, 6]
    hists = [collections.deque(_rows(rng, s), maxlen=HORIZON) for s in seen]
    slots = [4, 1, 0, 5, 2]
    jobs = [_jobs(rng, c) for c in COUNTS]
    group = [(slot, s, h, mt, q)
             for slot, s, h, (mt, q) in zip(slots, seen, hists, jobs)]
    buf, nb, n = pred.pack_tenants(group)
    assert (n, nb) == (sum(COUNTS), 32)
    out = pred.predict_tenants(buf, nb, n, len(group), per_task=per_task)
    params = jax.tree_util.tree_map(np.asarray, pred.params)
    net = {"ema_w": 0.8, "lstm_hidden": 32, "beta_eps": 1e-3,
           "beta_scale": pred.beta_scale}
    lo = 0
    for hist, (mt, q) in zip(hists, jobs):
        got = out[lo:lo + len(q)]
        lo += len(q)
        if not len(q):
            continue
        win = _window(hist)
        ref = reference.e_s(params, win, mt, q, pred.k, net)
        tier0 = pred.predict_features(win, mt, q, per_task=per_task)
        if per_task:
            assert_tier1(got[:, 0], ref)
            assert_tier1(got[:, 0], tier0[0])
            assert_tier1(got[:, 1:], tier0[1])
        else:
            assert_tier1(got, ref)
            assert_tier1(got, np.asarray(tier0.e_s))


def _profile():
    return Profile(n_hosts=N_HOSTS, max_tasks=MAX_TASKS, horizon=HORIZON)


def _snap(tenant, seq, m_h, mt, q):
    jobs = [wire.job_to_wire(i, int(qi), m, tasks=[(10 * i + s, s, s)
                                                    for s in range(int(qi))])
            for i, (m, qi) in enumerate(zip(mt, q))]
    return wire.snapshot_to_wire(tenant, seq, m_h, jobs=jobs)


def _tick(svc, snaps):
    ps = [svc.submit(t, s) for t, s in snaps.items()]
    svc.tick()
    for p in ps:
        assert p.result is not None and p.result["ok"], p.result
    return {t: [j["e_s"] for j in p.result["jobs"]]
            for t, p in zip(snaps, ps)}


def test_many_tenant_tick_matches_each_tenant_alone():
    """Ticks of five tenants through one service agree, tenant by tenant,
    with five services that each serve one of them alone (its own fused
    ``predict_interval`` path)."""
    names = [f"t{i}" for i in range(len(COUNTS))]
    many = PredictionService(ServiceConfig(profile=_profile()))
    alone = {t: PredictionService(ServiceConfig(profile=_profile()))
             for t in names}
    for t in names:
        assert many.hello(t, _profile().to_wire())["ok"]
        assert alone[t].hello(t, _profile().to_wire())["ok"]
    rng = np.random.default_rng(5)
    for seq in range(HORIZON + 2):
        snaps = {t: _snap(t, seq, _rows(rng, 1)[0], *_jobs(rng, c))
                 for t, c in zip(names, COUNTS)}
        got = _tick(many, snaps)
        for t in names:
            want = _tick(alone[t], {t: snaps[t]})[t]
            assert_tier1(np.float32(got[t]), np.float32(want))
    assert many.model.tenant_calls == HORIZON + 2


def test_slots_follow_host_histories_through_skips_leaves_and_lags():
    """Tenants skip ticks, leave, rejoin and fall two rows behind (ticks
    where one tenant alone has jobs take its own fused path and leave the
    slab alone).  After every tenant-batched tick each slot in it equals
    its tenant's window, and ``tenant_slot_rebuilds`` counts exactly the
    slots that were new or more than one row behind."""
    svc = PredictionService(ServiceConfig(profile=_profile(),
                                          max_tenants=3))
    model = svc.model
    rng = np.random.default_rng(9)
    seqs = collections.Counter()

    def hello(t):
        assert svc.hello(t, _profile().to_wire())["ok"]

    def tick(with_jobs, without=()):
        snaps = {}
        for t in (*with_jobs, *without):
            mt, q = _jobs(rng, 2 if t in with_jobs else 0)
            snaps[t] = _snap(t, seqs[t], _rows(rng, 1)[0], mt, q)
            seqs[t] += 1
        before = (model.tenant_calls, model.tenant_slot_rebuilds)
        _tick(svc, snaps)
        if len(with_jobs) > 1:
            assert model.tenant_calls == before[0] + 1
            slab = np.asarray(model._slab)
            for t in snaps:
                st = svc.tenants[t]
                assert model._slot_rows[st.slot] == st.snapshots
                np.testing.assert_array_equal(
                    slab[st.slot], st.controller.host_seq().reshape(
                        HORIZON, -1))
        else:
            assert model.tenant_calls == before[0]
        return model.tenant_slot_rebuilds - before[1]

    for t in "abc":
        hello(t)
    assert tick("abc") == 3                 # three new slots
    assert tick("ab") == 0                  # c skips: it is not behind
    assert tick("abc") == 0
    assert tick("a", "bc") == 0             # a alone: the slab waits
    assert tick("a", "bc") == 0
    assert tick("bc", "a") == 3             # all two rows behind
    svc.bye("c")
    hello("d")                              # takes c's slot
    assert svc.tenants["d"].slot == 2
    assert tick("abd") == 1                 # d's slot is new
    svc.bye("a")
    hello("c")                              # c comes back, in a's slot
    seqs["c"] = 0
    assert tick("cd", "b") == 1
    assert tick("bcd") == 0


def test_warm_tenant_tick_uploads_its_staging_vector_only(sanction_uploads):
    """Warm ticks of the tenant step under ``transfer_guard('disallow')``:
    no compile, no ``_stage`` upload, one launch a tick, and each tick's
    ``tenant_stage_bytes`` is the layout's count — one host row and flag
    per tenant slot, and q, the slot index and M_T per dispatched row."""
    names = [f"t{i}" for i in range(len(COUNTS))]
    svc = PredictionService(ServiceConfig(profile=_profile(),
                                          max_tenants=len(names)))
    for t in names:
        assert svc.hello(t, _profile().to_wire())["ok"]
    rng = np.random.default_rng(13)

    def tick(seq):
        return _tick(svc, {t: _snap(t, seq, _rows(rng, 1)[0],
                                    *_jobs(rng, c))
                           for t, c in zip(names, COUNTS)})

    tick(0)
    calls = sanction_uploads()
    before = fused_compile_count()
    model = svc.model
    with jax.transfer_guard_host_to_device("disallow"):
        for seq in range(1, 4):
            b0 = model.tenant_stage_bytes
            tick(seq)
            nb = 32
            want = 4 * (2 + len(names) * (1 + model.host_dim)
                        + nb * (2 + model.task_dim))
            assert model.tenant_stage_bytes - b0 == want
            assert tenant_layout(len(names), nb, model.host_dim,
                                 model.task_dim)[-1] * 4 == want
    assert fused_compile_count() == before
    assert calls == {"_launch": 3, "_stage": 0}


def test_staging_at_the_service_width_is_new_rows_only():
    """At the 64-tenant deployment (400 hosts, ``max_tasks`` 10, 2048
    rows) the tick's staging vector holds each tenant's new host row and
    each row's M_T, q and slot: under 2% of the 182 MB a per-row copy of
    each tenant's host window took.  Packing runs on the host."""
    n_hosts, max_tasks, tenants, rows = 400, 10, 64, 2048
    pred = StragglerPredictor(n_hosts=n_hosts, max_tasks=max_tasks)
    pred.open_slab(tenants)
    rng = np.random.default_rng(0)
    row = rng.random((n_hosts, features.HOST_FEATURES), np.float32)
    per = rows // tenants
    group = [(s, 1, [row], np.zeros((per, max_tasks,
                                     features.TASK_FEATURES), np.float32),
              np.ones(per, np.float32)) for s in range(tenants)]
    buf, nb, n = pred.pack_tenants(group)
    assert (nb, n) == (rows, rows)
    host_dim = n_hosts * features.HOST_FEATURES
    task_dim = max_tasks * features.TASK_FEATURES
    assert buf.nbytes == 4 * (2 + tenants * (1 + host_dim)
                              + rows * (2 + task_dim))
    per_row_copy = 4 * HORIZON * rows * (host_dim + task_dim)
    assert per_row_copy == 182_272_000
    assert buf.nbytes < 0.02 * per_row_copy
