"""Serving-core tests: bitwise equivalence, multi-tenant batching with
zero warm retraces, the boundary sanitizer, backpressure, and the
versioned retrain/shadow-eval/rollback lifecycle."""
import json
import os
from unittest import mock

import jax
import numpy as np
import pytest

from repro.core import encoder_lstm as net
from repro.core import features
from repro.core.predictor import StragglerPredictor, fused_compile_count
from repro.policy import wire
from repro.policy.actions import Action, ActionKind
from repro.service import (LocalClient, PredictionService, Profile,
                           ServiceConfig, ServiceDaemon, TelemetryError,
                           sanitize_snapshot)
from repro.service import retrain as svc_retrain
from repro.service import sanitize as svc_sanitize
from repro.train.checkpoint import VersionStore

N_HOSTS, MAX_TASKS, HORIZON = 3, 4, 5


def profile(**kw) -> Profile:
    return Profile(n_hosts=N_HOSTS, max_tasks=MAX_TASKS,
                   horizon=HORIZON, **kw)


def rand_mh(rng):
    return rng.random((N_HOSTS, features.HOST_FEATURES)) \
        .astype(np.float32)


def rand_mt(rng, q=3):
    m_t = np.zeros((MAX_TASKS, features.TASK_FEATURES), np.float32)
    m_t[:q] = rng.random((q, features.TASK_FEATURES))
    return m_t


def mk_snap(tenant, seq, m_h, m_t, q=3, job_id=1, done=None):
    tasks = [(100 + i, i % N_HOSTS, i) for i in range(q)]
    return wire.snapshot_to_wire(
        tenant, seq, m_h,
        jobs=[wire.job_to_wire(job_id, q, m_t, tasks=tasks)],
        done=done or [])


def compile_counters():
    return net.predict_sequence._cache_size() + fused_compile_count()


# ------------------------------ wire format ------------------------------

def test_action_wire_roundtrip():
    a = Action(kind=ActionKind.SPECULATE, task=7, target=2, host=5)
    b = wire.action_from_wire(wire.action_to_wire(a))
    assert b == a
    # defaults are omitted on the wire and restored on parse
    small = wire.action_to_wire(Action(kind=ActionKind.RERUN, task=1))
    assert set(small) == {"kind", "task"}
    assert wire.action_from_wire(small).n_clones == 1
    with pytest.raises(ValueError, match="unknown Action wire"):
        wire.action_from_wire({"kind": "rerun", "task": 1, "zap": 2})


def test_profile_wire_roundtrip_and_compat():
    p = profile(trigger="per_task", score_on=0.1)
    assert Profile.from_wire(p.to_wire()) == p
    assert p.compatible(profile(trigger="per_task", score_on=0.1))
    assert not p.compatible(profile())              # trigger differs
    assert not profile().compatible(
        Profile(n_hosts=N_HOSTS + 1, max_tasks=MAX_TASKS))
    with pytest.raises(ValueError, match="unknown Profile"):
        Profile.from_wire({"n_hosts": 2, "max_tasks": 2, "zap": 1})


# ------------------------------ sanitizer --------------------------------

def test_sanitizer_clamps_nonfinite_features():
    rng = np.random.default_rng(0)
    m_h = rand_mh(rng)
    m_h[0, 0] = np.nan
    m_h[1, 2] = np.inf
    snap = mk_snap("t", 0, m_h, rand_mt(rng))
    clean = sanitize_snapshot(snap, profile(), -1.0, mode="clamp")
    assert np.isfinite(clean["m_h"]).all()
    assert clean["m_h"][0, 0] == 0.0
    assert any("non-finite" in s for s in clean["issues"])


def test_sanitizer_reject_mode_raises_on_nonfinite():
    rng = np.random.default_rng(0)
    m_h = rand_mh(rng)
    m_h[0, 0] = np.nan
    snap = mk_snap("t", 0, m_h, rand_mt(rng))
    with pytest.raises(TelemetryError) as e:
        sanitize_snapshot(snap, profile(), -1.0, mode="reject")
    assert e.value.code == "bad-telemetry"


def test_sanitizer_drops_bad_durations():
    rng = np.random.default_rng(0)
    snap = mk_snap("t", 0, rand_mh(rng), rand_mt(rng),
                   done=[{"id": 4, "times": [1.0, -3.0, np.nan, 2.0]}])
    clean = sanitize_snapshot(snap, profile(), -1.0, mode="clamp")
    np.testing.assert_array_equal(clean["done"][0]["times"],
                                  np.float32([1.0, 2.0]))
    with pytest.raises(TelemetryError):
        sanitize_snapshot(snap, profile(), -1.0, mode="reject")


def test_sanitizer_rejects_out_of_order_and_structural():
    rng = np.random.default_rng(0)
    snap = mk_snap("t", 3, rand_mh(rng), rand_mt(rng))
    with pytest.raises(TelemetryError) as e:
        sanitize_snapshot(snap, profile(), 3.0)  # seq replay
    assert e.value.code == "out-of-order"
    bad = mk_snap("t", 9, rand_mh(rng)[:, :-1], rand_mt(rng))
    with pytest.raises(TelemetryError) as e:
        sanitize_snapshot(bad, profile(), -1.0)  # wrong M_H shape
    assert e.value.code == "bad-shape"
    bad_q = mk_snap("t", 9, rand_mh(rng), rand_mt(rng))
    bad_q["jobs"][0]["q"] = MAX_TASKS + 3
    with pytest.raises(TelemetryError) as e:
        sanitize_snapshot(bad_q, profile(), -1.0)
    assert e.value.code == "bad-job"


# ------------------------- bulk sanitizer path ---------------------------

def wire_snap(rng, n_jobs: int, seq: int = 7) -> dict:
    """A wire snapshot of ``n_jobs`` random jobs, each with some open
    tasks, and one done job."""
    jobs = []
    for jid in range(n_jobs):
        q = int(rng.integers(1, MAX_TASKS + 1))
        tasks = [(100 * jid + s, int(rng.integers(N_HOSTS)), s)
                 for s in range(q) if rng.random() < 0.7]
        jobs.append(wire.job_to_wire(jid, q, rand_mt(rng, q),
                                     open_count=len(tasks),
                                     deadline=bool(jid % 2), tasks=tasks))
    return wire.snapshot_to_wire("t", seq, rand_mh(rng), jobs=jobs,
                                 done=[{"id": 999, "times": [1.0, 2.5]}])


def _with(n_jobs: int, edit=None):
    """A case: a wire snapshot of ``n_jobs`` jobs, then ``edit(snap)``."""
    def make(rng):
        snap = wire_snap(rng, n_jobs)
        if edit is not None:
            edit(snap)
        return snap
    return make


def _nonfinite(*where):
    """NaN, inf and values beyond ``FEATURE_CLIP`` in M_H and/or in the
    M_T of jobs 2 and 5."""
    def edit(snap):
        if "m_h" in where:
            mh = snap["m_h"]
            mh[0], mh[5], mh[7] = np.nan, np.inf, -3e6
        if "m_t" in where:
            mt2, mt5 = snap["jobs"][2]["m_t"], snap["jobs"][5]["m_t"]
            mt2[1], mt2[3], mt2[4] = np.nan, -np.inf, 2e7
            mt5[0], mt5[6] = np.inf, -0.0
            snap["jobs"][0]["m_t"][2] = -0.0
    return edit


def _nan_then_bad_q(snap):
    snap["jobs"][1]["m_t"][0] = np.nan
    snap["jobs"][3]["q"] = MAX_TASKS + 2


def _nested(key):
    def edit(snap):
        if key == "m_h":
            snap["m_h"] = np.reshape(snap["m_h"], (N_HOSTS, -1)).tolist()
        else:
            snap["jobs"][1]["m_t"] = np.reshape(
                snap["jobs"][1]["m_t"], (MAX_TASKS, -1)).tolist()
    return edit


def _ndarray(key):
    def edit(snap):
        if key == "m_h":
            snap["m_h"] = np.float32(snap["m_h"]).reshape(N_HOSTS, -1)
        else:
            snap["jobs"][2]["m_t"] = np.float32(snap["jobs"][2]["m_t"])
    return edit


def _set_job(i, key, value):
    def edit(snap):
        snap["jobs"][i][key] = value
    return edit


def _set_feature(value, job=None, i=2):
    """Element ``i`` of M_H, or of job ``job``'s M_T, set to ``value``."""
    def edit(snap):
        (snap["m_h"] if job is None else snap["jobs"][job]["m_t"])[i] = value
    return edit


#: case -> (snapshot from a generator, whether it is the wire's plain
#: form, the bulk path's input)
BULK_CASES = {
    "jobs0": (_with(0), True),
    "jobs1": (_with(1), True),
    "jobs20": (_with(20), True),
    "jobs120": (_with(120), True),
    "nonfinite_in_m_h": (_with(8, _nonfinite("m_h")), True),
    "nonfinite_in_m_t": (_with(8, _nonfinite("m_t")), True),
    "nonfinite_in_both": (_with(8, _nonfinite("m_h", "m_t")), True),
    "nan_in_job1_bad_q_in_job3": (_with(6, _nan_then_bad_q), True),
    "slot_out_of_range": (
        _with(4, _set_job(2, "tasks", [[7, 0, 1], [8, 1, MAX_TASKS]])),
        True),
    "negative_slot": (_with(4, _set_job(1, "tasks", [[7, 0, -1]])), True),
    "mt_wrong_length": (
        _with(4, _set_job(1, "m_t", [0.5] * (MAX_TASKS * 5 - 1))), False),
    "non_integer_open": (_with(4, _set_job(2, "open", 1.5)), True),
    "task_entry_of_2": (_with(4, _set_job(1, "tasks", [[7, 0]])), False),
    "task_entry_of_4": (_with(4, _set_job(1, "tasks", [[7, 0, 2, 9]])),
                        False),
    "float_task_field": (_with(4, _set_job(0, "tasks", [[7.0, 0, 1]])),
                         False),
    "none_in_m_h": (_with(3, _set_feature(None)), True),
    "list_in_m_t": (_with(3, _set_feature([0.5], job=1)), False),
    "nested_m_h": (_with(5, _nested("m_h")), False),
    "nested_m_t": (_with(5, _nested("m_t")), False),
    "ndarray_m_h": (_with(5, _ndarray("m_h")), False),
    "ndarray_m_t": (_with(5, _ndarray("m_t")), False),
}


def _outcome(snap, mode, **kw):
    try:
        return sanitize_snapshot(snap, profile(), -1.0, mode=mode, **kw)
    except Exception as e:      # refusals of any kind are compared
        return e


def _same_array(a, b):
    assert a.dtype == b.dtype and a.shape == b.shape
    assert a.tobytes() == b.tobytes()


@pytest.mark.parametrize("mode", ["clamp", "reject"])
@pytest.mark.parametrize("case", sorted(BULK_CASES))
def test_sanitizer_bulk_path_matches_per_job_path(case, mode):
    """A wire snapshot converted in bulk gives exactly what the per-job
    path gives it: the same arrays, bitwise and in dtype, and the same
    issues in order, or the same refusal; other inputs take the per-job
    path."""
    make, plain = BULK_CASES[case]
    snap = make(np.random.default_rng(sorted(BULK_CASES).index(case)))
    counters = {"sanitize_bulk": 0}
    got = _outcome(snap, mode, counters=counters)
    with mock.patch.object(svc_sanitize, "_wire_floats",
                           return_value=None), \
            mock.patch.object(svc_sanitize, "_jobs_bulk",
                              return_value=None):
        want = _outcome(snap, mode)
    if isinstance(want, Exception):
        assert type(got) is type(want) and str(got) == str(want)
        assert getattr(got, "code", None) == getattr(want, "code", None)
    else:
        assert not isinstance(got, Exception), got
        assert got.keys() == want.keys()
        assert (got["seq"], got["issues"]) == (want["seq"], want["issues"])
        _same_array(got["m_h"], want["m_h"])
        assert len(got["jobs"]) == len(want["jobs"])
        for a, b in zip(got["jobs"], want["jobs"]):
            assert a.keys() == b.keys()
            assert [a[k] for k in ("id", "q", "open", "deadline")] \
                == [b[k] for k in ("id", "q", "open", "deadline")]
            _same_array(a["m_t"], b["m_t"])
            for x, y in zip(a["tasks"], b["tasks"], strict=True):
                _same_array(x, y)
        assert len(got["done"]) == len(want["done"])
        for a, b in zip(got["done"], want["done"]):
            assert a["id"] == b["id"]
            _same_array(a["times"], b["times"])
    assert counters["sanitize_bulk"] == int(
        plain and not isinstance(got, Exception))


def test_sanitizer_bulk_numpy_calls_do_not_grow_with_jobs():
    """The bulk path's numpy conversions are per snapshot, not per job."""
    rng = np.random.default_rng(3)
    calls = []
    for n_jobs in (1, 120):
        snap = wire_snap(rng, n_jobs)
        counters = {"sanitize_bulk": 0}
        with mock.patch.object(np, "fromiter", wraps=np.fromiter) as fi, \
                mock.patch.object(np, "asarray", wraps=np.asarray) as aa:
            sanitize_snapshot(snap, profile(), -1.0, counters=counters)
        assert counters["sanitize_bulk"] == 1
        calls.append((fi.call_count, aa.call_count))
    assert calls[0] == calls[1], calls


def test_service_counts_snapshots_sanitized_in_bulk():
    rng = np.random.default_rng(4)
    svc = PredictionService(ServiceConfig(profile=profile()))
    for t in ("a", "b"):
        assert svc.hello(t, profile().to_wire())["ok"]
    for seq in range(2):
        for t in ("a", "b"):
            svc.submit(t, wire_snap(rng, 3, seq))
    in_process = wire_snap(rng, 3, 2)
    in_process["m_h"] = rand_mh(rng)            # an ndarray: per-job path
    assert svc.submit("a", in_process).result is None
    refused = wire_snap(rng, 3, 2)
    refused["jobs"][1]["q"] = 0
    assert svc.submit("b", refused).result["error"] == "bad-job"
    st = svc.stats()
    assert st["sanitize_bulk"] == 4 and st["rejected"] == 1


# --------------------------- admission / queues --------------------------

def test_admission_control():
    svc = PredictionService(ServiceConfig(profile=profile(),
                                          max_tenants=2))
    assert svc.hello("a", profile().to_wire())["ok"]
    assert svc.hello("a", profile().to_wire())["rejoined"]
    bad = svc.hello("b", profile(k=9.9).to_wire())
    assert not bad["ok"] and bad["error"] == "incompatible-profile"
    assert svc.hello("b", profile().to_wire())["ok"]
    full = svc.hello("c", profile().to_wire())
    assert not full["ok"] and full["error"] == "at-capacity"
    # snapshots from a tenant that never said hello are refused
    p = svc.submit("ghost", {"seq": 0})
    assert p.result["error"] == "not-admitted"


def test_backpressure_sheds_oldest():
    svc = PredictionService(ServiceConfig(profile=profile(),
                                          queue_depth=2))
    svc.hello("a", profile().to_wire())
    rng = np.random.default_rng(0)
    ps = [svc.submit("a", mk_snap("a", i, rand_mh(rng), rand_mt(rng)))
          for i in range(3)]
    assert ps[0].result["error"] == "overload"    # shed, not dropped
    assert ps[1].result is None and ps[2].result is None
    svc.tick()                                     # one per tenant/tick
    svc.tick()
    assert ps[1].result["ok"] and ps[2].result["ok"]
    assert svc.stats()["sheds"] == 1


# --------------------------- bitwise equivalence -------------------------

def _reference_run(m_hs, m_t, q, per_task=False):
    """Drive a bare predictor exactly as the service tenant would."""
    pred = StragglerPredictor(n_hosts=N_HOSTS, max_tasks=MAX_TASKS,
                              horizon=HORIZON)
    out = None
    for m_h in m_hs:
        pred.push_host_row(m_h)
        out = pred.predict_interval(
            m_t[None], np.array([float(q)], np.float32),
            per_task=per_task)
    return out


def test_single_tenant_bitwise_equals_predict_interval():
    rng = np.random.default_rng(7)
    m_hs = [rand_mh(rng) for _ in range(3)]
    m_t = rand_mt(rng)
    svc = PredictionService(ServiceConfig(profile=profile()))
    c = LocalClient(svc, "t0")
    assert c.hello(profile())["ok"]
    for i, m_h in enumerate(m_hs):
        r = c.snapshot(mk_snap("t0", i, m_h, m_t))
    ref = _reference_run(m_hs, m_t, 3)
    assert r["jobs"][0]["e_s"] == float(np.asarray(ref)[0])


def test_single_tenant_bitwise_per_task_scores():
    rng = np.random.default_rng(8)
    m_hs = [rand_mh(rng) for _ in range(3)]
    m_t = rand_mt(rng)
    prof = profile(trigger="per_task")
    svc = PredictionService(ServiceConfig(profile=prof))
    c = LocalClient(svc, "t0")
    assert c.hello(prof)["ok"]
    for i, m_h in enumerate(m_hs):
        r = c.snapshot(mk_snap("t0", i, m_h, m_t))
    e_ref, s_ref = _reference_run(m_hs, m_t, 3, per_task=True)
    assert r["jobs"][0]["e_s"] == float(np.asarray(e_ref)[0])
    np.testing.assert_array_equal(
        np.float64(r["jobs"][0]["scores"]),
        np.float64(np.asarray(s_ref)[0, :3]))


def test_tcp_roundtrip_bitwise_and_json_lossless():
    """The acceptance criterion: telemetry in over TCP -> answers out,
    bitwise-equal to the in-process fused step (finite float32 survives
    the float64 JSON round trip losslessly)."""
    rng = np.random.default_rng(9)
    m_hs = [rand_mh(rng) for _ in range(3)]
    m_t = rand_mt(rng)
    with ServiceDaemon(ServiceConfig(profile=profile())) as d:
        c = d.tcp_client("tcp0")
        assert c.hello(profile())["ok"]
        for i, m_h in enumerate(m_hs):
            r = c.snapshot(mk_snap("tcp0", i, m_h, m_t))
        c.bye()
    ref = _reference_run(m_hs, m_t, 3)
    assert r["jobs"][0]["e_s"] == float(np.asarray(ref)[0])


def test_malformed_tenant_never_poisons_healthy_tenant():
    """A tenant streaming garbage is rejected at the boundary; the
    healthy tenant's answers stay bitwise-identical to a run where the
    malformed tenant never existed, and the service stays up."""
    rng = np.random.default_rng(10)
    m_hs = [rand_mh(rng) for _ in range(3)]
    m_t = rand_mt(rng)
    svc = PredictionService(ServiceConfig(profile=profile(),
                                          sanitize="reject"))
    good = LocalClient(svc, "good")
    evil = LocalClient(svc, "evil")
    assert good.hello(profile())["ok"] and evil.hello(profile())["ok"]
    for i, m_h in enumerate(m_hs):
        bad = mk_snap("evil", i, np.full_like(m_h, np.nan), m_t)
        rb = evil.snapshot(bad)
        assert not rb["ok"] and rb["error"] == "bad-telemetry"
        shape = evil.snapshot(mk_snap("evil", i + 100,
                                      m_h[:, :-1], m_t))
        assert not shape["ok"] and shape["error"] == "bad-shape"
        r = good.snapshot(mk_snap("good", i, m_h, m_t))
        assert r["ok"]
    ref = _reference_run(m_hs, m_t, 3)
    assert r["jobs"][0]["e_s"] == float(np.asarray(ref)[0])
    st = svc.stats()
    assert st["ok"] and st["rejected"] == 6


# ----------------------- multi-tenant batch serving ----------------------

def _round(svc, tenants, rng, seq, m_t):
    """Submit one snapshot per tenant, then one batch tick for all."""
    ps = [svc.submit(t, mk_snap(t, seq, rand_mh(rng), m_t))
          for t in tenants]
    svc.tick()
    for p in ps:
        assert p.result is not None and p.result["ok"], p.result
    return ps


def test_interleaved_tenants_zero_warm_retraces(sanction_uploads):
    """Interleaved multi-tenant traffic must reuse the power-of-two
    bucket cache: after each tenant-count pattern has run once, further
    ticks compile nothing and upload only through ``_stage`` and the
    single-tenant fused launch (``_launch``) — pinned under
    ``transfer_guard('disallow')`` exactly like the fused-step test."""
    svc = PredictionService(ServiceConfig(profile=profile()))
    rng = np.random.default_rng(11)
    tenants = [f"t{i}" for i in range(4)]
    for t in tenants:
        assert svc.hello(t, profile().to_wire())["ok"]
    m_t = rand_mt(rng)
    seq = 0
    # warm every pattern: single-tenant (fused), 2-, 3- and 4-tenant
    for group in ([tenants[0]], tenants[:2], tenants[:3], tenants):
        _round(svc, group, rng, seq, m_t)
        seq += 1

    calls = sanction_uploads()
    before = compile_counters()
    with jax.transfer_guard_host_to_device("disallow"):
        for group in (tenants[:3], [tenants[1]], tenants, tenants[:2],
                      [tenants[3]], tenants[:3]):
            _round(svc, group, rng, seq, m_t)
            seq += 1
    assert compile_counters() - before == 0, \
        "warm multi-tenant tick retraced a prediction program"
    assert calls["_launch"] > 0 and calls["_stage"] > 0


def test_multi_tenant_matches_single_tenant_answers():
    """The combined dispatch answers each tenant with the same E_S the
    unfused single-tenant path computes from identical features (same
    math at a wider batch shape -> allclose, not bitwise)."""
    rng = np.random.default_rng(12)
    svc = PredictionService(ServiceConfig(profile=profile()))
    tenants = ["a", "b", "c"]
    for t in tenants:
        assert svc.hello(t, profile().to_wire())["ok"]
    snaps = {t: (rand_mh(rng), rand_mt(rng)) for t in tenants}
    ps = [svc.submit(t, mk_snap(t, 0, mh, mt))
          for t, (mh, mt) in snaps.items()]
    svc.tick()
    for t, p in zip(tenants, ps):
        m_h, m_t = snaps[t]
        pred = StragglerPredictor(n_hosts=N_HOSTS, max_tasks=MAX_TASKS,
                                  horizon=HORIZON)
        seq = np.stack([m_h] * HORIZON)
        ref = pred.predict_features(seq, m_t[None],
                                    np.array([3.0], np.float32))
        np.testing.assert_allclose(p.result["jobs"][0]["e_s"],
                                   float(np.asarray(ref.e_s)[0]),
                                   rtol=1e-5)


# ------------------------ versioning / shadow eval -----------------------

def test_version_store_promote_rollback_retention(tmp_path):
    pred = StragglerPredictor(n_hosts=2, max_tasks=2)
    store = VersionStore(str(tmp_path), keep=2)
    store.save_version(0, pred.params)
    store.promote(0)
    for v in (1, 2):
        store.save_version(v, pred.params)
    store.promote(2)
    for v in (3, 4):
        store.save_version(v, pred.params)
    # retention dropped 1 but pinned the promotion trail {0, 2}
    assert 1 not in store.versions()
    assert {0, 2}.issubset(store.versions())
    assert store.current() == 2 and store.history() == [0]
    assert store.rollback() == 0
    assert store.current() == 0 and store.history() == []
    assert store.rollback() is None
    loaded = store.load_version(0, pred.params)
    for a, b in zip(jax.tree_util.tree_leaves(loaded),
                    jax.tree_util.tree_leaves(pred.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _drive_pairs(svc, client, rng, steps, start_seq=0):
    """Stream snapshots whose done records fill the replay buffer."""
    m_t = rand_mt(rng)
    for i in range(steps):
        done = ([{"id": start_seq + i - 1,
                  "times": (1.0 + rng.random(3)).tolist()}]
                if i or start_seq else [])
        r = client.snapshot(mk_snap(client.tenant, start_seq + i,
                                    rand_mh(rng), m_t,
                                    job_id=start_seq + i, done=done))
        assert r["ok"]


def test_shadow_eval_blocks_bad_candidate_then_promotes_and_rolls_back(
        tmp_path, monkeypatch):
    """The acceptance criterion: a corrupted candidate is never
    promoted (champion keeps serving, CURRENT unchanged); a good one is;
    rollback restores the previous version bitwise."""
    cfg = ServiceConfig(profile=profile(), ckpt_dir=str(tmp_path),
                        min_train_pairs=6, eval_holdback=3,
                        train_epochs=2, train_lr=1e-4)
    svc = PredictionService(cfg)
    c = LocalClient(svc, "t0")
    assert c.hello(profile())["ok"]
    rng = np.random.default_rng(13)
    _drive_pairs(svc, c, rng, steps=10)
    assert len(svc.buffer) >= cfg.min_train_pairs
    v0_leaves = [np.asarray(jax.device_get(x))
                 for x in jax.tree_util.tree_leaves(svc.params)]

    real_fit = svc_retrain.fit_candidate
    corrupt = {"on": True}

    def maybe_corrupt(champion, tx, ty, epochs=1, lr=1e-4):
        params, losses = real_fit(champion, tx, ty, epochs=1, lr=lr)
        if corrupt["on"]:
            params = jax.tree_util.tree_map(
                lambda a: a * np.float32("nan"), params)
        return params, losses

    monkeypatch.setattr(svc_retrain, "fit_candidate", maybe_corrupt)
    rej = c.retrain()
    assert rej["ok"] and rej["promoted"] is False
    assert not np.isfinite(rej["candidate_loss"])
    assert svc.model_version == 0 and svc.store.current() == 0
    assert svc.stats()["candidates_rejected"] == 1
    # champion params untouched by the rejected candidate
    for a, b in zip(jax.tree_util.tree_leaves(svc.params), v0_leaves):
        np.testing.assert_array_equal(np.asarray(a), b)

    corrupt["on"] = False
    ok = c.retrain()
    assert ok["promoted"] is True and ok["version"] == 1
    assert svc.store.current() == 1 and svc.model_version == 1
    assert np.isfinite(ok["candidate_loss"])
    changed = any(
        not np.array_equal(np.asarray(jax.device_get(a)), b)
        for a, b in zip(jax.tree_util.tree_leaves(svc.params),
                        v0_leaves))
    assert changed, "promotion did not swap the serving params"
    # every tenant predictor serves the promoted pytree
    assert svc.tenants["t0"].predictor.params is svc.params

    rb = c.rollback()
    assert rb["ok"] and rb["version"] == 0
    assert svc.store.current() == 0 and svc.model_version == 0
    for a, b in zip(jax.tree_util.tree_leaves(svc.params), v0_leaves):
        np.testing.assert_array_equal(np.asarray(jax.device_get(a)), b)


def test_degraded_mode_when_model_fails_to_load(tmp_path):
    """CURRENT pointing at a version that cannot load -> the service
    still answers, from the jitted Pareto tail over the tenant's own
    completed durations, flagged degraded."""
    with open(os.path.join(str(tmp_path), "CURRENT"), "w") as f:
        json.dump({"current": 7, "history": []}, f)
    svc = PredictionService(ServiceConfig(profile=profile(),
                                          ckpt_dir=str(tmp_path)))
    assert svc.degraded
    c = LocalClient(svc, "t0")
    assert c.hello(profile())["ok"]
    rng = np.random.default_rng(14)
    m_t = rand_mt(rng)
    r = c.snapshot(mk_snap(
        "t0", 0, rand_mh(rng), m_t,
        done=[{"id": 99, "times": [1.1, 1.4, 2.0, 5.0, 1.2, 1.3]}]))
    assert r["ok"] and r["degraded"] is True
    e_s = r["jobs"][0]["e_s"]
    assert np.isfinite(e_s) and 0.0 <= e_s <= 3.0
    assert svc.stats()["degraded_answers"] == 1


# --------------------- wall-clock retrain scheduling ---------------------

def test_retrain_scheduler_fires_per_period_and_coalesces():
    """The monotonic scheduler fires exactly once per elapsed period,
    re-arms from *now* (missed periods coalesce into one firing, never a
    catch-up burst), and 0 disables it."""
    from repro.service.daemon import RetrainScheduler
    t = {"now": 100.0}
    s = RetrainScheduler(10.0, clock=lambda: t["now"])
    assert s.enabled
    assert not s.due()                 # nothing elapsed
    t["now"] = 109.9
    assert not s.due()
    t["now"] = 110.0
    assert s.due()                     # one period elapsed
    assert not s.due()                 # latched: fired once, re-armed
    t["now"] = 145.0                   # 3.5 periods swallowed
    assert s.due()                     # single coalesced firing
    assert not s.due()
    t["now"] = 154.9
    assert not s.due()                 # re-armed from 145, not from 110
    t["now"] = 155.0
    assert s.due()

    off = RetrainScheduler(0.0, clock=lambda: t["now"])
    assert not off.enabled
    assert not off.due()


def test_wall_clock_retrain_trigger_end_to_end(tmp_path):
    """A daemon with ``retrain_interval_s`` set (and the snapshot-count
    trigger OFF) retrains and promotes when the injected monotonic clock
    crosses the period — and not before."""
    import time as _time
    t = {"now": 0.0}
    cfg = ServiceConfig(profile=profile(), ckpt_dir=str(tmp_path),
                        min_train_pairs=6, eval_holdback=3,
                        train_epochs=2, train_lr=1e-4,
                        retrain_every=0, retrain_interval_s=30.0)
    with ServiceDaemon(cfg, port=None,
                       retrain_clock=lambda: t["now"]) as d:
        svc = d.service
        assert d.retrain_scheduler.enabled
        c = LocalClient(svc, "t0")
        assert c.hello(profile())["ok"]
        rng = np.random.default_rng(21)
        _drive_pairs(svc, c, rng, steps=10)
        assert len(svc.buffer) >= cfg.min_train_pairs
        # clock has not advanced: the retrainer thread polls but must
        # not fire (snapshot trigger is off and the period is untouched)
        _time.sleep(0.3)
        assert svc.stats()["retrains"] == 0 and svc.model_version == 0
        t["now"] = 31.0                # cross the period on the fake clock
        deadline = _time.monotonic() + 10.0
        while svc.model_version == 0 and _time.monotonic() < deadline:
            _time.sleep(0.05)
        assert svc.stats()["retrains"] >= 1
        assert svc.model_version == 1, "wall-clock trigger never promoted"


def test_retrain_failure_counted_and_retrainer_survives(tmp_path,
                                                        monkeypatch):
    """A retrain that raises must not kill the retrainer thread or
    vanish silently: stats() grows ``retrain_failures`` and
    ``last_retrain_error``, the due-flag clears (no hot spin on a
    poisoned buffer), and the *next* period still fires."""
    import time as _time
    t = {"now": 0.0}
    cfg = ServiceConfig(profile=profile(), ckpt_dir=str(tmp_path),
                        min_train_pairs=6, eval_holdback=3,
                        train_epochs=2, train_lr=1e-4,
                        retrain_every=0, retrain_interval_s=30.0)
    with ServiceDaemon(cfg, port=None,
                       retrain_clock=lambda: t["now"]) as d:
        svc = d.service
        assert svc.stats()["retrain_failures"] == 0
        assert svc.stats()["last_retrain_error"] is None

        def boom():
            raise RuntimeError("forced retrain failure")
        monkeypatch.setattr(svc, "retrain_now", boom)
        t["now"] = 31.0                # cross the first period
        deadline = _time.monotonic() + 10.0
        while (svc.stats()["retrain_failures"] == 0
               and _time.monotonic() < deadline):
            _time.sleep(0.05)
        st = svc.stats()
        assert st["retrain_failures"] >= 1
        assert "forced retrain failure" in st["last_retrain_error"]
        assert not svc._retrain_due    # cleared: no hot retry spin
        assert d._retrainer.is_alive(), "retrainer thread died"
        seen = st["retrain_failures"]
        t["now"] = 62.0                # next period: thread still serving
        deadline = _time.monotonic() + 10.0
        while (svc.stats()["retrain_failures"] <= seen
               and _time.monotonic() < deadline):
            _time.sleep(0.05)
        assert svc.stats()["retrain_failures"] > seen
