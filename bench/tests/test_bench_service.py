"""A service cell's run, rehearsed on the CPU at a small size: the same
seed records the same telemetry, the check passes on the program as it
is and comes out false for each fault the tenant step can have, the
window compiles nothing and runs one tenant-batched launch a tick, and
the readers read the traced window's spans and counters."""
from __future__ import annotations

import copy
import pathlib
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import common, run, servicecell, servicefaults  # noqa: E402

CELL = "service-64.closed"
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
SECONDS = 1.5


def _small():
    _, config, traffic = common.cell(CELL)
    config = copy.deepcopy(config)
    traffic = copy.deepcopy(traffic)
    config["sim"].update(n_hosts=20, n_intervals=40)
    config["profile"].update(n_hosts=20)
    config["service"].update(max_tenants=8)
    traffic.update(tenants=6, episodes=[1, 2, 3], episode_stride=7,
                   check_per_episode=8)
    return config, traffic


def _execute(seed=11):
    config, traffic = _small()
    return run.execute(CELL, seed, SECONDS, False, device=DEVICE,
                       config=config, traffic=traffic,
                       t_start=time.perf_counter())


def test_sound_service_is_correct():
    result, checks = _execute()
    assert result["correct"], checks
    assert checks["unchecked"]["value"] == 0
    assert set(result["metrics"]) == {"decision_ms_p95", "intervals_per_s",
                                      "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0


def test_same_seed_same_telemetry():
    """A seed orders the traffic's pool of episodes; every order replays
    the same (episode, offset) pairs, so every seed does the same work."""
    config, traffic = _small()
    orders = [servicecell.episodes(traffic, 2**31 + s) for s in range(8)]
    assert servicecell.episodes(traffic, 2**31 + 3) == orders[3]
    assert all(sorted(o) == sorted(traffic["episodes"]) for o in orders)
    assert len({tuple(o) for o in orders}) > 1
    a = servicecell.record(config, orders[0][:2])
    assert a == servicecell.record(config, orders[0][:2])
    assert a[0] != a[1]
    assert [len(s) for s in a] == [config["sim"]["n_intervals"]] * 2
    rep = servicecell.Replay(a, 3, 7)
    assert [rep.pos[i] for i in range(3)] == [0, 0, 7]
    for _ in range(40):
        snap, new = rep.next(0)
    assert not new and snap is a[0][-1]
    snap, new = rep.next(0)
    assert new and snap is a[1][0]


def test_traced_window_is_one_launch_a_tick(tmp_path):
    """Traced on the CPU: no compile in the window, one tenant-step
    launch per ``service.tick`` span, the slot rebuilds are the tenants'
    episode starts, and each reader finds what it reads (the device's
    metrics from a stand-in reduction: the CPU has no device plane)."""
    config, traffic = _small()
    r = servicecell.run(config, traffic, 7, SECONDS, True,
                        time.perf_counter(), trace_dir=tmp_path)
    assert r.compiles_in_window == 0 and r.failed == 0
    c = r.counters
    assert c["tenant_calls"] == r.ticks == r.program["service.tick"]["count"]
    assert r.program["predictor.tenants"]["count"] == r.ticks
    assert r.program["service.submit"]["count"] == r.attempted
    assert len(r.tenant_calls) == r.ticks
    starts = traffic["tenants"] + sum(
        (7 * (i // 3) + r.ticks - 1) // 40 for i in range(6))
    assert c["tenant_slot_rebuilds"] == starts
    per = c["tenant_stage_bytes"] / c["tenant_calls"]
    assert per == pytest.approx(4 * (2 + 8 * (1 + 220) + sum(
        nb * 52 for _, nb in r.tenant_calls) / r.ticks))
    r.trace = {"n_ops": 10, "busy_ns": 1.0, "window_ns": 4.0}
    r.tenant_device = (r.ticks, 2e6 * r.ticks)
    ctx = servicecell.layer_context(r, config, common.peaks("TPU v5 lite"))
    got = {m["name"]: common.metric_reader(m["name"])(ctx)
           for m in common.metrics_for(CELL, "per_layer")}
    assert got["device_idle_share.svc"] == pytest.approx(75.0)
    assert 0 < got["tenant_step_roofline.svc"] < 100
    assert 0 <= got["tenant_pad_rows_share.svc"] < 100
    assert 0 < got["tenant_predict_ms.svc"] < got["service_host_ms.svc"] \
        + got["tenant_predict_ms.svc"] < r.window_s / r.ticks * 1e3
    assert got["decision_mfu.svc"] > 0


@pytest.mark.parametrize("fault", sorted(servicefaults.FAULTS))
def test_each_fault_is_not_correct(fault):
    with servicefaults.FAULTS[fault]():
        result, checks = _execute(seed=2**31 + 5)
    assert not result["correct"], checks
