"""A simulator cell's run, rehearsed on the CPU at a small size: the same
seed gives the same work, the check passes on the program as it is, and
comes out false for the lower-precision control and for each fault the
timed path can have."""
from __future__ import annotations

import copy
import pathlib
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import common, faults, run, simcell, weights  # noqa: E402

CELL = "planetlab-400.start"
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
SECONDS = 1.5


def _small():
    _, config, traffic = common.cell(CELL)
    config = copy.deepcopy(config)
    traffic = copy.deepcopy(traffic)
    config["sim"].update(n_hosts=20, n_intervals=40)
    traffic.update(warm_max_jobs=64, check_per_episode=8)
    return config, traffic


def _execute(seed=11):
    config, traffic = _small()
    return run.execute(CELL, seed, SECONDS, False, device=DEVICE,
                       config=config, traffic=traffic,
                       t_start=time.perf_counter())


def _sim_run(seed):
    config, traffic = _small()
    return config, simcell.run(config, traffic, seed, SECONDS, False,
                               time.perf_counter())


def test_sound_program_is_correct():
    result, checks = _execute()
    assert result["correct"], checks
    assert checks["unchecked"]["value"] == 0
    assert set(result["metrics"]) == {"decision_ms_p95", "intervals_per_s",
                                      "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0


def test_same_seed_same_inputs_every_seed_same_episodes():
    config, traffic = _small()
    from repro.sim.engine import Simulation

    def order(seed):
        it = simcell.episode_seeds(traffic, seed)
        return [next(it) for _ in range(6)]

    def arrivals(episode):
        sim = Simulation(simcell.sim_config(config, traffic, episode))
        for _ in range(config["sim"]["n_intervals"]):
            sim.step()
        return sim.tasks.view("work").copy()

    orders = [order(2**31 + s) for s in range(16)]
    assert order(2**31 + 3) == orders[3]
    assert all(sorted(o[:2]) == sorted(traffic["episodes"]) for o in orders)
    assert len({tuple(o) for o in orders}) == 2
    first = orders[0][0]
    assert np.array_equal(arrivals(first), arrivals(first))
    net, init = _small()[0]["network"], _small()[0]["init"]
    p1 = weights.host_params(2**40 + 1, 31, net, init)
    p2 = weights.host_params(2**40 + 1, 31, net, init)
    p3 = weights.host_params(2**41 + 1, 31, net, init)
    assert np.array_equal(p1["enc"][0]["w"], p2["enc"][0]["w"])
    assert not np.array_equal(p1["enc"][0]["w"], p3["enc"][0]["w"])


def test_control_in_three_passes_is_not_correct():
    config, r = _sim_run(13)
    checks = simcell.checks(r, config, 13)
    assert common.correct(checks), checks
    simcell.stand_in(r, config, 13)
    checks = simcell.checks(r, config, 13)
    assert checks["unchecked"]["value"] == 0
    gap = checks["es_gap_over_3pass"]
    assert gap["value"] == pytest.approx(1.0) and gap["value"] > gap["limit"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_each_fault_is_not_correct(fault):
    with faults.FAULTS[fault]():
        result, checks = _execute(seed=2**31 + 5)
    assert not result["correct"], checks
