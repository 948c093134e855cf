"""Scenario-registry + sweep-subsystem tests, and engine invariants the
sweep relies on (first-result-wins, no lost tasks, incremental job
accounting, parallel == serial)."""
import csv
import dataclasses
import os

import numpy as np
import pytest

from repro import jax_runtime
from repro.core import pareto
from repro.sim import SimConfig, Simulation, scenarios, small, sweep
from repro.sim import engine as E
from repro.sim.sweep import (CellResult, SweepResult, SweepSpec,
                             deterministic_summary as _det, run, run_cell)

REQUIRED_SCENARIOS = ("planetlab", "flash-crowd", "heavy-tail",
                      "hetero-fleet", "overload", "fault-storm")


# ------------------------------ scenarios ----------------------------------

def test_registry_contains_required_regimes():
    names = scenarios.names()
    for n in REQUIRED_SCENARIOS:
        assert n in names, n
    with pytest.raises(KeyError):
        scenarios.get("nope")


@pytest.mark.parametrize("name", REQUIRED_SCENARIOS)
def test_each_scenario_runs_end_to_end_with_finite_qos(name):
    cfg = scenarios.make_config(name, seed=0, n_hosts=12, n_intervals=30,
                                arrival_rate=0.8)
    sim = Simulation(cfg)
    s = sim.run()
    assert s["tasks_done"] > 0, name
    for k in sweep.QOS_KEYS:
        assert np.isfinite(s[k]), (name, k)


def test_hetero_fleet_has_mixed_per_host_ips():
    cfg = scenarios.make_config("hetero-fleet", n_hosts=9, n_intervals=5)
    sim = Simulation(cfg)
    assert len(np.unique(sim.host_ips)) == 3
    # scalar configs stay homogeneous
    assert len(np.unique(Simulation(small(n_hosts=9)).host_ips)) == 1


def test_host_ips_mean_averages_tiled_fleet():
    # 32 hosts over a 3-value tuple tile 11/11/10 — the fleet mean is NOT
    # the tuple mean
    cfg = SimConfig(n_hosts=32, host_ips=(4.17, 8.33, 16.66))
    assert cfg.host_ips_mean == pytest.approx(
        float(cfg.host_ips_array().mean()))
    assert cfg.host_ips_mean != pytest.approx(np.mean((4.17, 8.33, 16.66)))
    assert SimConfig(n_hosts=5).host_ips_mean == pytest.approx(8.33)


def test_straggler_counts_ignore_unplaced_hosts():
    """Originals that finish via a copy while unplaced (host == -1) must
    not credit a straggler to the last host via index wrap-around."""
    cfg = small(n_hosts=10, n_intervals=50, seed=1, fault_host_rate=0.15)
    sim = Simulation(cfg, technique=CloneStorm())
    sim.run()
    total_placed = sum(
        int((np.asarray(rec["straggler"]) & (np.asarray(rec["hosts"]) >= 0)
             ).sum()) for rec in sim.completed_jobs)
    assert sim.host_straggler_counts.sum() == total_placed


def test_flash_crowd_bursts_increase_load():
    base = scenarios.make_config("planetlab", n_hosts=12, n_intervals=48,
                                 arrival_rate=0.8)
    burst = scenarios.make_config("flash-crowd", n_hosts=12, n_intervals=48,
                                  arrival_rate=0.8)
    s_base = Simulation(base)
    s_burst = Simulation(burst)
    fac = [s_burst.workload.burst_factor(t) for t in range(48)]
    assert max(fac) == burst.burst_multiplier and min(fac) == 1.0
    s_base.run()
    s_burst.run()
    assert (s_burst.summary()["tasks_total"]
            > s_base.summary()["tasks_total"])


def test_overload_scenario_scales_arrivals():
    cfg = scenarios.make_config("overload", arrival_rate=0.6)
    assert cfg.arrival_rate == pytest.approx(0.6 * 2.5)
    assert cfg.reserved_utilization == 0.4


# ------------------------------- sweep -------------------------------------

def _tiny_spec(**kw) -> SweepSpec:
    base = dict(techniques=("none", "sgc"), seeds=(0, 1),
                scenarios=("planetlab", "fault-storm"),
                n_hosts=10, n_intervals=20, arrival_rate=0.8,
                max_workers=1)
    base.update(kw)
    return SweepSpec(**base)


def test_sweep_cell_grid_and_lookup():
    spec = _tiny_spec()
    assert len(spec.cells()) == 2 * 2 * 2
    res = run(spec)
    c = res.cell("fault-storm", "sgc", 1)
    assert c.summary["tasks_done"] >= 0 and c.wall_s > 0


def test_sweep_parallel_bitwise_equals_serial():
    spec = _tiny_spec()
    serial = run(spec)
    parallel = run(dataclasses.replace(spec, max_workers=2))
    assert parallel.n_workers == 2
    assert len(serial.cells) == len(parallel.cells)
    for a, b in zip(serial.cells, parallel.cells):
        assert (a.scenario, a.technique, a.seed) == (b.scenario,
                                                     b.technique, b.seed)
        assert _det(a.summary) == _det(b.summary), (a.scenario, a.technique)


def test_sweep_runs_in_process_on_accelerator(monkeypatch):
    """One process per chip: with an accelerator as JAX's default
    backend, a parallel spec spawns no pool — the process holding the
    chip runs every cell, with the serial results."""
    spec = _tiny_spec()
    serial = run(spec)
    monkeypatch.setattr(jax_runtime, "on_accelerator", lambda: True)

    def no_pool(n_workers):
        raise AssertionError("spawned a worker pool on an accelerator")
    monkeypatch.setattr(sweep, "_pool", no_pool)
    chip = run(dataclasses.replace(spec, max_workers=4))
    assert chip.n_workers == 1
    for a, b in zip(serial.cells, chip.cells):
        assert _det(a.summary) == _det(b.summary)


def test_sweep_parallel_equals_serial_with_pretrained_technique():
    """The per-process pretrain cache is exactly where serial (one shared
    cache) and parallel (each worker pretrains independently) runs could
    diverge — cover it with the cheapest pretrained technique."""
    spec = SweepSpec(techniques=("wrangler",), seeds=(0, 1),
                     scenarios=("planetlab",), n_hosts=10, n_intervals=20,
                     arrival_rate=0.8, max_workers=1)
    serial = run(spec)
    parallel = run(dataclasses.replace(spec, max_workers=2))
    for a, b in zip(serial.cells, parallel.cells):
        assert _det(a.summary) == _det(b.summary)


def test_sweep_csv_artifacts(tmp_path):
    spec = _tiny_spec(out_dir=str(tmp_path), csv_prefix="t")
    res = run(spec)
    cells_csv = os.path.join(str(tmp_path), "t_cells.csv")
    agg_csv = os.path.join(str(tmp_path), "t_agg.csv")
    assert os.path.exists(cells_csv) and os.path.exists(agg_csv)
    with open(cells_csv) as f:
        rows = list(csv.reader(f))
    assert len(rows) == 1 + len(res.cells)
    assert rows[0][:4] == ["scenario", "technique", "seed", "wall_s"]
    with open(agg_csv) as f:
        arows = list(csv.reader(f))
    assert len(arows) == 1 + len(spec.scenarios) * len(spec.techniques)


def test_aggregate_mean_and_ci():
    spec = SweepSpec(techniques=("none",), seeds=(0, 1, 2),
                     scenarios=("planetlab",), metrics=("m",))
    cells = [CellResult("planetlab", "none", i, {"m": v}, 0.0)
             for i, v in enumerate((1.0, 2.0, 3.0))]
    res = SweepResult(spec=spec, cells=cells, wall_s=0.0, n_workers=1)
    st = res.aggregate()[("planetlab", "none")]["m"]
    assert st["mean"] == pytest.approx(2.0)
    assert st["n"] == 3
    assert st["ci95"] == pytest.approx(1.96 * 1.0 / np.sqrt(3))


def test_overrides_may_replace_base_sizing_keys():
    # fig7-style sweep: arrival_rate comes through overrides without
    # colliding with the spec's explicit base sizing
    spec = _tiny_spec(overrides=(("arrival_rate", 1.8), ("n_hosts", 6)))
    cfg = spec.cell_config("planetlab", 0)
    assert cfg.arrival_rate == pytest.approx(1.8)
    assert cfg.n_hosts == 6
    # scenario arrival scaling still applies on top of the override
    cfg2 = spec.cell_config("overload", 0)
    assert cfg2.arrival_rate == pytest.approx(1.8 * 2.5)


def test_unknown_technique_and_scenario_raise():
    # unknown techniques raise ValueError naming the registered set (and
    # are caught at SweepSpec construction, before any worker spawns)
    with pytest.raises(ValueError, match="registered techniques"):
        run_cell(_tiny_spec(), "planetlab", "bogus", 0)
    with pytest.raises(ValueError, match="registered techniques"):
        _tiny_spec(techniques=("bogus",))
    with pytest.raises(KeyError):
        run_cell(_tiny_spec(), "bogus", "none", 0)


def test_make_technique_returns_fresh_pretrained_instances():
    cfg = small(n_hosts=10, n_intervals=20)
    t1 = sweep.make_technique("wrangler", cfg)
    t2 = sweep.make_technique("wrangler", cfg)
    assert t1 is not t2
    assert t1.w is not None  # pretrained on the cached warmup sim
    np.testing.assert_array_equal(t1.w, t2.w)


# -------------------------- engine invariants ------------------------------

class CloneStorm(E.Technique):
    """Clones every new original task 3x — stresses first-result-wins."""

    name = "clone-storm"

    def on_submit(self, new_idx):
        return [E.SimAction("clone", int(i), n_clones=3) for i in new_idx]


def test_first_result_wins_cancels_all_sibling_copies():
    cfg = small(n_hosts=10, n_intervals=40, seed=2)
    sim = Simulation(cfg, technique=CloneStorm())
    sim.run()
    tt = sim.tasks
    copies = np.nonzero(tt.view("is_copy"))[0]
    assert len(copies) > 0
    groups: dict = {}
    for c in copies:
        groups.setdefault(int(tt.orig[c]), []).append(int(c))
    checked_done = 0
    for orig, group in groups.items():
        if tt.state[orig] == E.DONE:
            checked_done += 1
            done_copies = [c for c in group if tt.state[c] == E.DONE]
            # at most one copy can win, and then it shares the original's
            # finish stamp; every other sibling must be cancelled
            assert len(done_copies) <= 1
            for c in done_copies:
                assert tt.finish_s[c] == tt.finish_s[orig]
            for c in group:
                if tt.state[c] != E.DONE:
                    assert tt.state[c] == E.CANCELLED, (orig, c)
    assert checked_done > 0


def test_no_original_task_lost_across_restarts_and_bounces():
    """Faults (host downtime, cloudlet restarts, VM-creation bounces) must
    never drop an original task: it stays pending/running/done forever."""
    cfg = small(n_hosts=10, n_intervals=60, seed=3, fault_host_rate=0.15,
                fault_task_rate=0.08, fault_vm_creation_rate=0.1)
    sim = Simulation(cfg)
    sim.run()
    tt = sim.tasks
    assert tt.view("restarts").sum() > 0  # the drill actually fired
    orig = ~tt.view("is_copy")
    states = tt.view("state")[orig]
    assert set(np.unique(states)) <= {E.PENDING, E.RUNNING, E.DONE}
    # incremental per-job open counts agree with the task table
    for job in range(sim.jobs.n):
        tids = sim.jobs.task_ids(job)
        open_n = int(np.isin(tt.state[tids],
                             [E.PENDING, E.RUNNING]).sum())
        assert sim.jobs.open_count[job] == open_n, job
        if sim.jobs.done[job]:
            assert open_n == 0
    # every accounted job's tasks are all terminal-done
    for rec in sim.completed_jobs:
        tids = sim.jobs.task_ids(rec["job"])
        assert (tt.state[tids] == E.DONE).all()
        assert (rec["times"] > 0).all()


class CopyChainer(E.Technique):
    """Speculates on running COPIES too (copy-of-a-copy chains), like the
    reactive baselines that scan active_mask without an is_copy filter."""

    name = "copy-chainer"

    def on_interval(self):
        tt = self.sim.tasks
        acts = []
        for i in np.nonzero(tt.active_mask())[0][:6]:
            acts.append(E.SimAction("speculate", int(i), target=0))
        return acts


def test_copy_of_copy_speculation_keeps_job_accounting_sound():
    cfg = small(n_hosts=10, n_intervals=50, seed=4)
    sim = Simulation(cfg, technique=CopyChainer())
    sim.run()
    tt = sim.tasks
    # the drill actually produced copy-of-copy chains
    copies = np.nonzero(tt.view("is_copy"))[0]
    assert any(tt.is_copy[int(tt.orig[c])] for c in copies)
    # per-job open counts never go negative and match the task table
    for job in range(sim.jobs.n):
        open_n = int(np.isin(tt.state[sim.jobs.task_ids(job)],
                             [E.PENDING, E.RUNNING]).sum())
        assert sim.jobs.open_count[job] == open_n, job
    # no job was accounted while an original was still incomplete
    for rec in sim.completed_jobs:
        tids = sim.jobs.task_ids(rec["job"])
        assert (tt.state[tids] == E.DONE).all()
        assert (tt.finish_s[tids] >= 0).all()


def test_actual_stragglers_matches_naive_reference():
    sim = Simulation(small(n_hosts=12, n_intervals=50, seed=1))
    sim.run()
    fast = sim.actual_stragglers_per_interval()
    # naive per-task reference (the pre-vectorization implementation)
    ref = np.zeros(sim.t)
    dt = sim.cfg.interval_seconds
    tt = sim.tasks
    for rec in sim.completed_jobs:
        for i, is_s in zip(sim.jobs.task_ids(rec["job"]), rec["straggler"]):
            if not is_s:
                continue
            lo = int(tt.submit_s[i] // dt)
            hi = int(max(tt.finish_s[i], tt.submit_s[i]) // dt)
            ref[lo:min(hi + 1, sim.t)] += 1
    np.testing.assert_array_equal(fast, ref)
    assert fast.sum() > 0


def test_fit_pareto_np_matches_jax_twin():
    rng = np.random.default_rng(0)
    for q in (2, 5, 10, 64):
        times = rng.pareto(2.0, q).astype(np.float32) + 1.0
        a_np, b_np = pareto.fit_pareto_np(times)
        a_j, b_j = pareto.fit_pareto(times)
        assert float(a_np) == pytest.approx(float(a_j), rel=1e-5)
        assert float(b_np) == pytest.approx(float(b_j), rel=1e-6)


# --------------------- grid validation + pool hardening ---------------------

@pytest.mark.parametrize("field", ["techniques", "seeds", "scenarios"])
def test_empty_grid_axis_rejected_at_construction(field):
    """An empty axis used to surface as a bare IndexError deep inside
    warm_pool_caches (spec.seeds[0]); now it's a ValueError naming the
    field, raised before any worker spawns."""
    kw = dict(techniques=("none",), seeds=(0,), scenarios=("planetlab",))
    kw[field] = ()
    with pytest.raises(ValueError, match=field):
        SweepSpec(**kw)


def test_ready_lanes_counts_only_successful_warmups(monkeypatch):
    """A warmup future that raised or was cancelled is ``done()`` too —
    the readiness gate must not count it as a live lane (it used to,
    over-submitting to lanes that never primed).  Failures surface as a
    one-time RuntimeWarning."""
    import concurrent.futures as cf
    import warnings

    monkeypatch.setattr(sweep, "_WARMUP_WARNED", False)
    ok = cf.Future()
    ok.set_result(True)
    bad = cf.Future()
    bad.set_exception(RuntimeError("warmup exploded"))
    cancelled = cf.Future()
    cancelled.cancel()
    pending = cf.Future()
    with pytest.warns(RuntimeWarning, match="warmup"):
        assert sweep._ready_lanes([ok, bad, cancelled, pending]) == 1
    with warnings.catch_warnings():      # warned once, not per poll
        warnings.simplefilter("error")
        assert sweep._ready_lanes([ok, bad, cancelled, pending]) == 1


def test_all_warmups_failed_falls_back_to_parent(monkeypatch):
    """Every lane's warmup raising (REPRO_TEST_FAIL_WARMUP) must leave
    the parallel path degraded-but-correct: the parent runs the whole
    grid itself, warns once, and stays bitwise-equal to serial."""
    import concurrent.futures as cf

    spec = _tiny_spec()
    serial = run(spec)
    monkeypatch.setenv("REPRO_TEST_FAIL_WARMUP", "1")
    sweep.shutdown_pool()                # fresh pool inherits the env
    try:
        sweep._pool(2)
        # warmups must have *resolved* (failed) before run() for the
        # warning to fire deterministically — tiny cells beat spawn
        cf.wait(sweep._POOL_READY, timeout=120)
        with pytest.warns(RuntimeWarning, match="warmup"):
            parallel = run(dataclasses.replace(spec, max_workers=2))
    finally:
        sweep.shutdown_pool()            # don't leak poisoned workers
    assert len(parallel.cells) == len(spec.cells())
    for a, b in zip(serial.cells, parallel.cells):
        assert _det(a.summary) == _det(b.summary)


def test_worker_killed_mid_grid_recovers_bitwise(tmp_path, monkeypatch):
    """SIGKILL a pool worker mid-cell (harvest-time BrokenProcessPool,
    the sweep twin of the fabric node-kill test): the parent reruns the
    lost unit, respawns the pool, and the full grid still lands
    bitwise-equal to serial."""
    spec = _tiny_spec()
    serial = run(spec)
    marker = tmp_path / "pool-killed-once"
    # target the FIRST unit submitted: warm idle workers pick it up
    # immediately, so the parent can neither run it inline nor steal it
    # back (running futures refuse cancel) — the kill is deterministic
    monkeypatch.setenv("REPRO_TEST_KILL_CELL",
                       f"planetlab:none:0:{marker}")
    sweep.shutdown_pool()                # fresh pool inherits the env
    try:
        # pre-warm so every unit goes to workers (a cold 1-cpu box would
        # otherwise run the kill cell in the parent, which never kills)
        sweep.warm_pool(2)
        parallel = run(dataclasses.replace(spec, max_workers=2))
    finally:
        sweep.shutdown_pool()            # recycle the armed workers
    assert marker.exists(), "the kill drill never fired in a worker"
    assert len(parallel.cells) == len(spec.cells())
    for a, b in zip(serial.cells, parallel.cells):
        assert (a.scenario, a.technique, a.seed) == (b.scenario,
                                                     b.technique, b.seed)
        assert _det(a.summary) == _det(b.summary), (a.scenario,
                                                    a.technique, a.seed)


def test_submit_time_broken_pool_recovers(monkeypatch):
    """Force ``pool.submit`` itself to raise BrokenProcessPool (the pool
    broke while the parent was busy elsewhere): the unit runs in the
    parent, the pool respawns, and the grid completes bitwise-equal."""
    import concurrent.futures as cf

    spec = _tiny_spec()
    serial = run(spec)
    sweep.shutdown_pool()
    real_pool = sweep._pool
    tripped = {"n": 0}

    class _Brittle:
        def __init__(self, p):
            self._p = p

        def submit(self, *a, **kw):
            if tripped["n"] == 0:
                tripped["n"] = 1
                raise cf.process.BrokenProcessPool("forced submit failure")
            return self._p.submit(*a, **kw)

    monkeypatch.setattr(sweep, "_pool",
                        lambda n: _Brittle(real_pool(n)))
    try:
        # warm first so the readiness gate reaches submit() at all on a
        # 1-cpu box (ready == 0 would keep the parent running inline)
        sweep.warm_pool(2)
        parallel = run(dataclasses.replace(spec, max_workers=2))
    finally:
        sweep.shutdown_pool()
    assert tripped["n"] == 1, "submit-time recovery never exercised"
    assert len(parallel.cells) == len(spec.cells())
    for a, b in zip(serial.cells, parallel.cells):
        assert _det(a.summary) == _det(b.summary)
