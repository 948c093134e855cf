"""Chaos drill driver: the acceptance scenario, outside pytest.

Runs the seeded fault-injection drills against both distributed
surfaces and asserts the recovery invariants:

  * **fabric**: a 2-node 24-cell grid through the :class:`ChaosProxy`
    with ``REPRO_FABRIC_KEY`` set — scripted frame corruption (rejected
    at the MAC check before unpickling), a mid-frame RST, a stall
    longer than the lease (a live node is reclaimed and re-admitted),
    and one node SIGKILLed mid-unit — must produce summaries
    **bitwise-equal** to serial ``run()``;
  * **service**: a tenant streamed through the proxy with reply
    corruption and RSTs — after the proxy quiesces, the server must
    hold exactly one application of every interval and answer the
    final snapshot bitwise-equal to a clean in-process predictor.

Every run's *realized* fault schedule (stream, chunk, fault, detail) is
written to ``benchmarks/artifacts/chaos/`` — the nightly chaos lane
uploads these, so a red run ships its own reproduction recipe.

    PYTHONPATH=src python benchmarks/chaos_drill.py [--seeds 0,1,2]
"""
from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import sys
import tempfile
import time

import numpy as np

# a host-side scheduling bench: every lane is a process with its own JAX
# runtime, which only the CPU backend allows (one process per chip)
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from repro.chaos import ChaosProxy, FaultPlan  # noqa: E402
from repro.core import features  # noqa: E402
from repro.policy import wire  # noqa: E402
from repro.service import (Profile, ServiceConfig,  # noqa: E402
                           ServiceDaemon)
from repro.service.daemon import ServiceClient  # noqa: E402
from repro.sim.fabric import (FabricCoordinator,  # noqa: E402
                              worker_main)
from repro.sim.sweep import (SweepSpec,  # noqa: E402
                             deterministic_summary as det, run)

ART_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "artifacts", "chaos")


def _drill_spec() -> SweepSpec:
    return SweepSpec(techniques=("none", "sgc"),
                     scenarios=("planetlab", "fault-storm"),
                     seeds=(0, 1, 2, 3, 4, 5), n_hosts=10,
                     n_intervals=20, arrival_rate=0.8, max_workers=1)


def fabric_drill(seed: int, serial) -> dict:
    spec = _drill_spec()
    marker = os.path.join(tempfile.mkdtemp(prefix="chaos-"), "killed")
    os.environ["REPRO_TEST_KILL_CELL"] = f"fault-storm:sgc:1:{marker}"
    os.environ["REPRO_FABRIC_KEY"] = f"drill-{seed}"
    c2s = FaultPlan(corrupt=0.01, skip_first=4, max_faults=2,
                    script={5: ("corrupt", 1234), 9: ("reset", None)},
                    stall_after=12, stall_s=5.0)
    s2c = FaultPlan(corrupt=0.01, skip_first=4, max_faults=2,
                    script={6: ("corrupt", 999)})
    t0 = time.perf_counter()
    try:
        with FabricCoordinator(lease_s=3.0) as coord:
            with ChaosProxy((coord.host, coord.port), seed=seed,
                            c2s=c2s, s2c=s2c) as px:
                ctx = multiprocessing.get_context("spawn")
                procs = [ctx.Process(
                    target=worker_main, args=(px.host, px.port),
                    kwargs=dict(node=f"chaos{i}", lanes=1),
                    daemon=True) for i in range(2)]
                for p in procs:
                    p.start()
                try:
                    res = run(spec, fabric=coord)
                finally:
                    for p in procs:
                        p.join(timeout=120)
                        if p.is_alive():
                            p.kill()
                px.dump_artifact(os.path.join(
                    ART_DIR, f"fabric-drill-seed{seed}.json"))
    finally:
        os.environ.pop("REPRO_TEST_KILL_CELL", None)
        os.environ.pop("REPRO_FABRIC_KEY", None)
    bitwise = (
        [(c.scenario, c.technique, c.seed) for c in res.cells]
        == spec.cells()
        and all(det(a.summary) == det(b.summary)
                for a, b in zip(serial.cells, res.cells)))
    return {"seed": seed, "wall_s": round(time.perf_counter() - t0, 3),
            "cells": len(res.cells), "bitwise_equal": bitwise,
            "node_killed": os.path.exists(marker),
            "faults": {e["fault"] for e in px.events} != set(),
            "fault_kinds": sorted({e["fault"] for e in px.events})}


N_HOSTS, MAX_TASKS, HORIZON = 3, 4, 5


def _snap(tenant, seq, m_h, m_t, q=3):
    tasks = [(100 + i, i % N_HOSTS, i) for i in range(q)]
    return wire.snapshot_to_wire(
        tenant, seq, m_h, jobs=[wire.job_to_wire(1, q, m_t,
                                                 tasks=tasks)],
        done=[])


def service_smoke(seed: int) -> dict:
    prof = Profile(n_hosts=N_HOSTS, max_tasks=MAX_TASKS,
                   horizon=HORIZON)
    rng = np.random.default_rng(2)
    m_t = np.zeros((MAX_TASKS, features.TASK_FEATURES), np.float32)
    m_t[:3] = rng.random((3, features.TASK_FEATURES))
    m_hs = [rng.random((N_HOSTS, features.HOST_FEATURES))
            .astype(np.float32) for _ in range(8)]
    t0 = time.perf_counter()
    with ServiceDaemon(ServiceConfig(profile=prof)) as d:
        c2s = FaultPlan(reset=0.05, skip_first=2, max_faults=2)
        s2c = FaultPlan(corrupt=0.10, reset=0.05, skip_first=2,
                        max_faults=3)
        with ChaosProxy(("127.0.0.1", d.port), seed=seed, c2s=c2s,
                        s2c=s2c) as px:
            c = ServiceClient(px.host, px.port, "t0", retries=8,
                              backoff_s=0.05, timeout=5.0)
            assert c.hello(prof)["ok"]
            for i, m_h in enumerate(m_hs[:-1]):
                for _ in range(6):
                    try:
                        r = c.snapshot(_snap("t0", i, m_h, m_t))
                    except (ConnectionError, TimeoutError):
                        continue
                    if isinstance(r, dict) and r.get("ok"):
                        break
            px.quiesce()
            r = c.snapshot(_snap("t0", len(m_hs) - 1, m_hs[-1], m_t))
            st = d.service.stats()
            px.dump_artifact(os.path.join(
                ART_DIR, f"service-smoke-seed{seed}.json"))
            c.bye()
    from repro.core.predictor import StragglerPredictor
    pred = StragglerPredictor(n_hosts=N_HOSTS, max_tasks=MAX_TASKS,
                              horizon=HORIZON)
    for m_h in m_hs:
        pred.push_host_row(m_h)
        ref = pred.predict_interval(m_t[None],
                                    np.array([3.0], np.float32))
    return {"seed": seed, "wall_s": round(time.perf_counter() - t0, 3),
            "applied_once": st["snapshots"] == len(m_hs),
            "resends": st["resends"],
            "final_bitwise": r["jobs"][0]["e_s"]
            == float(np.asarray(ref)[0])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="seeded chaos drills over fabric + service")
    ap.add_argument("--seeds", default="0",
                    help="comma-separated chaos seeds")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s.strip()]
    os.makedirs(ART_DIR, exist_ok=True)
    spec = _drill_spec()
    print(f"serial reference: {len(spec.cells())} cells", flush=True)
    serial = run(spec)
    report, ok = [], True
    for seed in seeds:
        f = fabric_drill(seed, serial)
        s = service_smoke(seed)
        ok &= (f["bitwise_equal"] and f["node_killed"]
               and s["applied_once"] and s["final_bitwise"])
        report.append({"fabric": f, "service": s})
        print(f"seed {seed}: fabric bitwise={f['bitwise_equal']} "
              f"killed={f['node_killed']} faults={f['fault_kinds']} "
              f"({f['wall_s']}s) | service applied_once="
              f"{s['applied_once']} bitwise={s['final_bitwise']} "
              f"resends={s['resends']} ({s['wall_s']}s)", flush=True)
    digest = os.path.join(ART_DIR, "chaos_digest.json")
    with open(digest, "w") as fp:
        json.dump({"seeds": seeds, "ok": ok, "runs": report}, fp,
                  indent=1, default=str)
    print(f"digest -> {digest}")
    if not ok:
        print("CHAOS DRILL FAILED: see artifacts for the realized "
              "fault schedules", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
