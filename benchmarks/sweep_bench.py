"""Serial-vs-parallel benchmark for the scenario-sweep subsystem.

Reports grid *throughput* with one-time costs split out, so steady-state
scaling is no longer conflated with pool bring-up (the old headline
"0.24x cold speedup" was almost entirely worker spawn + per-worker
duplicate pretraining):

  * ``spawn_s``        — bringing up the worker pool (fresh processes,
    jax + simulator imports), measured by ``sweep.warm_pool``;
  * ``warmup_s``       — per-worker jit-cache warmup (each worker runs
    one cell per technique so the XLA compiles of the prediction
    programs happen once at bring-up, not inside the first grid);
  * ``pretrain_s``     — parent-side pretraining of every (scenario,
    technique) that declares it (broadcast to workers as pickled bytes;
    paid once per process, not once per worker);
  * ``serial_wall_s``  — the grid run with ``max_workers=1`` after
    pretraining is cached (pure cell throughput, one lane);
  * ``parallel_wall_s``      — the first grid over the brought-up pool
    (grid-cold: none of its cells have run; infra-warm: spawn/warmup/
    pretrain already paid and reported above);
  * ``parallel_warm_wall_s`` — and again (what every later figure sweep
    in the same process pays);
  * ``parallel_cold_total_s`` — derived worst case for a one-shot cold
    process: spawn_s + warmup_s + parallel_wall_s;
  * ``per_cell_warm_s``      — mean/p95 per-cell wall inside the warm
    parallel run.

With ``--fabric-nodes N`` (default 2; 0 disables) the same grid also
runs over the **distributed sweep fabric** on localhost: a
``FabricCoordinator`` in this process serves units to N spawned node
agents over TCP, twice (``fabric_wall_s`` — fresh agents, cold caches —
then ``fabric_warm_wall_s``), and the fabric cells are asserted
bitwise-equal to serial too (``fabric_bitwise_equal``).  On a 1-cpu
container this measures fabric *overhead*, not speedup — the numbers
exist so a real multi-host run has a committed localhost reference.

Serial and parallel cell summaries are asserted bitwise-equal.  Host
context (``host``, ``host_cpus``, ``lanes``) is recorded because the
attainable speedup at W workers is capped by physical cores — the
scheduler adds the parent as an extra lane only when cores exceed
workers, and ``check_perf.py`` only compares matching fingerprints.

    PYTHONPATH=src python benchmarks/sweep_bench.py [--quick] [--workers N]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import multiprocessing
import os
import sys
import time

import numpy as np

# a host-side scheduling bench: every lane is a process with its own JAX
# runtime, which only the CPU backend allows (one process per chip)
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from common import host_fingerprint, write_csv  # noqa: E402

from repro.sim import scenarios, sweep  # noqa: E402
from repro.sim.fabric import FabricCoordinator, worker_main  # noqa: E402
from repro.sim.sweep import SweepSpec, deterministic_summary, run  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def bench_spec(quick: bool) -> SweepSpec:
    # `start` is in the grid deliberately: it is the paper's technique and
    # the one that exercises pretraining, so the parent-train-and-broadcast
    # path is measured rather than benchmarked around
    return SweepSpec(
        techniques=("none", "sgc", "dolly", "start") if quick
        else ("none", "sgc", "dolly", "grass", "nearestfit", "start"),
        seeds=(0, 1) if quick else (0, 1, 2, 3),
        scenarios=tuple(scenarios.names())[:4] if quick
        else tuple(scenarios.names()),
        n_hosts=32 if quick else 64,
        n_intervals=72 if quick else 288,
        arrival_rate=0.8 if quick else 1.0,
        pretrain_epochs=8,
    )


def bench_fabric(spec: SweepSpec, serial, n_nodes: int) -> dict:
    """Run the grid over a localhost fabric (coordinator here, ``n_nodes``
    spawned node agents), twice: fresh agents pay jax import + compiles
    in the first grid, the second is the steady state."""
    ctx = multiprocessing.get_context("spawn")
    with FabricCoordinator(lease_s=120.0) as coord:
        procs = [ctx.Process(target=worker_main,
                             args=(coord.host, coord.port),
                             kwargs=dict(node=f"bench-node{i}", lanes=1,
                                         exit_on_drain=False),
                             daemon=True)
                 for i in range(n_nodes)]
        for p in procs:
            p.start()
        try:
            first = run(spec, fabric=coord)
            warm = run(spec, fabric=coord)
        finally:
            for p in procs:
                p.terminate()
            for p in procs:
                p.join(timeout=10)
    equal = all(deterministic_summary(a.summary)
                == deterministic_summary(b.summary)
                for res in (first, warm)
                for a, b in zip(serial.cells, res.cells))
    return {
        "fabric_nodes": n_nodes,
        "fabric_wall_s": round(first.wall_s, 3),
        "fabric_warm_wall_s": round(warm.wall_s, 3),
        "fabric_speedup_warm": round(
            serial.wall_s / max(warm.wall_s, 1e-9), 2),
        "fabric_bitwise_equal": bool(equal),
    }


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--workers", type=int, default=None,
                    help="parallel worker count (default: cpu count)")
    ap.add_argument("--fabric-nodes", type=int, default=2,
                    help="localhost fabric node agents (0 disables the "
                         "fabric leg)")
    args = ap.parse_args(argv)

    spec = bench_spec(args.quick)
    n_workers = args.workers or (os.cpu_count() or 1)

    # one-time costs, measured on their own
    t0 = time.perf_counter()
    sweep._build_payloads(spec)
    pretrain_s = time.perf_counter() - t0
    sweep.shutdown_pool()
    spawn_s = sweep.warm_pool(n_workers)
    # per-worker jit-cache warmup (XLA-compiling the prediction programs
    # per batch bucket is seconds per worker — one-time, like spawn)
    warmup_s = sweep.warm_pool_caches(spec, n_workers)

    # grid throughput: serial (one lane, pretrain cached; best of two so
    # the parent's one-time jit compiles land in the first, discarded run
    # and shared-runner noise is damped) ...
    serial = min((run(dataclasses.replace(spec, max_workers=1))
                  for _ in range(2)), key=lambda r: r.wall_s)
    # ... vs the fresh pool (worker caches cold) and the warm pool
    parallel = run(dataclasses.replace(spec, max_workers=n_workers))
    warm = min((run(dataclasses.replace(spec, max_workers=n_workers))
                for _ in range(2)), key=lambda r: r.wall_s)

    equal = all(deterministic_summary(a.summary)
                == deterministic_summary(b.summary)
                for a, b in zip(serial.cells, parallel.cells))
    equal_warm = all(deterministic_summary(a.summary)
                     == deterministic_summary(b.summary)
                     for a, b in zip(serial.cells, warm.cells))
    speedup = serial.wall_s / max(parallel.wall_s, 1e-9)
    speedup_warm = serial.wall_s / max(warm.wall_s, 1e-9)
    cell_s = np.array([c.wall_s for c in warm.cells])
    cpus = os.cpu_count() or 1
    lanes = n_workers + (1 if cpus > n_workers else 0)

    fabric = {}
    if args.fabric_nodes > 0:
        # free the pool's workers first: fabric agents are their own
        # processes and a 1-cpu container can't host both fleets
        sweep.shutdown_pool()
        fabric = bench_fabric(spec, serial, args.fabric_nodes)

    rows = [
        ["cells", len(serial.cells), ""],
        ["host_cpus", cpus, ""],
        ["lanes", lanes, "workers + parent when cores allow"],
        ["spawn_s", round(spawn_s, 2), "one-time pool bring-up"],
        ["warmup_s", round(warmup_s, 2),
         "one-time per-worker jit-cache warmup"],
        ["pretrain_s", round(pretrain_s, 2),
         "parent-side, broadcast to workers"],
        ["serial_wall_s", round(serial.wall_s, 2), ""],
        [f"parallel_wall_s (x{parallel.n_workers})",
         round(parallel.wall_s, 2),
         "first grid after bring-up (one-time costs above)"],
        [f"parallel_warm_wall_s (x{warm.n_workers})",
         round(warm.wall_s, 2), "persistent pool, caches resident"],
        ["parallel_cold_total_s",
         round(spawn_s + warmup_s + parallel.wall_s, 2),
         "derived: one-shot cold process incl. bring-up"],
        ["speedup", round(speedup, 2), ""],
        ["speedup_warm", round(speedup_warm, 2), ""],
        ["bitwise_equal", int(equal and equal_warm), ""],
        ["per_cell_warm_s_mean", round(float(cell_s.mean()), 3), ""],
        ["per_cell_warm_s_p95",
         round(float(np.percentile(cell_s, 95)), 3), ""],
    ]
    for k in sorted(fabric):
        rows.append([k, fabric[k] if not isinstance(fabric[k], bool)
                     else int(fabric[k]),
                     "localhost 2-node fabric" if k == "fabric_nodes"
                     else ""])
    write_csv("sweep_bench.csv", ["metric", "value", "note"], rows)
    bench = {
        "cells": len(serial.cells),
        "host": host_fingerprint(),
        "workers": parallel.n_workers,
        "host_cpus": cpus,
        "lanes": lanes,
        "spawn_s": round(spawn_s, 3),
        "warmup_s": round(warmup_s, 3),
        "pretrain_s": round(pretrain_s, 3),
        "serial_wall_s": round(serial.wall_s, 3),
        "parallel_wall_s": round(parallel.wall_s, 3),
        "parallel_warm_wall_s": round(warm.wall_s, 3),
        "parallel_cold_total_s": round(
            spawn_s + warmup_s + parallel.wall_s, 3),
        "speedup": round(speedup, 2),
        "speedup_warm": round(speedup_warm, 2),
        "bitwise_equal": bool(equal and equal_warm),
        "per_cell_warm_s": round(float(cell_s.mean()), 4),
        "per_cell_warm_s_p95": round(float(np.percentile(cell_s, 95)), 4),
        **fabric,
    }
    path = os.path.join(REPO_ROOT, "BENCH_sweep.json")
    with open(path, "w") as f:
        json.dump(bench, f, indent=1, sort_keys=True)
        f.write("\n")

    print(f"{len(serial.cells)} cells "
          f"({len(spec.scenarios)} scenarios x {len(spec.techniques)} "
          f"techniques x {len(spec.seeds)} seeds) on {cpus} cpus")
    print(f"spawn:         {spawn_s:7.2f}s  (one-time)")
    print(f"warmup:        {warmup_s:7.2f}s  (one-time, per-worker jit)")
    print(f"pretrain:      {pretrain_s:7.2f}s  (one-time, parent)")
    print(f"serial:        {serial.wall_s:7.2f}s")
    print(f"parallel:      {parallel.wall_s:7.2f}s  ({parallel.n_workers} "
          f"workers, first grid after bring-up, speedup {speedup:.2f}x)")
    print(f"parallel-warm: {warm.wall_s:7.2f}s  (persistent pool, "
          f"speedup {speedup_warm:.2f}x)")
    if fabric:
        print(f"fabric:        {fabric['fabric_wall_s']:7.2f}s  "
              f"({fabric['fabric_nodes']} localhost nodes, first grid "
              f"incl. agent bring-up)")
        print(f"fabric-warm:   {fabric['fabric_warm_wall_s']:7.2f}s  "
              f"(speedup {fabric['fabric_speedup_warm']:.2f}x, "
              f"bitwise-equal {fabric['fabric_bitwise_equal']})")
    print(f"bitwise-equal results: {equal and equal_warm}")
    print(f"wrote {path}")
    assert equal, "parallel sweep diverged from serial"
    assert equal_warm, "warm-pool sweep diverged from serial"
    if fabric:
        assert fabric["fabric_bitwise_equal"], \
            "fabric sweep diverged from serial"
    return {"speedup": speedup, "speedup_warm": speedup_warm,
            "equal": equal and equal_warm, "cells": len(serial.cells),
            **fabric}


if __name__ == "__main__":
    main()
