"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows, one per figure with its
headline summary, and — when dry-run artifacts exist — the roofline
table. ``--full`` switches the simulator to Table-4 scale.  The speed of
the decision path is measured on the chip by ``bench/run.py`` (cells in
``BENCHMARK.json``), not here.

  PYTHONPATH=src python -m benchmarks.run [--full] [--skip-sim]
"""
from __future__ import annotations

import argparse
import json
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale simulation (400 hosts, 288 ivals)")
    ap.add_argument("--skip-sim", action="store_true")
    args, _ = ap.parse_known_args()

    print("name,us_per_call,derived")

    if not args.skip_sim:
        from benchmarks import sim_experiments as S
        t0 = time.time()
        S.prep(args.full)  # warm the sweep pretrain cache once
        print(f"prep_start_training,{(time.time() - t0) * 1e6:.0f},"
              f"epochs+warmup")

        for name, fn in (("fig2_grid", S.fig2_grid_search),
                         ("fig6_utilization", S.fig6_utilization),
                         ("fig7_workloads", S.fig7_workloads),
                         ("fig8_completion", S.fig8_completion_variance),
                         ("fig9_mape", S.fig9_mape),
                         ("fig10_overhead", S.fig10_overhead)):
            t0 = time.time()
            out = fn(args.full)
            us = (time.time() - t0) * 1e6
            print(f"{name},{us:.0f},{json.dumps(out)}")

    try:
        from benchmarks.roofline import table
        t = table()
        if t.count("\n") > 1:
            print("\n=== Roofline (from dry-run artifacts) ===")
            print(t)
    except Exception as e:  # artifacts may not exist yet
        print(f"roofline_table,0,unavailable: {e}")


if __name__ == "__main__":
    main()
