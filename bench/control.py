#!/usr/bin/env python3
"""The readings the check's limits are set from, for one cell, on the chip.

    python bench/control.py --workload <cell> --seconds <s> --seeds <n> ...
        [--fault stale|half|altered]

In one process, for each seed: the cell's set-up and a short window at
its own load, then the sampled decisions against the references.
Without ``--fault`` it reads the program and the control (the device
reference in three bfloat16 passes, the step below the configuration's
float32 at ``highest``), then puts the control's E_S in the program's
place and runs the cell's own checks on it.  With ``--fault`` it plants
that fault (``bench/faults.py``) in the timed path and runs the cell's
checks.  Prints one line per seed and a JSON summary: the largest
program reading is the lower end of the limit; the control, put in the
program's place, reads 1 (the number is in its units).  The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

from run import configure_jax, device_check

from bench import common, faults  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)
    wl, config, traffic = common.cell(args.workload)
    configure_jax()
    device_check(wl["chips"])
    from bench import simcell
    # a short window samples as many decisions as a full run does
    per_ep = max(1, round(traffic["check_per_episode"]
                          * common.spec()["run_seconds"] / args.seconds))
    rows = []
    for seed in args.seeds:
        plant = (faults.FAULTS[args.fault]() if args.fault
                 else contextlib.nullcontext())
        with plant:
            r = simcell.run(config, traffic, seed, args.seconds, False,
                            time.perf_counter(), check_per_episode=per_ep)
        row = {"seed": seed, "fault": args.fault}
        if args.fault:
            checks = simcell.checks(r, config, seed)
        else:
            c = simcell.check(r, config, seed)
            g = c["gaps"]
            row.update(decisions=c["decisions"], jobs=c["jobs"],
                       gaps=g, program_ratio=g["program"] / g["control"],
                       program_correct=common.correct(
                           simcell.ratio_checks(c, config)))
            simcell.stand_in(r, config, seed)
            checks = simcell.checks(r, config, seed)
        row.update(es_gap_over_3pass=checks["es_gap_over_3pass"]["value"],
                   correct=common.correct(checks))
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "fault": args.fault,
               "limit": config["check"]["es_gap_over_3pass"],
               "correct_any": any(r["correct"] for r in rows),
               "reading_min": min(r["es_gap_over_3pass"] for r in rows)}
    if not args.fault:
        summary["program_max"] = max(r["program_ratio"] for r in rows)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
