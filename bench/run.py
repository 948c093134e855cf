#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell's configuration, traffic mix and per-layer metrics are files
under ``bench/`` named in ``BENCHMARK.json``.  The run takes the chip,
names it, sets up and warms every shape (``setup_s``), measures for
``--seconds``, then checks the window's answers against the plain
reference.  ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` traces the window with the JAX profiler and reports the
per-layer metrics, the device's busy time and a breakdown.  Without a
TPU, or with fewer chips than the cell asks for, it prints no result
and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

class NoChip(RuntimeError):
    pass


def configure_jax() -> None:
    """Before JAX starts: no fallback to the CPU, and the TPU's logs kept
    off fixed paths."""
    os.environ.setdefault("JAX_PLATFORMS", "tpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def device_check(chips: int) -> dict:
    """Name the chip, or raise NoChip; then turn on the program's own
    compile cache (``JAX_COMPILATION_CACHE_DIR`` where set, else the
    fixed ``.jax_cache/`` in the checkout)."""
    import jax
    from repro import jax_runtime
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"no TPU: {e}") from None
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"no TPU: JAX's backend is {d.platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    jax_runtime.enable_compile_cache()
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def _trace_context(trace_dir: pathlib.Path) -> tuple[dict, dict]:
    """Reduce the window's trace; returns (reduction, device fields).
    A trace with no device op or no ``_fused_step`` program in the
    window fails the run: the reduction would read the wrong lines."""
    from bench import tracereduce
    path = tracereduce.find_xplane(trace_dir)
    if path is None:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    tr = tracereduce.load(path)
    windows = [s for s in tr["spans"] if s[0] == "bench.window"]
    if not windows:
        raise RuntimeError("the trace holds no bench.window span")
    _, lo, hi = windows[0]
    red = tracereduce.reduce(tr, lo, hi)
    if not red["n_ops"]:
        raise RuntimeError(f"no device op on the {tracereduce.OPS_LINE!r} "
                           f"line inside the window")
    red["programs"] = {"_fused_step": tracereduce.program_ns(
        tr["modules"], "_fused_step", lo, hi)}
    if not red["programs"]["_fused_step"][0]:
        raise RuntimeError(f"no _fused_step program on the "
                           f"{tracereduce.MODULES_LINE!r} line inside the "
                           f"window")
    red["devices"] = tr["devices"]
    return red, {"busy_s": red["busy_ns"] * 1e-9,
                 "window_s": red["window_ns"] * 1e-9}


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            device: dict, config: dict | None = None,
            traffic: dict | None = None,
            t_start: float = T_START) -> tuple[dict, dict]:
    """One run of a cell after the device check: set-up, window, check.
    Returns (result line without ``check``, checks).  Tests pass a small
    ``config``/``traffic`` and a described ``device`` to rehearse it."""
    import importlib

    from bench import common
    wl, cfg_file, traffic_file = common.cell(workload)
    config = config or cfg_file
    traffic = traffic or traffic_file
    # the configuration names the module under bench/ that drives it
    harness = importlib.import_module(f"bench.{config['harness']}")
    peaks = common.peaks(device["kind"])
    trace_dir = ROOT / ".bench_trace" / f"{workload}-{seed}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        run_ = harness.run(config, traffic, seed, seconds, trace, t_start,
                           trace_dir=trace_dir)
        dev = dict(device)
        dev["memory_peak_bytes"] = run_.memory_peak_bytes
        if trace:
            red, fields = _trace_context(trace_dir)
            dev.update(fields)
            run_.trace = red
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    harness.report(run_)
    common.log(f"compiles in the window: {run_.compiles_in_window} new "
               f"prediction programs")
    if trace:
        ctx = harness.layer_context(run_, config, peaks)
        metrics = {}
        for m in common.metrics_for(workload, "per_layer"):
            value = common.metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = harness.end_to_end(run_)
        e2e["setup_s"] = run_.setup_s
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in common.metrics_for(workload, "end_to_end")}
    checks = harness.checks(run_, config, seed)
    result = {"correct": common.correct(checks), "attempted": run_.attempted,
              "failed": run_.failed, "metrics": metrics, "device": dev}
    if trace:
        result["breakdown"] = {"device_ops": run_.trace["device_ops"],
                               "idle_gaps": run_.trace["idle_gaps"]}
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import common
    wl, _, _ = common.cell(args.workload)
    configure_jax()
    device = device_check(wl["chips"])
    common.log(f"device: {device}")
    result, checks = execute(args.workload, args.seed, args.seconds,
                             bool(args.trace), device=device)
    common.emit(result, checks)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        sys.exit(2)
