#!/usr/bin/env python3
"""The program's own spans in a profiler trace: what each layer of
START's decision path took, and what the host was doing while the device
sat idle.

``bench.tracereduce`` reads the device planes and the benchmark's
``bench.*`` spans.  This module reads the spans the program writes
itself (``repro.trace``: ``sim.*``, ``start.*``, ``predictor.*``; the
engine's and the policy's with the interval ``t``, the predictor's with
``n`` and ``nb``), and gives each span name's count, total and self time
in a window, and the device's idle time split over the spans by overlap:
each idle nanosecond goes to the innermost program span open at that
nanosecond, so a gap that crosses three spans is split three ways.

    python3 bench/programspans.py --workload <cell> --seed <n> \
        --seconds <s>

runs one traced window of a simulator cell on the chip, as
``bench/run.py --trace 1`` sets it up, logs the idle time by program
span and the predictor's time by batch size, and prints one JSON line:
the layer readings of ``readings``, the predictor's counters over the
window, the outside metrics the readings should agree with, and the
traced window's ``decision_ms_p95``.  Without a TPU it exits 2.
"""
from __future__ import annotations

import bisect
import collections
import pathlib
import sys
import time

T_START = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parent.parent
PREFIXES = ("sim.", "start.", "predictor.")

# reading -> the spans whose self time it sums, per interval
LAYERS = {
    "predict_host_ms.sim": ("predictor.sync_ring", "predictor.pack",
                            "predictor.dispatch"),
    "readback_wait_ms.sim": ("predictor.readback",),
    "features_ms.sim": ("start.host_features", "start.task_features"),
    "trigger_ms.sim": ("start.trigger", "start.guard"),
    "engine_sched_ms.sim": ("sim.arrivals", "sim.submit", "sim.place",
                            "sim.faults"),
    "engine_advance_ms.sim": ("sim.progress", "sim.record"),
}
# reading -> the span whose share of the device's idle time it is
IDLE = {"readback_idle_ms.sim": "predictor.readback"}
# the predictor's counters read over the window
COUNTERS = ("fused_calls", "catchup_rolls", "ring_rebuilds", "rows_real",
            "rows_dispatched")


def load(path) -> list[tuple]:
    """``(name, start_ns, end_ns, thread, args)`` of every program span
    on the host planes of an ``.xplane.pb``; ``thread`` numbers the host
    lines (one per thread) and ``args`` are the span's stats."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    out = []
    thread = 0
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    out.append((ev.name, ev.start_ns, ev.end_ns, thread,
                                dict(ev.stats)))
            thread += 1
    return out


def _by_thread(spans) -> dict:
    groups: dict = collections.defaultdict(list)
    for sp in spans:
        groups[sp[3] if len(sp) > 3 else None].append(sp)
    return groups


def _clip(spans, lo: float, hi: float) -> list[tuple]:
    """Spans that start inside ``[lo, hi)`` or straddle ``lo``, cut to
    the window (nesting survives the cut)."""
    return [(sp[0], max(sp[1], lo), min(sp[2], hi)) + tuple(sp[3:])
            for sp in spans if lo <= sp[1] < hi or sp[1] < lo < sp[2]]


def self_pieces(spans) -> list[tuple[str, float, float]]:
    """``(name, start, end)`` pieces of each span's time that none of its
    children covers, in time order.  ``spans`` are ``(name, start, end,
    ...)`` of one thread, which nest."""
    out: list[tuple[str, float, float]] = []
    stack: list[list] = []       # [name, end, time its own piece resumes]

    def close(top):
        name, end, cur = top
        if end > cur:
            out.append((name, cur, end))
        if stack:
            stack[-1][2] = end

    for sp in sorted(spans, key=lambda s: (s[1], -s[2])):
        name, s, e = sp[0], sp[1], sp[2]
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack and s > stack[-1][2]:
            out.append((stack[-1][0], stack[-1][2], s))
        stack.append([name, e, s])
    while stack:
        close(stack.pop())
    return sorted(out, key=lambda p: p[1])


def span_stats(spans, lo: float, hi: float) -> dict[str, dict]:
    """``{name: {"count", "total_ns", "self_ns"}}`` of the spans in the
    window ``[lo, hi]``, cut to it.  Self time is a span's time less what
    its children on the same thread cover."""
    stats: dict[str, dict] = {}
    for group in _by_thread(_clip(spans, lo, hi)).values():
        for sp in group:
            st = stats.setdefault(sp[0], {"count": 0, "total_ns": 0.0,
                                          "self_ns": 0.0})
            st["count"] += 1
            st["total_ns"] += sp[2] - sp[1]
        for name, s, e in self_pieces(group):
            stats[name]["self_ns"] += e - s
    return stats


def idle_by_span(gap_list, spans) -> dict[str, float]:
    """Idle nanoseconds of ``gap_list`` by the innermost span open over
    each part of each gap (``"none"`` where no span is open): a gap is
    split by overlap, never named after one instant.  ``spans`` are of
    one thread."""
    pieces = self_pieces(spans)
    ends = [p[2] for p in pieces]
    total: dict[str, float] = collections.defaultdict(float)
    for g0, g1 in gap_list:
        covered = 0.0
        k = bisect.bisect_right(ends, g0)
        while k < len(pieces) and pieces[k][1] < g1:
            name, s, e = pieces[k]
            part = min(e, g1) - max(s, g0)
            total[name] += part
            covered += part
            k += 1
        if g1 - g0 > covered:
            total["none"] += g1 - g0 - covered
    return dict(total)


def main_thread(spans):
    """The thread that wrote the most program spans (the decision
    path's)."""
    counts = collections.Counter(sp[3] for sp in spans)
    return counts.most_common(1)[0][0] if counts else None


def counters(pred) -> dict[str, int]:
    """The predictor's ``COUNTERS`` now."""
    return {name: getattr(pred, name) for name in COUNTERS}


def by_bucket(spans, lo: float, hi: float) -> dict[int, dict]:
    """Per dispatched batch size ``nb``: the ``predictor.interval`` calls
    that start in the window, their mean real rows ``n``, and the mean ms
    of each ``predictor.*`` span, from the spans' own ``n`` and ``nb``:
    whether the dispatch and the readback grow with the batch or stay one
    round trip."""
    sums: dict = collections.defaultdict(
        lambda: collections.defaultdict(float))
    for sp in spans:
        if sp[0].startswith("predictor.") and lo <= sp[1] < hi:
            b = sums[int(sp[4]["nb"])]
            b[sp[0]] += (sp[2] - sp[1]) * 1e-6
            if sp[0] == "predictor.interval":
                b["calls"] += 1
                b["n"] += sp[4]["n"]
    out = {}
    for nb, b in sorted(sums.items()):
        calls = b.pop("calls", 0)
        if calls:
            out[nb] = {"calls": int(calls), "n_mean": b.pop("n") / calls,
                       **{name: ms / calls for name, ms in b.items()}}
    return out


def readings(stats: dict, idle: dict, intervals: int,
             counts: dict) -> dict:
    """The layer readings: ms per interval of each group of spans in
    ``LAYERS`` (self time), of idle device time inside each span in
    ``IDLE``, and the share of dispatched rows that were padding (%),
    from the predictor's ``rows_real`` and ``rows_dispatched`` over the
    window (``counts``).  A reading with nothing to read is left out."""
    out = {}
    per = 1e-6 / max(intervals, 1)
    for key, names in LAYERS.items():
        if any(n in stats for n in names):
            out[key] = sum(stats[n]["self_ns"] for n in names
                           if n in stats) * per
    for key, name in IDLE.items():
        if name in stats:
            out[key] = idle.get(name, 0.0) * per
    dispatched = counts.get("rows_dispatched", 0)
    if dispatched:
        out["pad_rows_share.sim"] = (
            100.0 * (dispatched - counts["rows_real"]) / dispatched)
    return out


def idle_table(idle: dict) -> str:
    total = sum(idle.values()) or 1.0
    lines = ["device idle by program span (split by overlap):"]
    for name, ns in sorted(idle.items(), key=lambda kv: -kv[1]):
        lines.append(f"  {name:<24} {ns * 1e-9:10.6f} s "
                     f"{100.0 * ns / total:6.2f}%")
    return "\n".join(lines)


def bucket_table(buckets: dict) -> str:
    names = ("interval", "sync_ring", "pack", "dispatch", "readback")
    lines = ["predictor ms per call by batch size:",
             f"  {'nb':>5} {'calls':>6} {'n_mean':>7} "
             + " ".join(f"{k:>9}" for k in names)]
    for nb, b in buckets.items():
        lines.append(
            f"  {nb:5d} {b['calls']:6d} {b['n_mean']:7.1f} "
            + " ".join(f"{b.get('predictor.' + k, 0.0):9.4f}"
                       for k in names))
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse
    import json
    import shutil

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import common, run, simcell, tracereduce
    wl, config, traffic = common.cell(args.workload)
    if config["harness"] != "simcell":
        raise SystemExit(f"{args.workload} is not a simulator cell")
    run.configure_jax()
    device = run.device_check(wl["chips"])
    common.log(f"device: {device}")
    # the warm-up is the last use of the predictor before the window:
    # note it and its counters there
    box = {}
    warm = simcell.warm

    def warm_then_count(pred, max_jobs):
        shapes = warm(pred, max_jobs)
        box["pred"], box["before"] = pred, counters(pred)
        return shapes

    simcell.warm = warm_then_count
    trace_dir = ROOT / ".bench_trace" / f"spans-{args.workload}-{args.seed}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        run_ = simcell.run(config, traffic, args.seed, args.seconds, True,
                           T_START, trace_dir=trace_dir)
        path = tracereduce.find_xplane(trace_dir)
        trace = tracereduce.load(path)
        spans = load(path)
    finally:
        simcell.warm = warm
        shutil.rmtree(trace_dir, ignore_errors=True)
    after = counters(box["pred"])
    counts = {k: after[k] - box["before"][k] for k in COUNTERS}
    (_, lo, hi), = [s for s in trace["spans"] if s[0] == "bench.window"]
    merged = tracereduce.merge([o for o in trace["ops"]
                                if o[2] > lo and o[1] < hi])
    thread = main_thread(spans)
    main_spans = [sp for sp in _clip(spans, lo, hi) if sp[3] == thread]
    stats = span_stats(spans, lo, hi)
    idle = idle_by_span(tracereduce.gaps(merged, lo, hi), main_spans)
    buckets = by_bucket(spans, lo, hi)
    common.log(idle_table(idle))
    common.log(bucket_table(buckets))
    simcell.report(run_)
    got = readings(stats, idle, run_.intervals, counts)
    ctx = simcell.layer_context(run_, config, common.peaks(device["kind"]))
    outside = {m: common.metric_reader(m)(ctx) for m in
               ("predict_ms.sim", "engine_ms.sim", "controller_host_ms.sim")}
    per = 1e-6 / run_.intervals

    def ms(name):
        return stats.get(name, {}).get("total_ns", 0.0) * per

    interval_ms = ms("predictor.interval")
    agree = {
        "predictor_interval_ms": interval_ms,
        "predictor_interval_over_predict_ms": (
            interval_ms / outside["predict_ms.sim"]),
        "children_over_predictor_interval": (
            (got["predict_host_ms.sim"] + got["readback_wait_ms.sim"])
            / interval_ms),
        "step_less_policy_ms": ms("sim.step") - ms("sim.policy"),
        "step_less_policy_over_engine_ms": (
            (ms("sim.step") - ms("sim.policy")) / outside["engine_ms.sim"]),
        # every fused call of the window was traced
        "fused_calls_over_interval_spans": (
            counts["fused_calls"]
            / max(stats.get("predictor.interval", {}).get("count", 0), 1)),
    }
    busy = tracereduce.busy_ns(merged, lo, hi)
    out = {"device": device, "intervals": run_.intervals,
           "readings": got, "counters": counts, "outside": outside,
           "agree": agree, "traced": simcell.end_to_end(run_),
           "idle_share": 1.0 - busy / (hi - lo),
           "spans": {n: st for n, st in sorted(stats.items())},
           "idle_by_span_s": {n: ns * 1e-9 for n, ns in idle.items()},
           "by_bucket": buckets}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run as _run
    try:
        sys.exit(main())
    except _run.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        sys.exit(2)
