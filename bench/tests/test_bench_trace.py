"""The reduction from a profiler trace to busy time, idle share, program
time and idle gaps by host span, on hand-made intervals and on a slice
of a trace recorded on a TPU v5e (``data/v5e_trace_slice.json.gz``)."""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import tracereduce as tr  # noqa: E402


def test_union_busy_and_gaps_by_hand():
    ops = [("a", 10, 20), ("b", 15, 30), ("c", 40, 50), ("d", 45, 48),
           ("e", 90, 120)]
    merged = tr.merge(ops)
    assert merged == [(10, 30), (40, 50), (90, 120)]
    assert tr.busy_ns(merged, 0, 100) == 20 + 10 + 10
    assert tr.gaps(merged, 0, 100) == [(0, 10), (30, 40), (50, 90)]
    assert tr.gaps(merged, 12, 45) == [(30, 40)]


def test_gaps_are_named_by_the_innermost_open_span():
    spans = [("bench.step", 0, 100), ("bench.decide", 20, 60),
             ("bench.predict", 30, 50), ("bench.step", 100, 200)]
    got = tr.attribute([(0, 10), (35, 45), (52, 58), (70, 80),
                        (150, 160), (205, 215)], spans)
    assert got == {"bench.step": 30, "bench.predict": 10,
                   "bench.decide": 6, "none": 10}


def test_reduce_ranks_ops_and_gaps():
    trace = {"ops": [("dot", 0, 10), ("add", 10, 12), ("dot", 50, 60)],
             "modules": [("jit__fused_step", 0, 12),
                         ("jit__fused_step", 50, 60)],
             "spans": [("bench.window", 0, 100), ("bench.step", 0, 100)]}
    red = tr.reduce(trace, 0, 100)
    assert red["busy_ns"] == 22 and red["window_ns"] == 100
    assert red["device_ops"] == [["dot", 20e-9], ["add", 2e-9]]
    assert red["idle_gaps"][0][0] == "bench.step"
    assert red["idle_gaps"][0][1] == pytest.approx(78e-9)
    assert tr.program_ns(trace["modules"], "_fused_step", 0, 100) == (2, 22.0)
    assert tr.program_ns(trace["modules"], "_fused_step", 5, 55) == (2, 12.0)
    assert tr.program_ns(trace["modules"], "_fused_step", 20, 40) == (0, 0.0)


def test_load_reads_the_benchmark_spans_of_a_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(tr.find_xplane(tmp_path))
    names = [s[0] for s in t["spans"]]
    assert names.count("bench.window") == 1 and names.count("bench.step") == 3
    (_, lo, hi), = [s for s in t["spans"] if s[0] == "bench.window"]
    assert all(lo <= s[1] <= s[2] <= hi for s in t["spans"])
    red = tr.reduce(t, lo, hi)
    assert red["window_ns"] == hi - lo and red["busy_ns"] <= hi - lo


def _chip_slice():
    import gzip
    import json
    path = pathlib.Path(__file__).parent / "data" / "v5e_trace_slice.json.gz"
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_reduce_a_slice_recorded_on_the_chip():
    """12 ms of a traced ``planetlab-400.start`` window on one v5e: the
    device's ops and programs, and the benchmark's host spans."""
    sl = _chip_slice()
    lo, hi = sl["window"]
    red = tr.reduce(sl, lo, hi)
    # busy time by brute force: every nanosecond boundary between events
    edges = sorted({lo, hi} | {min(max(t, lo), hi) for _, s, e in sl["ops"]
                               for t in (s, e)})
    busy = sum(b - a for a, b in zip(edges, edges[1:])
               if any(s <= a and b <= e for _, s, e in sl["ops"]))
    assert red["busy_ns"] == pytest.approx(busy)
    assert 0 < red["busy_ns"] < red["window_ns"] == hi - lo
    assert red["n_ops"] == len(sl["ops"])
    idle = sum(ns for _, ns in red["idle_gaps"]) * 1e9
    assert idle == pytest.approx(red["window_ns"] - red["busy_ns"])
    assert all(name.startswith(tr.SPAN_PREFIX) for name, _ in red["idle_gaps"])
    calls, ns = tr.program_ns(sl["modules"], "_fused_step", lo, hi)
    assert calls >= 1 and 0 < ns <= hi - lo
