"""The program-span reduction: count, total and self time of each span,
and the device's idle time split over the spans by overlap, on hand-made
intervals and on a slice of a v5e trace that holds the program's spans
(``data/v5e_program_spans_slice.json.gz``); and ``tracereduce.reduce``
still reads the older slice exactly as it did."""
from __future__ import annotations

import gzip
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import programspans as ps  # noqa: E402
from bench import tracereduce as tr  # noqa: E402

DATA = pathlib.Path(__file__).parent / "data"
# one thread: A holds B and D, B holds C
SPANS = [("A", 0, 100), ("B", 10, 50), ("C", 20, 30), ("D", 60, 70)]


def test_self_time_is_time_no_child_covers():
    st = ps.span_stats(SPANS, 0, 100)
    assert {n: (s["count"], s["total_ns"], s["self_ns"])
            for n, s in st.items()} == {
        "A": (1, 100, 50), "B": (1, 40, 30), "C": (1, 10, 10),
        "D": (1, 10, 10)}
    assert ps.self_pieces(SPANS) == [("A", 0, 10), ("B", 10, 20),
                                     ("C", 20, 30), ("B", 30, 50),
                                     ("A", 50, 60), ("D", 60, 70),
                                     ("A", 70, 100)]


def test_span_stats_cut_to_the_window():
    st = ps.span_stats(SPANS, 15, 65)
    assert {n: (s["count"], s["total_ns"], s["self_ns"])
            for n, s in st.items()} == {
        "A": (1, 50, 10), "B": (1, 35, 25), "C": (1, 10, 10),
        "D": (1, 5, 5)}
    assert "D" not in ps.span_stats(SPANS, 0, 60)


def test_threads_are_reduced_apart():
    spans = [("A", 0, 100, 0), ("B", 10, 50, 0), ("A", 0, 100, 1),
             ("C", 40, 60, 1)]
    st = ps.span_stats(spans, 0, 100)
    assert st["A"] == {"count": 2, "total_ns": 200, "self_ns": 140}
    assert ps.main_thread(spans + [("E", 1, 2, 1)]) == 1


def test_idle_is_split_by_overlap_not_by_midpoint():
    gaps = [(5, 25), (62, 65), (95, 120)]
    got = ps.idle_by_span(gaps, SPANS)
    # the first gap crosses A, B and C
    assert got == {"A": 5 + 5, "B": 10, "C": 5, "D": 3, "none": 20}
    assert sum(got.values()) == sum(b - a for a, b in gaps)
    # the midpoint rule names the whole first gap after B alone
    assert tr.attribute([(5, 25)], SPANS) == {"B": 20}


def test_readings_per_interval():
    ns = 1e6
    stats = {n: {"count": 2, "total_ns": 2 * ns, "self_ns": ns}
             for names in ps.LAYERS.values() for n in names}
    got = ps.readings(stats, {"predictor.readback": 3 * ns}, 2,
                      {"rows_real": 30, "rows_dispatched": 40})
    assert got["predict_host_ms.sim"] == pytest.approx(1.5)
    assert got["readback_wait_ms.sim"] == pytest.approx(0.5)
    assert got["readback_idle_ms.sim"] == pytest.approx(1.5)
    assert got["engine_sched_ms.sim"] == pytest.approx(2.0)
    assert got["pad_rows_share.sim"] == pytest.approx(25.0)
    assert ps.readings({}, {}, 2, {"rows_real": 0,
                                   "rows_dispatched": 0}) == {}


def test_predictor_time_by_batch_size():
    args = [{"n": 3, "nb": 4}, {"n": 2, "nb": 4}, {"n": 9, "nb": 16}]
    spans = []
    for k, a in enumerate(args):
        t0 = k * 10_000_000
        spans += [("predictor.interval", t0, t0 + 4e6, 0, a),
                  ("predictor.dispatch", t0, t0 + 1e6 * (k + 1), 0, a),
                  ("predictor.readback", t0 + 3e6, t0 + 4e6, 0, a),
                  ("sim.step", t0, t0 + 5e6, 0, {"t": k})]
    got = ps.by_bucket(spans, 0, 25_000_000)
    assert got == {
        4: {"calls": 2, "n_mean": 2.5, "predictor.interval": 4.0,
            "predictor.dispatch": 1.5, "predictor.readback": 1.0},
        16: {"calls": 1, "n_mean": 9.0, "predictor.interval": 4.0,
             "predictor.dispatch": 3.0, "predictor.readback": 1.0}}
    assert list(ps.by_bucket(spans, 0, 15_000_000)) == [4]
    assert "nb" in ps.bucket_table(got)


def _slice(name):
    with gzip.open(DATA / name, "rt") as f:
        return json.load(f)


def test_reduce_reads_the_older_slice_as_before():
    sl = _slice("v5e_trace_slice.json.gz")
    lo, hi = sl["window"]
    want = json.loads((DATA / "v5e_trace_slice.reduce.json").read_text())
    assert tr.reduce(sl, lo, hi) == want


def test_reduce_the_program_spans_of_a_slice_recorded_on_the_chip():
    """Four whole intervals of a traced ``planetlab-400.start`` window on
    one v5e: 19 program spans an interval on one thread; self times sum
    to the roots, and the overlap split sums to the window's idle time."""
    sl = _slice("v5e_program_spans_slice.json.gz")
    lo, hi = sl["window"]
    spans = [tuple(sp) for sp in sl["program_spans"]]
    assert len(spans) == 4 * 19 and ps.main_thread(spans) == spans[0][3]
    assert len({sp[4]["t"] for sp in spans if sp[0] == "sim.step"}) == 4
    st = ps.span_stats(spans, lo, hi)
    assert {n: s["count"] for n, s in st.items()} == {
        n: 4 for n in {sp[0] for sp in spans}}
    roots = sum(sp[2] - sp[1] for sp in spans if sp[0] == "sim.step")
    assert sum(s["self_ns"] for s in st.values()) == pytest.approx(roots)
    assert st["predictor.readback"]["self_ns"] == \
        st["predictor.readback"]["total_ns"]
    merged = tr.merge([o for o in sl["ops"] if o[2] > lo and o[1] < hi])
    idle = ps.idle_by_span(tr.gaps(merged, lo, hi), spans)
    assert sum(idle.values()) == pytest.approx(
        (hi - lo) - tr.busy_ns(merged, lo, hi))
    assert set(idle) <= set(st) | {"none"}
    buckets = ps.by_bucket(spans, lo, hi)
    assert sum(b["calls"] for b in buckets.values()) == \
        st["predictor.interval"]["count"]
    counts = {"rows_real": sum(b["calls"] * b["n_mean"]
                               for b in buckets.values()),
              "rows_dispatched": sum(b["calls"] * nb
                                     for nb, b in buckets.items())}
    got = ps.readings(st, idle, 4, counts)
    assert set(got) == set(ps.LAYERS) | set(ps.IDLE) | {"pad_rows_share.sim"}
    assert 0 < got["readback_idle_ms.sim"] <= got["readback_wait_ms.sim"]
