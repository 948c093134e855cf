"""From a JAX profiler trace to the numbers the per-layer metrics read.

Device busy time is the union of the intervals in which an operation
ran on a device (the ``XLA Ops`` line of each device plane), clipped to
the traced window.  A program's device time is the sum of its events on
the ``XLA Modules`` line, clipped to the same window.  Each idle gap is named by the innermost
benchmark span (``bench.*``, a ``jax.profiler.TraceAnnotation``) open
on the host at the gap's midpoint.
"""
from __future__ import annotations

import bisect
import collections
import pathlib

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir) -> pathlib.Path | None:
    files = sorted(pathlib.Path(trace_dir).glob("**/*.xplane.pb"))
    return files[-1] if files else None


def load(path) -> dict:
    """``{"ops": [...], "modules": [...], "spans": [...], "devices": n}``
    with every entry ``(name, start_ns, end_ns)`` on the host's clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    ops, modules, spans = [], [], []
    devices = 0
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            got = False
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dst = ops
                elif line.name == MODULES_LINE:
                    dst = modules
                else:
                    continue
                for ev in line.events:
                    dst.append((ev.name, ev.start_ns, ev.end_ns))
                    got = True
            devices += got
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
    return {"ops": ops, "modules": modules, "spans": spans,
            "devices": devices}


def merge(intervals) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` (or ``(name, start, end)``) intervals."""
    out: list[list[float]] = []
    for iv in sorted((iv[-2], iv[-1]) for iv in intervals):
        s, e = iv
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(merged, lo: float, hi: float) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of ``[lo, hi]`` between merged busy intervals."""
    out = []
    cur = lo
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def attribute(gap_list, spans) -> dict[str, float]:
    """Idle nanoseconds by the innermost span open at each gap's
    midpoint (``"none"`` where no span is open).  Spans of one thread
    nest, so a stack swept in time order finds the innermost one."""
    by_start = sorted(spans, key=lambda s: (s[1], -s[2]))
    starts = [s[1] for s in by_start]
    total: dict[str, float] = collections.defaultdict(float)
    stack: list[tuple] = []
    i = 0
    for g0, g1 in sorted(gap_list):
        mid = 0.5 * (g0 + g1)
        j = bisect.bisect_right(starts, mid)
        while i < j:
            sp = by_start[i]
            while stack and stack[-1][2] <= sp[1]:
                stack.pop()
            stack.append(sp)
            i += 1
        while stack and stack[-1][2] <= mid:
            stack.pop()
        total[stack[-1][0] if stack else "none"] += g1 - g0
    return dict(total)


def program_ns(modules, name_part: str, lo: float,
               hi: float) -> tuple[int, float]:
    """(events, ns) of the device programs whose name holds
    ``name_part``, clipped to the window ``[lo, hi]``."""
    hits = [min(e, hi) - max(s, lo) for n, s, e in modules
            if name_part in n and e > lo and s < hi]
    return len(hits), float(sum(hits))


def top(totals: dict[str, float], n: int = 10) -> list[list]:
    """The ``n`` largest entries as ``[[name, seconds], ...]``."""
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9] for name, ns in ranked]


def reduce(trace: dict, lo: float, hi: float) -> dict:
    """Busy and idle time of the window ``[lo, hi]`` (ns), the device
    ops that took most time, and the idle time by host span."""
    ops = [o for o in trace["ops"] if o[2] > lo and o[1] < hi]
    merged = merge(ops)
    busy = busy_ns(merged, lo, hi)
    op_totals: dict[str, float] = collections.defaultdict(float)
    for name, s, e in ops:
        op_totals[name] += min(e, hi) - max(s, lo)
    idle = attribute(gaps(merged, lo, hi), trace["spans"])
    return {"window_ns": hi - lo, "busy_ns": busy,
            "device_ops": top(op_totals), "idle_gaps": top(idle),
            "n_ops": len(ops)}
