"""Service-boundary telemetry sanitizer.

A multi-tenant daemon shares one batch (and one process) across tenants,
so one malformed snapshot must never poison another tenant's answers or
crash the tick loop.  This is the network-boundary mirror of the
controller-side ``STARTController._sanitize_es`` guard (PR 6): that one
protects the trigger from a degenerate *prediction*; this one protects
the predictor from degenerate *telemetry*.

Two modes, chosen per server (``ServiceConfig.sanitize``):

  * ``"clamp"`` (default): non-finite features -> 0.0 and magnitudes
    clipped to ``FEATURE_CLIP``; non-positive / non-finite durations are
    dropped from ``done`` records.  The snapshot is answered normally
    and the response lists what was repaired under ``"sanitized"``.
  * ``"reject"``: the same conditions fail the snapshot with a
    :class:`TelemetryError` instead of repairing it.

Structural violations — wrong matrix shapes, q outside [1, max_tasks],
task slots outside the matrix, a non-monotonic interval stamp — are
rejected in BOTH modes: there is no meaningful repair, and silently
reordering a tenant's timeline would corrupt its server-side history.
"""
from __future__ import annotations

import math
from itertools import accumulate, chain

import numpy as np

from repro.core import features

#: clamp bound for repaired feature magnitudes (normalized features are
#: O(1); anything huge is garbage but must not overflow float32 math)
FEATURE_CLIP = 1e6

#: the clamp-mode notes a repaired feature block leaves in ``issues``
_ZEROED = "{}: zeroed {} non-finite"
_CLIPPED = "{}: clipped {} oversized"


class TelemetryError(ValueError):
    """A snapshot the service refuses to process; ``code`` is the wire
    error code the tenant gets back."""

    def __init__(self, code: str, message: str):
        super().__init__(message)
        self.code = code


def _clean_block(arr, shape: tuple, what: str, mode: str,
                 issues: list[str]) -> np.ndarray:
    """Shape-check + finite-check one feature block."""
    a = np.asarray(arr, dtype=np.float32)
    if a.size != int(np.prod(shape)):
        raise TelemetryError(
            "bad-shape", f"{what}: expected {shape} "
            f"({int(np.prod(shape))} values), got {a.size}")
    a = a.reshape(shape)
    bad = ~np.isfinite(a)
    if bad.any():
        if mode == "reject":
            raise TelemetryError(
                "bad-telemetry", f"{what}: {int(bad.sum())} non-finite "
                f"feature(s)")
        a = np.where(bad, np.float32(0.0), a)
        issues.append(_ZEROED.format(what, int(bad.sum())))
    big = np.abs(a) > FEATURE_CLIP
    if big.any():
        if mode == "reject":
            raise TelemetryError(
                "bad-telemetry", f"{what}: {int(big.sum())} feature(s) "
                f"beyond +-{FEATURE_CLIP:g}")
        a = np.clip(a, -FEATURE_CLIP, FEATURE_CLIP)
        issues.append(_CLIPPED.format(what, int(big.sum())))
    return a


def _wire_floats(arr, size: int) -> np.ndarray | None:
    """``arr`` as flat float32 in one ``np.fromiter`` pass when it is what
    the wire sends, a flat list of ``size`` numbers; else None.  Element
    for element the same conversion as ``np.asarray(arr, np.float32)``."""
    if type(arr) is not list or len(arr) != size:
        return None
    try:
        return np.fromiter(arr, np.float32, count=size)
    except (TypeError, ValueError, OverflowError):
        return None     # an element np.asarray takes, or refuses, itself


def _job_head(j: dict, max_tasks: int) -> tuple[int, int | float]:
    """A job's id and q, checked."""
    jid = j.get("id")
    if not isinstance(jid, int) or isinstance(jid, bool):
        raise TelemetryError("bad-job", f"non-integer job id {jid!r}")
    q = j.get("q")
    if not isinstance(q, (int, float)) or isinstance(q, bool) \
            or not math.isfinite(q) or not 1 <= q <= max_tasks:
        raise TelemetryError(
            "bad-job", f"job {jid}: q={q!r} outside [1, {max_tasks}]")
    return jid, q


def _job(j: dict, jid: int, q, m_t: np.ndarray, tasks: tuple) -> dict:
    """The sanitized job, once its M_T and tasks are: checks ``open``."""
    open_count = j.get("open", int(q))
    if not isinstance(open_count, int) or isinstance(open_count, bool):
        raise TelemetryError(
            "bad-job", f"job {jid}: non-integer open {open_count!r}")
    return {"id": int(jid), "q": float(q), "m_t": m_t,
            "open": max(0, open_count),
            "deadline": bool(j.get("deadline", False)), "tasks": tasks}


def _jobs_each(wire_jobs, profile, mode: str,
               issues: list[str]) -> list[dict]:
    """The jobs one at a time, each its own numpy blocks: any input that
    is not the wire's plain form, and every snapshot refused."""
    jobs = []
    for j in wire_jobs:
        jid, q = _job_head(j, profile.max_tasks)
        m_t = _clean_block(j.get("m_t", ()),
                           (profile.max_tasks, features.TASK_FEATURES),
                           f"job {jid} m_t", mode, issues)
        tids, hosts, slots = [], [], []
        for ent in j.get("tasks") or ():
            t, h, s = (int(ent[0]), int(ent[1]), int(ent[2]))
            if not 0 <= s < profile.max_tasks:
                raise TelemetryError(
                    "bad-job", f"job {jid}: task {t} slot {s} outside "
                    f"[0, {profile.max_tasks})")
            tids.append(t)
            hosts.append(h)
            slots.append(s)
        jobs.append(_job(j, jid, q, m_t,
                         (np.asarray(tids, np.int64),
                          np.asarray(hosts, np.int64),
                          np.asarray(slots, np.int64))))
    return jobs


def _jobs_bulk(wire_jobs, profile,
               mode: str) -> tuple[list[dict], list[str]] | None:
    """The jobs of a wire snapshot in one conversion: every flat M_T list
    into one ``(jobs, max_tasks, TASK_FEATURES)`` float32 block, checked
    once, and every ``[task, host, slot]`` into one int64 array; each job
    takes its row and its slices.

    Returns ``(jobs, issues)``, or None where the jobs are not the wire's
    plain form (dicts with flat M_T lists of the profile's size, tasks as
    lists of three ints) or anything in them is refused: ``_jobs_each``
    then decides, so both paths accept, repair and refuse alike."""
    if type(wire_jobs) is not list:
        return None
    mt_shape = (profile.max_tasks, features.TASK_FEATURES)
    per = mt_shape[0] * mt_shape[1]
    heads, mts, ents, counts = [], [], [], []
    try:
        for j in wire_jobs:
            if type(j) is not dict:
                return None
            m_t, tasks = j.get("m_t"), j.get("tasks", [])
            if type(m_t) is not list or len(m_t) != per \
                    or type(tasks) is not list:
                return None
            heads.append(_job_head(j, profile.max_tasks))
            mts.append(m_t)
            ents += tasks
            counts.append(len(tasks))
    except TelemetryError:
        return None
    if ents and (set(map(type, ents)) != {list}
                 or set(map(len, ents)) != {3}
                 or set(map(type, chain.from_iterable(ents))) != {int}):
        return None
    try:
        block = np.fromiter(chain.from_iterable(mts), np.float32,
                            count=len(mts) * per).reshape(-1, *mt_shape)
        cols = np.fromiter(chain.from_iterable(ents), np.int64,
                           count=3 * len(ents)).reshape(-1, 3).T.copy()
    except (TypeError, ValueError, OverflowError):
        return None     # an element _jobs_each takes, or refuses, itself
    if not ((cols[2] >= 0) & (cols[2] < profile.max_tasks)).all():
        return None
    issues: list[str] = []
    if not (np.abs(block) <= FEATURE_CLIP).all():   # NaN compares False
        if mode == "reject":
            return None
        bad = ~np.isfinite(block)
        block = np.where(bad, np.float32(0.0), block)
        big = np.abs(block) > FEATURE_CLIP
        block = np.clip(block, -FEATURE_CLIP, FEATURE_CLIP)
        for (jid, _), n_bad, n_big in zip(heads,
                                          bad.sum(axis=(1, 2)).tolist(),
                                          big.sum(axis=(1, 2)).tolist()):
            if n_bad:
                issues.append(_ZEROED.format(f"job {jid} m_t", n_bad))
            if n_big:
                issues.append(_CLIPPED.format(f"job {jid} m_t", n_big))
    bounds = list(accumulate(counts, initial=0))
    try:
        jobs = [_job(j, jid, q, m_t, (cols[0, lo:hi], cols[1, lo:hi],
                                      cols[2, lo:hi]))
                for j, (jid, q), m_t, lo, hi in zip(
                    wire_jobs, heads, block, bounds, bounds[1:])]
    except TelemetryError:
        return None
    return jobs, issues


def sanitize_snapshot(snap: dict, profile, last_seq: float,
                      mode: str = "clamp",
                      counters: dict | None = None) -> dict:
    """Validate + repair one snapshot request against a tenant profile.

    Returns ``{"seq", "m_h", "jobs", "done", "issues"}`` with numpy
    feature blocks, or raises :class:`TelemetryError`.  ``jobs`` entries
    are ``{"id", "q", "m_t", "open", "deadline", "tasks"}`` with
    ``tasks`` as ``(tids, hosts, slots)`` int arrays.

    A snapshot in the wire's plain form (``policy.wire``: flat float
    lists, tasks as three ints) is converted in bulk, a few numpy calls
    whatever its job count; any other input one block per job.  Both
    give the same arrays and issues, and refuse with the same error.
    Where ``counters`` is given, its ``"sanitize_bulk"`` counts the
    snapshots converted in bulk.
    """
    issues: list[str] = []
    seq = snap.get("seq")
    if not isinstance(seq, (int, float)) or isinstance(seq, bool) \
            or not math.isfinite(seq):
        raise TelemetryError("bad-seq", f"non-numeric seq {seq!r}")
    if seq <= last_seq:
        raise TelemetryError(
            "out-of-order", f"seq {seq} <= last processed {last_seq}")
    raw = snap.get("m_h", ())
    flat = _wire_floats(raw, profile.n_hosts * features.HOST_FEATURES)
    m_h = _clean_block(raw if flat is None else flat,
                       (profile.n_hosts, features.HOST_FEATURES),
                       "m_h", mode, issues)
    wire_jobs = snap.get("jobs") or []
    bulk = _jobs_bulk(wire_jobs, profile, mode)
    if bulk is None:
        jobs = _jobs_each(wire_jobs, profile, mode, issues)
    else:
        jobs, notes = bulk
        issues += notes
    done = []
    for d in snap.get("done") or ():
        did = d.get("id")
        if not isinstance(did, int) or isinstance(did, bool):
            raise TelemetryError("bad-done",
                                 f"non-integer done id {did!r}")
        times = np.asarray(d.get("times", ()), np.float32)
        bad = (~np.isfinite(times)) | (times <= 0.0)
        if bad.any():
            if mode == "reject":
                raise TelemetryError(
                    "bad-telemetry", f"done {did}: {int(bad.sum())} "
                    f"non-positive/non-finite duration(s)")
            issues.append(f"done {did}: dropped {int(bad.sum())} "
                          f"bad duration(s)")
            times = times[~bad]
        if times.size:
            done.append({"id": int(did), "times": times})
        elif mode == "clamp":
            issues.append(f"done {did}: dropped (no valid durations)")
    if counters is not None and flat is not None and bulk is not None:
        counters["sanitize_bulk"] += 1
    return {"seq": float(seq), "m_h": m_h, "jobs": jobs, "done": done,
            "issues": issues}
