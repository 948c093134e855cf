"""Service cells: many tenants answered by one ``PredictionService``.

Set-up installs the configuration's weights (the fixed init seed, as
``simcell`` does) into a service with the configuration's settings,
records the tenants' telemetry (the traffic file's pool of
``Simulation`` episodes of the configuration's fleet, in an order drawn
from the seed, under technique ``none``, each interval turned
into the wire snapshot a tenant sends: M_H and M_T from the numpy twins
START's controller uses, the jobs with their open counts, deadlines and
tasks, and the jobs done with their times), and warms every batch shape
the replay reaches.  The window then runs a closed loop: every tenant
submits its next interval as soon as its answer is back, and the loop
submits all of them, then calls ``tick`` as the daemon's batch worker
does.  A tenant whose episode ends says ``bye``, ``hello`` again and
starts the next episode.  The JSON-lines transport is outside the timed
path: snapshots are the decoded wire dicts ``ServiceDaemon`` hands to
``submit``.

The check keeps, for a seeded sample of ticks and tenants, the sanitized
host window each was answered on, its M_T, q and k and the E_S it got,
and compares them with ``bench.reference`` as ``simcell`` does.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import itertools
import time
from unittest import mock

import numpy as np

from bench import common, flops, programspans, reference, tenantflops, \
    tracereduce, weights
from bench.simcell import episode_seeds, ratio_checks, sim_config

#: the service's counters read over the window
COUNTERS = ("tenant_calls", "tenant_rows_real", "tenant_rows_dispatched",
            "tenant_slot_rebuilds", "tenant_stage_bytes")


def _span(trace: bool, name: str):
    if not trace:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def _tap_class():
    from repro.policy import EVENT_INTERVAL, Policy
    from repro.policy import wire
    from repro.sim.techniques import start_tech

    class Tap(Policy):
        """Technique ``none`` that keeps each interval as the wire
        snapshot its cluster's tenant would send."""

        submit_hook = False

        def __init__(self):
            self.snaps: list[dict] = []
            self._done = 0

        def decide(self, view):
            if view.event != EVENT_INTERVAL:
                return []
            jobs_t = view.jobs
            active = jobs_t.active()
            mts = start_tech._task_matrices(view, active)
            jobs = []
            for j, mt in zip(active.tolist(), mts):
                start = int(jobs_t.start[j])
                tasks = [(i, int(view.tasks.host[i]), i - start)
                         for i in jobs_t.incomplete_tasks(j).tolist()]
                jobs.append(wire.job_to_wire(
                    j, int(jobs_t.count[j]), mt,
                    open_count=int(jobs_t.open_count[j]),
                    deadline=bool(jobs_t.deadline[j]), tasks=tasks))
            done = [{"id": r["job"], "times": r["times"]}
                    for r in view.completed_jobs[self._done:]]
            self._done = len(view.completed_jobs)
            self.snaps.append(wire.snapshot_to_wire(
                "", view.t, start_tech._host_matrix(view), jobs, done))
            return []

    return Tap


def episodes(traffic: dict, seed: int) -> list[int]:
    """The simulator seeds of the traffic file's pool of episodes, in an
    order drawn from ``seed``.  Every seed replays the same episodes at
    the same offsets (``Replay`` gives each episode every offset), so
    every seed does the same work; fresh episodes per seed moved the
    mean job rows a tick by 8% between seeds."""
    pool = traffic["episodes"]
    return list(itertools.islice(episode_seeds(traffic, seed), len(pool)))


def record(config: dict, seeds: list[int]) -> list[list[dict]]:
    """An episode of the configuration's fleet for each simulator seed:
    one list of wire snapshots each."""
    from repro.sim.engine import Simulation
    tap_cls = _tap_class()
    out = []
    for s in seeds:
        tap = tap_cls()
        sim = Simulation(sim_config(config, {}, s), technique=tap)
        for _ in range(sim.cfg.n_intervals):
            sim.step()
        out.append(tap.snaps)
    return out


class Replay:
    """Where each tenant is in the recorded episodes: tenant ``i`` starts
    episode ``i mod E`` at interval ``stride * (i // E)`` and moves on to
    the next episode when one ends."""

    def __init__(self, streams: list, tenants: int, stride: int):
        self.streams = streams
        e = len(streams)
        self.ep = [i % e for i in range(tenants)]
        self.pos = [stride * (i // e) % len(streams[0])
                    for i in range(tenants)]

    def next(self, i: int) -> tuple[dict, bool]:
        """Tenant ``i``'s next snapshot, and whether it starts an
        episode (so the tenant says ``bye`` and ``hello`` first)."""
        new = self.pos[i] == len(self.streams[self.ep[i]])
        if new:
            self.ep[i] = (self.ep[i] + 1) % len(self.streams)
            self.pos[i] = 0
        snap = self.streams[self.ep[i]][self.pos[i]]
        self.pos[i] += 1
        return snap, new


def reachable_rows(streams: list, tenants: int, stride: int) -> set[int]:
    """Every tick's job rows over one whole cycle of the replay (it
    repeats after that)."""
    rep = Replay(streams, tenants, stride)
    ticks = sum(len(s) for s in streams) + len(streams[0])
    return {sum(len(rep.next(i)[0]["jobs"]) for i in range(tenants))
            for _ in range(ticks)}


def warm(svc, names: list, profile, rows: set[int]) -> list[int]:
    """Compile the tenant step at the bucket of every reachable row count
    (and the slot rebuild), through the service's own path: the tenants
    say ``hello``, tick once per bucket on random snapshots, then say
    ``bye`` and ``hello`` again, so the window starts with the tenants
    admitted afresh."""
    from repro.core import features
    from repro.core.predictor import bucket_size
    from repro.policy import wire
    rng = np.random.default_rng(0)
    buckets = sorted({bucket_size(n) for n in rows})
    for t in names:
        svc.hello(t, profile.to_wire())
    mt = np.zeros((profile.max_tasks, features.TASK_FEATURES), np.float32)
    for seq, nb in enumerate(buckets):
        m_h = rng.random((profile.n_hosts, features.HOST_FEATURES),
                         np.float32)
        per = np.full(len(names), nb // len(names))
        per[:nb % len(names)] += 1
        for t, c in zip(names, per):
            jobs = [wire.job_to_wire(j, 1, mt) for j in range(c)]
            svc.submit(t, wire.snapshot_to_wire(t, seq, m_h, jobs))
        svc.tick()
    for t in names:
        svc.bye(t)
        svc.hello(t, profile.to_wire())
    return buckets


@dataclasses.dataclass
class Sample:
    window: np.ndarray     # (T, n_hosts, 11) the host window answered on
    mt: np.ndarray         # (jobs, max_tasks, 5)
    q: np.ndarray
    k: float
    e_s: np.ndarray        # what the service answered


@dataclasses.dataclass
class ServiceRun:
    setup_s: float
    window_s: float
    ticks: int
    attempted: int
    failed: int
    latency_s: list
    jobs_answered: list    # job rows of each tenant interval answered
    rows_per_tick: list
    samples: list
    counters: dict
    compiles_in_window: int
    warm_buckets: list
    memory_peak_bytes: int | None
    slots: int
    program: dict | None   # program spans over the window (traced runs)
    tenant_calls: list     # (g, nb) of each traced tenant-step span
    tenant_device: tuple   # (programs, ns) of the tenant step's device time
    trace: dict | None = None


def _program_trace(trace_dir) -> tuple[dict, list, tuple]:
    """Span stats of the program's ``service.*`` and ``predictor.*``
    spans over the ``bench.window`` span, each tenant-step span's ``(g,
    nb)``, and the tenant step's device time in the window."""
    path = tracereduce.find_xplane(trace_dir)
    if path is None:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    with mock.patch.object(programspans, "PREFIXES",
                           programspans.PREFIXES + ("service.",)):
        spans = programspans.load(path)
    tr = tracereduce.load(path)
    (_, lo, hi), = [s for s in tr["spans"] if s[0] == "bench.window"]
    calls = [(int(sp[4]["g"]), int(sp[4]["nb"])) for sp in spans
             if sp[0] == "predictor.tenants" and lo <= sp[1] < hi]
    return (programspans.span_stats(spans, lo, hi), calls,
            tracereduce.program_ns(tr["modules"], "_fused_step_tenants",
                                   lo, hi))


def run(config: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, t_start: float, trace_dir=None) -> ServiceRun:
    # the tenant step by name first: a program without it stops here
    from repro.core.predictor import _fused_step_tenants  # noqa: F401
    import jax
    from repro.service import PredictionService, Profile, ServiceConfig

    net = config["network"]
    profile = Profile(**config["profile"])
    svc = PredictionService(ServiceConfig(profile=profile,
                                          **config["service"]))
    input_dim = profile.n_hosts * reference.HOST_FEATURES \
        + profile.max_tasks * reference.TASK_FEATURES
    with svc.lock:
        svc._install(weights.make_params(config["init"]["seed"], input_dim,
                                         net, config["init"]), 0)
    streams = record(config, episodes(traffic, seed))
    n_tenants, stride = traffic["tenants"], traffic["episode_stride"]
    names = [f"tenant-{i:03d}" for i in range(n_tenants)]
    buckets = warm(svc, names, profile,
                   reachable_rows(streams, n_tenants, stride))
    rep = Replay(streams, n_tenants, stride)
    rng = common.rng(seed, "check")
    p_check = traffic["check_per_episode"] / len(streams[0])
    model = svc.model
    before = {c: getattr(model, c) for c in COUNTERS}
    compiles = model.compile_count
    latency, jobs_answered, rows, samples = [], [], [], []
    attempted = failed = ticks = 0
    # the recorded telemetry stands in for the wire: keep its millions of
    # objects out of the collector's passes over the service's own
    gc.collect()
    gc.freeze()
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    t0 = time.perf_counter()
    t_end = t0 + seconds
    with _span(trace, "bench.window"):
        while time.perf_counter() < t_end:
            pending, sent = [], []
            with _span(trace, "bench.submit"):
                for i, t in enumerate(names):
                    snap, new = rep.next(i)
                    if new:
                        svc.bye(t)
                        svc.hello(t, profile.to_wire())
                    sent.append(time.perf_counter())
                    pending.append(svc.submit(t, snap))
            with _span(trace, "bench.tick"):
                svc.tick()
            done = time.perf_counter()
            attempted += len(pending)
            ticks += 1
            n_rows = 0
            for p, s in zip(pending, sent):
                if p.result is None or not p.result.get("ok"):
                    failed += 1
                    continue
                latency.append(done - s)
                jobs_answered.append(len(p.result["jobs"]))
                n_rows += len(p.result["jobs"])
            rows.append(n_rows)
            if ticks == 1 or rng.random() < p_check:
                samples += _sample(svc, names, pending, rng,
                                   traffic["check_tenants"])
    window_s = time.perf_counter() - t0
    gc.unfreeze()
    in_window = model.compile_count - compiles
    counts = {c: getattr(model, c) - before[c] for c in COUNTERS}
    program, calls, device = None, [], (0, 0.0)
    if trace:
        jax.profiler.stop_trace()
        program, calls, device = _program_trace(trace_dir)
    stats = jax.devices()[0].memory_stats() or {}
    return ServiceRun(
        setup_s=setup_s, window_s=window_s, ticks=ticks,
        attempted=attempted, failed=failed, latency_s=latency,
        jobs_answered=jobs_answered, rows_per_tick=rows, samples=samples,
        counters=counts, compiles_in_window=in_window,
        warm_buckets=buckets,
        memory_peak_bytes=stats.get("peak_bytes_in_use"),
        slots=config["service"]["max_tenants"], program=program,
        tenant_calls=calls, tenant_device=device)


def _sample(svc, names, pending, rng, k: int) -> list[Sample]:
    """Up to ``k`` tenants of the tick just answered, with jobs."""
    with_jobs = [i for i, p in enumerate(pending)
                 if p.result and p.result.get("ok") and p.result["jobs"]]
    out = []
    for i in rng.permutation(with_jobs)[:k].tolist():
        snap, res = pending[i].snap, pending[i].result
        ctrl = svc.tenants[names[i]].controller
        out.append(Sample(
            window=ctrl._host_seq().copy(),
            mt=np.stack([j["m_t"] for j in snap["jobs"]]),
            q=np.array([j["q"] for j in snap["jobs"]]),
            k=float(ctrl.predictor.k),
            e_s=np.array([j["e_s"] for j in res["jobs"]])))
    return out


def check(run_: ServiceRun, config: dict) -> dict:
    """Every sampled answer's E_S against the references, as
    ``simcell.check`` compares a decision's."""
    net = config["network"]
    prof = config["profile"]
    input_dim = prof["n_hosts"] * reference.HOST_FEATURES \
        + prof["max_tasks"] * reference.TASK_FEATURES
    hp = weights.host_params(config["init"]["seed"], input_dim, net,
                             config["init"])
    gaps = {"program": 0.0, "control": 0.0, "program_f64": 0.0, "f32": 0.0}
    n_jobs = 0
    for s in run_.samples:
        args = (hp, s.window, s.mt, s.q, s.k, net)
        dev = reference.e_s_device(*args)
        want = reference.e_s(*args)
        for name, got, ref in (
                ("program", s.e_s, dev),
                ("control", reference.e_s_device(*args, passes=3), dev),
                ("program_f64", s.e_s, want),
                ("f32", reference.e_s(*args, precision="f32"), want)):
            gaps[name] = max(gaps[name], reference.rel_gap(got, ref))
        n_jobs += len(s.q)
    return {"gaps": gaps, "decisions": len(run_.samples), "jobs": n_jobs}


def checks(run_: ServiceRun, config: dict, seed: int) -> dict:
    """``es_gap_over_3pass`` and ``unchecked``, as a simulator cell's."""
    return ratio_checks(check(run_, config), config)


def end_to_end(run_: ServiceRun) -> dict:
    return {"decision_ms_p95": common.p95(run_.latency_s) * 1e3,
            "intervals_per_s": len(run_.latency_s) / run_.window_s}


def layer_context(run_: ServiceRun, config: dict, peaks: dict) -> dict:
    """What the per-layer readers of a service cell read."""
    net = config["network"]
    prof = config["profile"]
    host_dim = prof["n_hosts"] * reference.HOST_FEATURES
    task_dim = prof["max_tasks"] * reference.TASK_FEATURES
    least = sum(max(
        tenantflops.tenant_step_flops(g, nb, host_dim, task_dim, net)
        / peaks["bf16_flops_per_s"],
        tenantflops.tenant_step_bytes(run_.slots, g, nb, host_dim,
                                      task_dim, net)
        / peaks["hbm_bytes_per_s"]) for g, nb in run_.tenant_calls)
    return {
        "kind": "service",
        "window_s": run_.window_s,
        "program": run_.program,
        "counters": run_.counters,
        "tenant_least_s": least,
        "tenant_device": run_.tenant_device,
        "decision_flops": float(sum(
            flops.decision_flops(n, host_dim, task_dim, net)
            for n in run_.jobs_answered)),
        "peaks": peaks,
        "trace": run_.trace,
    }


def report(run_: ServiceRun) -> None:
    rows = np.asarray(run_.rows_per_tick or [0])
    c = run_.counters
    common.log(
        f"window: {run_.ticks} ticks, {len(run_.latency_s)} tenant "
        f"intervals answered, {run_.failed} failed; job rows a tick mean "
        f"{rows.mean():.1f} max {rows.max()}; tenant-step launches "
        f"{c['tenant_calls']}, slot rebuilds {c['tenant_slot_rebuilds']}, "
        f"staged {c['tenant_stage_bytes'] / max(c['tenant_calls'], 1):.0f}"
        f" B a launch; {len(run_.samples)} tenant intervals sampled for "
        f"the check; buckets warmed {run_.warm_buckets}; setup "
        f"{run_.setup_s:.3f} s")
    if run_.program:
        common.log("program spans over the window: " + ", ".join(
            f"{name} {st['count']} x {st['total_ns'] / st['count'] * 1e-6:.3f}"
            f" ms (self {st['self_ns'] / st['count'] * 1e-6:.3f})"
            for name, st in sorted(run_.program.items())))
