"""Simulator cells: START deciding every interval of the cloud simulator.

Set-up makes the weights from the configuration's fixed init seed (they
steer START's mitigations, so weights that moved with ``--seed`` moved
the simulated work), builds one ``StragglerPredictor`` and warms every
batch shape the window can pick.  The window then runs
288-interval episodes back to back, each a ``Simulation`` with a
``START`` policy whose controller shares that predictor, as
``sweep.run_cell`` builds a cell.  The episodes are the traffic file's
fixed pool, in an order drawn from the seed: every seed then does the
same work, where fresh episodes per seed moved the mean load by 4 to 8%
between seeds.  The benchmark times the
policy's ``observe`` + ``decide`` of every interval itself, and keeps the
raw telemetry of a seeded sample of decisions for the reference.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import time

import numpy as np

from bench import common, flops, reference, weights

TECHNIQUES = {"start": "START"}


def sim_config(config: dict, traffic: dict, seed: int):
    from repro.sim.config import SimConfig
    kw = dict(config["sim"])
    kw.update(traffic.get("sim", {}))
    for key, val in kw.items():
        if isinstance(val, list):
            kw[key] = tuple(val)
    return SimConfig(seed=seed, **kw)


def episode_seeds(traffic: dict, seed: int):
    """The simulator seeds of a run's episodes, in order: the pool,
    permuted by ``seed``, repeated."""
    pool = traffic["episodes"]
    order = common.rng(seed, "episodes").permutation(len(pool))
    return itertools.cycle([pool[i] for i in order])


def _span(trace: bool, name: str):
    if not trace:
        return _NULL
    import jax
    return jax.profiler.TraceAnnotation(name)


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


@dataclasses.dataclass
class Sample:
    t: int
    util: list            # (T,) util arrays, oldest first
    n_tasks: list
    cap: np.ndarray
    cost: np.ndarray
    power_max: np.ndarray
    counts: np.ndarray    # tasks of each active job
    req: np.ndarray       # every task of those jobs, back to back
    host: np.ndarray
    prev_host: np.ndarray
    e_s: np.ndarray | None = None


class Recorder:
    """Wraps one episode's policy and the shared predictor: times each
    interval's decision (``observe`` start to ``decide`` end, less the
    recording itself) and keeps the raw inputs and E_S of sampled
    decisions."""

    def __init__(self, pred, horizon: int, rng: np.random.Generator,
                 per_episode: int, trace: bool):
        self.horizon = horizon
        self.rng = rng
        self.per_episode = per_episode
        self.trace = trace
        self.decision_s: list[float] = []
        self.predict_s = 0.0
        self.predict_calls = 0
        self.jobs_predicted: list[int] = []
        self.samples: list[Sample] = []
        self.max_n = 0
        self._hosts: collections.deque = collections.deque(maxlen=horizon)
        self._static = None
        self._capture: Sample | None = None
        orig = pred.predict_interval

        def predict_interval(m_t, q):
            with _span(trace, "bench.predict"):
                t0 = time.perf_counter()
                out = orig(m_t, q)
                self.predict_s += time.perf_counter() - t0
            self.predict_calls += 1
            self.jobs_predicted.append(int(m_t.shape[0]))
            if self._capture is not None:
                self._capture.e_s = np.array(out, np.float64)
            return out

        pred.predict_interval = predict_interval

    def bind(self, tech, sim, n_intervals: int) -> None:
        hosts = sim.cluster
        self._static = (hosts.cap.copy(), hosts.cost.copy(),
                        hosts.power_max.copy())
        self._hosts.clear()
        chosen = set(self.rng.choice(
            np.arange(self.horizon - 1, n_intervals),
            size=min(self.per_episode, n_intervals - self.horizon + 1),
            replace=False).tolist())
        orig_observe, orig_decide = tech.observe, tech.decide
        state = {"obs": 0.0}

        def observe(view):
            self._hosts.append((view.t, view.hosts.util.copy(),
                                view.hosts.n_tasks.copy()))
            with _span(self.trace, "bench.observe"):
                t0 = time.perf_counter()
                orig_observe(view)
                state["obs"] = time.perf_counter() - t0

        def decide(view):
            active = view.jobs.active()
            n = len(active)
            self._capture = None
            if n and view.t >= self.horizon - 1 and (
                    view.t in chosen or n > self.max_n):
                self._capture = self._sample(view, active)
            self.max_n = max(self.max_n, n)
            with _span(self.trace, "bench.decide"):
                t0 = time.perf_counter()
                acts = orig_decide(view)
                dt = time.perf_counter() - t0
            self.decision_s.append(state["obs"] + dt)
            if self._capture is not None and self._capture.e_s is not None:
                self.samples.append(self._capture)
            self._capture = None
            return acts

        tech.observe, tech.decide = observe, decide

    def _sample(self, view, active) -> Sample | None:
        ts = [h[0] for h in self._hosts]
        if ts != list(range(view.t - self.horizon + 1, view.t + 1)):
            return None
        starts = np.asarray(view.jobs.start)[active]
        counts = np.asarray(view.jobs.count)[active]
        tids = np.concatenate([np.arange(s, s + c)
                               for s, c in zip(starts, counts)])
        tt = view.tasks
        return Sample(
            t=view.t, util=[h[1] for h in self._hosts],
            n_tasks=[h[2] for h in self._hosts], cap=self._static[0],
            cost=self._static[1], power_max=self._static[2],
            counts=counts.copy(), req=np.asarray(tt.req)[tids].copy(),
            host=np.asarray(tt.host)[tids].copy(),
            prev_host=np.asarray(tt.prev_host)[tids].copy())


def warm(pred, max_jobs: int) -> int:
    """Compile every batch shape the window can pick, as a process whose
    load ramps up from zero picks them: the job counts 1..max_jobs are
    put to ``batch_size`` in order (which spends the exact-shape budget
    on the first wasteful counts), then each distinct shape runs once,
    after an idle interval so the ring's catch-up roll compiles too."""
    from repro.core import features
    shapes: dict[int, int] = {}
    for n in range(1, max_jobs + 1):
        shapes.setdefault(pred.batch_size(n), n)
    rng = np.random.default_rng(0)
    row = rng.random((pred.n_hosts, features.HOST_FEATURES), np.float32)
    m_t = rng.random((max_jobs, pred.max_tasks, features.TASK_FEATURES),
                     np.float32)
    q = rng.integers(2, pred.max_tasks + 1, max_jobs).astype(np.float32)
    pred.push_host_row(row)
    pred.predict_interval(m_t[:1], q[:1])
    for n in shapes.values():
        pred.push_host_row(row)
        pred.push_host_row(row)
        pred.predict_interval(m_t[:n], q[:n])
    return len(shapes)


@dataclasses.dataclass
class SimRun:
    setup_s: float
    window_s: float
    intervals: int
    episodes: int
    recorder: Recorder
    compiles_in_window: int
    warm_shapes: int
    memory_peak_bytes: int | None
    trace: dict | None
    sim_config: object

    @property
    def attempted(self) -> int:
        return self.intervals

    @property
    def failed(self) -> int:
        return 0


def run(config: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, t_start: float, check_per_episode: int | None = None,
        trace_dir=None) -> SimRun:
    import jax
    from repro.core.predictor import StragglerPredictor
    from repro.core.start import STARTController
    from repro.sim.engine import Simulation
    from repro.sim.techniques import start_tech

    net = config["network"]
    cfg0 = sim_config(config, traffic, seed)
    tech_name = traffic["technique"]
    tech_cls = getattr(start_tech, TECHNIQUES[tech_name])
    tech_kw = dict(config["start"])
    input_dim = cfg0.n_hosts * reference.HOST_FEATURES \
        + cfg0.max_tasks * reference.TASK_FEATURES

    params = weights.make_params(config["init"]["seed"], input_dim, net,
                                 config["init"])
    pred = StragglerPredictor(n_hosts=cfg0.n_hosts,
                              max_tasks=cfg0.max_tasks, k=cfg0.k,
                              horizon=net["horizon"],
                              beta_scale=net["beta_scale"])
    pred.params = params
    shapes = warm(pred, traffic["warm_max_jobs"])

    per_ep = traffic["check_per_episode"] if check_per_episode is None \
        else check_per_episode
    rec = Recorder(pred, net["horizon"], common.rng(seed, "check"),
                   per_ep, trace)

    before = pred.compile_count
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    t0 = time.perf_counter()
    t_end = t0 + seconds
    intervals = episodes = 0
    done = False
    pool = episode_seeds(traffic, seed)
    with _span(trace, "bench.window"):
        while not done:
            cfg = dataclasses.replace(cfg0, seed=next(pool))
            with _span(trace, "bench.episode"):
                ctrl = STARTController(cfg.n_hosts, cfg.max_tasks, k=cfg.k,
                                       horizon=net["horizon"],
                                       predictor=pred)
                tech = tech_cls(controller=ctrl, **tech_kw)
                sim = Simulation(cfg, technique=tech)
                rec.bind(tech, sim, cfg.n_intervals)
            for _ in range(cfg.n_intervals):
                with _span(trace, "bench.step"):
                    sim.step()
                intervals += 1
                if time.perf_counter() >= t_end:
                    done = True
                    break
            episodes += 1
    window_s = time.perf_counter() - t0
    in_window = pred.compile_count - before
    if trace:
        jax.profiler.stop_trace()
    stats = jax.devices()[0].memory_stats() or {}
    return SimRun(setup_s=setup_s, window_s=window_s, intervals=intervals,
                  episodes=episodes, recorder=rec,
                  compiles_in_window=in_window,
                  warm_shapes=shapes,
                  memory_peak_bytes=stats.get("peak_bytes_in_use"),
                  trace=None, sim_config=cfg0)


def _reference_inputs(run_: SimRun, config: dict, seed: int):
    """For each sampled decision, ``(sample, args)``: the reference's
    inputs rebuilt from the sample's raw telemetry, with weights made
    again from the seed."""
    net = config["network"]
    cfg = run_.sim_config
    start_kw = config["start"]
    input_dim = cfg.n_hosts * reference.HOST_FEATURES \
        + cfg.max_tasks * reference.TASK_FEATURES
    hp = weights.host_params(config["init"]["seed"], input_dim, net,
                             config["init"])
    for s in run_.recorder.samples:
        mh = np.stack([reference.host_matrix(u, s.cap, s.cost,
                                             s.power_max, nt)
                       for u, nt in zip(s.util, s.n_tasks)])
        host_col = np.where(s.host >= 0, s.host, s.prev_host)
        mt = reference.task_matrices(s.req, host_col, s.counts,
                                     cfg.n_hosts, cfg.max_tasks)
        k = reference.start_k(s.util[-1], cfg.reserved_utilization,
                              start_kw["k_lo"], start_kw["k_hi"])
        yield s, (hp, mh, mt, s.counts, k, net)


def check(run_: SimRun, config: dict, seed: int) -> dict:
    """Every sampled decision's E_S against the references.  Returns the
    widest relative gaps from the six-pass device reference of the
    program (``program``) and of the three-pass control (``control``),
    and, for the record, the program's and plain float32's gaps from
    float64 (``program_f64``, ``f32``)."""
    gaps = {"program": 0.0, "control": 0.0, "program_f64": 0.0,
            "f32": 0.0}
    n_jobs = 0
    for s, args in _reference_inputs(run_, config, seed):
        dev = reference.e_s_device(*args)
        want = reference.e_s(*args)
        for name, got, ref in (
                ("program", s.e_s, dev),
                ("control", reference.e_s_device(*args, passes=3), dev),
                ("program_f64", s.e_s, want),
                ("f32", reference.e_s(*args, precision="f32"), want)):
            gaps[name] = max(gaps[name], reference.rel_gap(got, ref))
        n_jobs += len(s.counts)
    return {"gaps": gaps, "decisions": len(run_.recorder.samples),
            "jobs": n_jobs}


def stand_in(run_: SimRun, config: dict, seed: int) -> None:
    """Put the control in the program's place: every sampled E_S becomes
    the three-pass device reference's, so the run's own ``checks`` judge
    it."""
    for s, args in _reference_inputs(run_, config, seed):
        s.e_s = reference.e_s_device(*args, passes=3)


def layer_context(run_: SimRun, config: dict, peaks: dict) -> dict:
    """What the per-layer readers of a simulator cell read."""
    net = config["network"]
    cfg = run_.sim_config
    host_dim = cfg.n_hosts * reference.HOST_FEATURES
    task_dim = cfg.max_tasks * reference.TASK_FEATURES
    rec = run_.recorder
    least = [max(flops.fused_step_flops(n, host_dim, task_dim, net)
                 / peaks["bf16_flops_per_s"],
                 flops.fused_step_bytes(n, host_dim, task_dim, net)
                 / peaks["hbm_bytes_per_s"])
             for n in rec.jobs_predicted]
    bytes_bound = sum(
        flops.fused_step_bytes(n, host_dim, task_dim, net)
        / peaks["hbm_bytes_per_s"]
        >= flops.fused_step_flops(n, host_dim, task_dim, net)
        / peaks["bf16_flops_per_s"] for n in rec.jobs_predicted)
    return {
        "kind": "simulator",
        "window_s": run_.window_s,
        "intervals": run_.intervals,
        "decision_s": float(sum(rec.decision_s)),
        "predict_s": rec.predict_s,
        "predict_calls": rec.predict_calls,
        "decision_flops": float(sum(
            flops.decision_flops(n, host_dim, task_dim, net)
            for n in rec.jobs_predicted)),
        "fused_least_s": float(sum(least)),
        "fused_bytes_bound_calls": int(bytes_bound),
        "peaks": peaks,
        "trace": run_.trace,
    }


def report(run_: SimRun) -> None:
    rec = run_.recorder
    jobs = np.asarray(rec.jobs_predicted or [0])
    common.log(
        f"window: {run_.intervals} intervals in {run_.episodes} episodes, "
        f"{rec.predict_calls} predictions, active jobs mean "
        f"{jobs.mean():.1f} max {jobs.max()}, {len(rec.samples)} "
        f"decisions sampled for the check; {run_.warm_shapes} batch "
        f"shapes warmed; setup {run_.setup_s:.3f} s")


def checks(run_: SimRun, config: dict, seed: int) -> dict:
    """The numbers compared, each with its limit: the program's widest
    E_S gap from the six-pass device reference in units of the
    three-pass control's (``es_gap_over_3pass``), and whether any
    decision was checked at all (``unchecked``)."""
    return ratio_checks(check(run_, config, seed), config)


def ratio_checks(c: dict, config: dict) -> dict:
    g = c["gaps"]
    common.log(f"reference: {c['decisions']} decisions, {c['jobs']} job "
               f"rows; widest relative E_S gap from the six-pass device "
               f"reference: program {g['program']!r}, three-pass control "
               f"{g['control']!r}; from float64: program "
               f"{g['program_f64']!r}, plain float32 {g['f32']!r}")
    ratio = g["program"] / max(g["control"], 1e-12)
    return {"es_gap_over_3pass": {
                "value": min(ratio, 1e30),
                "limit": config["check"]["es_gap_over_3pass"]},
            "unchecked": {"value": int(c["decisions"] == 0), "limit": 0}}


def end_to_end(run_: SimRun) -> dict:
    rec = run_.recorder
    return {
        "decision_ms_p95": common.p95(rec.decision_s) * 1e3,
        "intervals_per_s": run_.intervals / run_.window_s,
    }
