#!/usr/bin/env python3
"""Bring-up smoke for START's decision path on one TPU chip.

Drives the system once through the entry points a user calls, at the
paper's deployment width (Table 4: 400 hosts of the Table-3 mix,
``max_tasks=10``, Poisson(1.2) arrivals — the ``SimConfig`` defaults),
in ONE process that holds the chip:

  1. device   — names the device; exits non-zero without a TPU (JAX is
     pinned to the TPU backend, so a backend that fails to start raises
     instead of dropping to the CPU);
  2. pretrain — ``sweep.make_technique("start", SimConfig(), ...)``;
  3. simulate — ``Simulation(SimConfig(n_intervals=N), technique=START)``
     with the fused decision step, then every recorded interval's fused
     E_S is held to the unfused ``predict_features`` reference on the
     chip within ``tests/tolerance.py``'s Tier-1 bound; one
     ``start-eager`` interval repeats the check with ``per_task=True``;
  4. service  — a ``ServiceDaemon`` loading the pretrained params from a
     checkpoint answers two TCP tenants (a degraded service is a fail);
  5. kernel   — ``StragglerPredictor(use_pallas_cell=True)`` on the same
     inputs: its fused program must hold the compiled Mosaic kernel
     (``tpu_custom_call``) and agree with the jnp cell's within the
     Tier-1 bound.

Every phase raises on failure.  The last line of stdout is one JSON
object naming the device; it is printed only when every phase passed.
Timings printed are chip timings (host clock around work that ends in a
device readback), with compile time reported apart from warm time.

    python chip_smoke.py
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import tempfile
import threading
import time
import traceback

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(_HERE, "src"), os.path.join(_HERE, "tests")]


#: how printed numbers are labelled: device numbers only from a chip
_CLOCK, _DEVICE = "chip timing", "the chip"


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Sizing:
    """What one smoke run drives.  The defaults are the deployment width;
    tests pass a small one with ``pallas="interpret"`` to rehearse every
    phase on the CPU."""

    n_hosts: int = 400
    n_intervals: int = 288
    eager_intervals: int = 12
    pretrain_epochs: int = 4
    snapshots: int = 4             # per service tenant
    pallas: bool | str = True      # "interpret" only for CPU rehearsal


def log(msg: str) -> None:
    print(msg, flush=True)


def device_check() -> dict:
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"no TPU found: {e}") from None
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        raise NoChip(f"no TPU found: JAX's backend is {d.platform!r}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


class _Recorder:
    """Wraps a predictor's ``predict_interval``: records each call's
    inputs (the host window the fused ring holds, M_T, q, k) and raw
    output, and times it — split into calls that compiled a program and
    warm calls."""

    def __init__(self, pred):
        from repro.core.predictor import fused_compile_count
        self.pred = pred
        self.orig = pred.predict_interval
        self.count = fused_compile_count
        self.calls: list[tuple] = []
        self.compile_s: list[float] = []
        self.warm_s: list[float] = []
        pred.predict_interval = self

    def window(self) -> np.ndarray:
        rows = list(self.pred._row_hist)
        while len(rows) < self.pred.horizon:
            rows.insert(0, rows[0])
        return np.stack(rows[-self.pred.horizon:])

    def __call__(self, m_t, q, per_task=False):
        seq, k = self.window(), float(self.pred.k)
        before = self.count()
        t0 = time.perf_counter()
        out = self.orig(m_t, q, per_task=per_task)
        dt = time.perf_counter() - t0
        (self.compile_s if self.count() > before else self.warm_s).append(dt)
        self.calls.append((seq, np.array(m_t, np.float32),
                           np.array(q, np.float32), k, per_task, out))
        return out


def _timing(label: str, rec: _Recorder) -> None:
    warm = np.asarray(rec.warm_s) * 1e3
    log(f"{_CLOCK} [{label}]: {len(rec.compile_s)} compiling calls "
        f"{sum(rec.compile_s):.3f} s total; {len(warm)} warm calls "
        f"median {np.median(warm) if len(warm) else float('nan'):.3f} ms "
        f"p99 {np.percentile(warm, 99) if len(warm) else float('nan'):.3f} "
        f"ms per interval")


def phase_pretrain(sz: Sizing):
    from repro.sim import sweep
    from repro.sim.config import SimConfig
    cfg = SimConfig(n_hosts=sz.n_hosts)
    t0 = time.perf_counter()
    tech = sweep.make_technique("start", cfg,
                                pretrain_epochs=sz.pretrain_epochs)
    wall = time.perf_counter() - t0
    pred = tech._controller.predictor
    losses = pred.losses
    steps = int(pred.opt.step)
    log(f"pretrain: {steps} fit() steps over {len(losses)} epochs, "
        f"loss first {losses[0]:.6g} last {losses[-1]:.6g}; "
        f"{_CLOCK}: {wall:.2f} s wall (warmup simulation + fit, "
        f"compiles included)")
    if steps <= 0 or not np.isfinite([losses[0], losses[-1]]).all():
        raise AssertionError(f"pretraining failed: steps={steps} "
                             f"losses={losses[:1]}..{losses[-1:]}")
    return cfg, tech


def _tier1_check(pred, calls, label: str) -> dict:
    from tolerance import assert_tier1
    worst = {"max_rel": 0.0, "max_abs": 0.0, "max_ulp": 0}
    k0 = pred.k
    try:
        for i, (seq, m_t, q, k, per_task, out) in enumerate(calls):
            pred.k = k
            if per_task:
                want = pred.predict_features(seq, m_t, q, per_task=True)
                pairs = [(out[0], want[0]), (out[1], want[1])]
            else:
                want = pred.predict_features(seq, m_t, q).e_s
                pairs = [(out, want)]
            for got, ref in pairs:
                d = assert_tier1(got, ref, context=f"{label} call {i}")
                for key in worst:
                    worst[key] = max(worst[key], d[key])
    finally:
        pred.k = k0
    log(f"tier-1 [{label}]: {len(calls)} intervals vs unfused reference "
        f"on {_DEVICE}: max_rel {worst['max_rel']:.3e} max_abs "
        f"{worst['max_abs']:.3e} max_ulp {worst['max_ulp']}")
    return worst


def phase_simulate(sz: Sizing, cfg, tech):
    from repro.core.predictor import fused_compile_count
    from repro.sim import sweep
    from repro.sim.engine import Simulation
    from repro.sim.techniques.start_tech import STARTEager

    ctrl = tech._controller
    if not ctrl.use_fused_step:
        raise AssertionError("the fused decision step is off")
    pred = ctrl.predictor
    rec = _Recorder(pred)
    run_cfg = dataclasses.replace(cfg, n_intervals=sz.n_intervals)
    t0 = time.perf_counter()
    summary = Simulation(run_cfg, technique=tech).run()
    wall = time.perf_counter() - t0
    es = [c[5] for c in rec.calls]
    log(f"simulate: {sz.n_intervals} intervals at {cfg.n_hosts} hosts"
        f"{'' if sz.n_intervals == 288 else ' (cut from 288)'}, "
        f"{len(rec.calls)} fused predictions, buckets "
        f"{sorted(pred.buckets_used)}, tasks_done {summary['tasks_done']}, "
        f"sla_violation_rate {summary['sla_violation_rate']:.4f}; "
        f"{_CLOCK}: {wall:.2f} s wall")
    _timing("start fused step", rec)
    if fused_compile_count() <= 0 or pred.h2d_stages <= 0 or not rec.calls:
        raise AssertionError(
            f"fused step not taken: compiles={fused_compile_count()} "
            f"h2d_stages={pred.h2d_stages} calls={len(rec.calls)}")
    if not all(np.isfinite(e).all() for e in es):
        raise AssertionError("non-finite E_S from the fused step")
    drift = _tier1_check(pred, rec.calls, "start")

    # start-eager: the same pretrained model behind the per-task trigger
    fresh = sweep.make_technique("start", cfg,
                                 pretrain_epochs=sz.pretrain_epochs)
    eager = STARTEager(controller=fresh._controller)
    erec = _Recorder(eager._controller.predictor)
    Simulation(dataclasses.replace(cfg, n_intervals=sz.eager_intervals),
               technique=eager).run()
    per_task = [c for c in erec.calls if c[4]]
    if not per_task:
        raise AssertionError("start-eager ran no per-task prediction")
    _timing("start-eager fused step, per_task", erec)
    e_drift = _tier1_check(eager._controller.predictor, per_task[-1:],
                           "start-eager per_task")
    return rec.calls, drift, e_drift


def _snapshot(rng, n_hosts, max_tasks, tenant, seq):
    from repro.core import features
    from repro.policy import wire
    m_h = rng.random((n_hosts, features.HOST_FEATURES), np.float32)
    jobs = []
    for j in range(3):
        q = int(rng.integers(2, max_tasks + 1))
        m_t = np.zeros((max_tasks, features.TASK_FEATURES), np.float32)
        m_t[:q] = rng.random((q, features.TASK_FEATURES), np.float32)
        tasks = [(100 * (10 * seq + j) + i, int(rng.integers(n_hosts)), i)
                 for i in range(q)]
        jobs.append(wire.job_to_wire(10 * seq + j, q, m_t, tasks=tasks))
    return wire.snapshot_to_wire(tenant, seq, m_h, jobs=jobs)


def phase_service(sz: Sizing, cfg, tech) -> dict:
    from repro.service import Profile, ServiceConfig, ServiceDaemon
    from repro.train.checkpoint import VersionStore
    import jax

    pred = tech._controller.predictor
    profile = Profile(n_hosts=cfg.n_hosts, max_tasks=cfg.max_tasks,
                      horizon=pred.horizon, k=cfg.k,
                      beta_scale=pred.beta_scale)
    with tempfile.TemporaryDirectory() as ckpt:
        store = VersionStore(ckpt)
        store.save_version(0, pred.params)
        store.promote(0)
        scfg = ServiceConfig(profile=profile, ckpt_dir=ckpt)
        with ServiceDaemon(scfg, port=0) as daemon:
            svc = daemon.service
            if svc.degraded:
                raise AssertionError("service degraded: the pretrained "
                                     "params did not load")
            for a, b in zip(jax.tree_util.tree_leaves(svc.params),
                            jax.tree_util.tree_leaves(pred.params)):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))
            answers, lat, errors = [], [], []

            def tenant(name: str, seed: int) -> None:
                rng = np.random.default_rng(seed)
                cli = daemon.tcp_client(name)
                try:
                    r = cli.hello(profile)
                    if not r.get("ok"):
                        raise AssertionError(f"hello refused: {r}")
                    for seq in range(sz.snapshots):
                        snap = _snapshot(rng, cfg.n_hosts, cfg.max_tasks,
                                         name, seq)
                        t0 = time.perf_counter()
                        r = cli.snapshot(snap)
                        lat.append(time.perf_counter() - t0)
                        answers.append(r)
                    cli.bye()
                except Exception as e:      # surfaced below
                    errors.append(f"{name}: {type(e).__name__}: {e}")
                finally:
                    cli.close()

            threads = [threading.Thread(target=tenant, args=(n, s))
                       for s, n in enumerate(("etl", "web"))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            stats = svc.stats()
    if errors:
        raise AssertionError("; ".join(errors))
    bad = [r for r in answers if not r.get("ok") or r.get("degraded")
           or not all(np.isfinite(j["e_s"]) for j in r["jobs"])]
    if len(answers) != 2 * sz.snapshots or bad:
        raise AssertionError(f"{len(answers)} answers, bad: {bad[:1]}")
    if stats["degraded"] or stats["retrain_failures"]:
        raise AssertionError(f"service stats: {stats}")
    ms = np.asarray(lat) * 1e3
    log(f"service: {len(answers)} answers to 2 TCP tenants at "
        f"{cfg.n_hosts} hosts, {stats['ticks']} ticks, "
        f"{stats['batch_rows']} job rows, degraded {stats['degraded']}, "
        f"retrain_failures {stats['retrain_failures']}; {_CLOCK}: "
        f"answer latency median {np.median(ms):.2f} ms max "
        f"{ms.max():.2f} ms (first answers include compiles)")
    return stats


def phase_kernel(sz: Sizing, cfg, tech, calls) -> float:
    import jax
    import jax.numpy as jnp
    from repro.core import encoder_lstm as net
    from repro.core.predictor import (_N_SCALARS, StragglerPredictor,
                                      _fused_step)
    from repro.kernels.lstm_cell import lstm_cell, lstm_cell_ref
    from tolerance import assert_tier1

    pred = tech._controller.predictor
    picks = [c for c in calls if not c[4]]
    picks = picks[::max(1, len(picks) // 8)][:8]

    def fresh(pallas):
        p = StragglerPredictor(n_hosts=cfg.n_hosts, max_tasks=cfg.max_tasks,
                               horizon=pred.horizon,
                               beta_scale=pred.beta_scale,
                               use_pallas_cell=pallas)
        p.params = pred.params
        return p

    worst = 0.0
    for i, (seq, m_t, q, k, _, _) in enumerate(picks):
        outs = []
        for pallas in (sz.pallas, False):
            p = fresh(pallas)
            p.k = k
            for row in seq:
                p.push_host_row(row)
            outs.append(p.predict_interval(m_t, q))
        d = assert_tier1(*outs, context=f"Pallas cell, interval {i}")
        worst = max(worst, d["max_rel"])

    # the lowered fused program must carry the compiled Mosaic kernel
    p = fresh(sz.pallas)
    nb = 16
    f32 = jnp.float32
    text = _fused_step.lower(
        p.params, jax.ShapeDtypeStruct((p.horizon, p.host_dim), f32),
        jax.ShapeDtypeStruct(
            (_N_SCALARS + p.host_dim + nb * (1 + p.task_dim),), f32),
        nb=nb, task_dim=p.task_dim, use_pallas=sz.pallas,
        unroll=p._unroll(nb)).as_text()
    if sz.pallas is True and "tpu_custom_call" not in text:
        raise AssertionError("fused step with use_pallas_cell=True holds "
                             "no tpu_custom_call")

    # and the raw cell at a serving block
    rng = np.random.default_rng(0)
    layer = pred.params["lstm"][0]
    x, h, c = (jnp.asarray(rng.normal(size=(128, 32)), f32)
               for _ in range(3))
    args = (x, h, c, layer["wx"], layer["wh"], layer["b"])
    with jax.default_matmul_precision(net.MATMUL_PRECISION):
        hk, ck = jax.jit(lambda *a: lstm_cell(
            *a, interpret=sz.pallas == "interpret"))(*args)
        hr, cr = jax.jit(lstm_cell_ref)(*args)
    cell_err = max(float(jnp.abs(hk - hr).max()),
                   float(jnp.abs(ck - cr).max()))
    log(f"kernel: fused step with the Pallas cell holds tpu_custom_call "
        f"{'tpu_custom_call' in text}; E_S max rel err vs the jnp cell "
        f"{worst:.3e} over {len(picks)} intervals (Tier-1 bound); raw "
        f"cell (128x32) max abs err vs lstm_cell_ref {cell_err:.3e}")
    return worst


def run(sz: Sizing) -> dict:
    """Every phase after the device check; returns what was measured."""
    import jax
    from repro import jax_runtime
    global _CLOCK, _DEVICE
    if jax.default_backend() != "tpu":      # a CPU rehearsal
        _CLOCK = "host timing (cpu rehearsal, not a device number)"
        _DEVICE = "the cpu"
    log(f"compile cache: {jax_runtime.enable_compile_cache()}")
    cfg, tech = phase_pretrain(sz)
    calls, drift, e_drift = phase_simulate(sz, cfg, tech)
    stats = phase_service(sz, cfg, tech)
    kernel = phase_kernel(sz, cfg, tech, calls)
    return {"tier1": drift, "tier1_eager": e_drift, "kernel_rel": kernel,
            "service": stats}


def main() -> int:
    t0 = time.perf_counter()
    device = device_check()
    run(Sizing())
    log(f"chip timing: whole smoke {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    # before JAX is first imported: a TPU backend that fails to start
    # raises instead of falling back to the CPU
    os.environ.setdefault("JAX_PLATFORMS", "tpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        sys.exit(main())
    except NoChip as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        sys.exit(2)
    except Exception as e:
        traceback.print_exc()
        print(f"chip_smoke FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        sys.exit(1)
