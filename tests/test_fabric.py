"""Remote elastic sweep fabric: protocol, scheduling, fault tolerance.

Three layers:

  * wire + scheduler unit tests drive ``FabricCoordinator._dispatch``
    directly with a fake clock (lease reclaim, work stealing,
    duplicate-result dedupe, failure poisoning, partial results);
  * an in-thread full-stack test runs ``run(spec, fabric=...)`` against
    a worker living in this process (fast, no spawn cost);
  * real multi-process tests spawn 2 node agents and assert the
    acceptance criteria: a >=24-cell grid bitwise-equal to serial, and
    grid completion after one node is SIGKILLed mid-unit (reusing the
    same ``REPRO_TEST_KILL_CELL`` harness as the broken-pool tests).
"""
import io
import json
import multiprocessing
import os
import pathlib
import threading
import time

import pytest

from repro import jax_runtime
from repro.sim import fabric, sweep
from repro.sim.fabric import (FabricCoordinator, FabricWorker,
                              ProtocolError, recv_frame, send_frame,
                              worker_main)
from repro.sim.sweep import (CellResult, SweepSpec,
                             deterministic_summary as _det, run)


def _spec(**kw) -> SweepSpec:
    base = dict(techniques=("none", "sgc"), seeds=(0, 1),
                scenarios=("planetlab",), n_hosts=10, n_intervals=20,
                arrival_rate=0.8, max_workers=1)
    base.update(kw)
    return SweepSpec(**base)


# ------------------------------ wire frames --------------------------------

def test_frame_roundtrip_and_eof():
    buf = io.BytesIO()
    send_frame(buf, {"op": "hello", "node": "n1", "blob": b"\x00\xff"})
    send_frame(buf, {"op": "bye"})
    buf.seek(0)
    assert recv_frame(buf)["blob"] == b"\x00\xff"
    assert recv_frame(buf)["op"] == "bye"
    assert recv_frame(buf) is None          # clean EOF


def test_frame_rejects_oversize_and_truncation():
    import struct
    buf = io.BytesIO(struct.pack(">Q", fabric.MAX_FRAME + 1))
    with pytest.raises(ProtocolError, match="MAX_FRAME"):
        recv_frame(buf)
    buf = io.BytesIO()
    send_frame(buf, {"op": "x"})
    truncated = io.BytesIO(buf.getvalue()[:-2])
    with pytest.raises(ProtocolError, match="mid-frame"):
        recv_frame(truncated)


# --------------------------- scheduler internals ---------------------------

class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


@pytest.fixture
def coord():
    clock = _Clock()
    c = FabricCoordinator(lease_s=30.0, clock=clock)
    c.clock = clock                       # test handle
    yield c
    c.stop()


def _join(c, node):
    c._dispatch({"op": "hello", "node": node, "lanes": 1})
    resp = c._dispatch({"op": "request", "node": node, "epoch": -1})
    assert resp["op"] == "grid"
    return resp["epoch"]


def _pull(c, node, epoch):
    return c._dispatch({"op": "request", "node": node, "epoch": epoch})


def _results_for(cells):
    return [CellResult(sc, tech, seed, {"tasks_done": 1}, 0.0)
            for sc, tech, seed in cells]


def test_lease_reclaim_requeues_stalled_nodes_units(coord):
    coord._load_grid(_spec(seeds=(0,), techniques=("none",)))
    ep = _join(coord, "a")
    got = _pull(coord, "a", ep)
    assert got["op"] == "unit"
    # node a goes silent past its lease; node b joins and inherits the
    # reclaimed unit
    coord.clock.t += coord.lease_s + 1.0
    ep_b = _join(coord, "b")
    got_b = _pull(coord, "b", ep_b)
    assert got_b["op"] == "unit" and got_b["uid"] == got["uid"]
    assert "a" not in coord._nodes        # reaped
    coord._dispatch({"op": "result", "node": "b", "uid": got_b["uid"],
                     "results": _results_for(got_b["cells"])})
    assert coord._grid_done.is_set()


def test_disconnect_requeues_inflight_units(coord):
    coord._load_grid(_spec(seeds=(0,), techniques=("none",)))
    ep = _join(coord, "a")
    got = _pull(coord, "a", ep)
    assert got["op"] == "unit"
    coord._disconnect("a")                # abrupt socket drop
    assert "a" not in coord._nodes
    assert got["uid"] in coord._queue


def test_work_stealing_and_duplicate_result_dropped(coord):
    coord._load_grid(_spec(seeds=(0, 1), techniques=("none",)))
    ep = _join(coord, "a")
    u1 = _pull(coord, "a", ep)
    u2 = _pull(coord, "a", ep)
    assert {u1["op"], u2["op"]} == {"unit"}
    # queue drained: b steals a speculative copy of a's oldest unit
    ep_b = _join(coord, "b")
    stolen = _pull(coord, "b", ep_b)
    assert stolen["op"] == "unit" and stolen["uid"] == u1["uid"]
    # b finishes first; a's duplicate result for the same unit is
    # dropped (first result wins — identical anyway, cells are pure)
    coord._dispatch({"op": "result", "node": "b", "uid": stolen["uid"],
                     "results": _results_for(stolen["cells"])})
    done_before = len(coord._done_cells)
    coord._dispatch({"op": "result", "node": "a", "uid": u1["uid"],
                     "results": _results_for(u1["cells"])})
    assert len(coord._done_cells) == done_before
    coord._dispatch({"op": "result", "node": "a", "uid": u2["uid"],
                     "results": _results_for(u2["cells"])})
    assert coord._grid_done.is_set()


def test_stealing_disabled_yields_wait(coord):
    coord.max_speculate = 0
    coord._load_grid(_spec(seeds=(0,), techniques=("none",)))
    ep = _join(coord, "a")
    assert _pull(coord, "a", ep)["op"] == "unit"
    ep_b = _join(coord, "b")
    assert _pull(coord, "b", ep_b)["op"] == "wait"


def test_partial_result_streams_incrementally(coord):
    spec = _spec(seeds=(0, 1), techniques=("none",))
    coord._load_grid(spec)
    ep = _join(coord, "a")
    got = _pull(coord, "a", ep)
    coord._dispatch({"op": "result", "node": "a", "uid": got["uid"],
                     "results": _results_for(got["cells"])})
    part = coord.partial_result()
    assert 0 < len(part.cells) < len(spec.cells())
    keys = [(c.scenario, c.technique, c.seed) for c in part.cells]
    assert keys == [c for c in spec.cells() if c in set(keys)]  # order


def test_failed_unit_requeues_then_poisons_grid(coord):
    coord._load_grid(_spec(seeds=(0,), techniques=("none",)))
    ep = _join(coord, "a")
    for attempt in range(coord.max_unit_failures):
        got = _pull(coord, "a", ep)
        assert got["op"] == "unit", attempt
        coord._dispatch({"op": "failed", "node": "a", "uid": got["uid"],
                         "detail": "ValueError: boom"})
    assert coord._grid_done.is_set()
    assert "boom" in coord._grid_error


def test_drain_only_after_grid_completes(coord):
    coord._load_grid(_spec(seeds=(0,), techniques=("none",)))
    ep = _join(coord, "a")
    got = _pull(coord, "a", ep)
    coord._dispatch({"op": "result", "node": "a", "uid": got["uid"],
                     "results": _results_for(got["cells"])})
    assert _pull(coord, "a", ep)["op"] == "drain"


# ------------------------------ cache shipping -----------------------------

def test_cache_shipping_roundtrip(tmp_path, monkeypatch):
    # keep the test from pointing the process-wide jax cache at tmp_path
    monkeypatch.setattr(jax_runtime, "enable_compile_cache", lambda: None)
    src = tmp_path / "src-cache"
    src.mkdir()
    (src / "prog_a.bin").write_bytes(b"exec-a")
    sub = src / "sub"
    sub.mkdir()
    (sub / "prog_b.bin").write_bytes(b"exec-b")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(src))
    files = fabric.collect_cache_files()
    assert files == {"prog_a.bin": b"exec-a",
                     os.path.join("sub", "prog_b.bin"): b"exec-b"}
    # worker side: shipped files land in this node's cache dir
    dst = tmp_path / "dst-cache"
    dst.mkdir()
    (dst / "prog_a.bin").write_bytes(b"local-wins")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(dst))
    path = fabric.install_cache_files(files)
    assert path == str(dst)
    # existing files never overwritten; missing ones shipped in
    assert (dst / "prog_a.bin").read_bytes() == b"local-wins"
    assert (dst / "sub" / "prog_b.bin").read_bytes() == b"exec-b"


def test_collect_cache_files_empty_when_unset(tmp_path, monkeypatch):
    # env unset and the default directory not yet created: nothing to ship
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax_runtime, "DEFAULT_CACHE_DIR",
                        tmp_path / "never-created")
    assert fabric.collect_cache_files() == {}
    assert fabric.install_cache_files({}) is None


def test_compile_cache_env_var_wins_over_default(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    assert jax_runtime.compile_cache_dir() == str(tmp_path / "c")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    assert jax_runtime.compile_cache_dir() == str(
        jax_runtime.DEFAULT_CACHE_DIR)


def test_compile_cache_default_is_fixed_and_inside_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = pathlib.Path(__file__).resolve().parents[1]
    path = pathlib.Path(jax_runtime.compile_cache_dir())
    assert path == checkout / ".jax_cache"
    assert jax_runtime.compile_cache_dir() == str(path)   # no per-call name
    ignored = (checkout / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


# ------------------------------ CLI helpers --------------------------------

def test_spec_from_json_roundtrip(tmp_path):
    path = tmp_path / "grid.json"
    path.write_text(json.dumps({
        "techniques": ["none", "sgc"], "seeds": [0, 1],
        "scenarios": ["planetlab"], "n_hosts": 10, "n_intervals": 20}))
    spec = fabric._spec_from_json(str(path))
    assert spec.techniques == ("none", "sgc") and spec.n_hosts == 10
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"nope": 1}))
    with pytest.raises(ValueError, match="nope"):
        fabric._spec_from_json(str(bad))
    assert fabric._parse_bind(":0") == ("127.0.0.1", 0)
    assert fabric._parse_bind("10.0.0.2:9999") == ("10.0.0.2", 9999)


# ------------------------- full stack, in-thread ---------------------------

def test_fabric_run_in_thread_bitwise_equals_serial():
    spec = _spec()
    serial = run(spec)
    with FabricCoordinator(lease_s=30.0) as coord:
        w = FabricWorker(coord.host, coord.port, node="t1",
                         exit_on_drain=False)
        th = threading.Thread(target=w.run, daemon=True)
        th.start()
        try:
            res = run(spec, fabric=coord)
        finally:
            w.stop()
    assert [(c.scenario, c.technique, c.seed) for c in res.cells] == \
        spec.cells()
    for a, b in zip(serial.cells, res.cells):
        assert _det(a.summary) == _det(b.summary)
    th.join(timeout=10)


def test_worker_runs_inline_on_accelerator(monkeypatch):
    """One process per chip: an agent asked for 4 lanes on an
    accelerator backend runs every unit itself instead of spawning a
    local pool whose children would need the chip."""
    monkeypatch.setattr(jax_runtime, "on_accelerator", lambda: True)

    def no_pool(self):
        raise AssertionError("spawned a local pool on an accelerator")
    monkeypatch.setattr(FabricWorker, "_local_pool", no_pool)
    spec = _spec(seeds=(0,))
    serial = run(spec)
    with FabricCoordinator(lease_s=30.0) as coord:
        w = FabricWorker(coord.host, coord.port, node="chip", lanes=4,
                         exit_on_drain=False)
        th = threading.Thread(target=w.run, daemon=True)
        th.start()
        try:
            res = run(spec, fabric=coord)
        finally:
            w.stop()
    assert w.lanes == 1
    for a, b in zip(serial.cells, res.cells):
        assert _det(a.summary) == _det(b.summary)
    th.join(timeout=10)


def test_run_grid_timeout_keeps_partial(coord):
    spec = _spec(seeds=(0,), techniques=("none",))
    with pytest.raises(TimeoutError, match="partial_result"):
        coord.run_grid(spec, timeout=0.5)    # no workers ever join
    assert coord.partial_result().cells == []


# ------------------------- full stack, multi-process -----------------------

def _spawn_workers(coord, n, **kw):
    ctx = multiprocessing.get_context("spawn")
    procs = [ctx.Process(target=worker_main,
                         args=(coord.host, coord.port),
                         kwargs=dict(node=f"node{i}", lanes=1, **kw),
                         daemon=True)
             for i in range(n)]
    for p in procs:
        p.start()
    return procs


def _reap_workers(procs, timeout=60):
    for p in procs:
        p.join(timeout=timeout)
        if p.is_alive():
            p.kill()
            p.join(timeout=5)


def test_fabric_two_nodes_bitwise_equals_serial_24_cells():
    """Acceptance: a localhost 2-node fabric run of a >=24-cell grid is
    bitwise-identical to serial ``run()`` on deterministic_summary."""
    spec = _spec(techniques=("none", "sgc"),
                 scenarios=("planetlab", "fault-storm"),
                 seeds=(0, 1, 2, 3, 4, 5))
    assert len(spec.cells()) >= 24
    serial = run(spec)
    with FabricCoordinator(lease_s=60.0) as coord:
        procs = _spawn_workers(coord, 2)
        try:
            res = run(spec, fabric=coord)
        finally:
            _reap_workers(procs)
    assert [(c.scenario, c.technique, c.seed) for c in res.cells] == \
        spec.cells()
    for a, b in zip(serial.cells, res.cells):
        assert _det(a.summary) == _det(b.summary), (a.scenario,
                                                    a.technique, a.seed)


def test_fabric_completes_after_node_killed_mid_grid(tmp_path,
                                                     monkeypatch):
    """Acceptance: SIGKILL one node mid-grid; the lease/disconnect
    reclaim requeues its in-flight unit and the surviving node finishes
    every cell, still bitwise-equal to serial.  Reuses the same
    ``REPRO_TEST_KILL_CELL`` harness as the broken-pool tests."""
    spec = _spec(techniques=("none", "sgc"),
                 scenarios=("planetlab", "fault-storm"),
                 seeds=(0, 1, 2))
    serial = run(spec)                    # env not armed yet: no kill
    marker = tmp_path / "killed-once"
    monkeypatch.setenv("REPRO_TEST_KILL_CELL",
                       f"fault-storm:sgc:1:{marker}")
    with FabricCoordinator(lease_s=60.0) as coord:
        procs = _spawn_workers(coord, 2)
        try:
            res = run(spec, fabric=coord)
        finally:
            _reap_workers(procs)
    assert marker.exists(), "the kill drill never fired"
    assert any(p.exitcode not in (0, None) for p in procs), \
        "no node actually died"
    assert [(c.scenario, c.technique, c.seed) for c in res.cells] == \
        spec.cells()
    for a, b in zip(serial.cells, res.cells):
        assert _det(a.summary) == _det(b.summary), (a.scenario,
                                                    a.technique, a.seed)


def test_worker_gives_up_when_coordinator_gone():
    coord = FabricCoordinator().start()
    w = FabricWorker(coord.host, coord.port, node="w",
                     reconnect_tries=2, reconnect_delay_s=0.05)
    w._connect()
    coord.stop()
    w._file = None                        # socket dropped with the server
    t0 = time.perf_counter()
    with pytest.raises(ConnectionError, match="unreachable"):
        w._request({"op": "request", "node": "w", "epoch": -1})
    assert time.perf_counter() - t0 < 30  # bounded, not an endless retry


def test_two_sequential_grids_same_fabric(coord):
    """The coordinator outlives a grid: epoch bumps and the same node
    serves the next one (the persistent-pool analogue)."""
    for seeds in ((0,), (1,)):
        coord._load_grid(_spec(seeds=seeds, techniques=("none",)))
        ep = _join(coord, "a")
        while True:
            got = _pull(coord, "a", ep)
            if got["op"] == "drain":
                break
            assert got["op"] == "unit"
            coord._dispatch({"op": "result", "node": "a",
                             "uid": got["uid"],
                             "results": _results_for(got["cells"])})
        assert coord._grid_done.is_set()
    assert coord._epoch == 2


def test_run_cell_pure_across_processes_spot_check():
    """One cell run here vs in a fabric unit must agree exactly — the
    purity every reclaim/steal/duplicate decision rests on."""
    spec = _spec(seeds=(0,), techniques=("none",))
    a = sweep.run_cell(spec, "planetlab", "none", 0)
    b = sweep._run_unit(spec, (("planetlab", "none", 0),), {})[0]
    assert _det(a.summary) == _det(b.summary)
