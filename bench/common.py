"""What every cell shares: the spec, seeds, the result line.

Nothing here imports JAX at module level: the device check decides how
JAX starts.
"""
from __future__ import annotations

import json
import math
import pathlib
import sys
import zlib

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class SpecError(ValueError):
    pass


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(name: str) -> tuple[dict, dict, dict]:
    """(workload entry, configuration file, traffic file) of one cell."""
    s = spec()
    wl = [w for w in s["workloads"] if w["name"] == name]
    if not wl:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    wl = wl[0]
    cfgs = [c for c in s["configs"] if c["name"] == wl["config"]]
    if not cfgs:
        raise SpecError(f"workload {name!r} names no known config")
    config = load_json(ROOT / cfgs[0]["file"])
    traffic = load_json(BENCH / "traffic" / f"{wl['traffic']}.json")
    return wl, config, traffic


def metrics_for(name: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries a cell reports."""
    return [m for m in spec()[kind]
            if "workloads" not in m or name in m["workloads"]]


def metric_reader(name: str):
    """The per-layer reader ``bench/metrics/<name>.py``: its ``read(ctx)``
    returns the metric's value, or None where it finds nothing to read."""
    import importlib.util
    path = BENCH / "metrics" / f"{name}.py"
    mod_name = "bench_metric_" + name.replace(".", "_").replace("-", "_")
    sp = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise SpecError(f"device {device_kind!r} is not in bench/peaks.json")
    return table[device_kind]


def seed_int(seed: int, *salt) -> int:
    """A 32-bit integer derived from a seed of any size and a salt.
    ``jax.random.PRNGKey`` silently truncates seeds past 32 bits."""
    words = [abs(int(seed)), int(seed < 0)]
    words += [zlib.crc32(str(s).encode()) for s in salt]
    return int(np.random.SeedSequence(words).generate_state(1)[0])


def rng(seed: int, *salt) -> np.random.Generator:
    return np.random.default_rng(seed_int(seed, *salt))


def p95(values) -> float:
    v = np.asarray(values, np.float64)
    return float(np.percentile(v, 95)) if v.size else math.nan


def correct(checks: dict) -> bool:
    """Every compared number within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def emit(result: dict, checks: dict) -> None:
    """The run's last lines: each compared number beside its limit on
    standard error, and the result line (``check`` last) on stdout."""
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    out = dict(result)
    out["check"] = checks
    print(json.dumps(out), flush=True)
