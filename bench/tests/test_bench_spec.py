"""BENCHMARK.json holds to its contract, and every cell resolves to its
configuration, traffic and metric files by name."""
from __future__ import annotations

import json
import pathlib
import re
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import common  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = common.spec()


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert all(_line(w) for w in SPEC["command"])
    assert SPEC["command"][1].startswith("bench/")
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 << 10


@pytest.mark.parametrize("kind,keys", [
    ("configs", {"name", "source", "file", "reduced", "why"}),
    ("workloads", {"name", "config", "traffic", "chips", "why"}),
    ("end_to_end", {"name", "unit", "better", "bound", "source"}),
    ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
])
def test_entries_have_their_keys(kind, keys):
    names = [e["name"] for e in SPEC[kind]]
    assert len(names) == len(set(names))
    for e in SPEC[kind]:
        extra = set(e) - keys
        assert keys <= set(e) and extra <= {"workloads"}, e["name"]
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                             "higher")
        for text in ("why", "layer", "source"):
            if text in e:
                assert _line(e[text]), (e["name"], text)


def test_every_cell_resolves_by_name():
    used = set()
    for w in SPEC["workloads"]:
        wl, config, traffic = common.cell(w["name"])
        assert w["chips"] in (1, 4)
        assert config["name"] == wl["config"]
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (ROOT / "bench" / f"{config['harness']}.py").is_file()
        used.add(w["config"])
        e2e = [m["name"] for m in common.metrics_for(w["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert common.metrics_for(w["name"], "per_layer")
    assert used == {c["name"] for c in SPEC["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_config_files_name_their_source_and_cuts():
    for c in SPEC["configs"]:
        path = ROOT / c["file"]
        assert path.parts[len(ROOT.parts)] == "bench"
        cfg = json.loads(path.read_text())
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert cfg["assumed"] and cfg["precision"]


def test_every_per_layer_metric_has_a_reader():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert callable(common.metric_reader(m["name"]))
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            assert w in cells
            moved = common.metrics_for(w, "end_to_end")
            assert m["moves"] in {x["name"] for x in moved}
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        limit = 0.25
        assert 0.01 <= m["bound"] <= limit


def test_readers_return_nothing_without_data():
    for m in SPEC["per_layer"]:
        assert common.metric_reader(m["name"])({"kind": "none"}) is None


def test_peaks_table_refuses_an_unknown_device():
    assert common.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(common.SpecError):
        common.peaks("some other chip")
