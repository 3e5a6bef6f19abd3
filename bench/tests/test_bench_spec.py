"""BENCHMARK.json loads, every cell resolves to its files, and names,
units and the metrics each cell reports keep the benchmark's rules."""
import json
import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import spec  # noqa: E402

SPEC = spec.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
HELD = spec.with_held(SPEC)       # with the cells held out of BENCHMARK.json
CELLS = [w["name"] for w in HELD["workloads"]]


def test_top_level_keys_and_sizes():
    assert set(SPEC) == KEYS
    assert (BENCH.parent / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= SPEC["run_seconds"] <= 51
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]


def test_names_units_and_entry_keys():
    names = []
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in HELD["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200
    for kind in ("end_to_end", "per_layer"):
        for m in HELD[kind]:
            allowed = ({"name", "unit", "better", "bound", "source"}
                       if kind == "end_to_end" else
                       {"name", "unit", "better", "source", "layer", "moves"})
            assert set(m) - {"workloads"} == allowed
            assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    names += CELLS + [m["name"] for k in ("end_to_end", "per_layer")
                      for m in HELD[k]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))


def test_bounds():
    for m in HELD["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    w = spec.cell(HELD, cell)
    config = spec.config_of(HELD, w)
    traffic = spec.traffic_of(w)
    assert config["name"] == w["config"]
    assert traffic["loop"] in ("closed", "open")
    assert config["n_nodes"] % config["cluster_size"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    w = spec.cell(HELD, cell)
    ends = [m["name"] for m in spec.metrics_of(HELD, w, "end_to_end")]
    layers = spec.metrics_of(HELD, w, "per_layer")
    assert "setup_s" in ends and len(ends) >= 2 and layers
    assert all(n in spec.driver(spec.config_of(HELD, w)).E2E for n in ends)
    for m in layers:                      # moves a metric the cell reports
        assert m["moves"] in ends


def test_every_per_layer_metric_has_a_reader():
    for m in HELD["per_layer"]:
        assert callable(spec.reader(m["name"]))
        assert m["workloads"] and set(m["workloads"]) <= set(CELLS)


def test_configuration_files_are_unique_and_under_paths():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for f in files:
        assert f.startswith("bench/")
        json.loads((BENCH.parent / f).read_text())
