"""``BENCHMARK.json`` and the files it names.

A cell ``<config>.<traffic>`` resolves to ``configs/<config>.json`` and
``traffic/<traffic>.json`` under the benchmark's directory, and each
per-layer metric ``<name>`` to the reader ``metrics/<name>.py``, which
defines ``read(run)`` returning a number, or None where the run had
nothing for it to read.  Adding a configuration, a traffic mix or a
metric is adding its file and its entry; nothing here changes.

A configuration's ``kind`` names the driver of its cells,
``harness/<kind>.py`` (``service`` where the file has no ``kind``).  A
driver defines ``Cell`` (set-up, window, comparison), ``E2E`` (the
end-to-end metrics it can report, each ``f(window, setup_s)``) and
``LIMITS`` (the limit of every number its comparison gives); a cell of a
new kind is a new driver file plus its configuration, traffic and
entries.

A cell measured but kept out of ``BENCHMARK.json`` is held in
``held/<cell>.json``, with the entries it would add (``configs``,
``workloads``, ``end_to_end``, ``per_layer``); the benchmark's own runs never read it,
the knee sweep and the harness's tests do.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent      # the benchmark's files
ROOT = BENCH.parent                                  # the checkout


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def with_held(spec: dict) -> dict:
    """``spec`` with the entries of every held cell added."""
    out = {k: list(v) if isinstance(v, list) else v for k, v in spec.items()}
    for path in sorted((BENCH / "held").glob("*.json")):
        held = _json(path)
        for kind in ("configs", "workloads", "end_to_end", "per_layer"):
            out[kind] += held.get(kind, [])
    return out


def cell(spec: dict, workload: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def config_of(spec: dict, w: dict, root: Path = ROOT) -> dict:
    entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    return _json(root / entry["file"])


def kind_of(config: dict) -> str:
    return config.get("kind", "service")


def driver(config: dict):
    """The module ``harness/<kind>.py`` that runs the configuration's
    cells."""
    kind = kind_of(config)
    if not re.fullmatch(r"[a-z][a-z0-9_]*", kind):
        raise ValueError(f"bad configuration kind {kind!r}")
    return importlib.import_module(f"harness.{kind}")


def traffic_of(w: dict) -> dict:
    return _json(BENCH / "traffic" / f"{w['traffic']}.json")


def metrics_of(spec: dict, w: dict, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports: those
    that list it under ``workloads``, and those with no such list."""
    return [m for m in spec[kind]
            if w["name"] in m.get("workloads", [w["name"]])]


def reader(name: str):
    """``read`` of ``metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
