"""A whole run of each service cell, at a tiny size on the CPU, with the
harness's look for a chip skipped: sound, it is correct; with the
reference one precision lower in the program's place (the control), or
with the timed path broken underneath in each way the cell can break, it
is not.  And the command itself refuses to give a result without a chip,
or in a checkout that holds only the benchmark."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

FL = "committee-256.fl-round"
TEL = "committee-256.telemetry-hist"        # held out of BENCHMARK.json


def _run_module():
    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _spec():
    from harness import spec
    return spec.with_held(spec.load_spec())


def _tiny(cell):
    """The cell's own files, cut to a size the CPU runs in seconds."""
    from harness import spec
    s = _spec()
    w = spec.cell(s, cell)
    config = dict(spec.config_of(s, w), n_nodes=16)
    traffic = json.loads(json.dumps(spec.traffic_of(w)))
    if traffic["payload"]["kind"] == "vector":
        traffic["payload"]["elems"] = 1024
    else:
        traffic["rate_per_s"] = 40
        traffic["batching"]["max_batch"] = 3
    return config, traffic


def _run(cell, control=False):
    config, traffic = _tiny(cell)
    return _run_module().run_cell(cell, 2**40 + 11, 0.5, False,
                                  control=control, check_device=False,
                                  config=config, traffic=traffic,
                                  bench=_spec())


def _altered_answer(mp):
    from repro.service.session import Session
    reveal = Session.reveal

    def altered(self, revealed):
        out = np.array(revealed, np.float32, copy=True)
        out[0] += 1.0
        reveal(self, out)
    mp.setattr(Session, "reveal", altered)


def _half_left_out(mp):
    from repro.service.session import Session
    fill = Session.fill_payload_rows

    def half(self, out, start, row_elems):
        k = fill(self, out, start, row_elems)
        rows = out[start:start + k]
        rows[:, 1::2] = 0.0              # odd slots left out,
        rows[:, 0::2] *= 2.0             # the rest scaled up to the mean
        return k
    mp.setattr(Session, "fill_payload_rows", half)


def _no_exchange(mp):
    from repro.core import engine

    def own_rows(self, x3, rnd, shift):
        S, n, T = x3.shape
        return x3.reshape(S * n, T)
    mp.setattr(engine.SimTransport, "_shift", own_rows)


def _state_unchanged(mp):
    from repro.core import engine
    mp.setattr(engine, "vote_combine_batch_fn",
               lambda copies, acc, impl=None: acc)


def _another_sessions_answer(mp):
    # every session is handed the answer the session before it revealed
    # (the first in the window gets the last warm-up session's)
    from repro.service.session import Session
    reveal = Session.reveal
    last = []

    def stale(self, revealed):
        out = np.array(revealed, copy=True)
        reveal(self, last[-1] if last else out)
        last[:] = [out]
    mp.setattr(Session, "reveal", stale)


FAULTS = {"altered_answer": _altered_answer, "half_left_out": _half_left_out,
          "no_exchange": _no_exchange, "state_unchanged": _state_unchanged,
          "another_sessions_answer": _another_sessions_answer}


@pytest.mark.parametrize("cell", [FL, TEL])
def test_sound_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    names = set(out["metrics"])
    assert "setup_s" in names and len(names) == 2
    assert out["device"]["count"] == 1


@pytest.mark.parametrize("cell", [FL, TEL])
def test_control_is_not_correct(cell):
    out = _run(cell, control=True)
    assert not out["correct"]
    key = "err_over_tol" if cell == FL else "wrong_bins"
    assert out["checks"][key]["value"] > out["checks"][key]["limit"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", [FL, TEL])
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = _run(cell)
    assert not out["correct"], (fault, out["checks"])


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", FL, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_chip_no_result():
    p = _command(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_gives_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
