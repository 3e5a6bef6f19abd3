"""The chip smoke's guards, and the service load drivers it runs.

``chip_smoke.py`` must refuse to report a result anywhere but on a TPU,
and the ``serve_agg`` drivers it calls must check every revealed session
against a plain numpy reference."""
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from repro.api import SecureAggregator, Topology, Wire
from repro.launch.serve_agg import run_func_load, run_load
from repro.service import BatchingConfig

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _smoke(cwd, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chip_smoke.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("args", [(), ("--four-chips",)])
def test_chip_smoke_fails_without_tpu(args):
    r = _smoke(os.path.abspath(ROOT), *args)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_chip_smoke_fails_outside_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = _smoke(str(tmp_path))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("transport", ["full", "digest"])
def test_run_load_checks_float_updates_against_bound(transport):
    """Float updates from the clip range reveal within the quantization
    bound, one session per batch, with nothing retried."""
    agg = SecureAggregator(topology=Topology(n_nodes=16),
                           wire=Wire(transport=transport),
                           batching=BatchingConfig(max_batch=1))
    out = run_load(agg, None, sessions=3, elems=1000, churn_every=0)
    assert out["revealed"] == out["exact"] == 3
    res = out["stats"]["resilience"]
    assert res["retries"] == res["quarantined"] == 0
    # the payloads are floats: a 0/1-only draw would make the check vacuous
    assert np.any(np.abs(agg.result(0) - np.round(agg.result(0))) > 1e-3)


def test_run_func_load_histograms_match_numpy():
    agg = SecureAggregator(
        topology=Topology(n_nodes=16),
        batching=BatchingConfig(max_batch=8, max_age=3600.0))
    out = run_func_load(agg, None, sessions=16, fn="histogram", bins=8,
                        steps=256, k=1, churn_every=0, seed=3)
    assert out["revealed"] == out["exact"] == 16
    assert tuple(out["stats"]["batches"]["sizes"]) == (8, 8)
