"""The training cells' harness on the CPU.  A whole run of the
mamba2-370m-dp4 cell at smoke widths over four forced host devices (in a
process of its own, ``train_tiny.py``): sound, it is correct; with the
control, or with the timed path broken underneath in each way the cell
can break, it is not; a further training configuration runs from its own
entries alone.  The plain reference against the program and against the
plain recurrence; the step's FLOPs against a hand count; and the service
cells still resolve to the service driver."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

from harness import counts, spec  # noqa: E402
from harness import ref_mamba2 as R  # noqa: E402

import train_tiny  # noqa: E402

FAULTS = sorted(train_tiny.FAULTS)


def _tiny(scenario: str, devices: int = 4) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    p = subprocess.run([sys.executable, str(BENCH / "tests" / "train_tiny.py"),
                        scenario], env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_train_cell_sound_run_is_correct():
    out = _tiny("sound")
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert out["device"]["count"] == 4
    assert set(out["checks"]) == set(
        spec.driver({"kind": "train"}).LIMITS)


def test_train_control_is_not_correct():
    out = _tiny("control")
    assert not out["correct"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("fault", FAULTS)
def test_train_broken_step_is_not_correct(fault):
    out = _tiny(fault)
    assert not out["correct"], (fault, out["checks"])


def test_another_training_configuration_needs_only_its_entries():
    out = _tiny("another", devices=2)
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 2
    assert set(out["metrics"]) == {"setup_s", "train_tokens_per_s"}


def test_reference_ranks_add_up_to_the_summed_pass():
    out = _tiny("ranks")
    assert out["ranks"] == 4
    assert out["sum_err"] <= 1e-5
    assert out["first_rows_err"] <= 1e-5


SMOKE = dict(train_tiny.SMOKE, tie_embeddings=True, norm_eps=1e-6)


def _program(dtype="float32"):
    from harness import train
    config = {"arch": "mamba2-370m",
              "model": dict(SMOKE, dtype=dtype, param_dtype="float32",
                            opt_state_dtype="float32", remat=False)}
    return train.program_config(config)


def test_reference_weights_have_the_program_layout():
    import jax
    from repro.launch import steps
    got = jax.eval_shape(lambda: R.init(SMOKE, R.key_of(7)))
    want = steps.abstract_params(_program())
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert [x.shape for x in jax.tree.leaves(got)] == \
        [x.shape for x in jax.tree.leaves(want)]


def test_reference_matches_the_program_on_seeded_weights():
    """In float32 the program's loss and gradients equal the reference's
    to float32 rounding: the same model, written twice."""
    import jax
    from repro.models import model
    params = jax.jit(lambda k: R.init(SMOKE, k))(R.key_of(2**40 + 5))
    ids = np.random.default_rng(0).integers(0, 128, size=(3, 97),
                                            dtype=np.int32)
    b = {"tokens": ids[:, :-1], "labels": ids[:, 1:]}
    cfg = _program()
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(lambda p: model.loss_fn(
            cfg, p, b, total_tokens=288))(params)
        lr, gr = jax.value_and_grad(lambda p: R.loss(
            SMOKE, p, b["tokens"], b["labels"], 288))(params)
    assert abs(float(lp) - float(lr)) <= 1e-5 * abs(float(lr))
    for a, c in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        a, c = np.asarray(a), np.asarray(c)
        assert np.abs(a - c).max() <= 1e-4 * np.abs(c).max()


def test_ssd_by_chunks_equals_the_recurrence():
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    b, l, h, p, n = 2, 24, 3, 4, 5
    x = rng.standard_normal((b, l, h, p))
    dt = rng.uniform(0.01, 0.5, (b, l, h))
    A = -rng.uniform(1, 16, h)
    B = rng.standard_normal((b, l, n))
    C = rng.standard_normal((b, l, n))
    s = np.zeros((b, h, p, n))
    want = np.zeros((b, l, h, p))
    for t in range(l):
        s = (s * np.exp(dt[:, t] * A)[..., None, None]
             + np.einsum("bh,bhp,bn->bhpn", dt[:, t], x[:, t], B[:, t]))
        want[:, t] = np.einsum("bhpn,bn->bhp", s, C[:, t])
    f = lambda a: jnp.asarray(a, jnp.float32)
    for chunk in (4, 8, 24):
        got = R.ssd(f(x * dt[..., None]), f(dt * A), f(B), f(C), chunk)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                                   atol=1e-4)


def test_train_step_flops_by_hand_at_smoke_widths():
    # d 64, 2 layers, vocab 128, d_state 32, head dim 16, expand 2: inner
    # 128 wide, 8 heads; chunk 32 for 64-token rows
    in_proj = 64 * (128 + 128 + 32 + 32 + 8)
    out_proj = 128 * 64
    params = 2 * (in_proj + out_proj) + 128 * 64
    assert counts.matmul_params(SMOKE) == params == 66560
    ssd = (2 * 32 * 32            # scores C B^T over a 32-chunk
           + 2 * 32 * 8 * 16      # diagonal blocks' outputs
           + 2 * 32 * 8 * 16      # chunk states
           + 2 * 32 * 8 * 16)     # states' outputs
    assert counts.ssd_flops_per_token(SMOKE, 64) == ssd == 26624
    tokens = 8 * 64
    assert counts.train_step_flops(SMOKE, 8, 64) == \
        tokens * (6 * params + 3 * 2 * ssd) == 286261248


def test_service_cells_resolve_to_the_service_driver():
    from harness import e2e, oracles, service, train
    s = spec.with_held(spec.load_spec())
    for w in s["workloads"]:
        config = spec.config_of(s, w)
        want = train if w["config"] == "mamba2-370m-dp4" else service
        assert spec.driver(config) is want
    assert service.E2E is e2e.E2E and service.LIMITS is oracles.LIMITS
    assert set(service.E2E) == {"sessions_per_s", "reveal_p95_ms",
                                "setup_s"}
    assert service.LIMITS == {"err_over_tol": 1.0, "wrong_bins": 0,
                              "unrevealed": 0, "absorbed": 0,
                              "window_compiles": 0}
    with pytest.raises(ValueError):
        spec.driver({"kind": "../run"})
