"""The plain reference of the service cells and its control, at a tiny
size: the bound matches the program's own account of its quantization,
an exact fixed-point sum passes, and the reference one precision lower
fails."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import loadgen, oracles  # noqa: E402


@pytest.mark.parametrize("n,clip,guard", [(16, 1.0, 2), (256, 1.0, 2),
                                          (256, 8.0, 3)])
def test_bound_is_the_configurations_quantization_bound(n, clip, guard):
    from repro.core.masking import MaskConfig, quantization_error_bound
    want = (quantization_error_bound(MaskConfig(n_nodes=n, clip=clip,
                                                guard_bits=guard))
            + n * clip * float(np.finfo(np.float32).eps))
    assert oracles.vector_tolerance(n, clip, guard) == pytest.approx(want)


def _fixed_point_sum(x, n, clip=1.0, guard=2):
    head = int(np.ceil(np.log2(n))) + guard
    scale = 2.0 ** (31 - head) / clip
    q = np.round(np.clip(x, -clip, clip) * scale).astype(np.int64)
    return (q.sum(0) / scale).astype(np.float32)


def test_exact_sum_passes_and_control_fails():
    rng = np.random.default_rng(0)
    x = (rng.random((16, 4096), dtype=np.float32) * 2 - 1)
    want = x.sum(0, dtype=np.float64)
    tol = oracles.vector_tolerance(16, 1.0, 2)
    assert oracles.vector_error(_fixed_point_sum(x, 16), want) <= tol
    assert oracles.vector_error(oracles.control_vector(x), want) > 10 * tol


def test_histogram_oracle_and_its_control():
    v = np.random.default_rng(1).random((32, 256))
    for row in v:
        want = np.histogram(row, bins=64, range=(0.0, 1.0))[0]
        assert oracles.wrong_bins(want, row, 64) == 0
    wrong = sum(oracles.wrong_bins(oracles.control_histogram(row, 64), row,
                                   64) for row in v)
    assert wrong > 0
    assert oracles.wrong_bins(np.zeros(16), v[0], 64) == 64


def test_verdict_holds_every_number_to_its_limit():
    ok = {"a": {"value": 0.5, "limit": 1.0}, "b": {"value": 0, "limit": 0}}
    assert oracles.verdict(ok)
    assert not oracles.verdict(dict(ok, b={"value": 1, "limit": 0}))


def test_same_seed_same_inputs_and_fixed_arrivals():
    t = {"loop": "open", "rate_per_s": 100, "arrival_seed": 7,
         "payload": {"kind": "histogram", "bins": 8},
         "batching": {"max_batch": 4}}
    a = loadgen.make_load(t, n_nodes=8, clip=1.0, seed=2**40 + 3, seconds=2)
    b = loadgen.make_load(t, n_nodes=8, clip=1.0, seed=2**40 + 3, seconds=2)
    c = loadgen.make_load(t, n_nodes=8, clip=1.0, seed=5, seconds=2)
    assert np.array_equal(a.due, b.due) and np.array_equal(a.values, b.values)
    # another seed: the same set of gaps in another order
    assert len(a.due) == len(c.due) == 200
    ga, gc = np.diff(a.due), np.diff(c.due)
    assert not np.array_equal(ga, gc)
    assert a.due[-1] < 2.0 and c.due[-1] < 2.0
    assert np.allclose(np.sort(np.append(ga, 2.0 - a.due[-1])),
                       np.sort(np.append(gc, 2.0 - c.due[-1])))


def test_every_vector_session_has_a_sum_of_its_own():
    # a window's sessions and the warm-up's (negative indices) all differ
    # by far more than the bound, so an answer served to the wrong
    # session, or left over from the warm-up, reads as wrong
    t = {"loop": "closed", "clients": 2,
         "payload": {"kind": "vector", "elems": 256, "pool": 2},
         "batching": {"max_batch": 1}}
    load = loadgen.make_load(t, n_nodes=8, clip=1.0, seed=2**40 + 5,
                             seconds=1)
    tol = oracles.vector_tolerance(8, 1.0, 2)
    idx = list(range(-3, 9))
    sums = {i: load.vector_sum(i) for i in idx}
    for i in idx:
        x, j, row = load.vector(i)
        full = x.astype(np.float64)
        full[j] = row
        assert np.allclose(full.sum(0), sums[i])
        for k in idx:
            if k != i:
                assert oracles.vector_error(sums[i], sums[k]) > 100 * tol
