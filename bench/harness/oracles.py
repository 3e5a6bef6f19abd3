"""The plain reference of the service cells, and the comparison that
decides ``correct``.  Nothing here imports the program: the bound is the
quantization arithmetic of the configuration as stated (the same formula
as ``launch/serve_agg.run_load``'s oracle), and a histogram is
``np.histogram``.

The control (``--control 1``) puts the reference, computed one precision
lower, in the program's place: contributions rounded to bfloat16 before
they are summed or binned.  It has to fail the same comparison.
"""
from __future__ import annotations

import math

import ml_dtypes
import numpy as np

F32_EPS = float(np.finfo(np.float32).eps)

# Limits of the numbers compared.  err_over_tol: the largest gap between a
# revealed sum and the float64 sum, as a share of the quantization bound
# the configuration states (clip, guard bits, n), which is the limit.
# wrong_bins: bins whose count differs from np.histogram's; a count is
# exact or wrong.  unrevealed: sessions that never revealed.  absorbed:
# retries, bisections, quarantines and degraded batches, each of which
# hides a device fault.  window_compiles: programs traced, compiled or
# loaded from the compile cache inside the window, where set-up should
# have left none.
LIMITS = {"err_over_tol": 1.0, "wrong_bins": 0, "unrevealed": 0,
          "absorbed": 0, "window_compiles": 0}


def vector_tolerance(n_nodes: int, clip: float, guard_bits: int) -> float:
    """Largest error an exact fixed-point sum may show: half a quantum per
    contribution, plus the float32 rounding of the revealed value."""
    head = max(1, math.ceil(math.log2(max(n_nodes, 2)))) + guard_bits
    scale = float(2 ** (31 - head)) / clip
    return 0.5 * n_nodes / scale + n_nodes * clip * F32_EPS


def vector_error(got: np.ndarray, want: np.ndarray) -> float:
    """Largest elementwise gap between a revealed sum and the float64 sum."""
    return float(np.abs(np.asarray(got, np.float64) - want).max())


def histogram(values: np.ndarray, bins: int) -> np.ndarray:
    return np.histogram(np.clip(values, 0.0, 1.0), bins=bins,
                        range=(0.0, 1.0))[0]


def wrong_bins(got, values: np.ndarray, bins: int) -> int:
    """Bins whose revealed count differs from the reference's."""
    got = np.asarray(got)
    want = histogram(values, bins)
    if got.shape != want.shape:
        return bins
    return int(np.count_nonzero(got != want))


def bf16(x: np.ndarray) -> np.ndarray:
    return np.asarray(x).astype(ml_dtypes.bfloat16).astype(np.float32)


def control_vector(x: np.ndarray) -> np.ndarray:
    """The reference one precision lower: bfloat16 contributions, summed
    in float32, row block by row block."""
    out = np.zeros(x.shape[1], np.float32)
    for lo in range(0, x.shape[0], 32):
        out += bf16(x[lo:lo + 32]).sum(0, dtype=np.float32)
    return out


def control_histogram(values: np.ndarray, bins: int) -> np.ndarray:
    """The reference one precision lower: bfloat16 values, binned."""
    return histogram(bf16(values).astype(np.float64), bins)


def verdict(checks: dict) -> bool:
    """``correct``: every number compared is within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())
