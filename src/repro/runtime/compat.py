"""Mesh builders every driver, benchmark and test shares."""
from __future__ import annotations

import jax
import numpy as np


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the model's sharding
    rules are written for GSPMD propagation, not explicit-axis typing."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def host_mesh(data: int = 1, model: int = 1,
              pod: int = 0) -> jax.sharding.Mesh:
    """The one mesh bootstrap every CLI driver shares (``launch.serve``,
    ``launch.serve_agg``, tests): a small mesh over the host's devices,
    built through :func:`make_mesh`."""
    if pod:
        shape, axes = (pod, data, model), ("pod", "data", "model")
    else:
        shape, axes = (data, model), ("data", "model")
    return make_mesh(shape, axes)


def node_mesh(n_nodes: int, axis: str = "data") -> jax.sharding.Mesh:
    """One-device-per-protocol-node mesh over the first ``n_nodes`` host
    devices — the shared bootstrap for ``MeshTransport`` drivers and
    benches (keeps device ordering / axis naming in one place, like
    :func:`host_mesh` does for the LM drivers)."""
    devs = jax.devices()
    assert len(devs) >= n_nodes, \
        f"mesh transport needs {n_nodes} devices (have {len(devs)})"
    return jax.sharding.Mesh(np.array(devs[:n_nodes]), (axis,))
