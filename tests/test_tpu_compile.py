"""Ahead-of-time compiles of the secure-aggregation kernels for a TPU v5e
that is described, not attached.

Interpret mode accepts programs that Mosaic refuses (an unsigned vector
min/max once kept the vote kernel off the chip), so every main-path
kernel is lowered and compiled natively here at the engine's widths: one
batch of S·n = 256 rows by ``chunk_elems`` = 65,536 uint32 elements.
Each compile must contain the Pallas custom call and fit one chip's
16 GB.  Nothing runs; this says nothing about results or time.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.configs import get_smoke_config
from repro.configs.base import ShapeConfig
from repro.core.engine import build_batch_executable
from repro.core.plan import AggConfig, compile_plan
from repro.kernels.secure_agg.secure_agg import (mask_encrypt_batch,
                                                 unmask_decrypt_batch,
                                                 vote_combine,
                                                 vote_combine_rows)
from repro.launch import steps as ST

ROWS, ELEMS = 256, 1 << 16
HBM_BYTES = 16 * 10 ** 9          # TPU v5e: 16 GB of HBM per chip
SCALE, CLIP = float(2 ** 21), 1.0


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used <= HBM_BYTES, used
    return compiled


@pytest.mark.parametrize("mode,cluster_size", [("mask", 0), ("pairwise", 4)])
def test_mask_encrypt_batch_compiles_for_v5e(one_chip, mode, cluster_size):
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compile(
        lambda x, nid, seeds, offs: mask_encrypt_batch(
            x, nid, seeds, SCALE, CLIP, mode=mode, offsets=offs,
            cluster_size=cluster_size, interpret=False),
        sd((ROWS, ELEMS), jnp.float32), sd((ROWS,), jnp.uint32),
        sd((ROWS,), jnp.uint32), sd((ROWS,), jnp.uint32))


def test_unmask_decrypt_batch_compiles_for_v5e(one_chip):
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compile(
        lambda agg, seeds, offs: unmask_decrypt_batch(
            agg, ROWS, seeds, SCALE, offsets=offs, interpret=False),
        sd((ROWS, ELEMS), jnp.uint32), sd((ROWS,), jnp.uint32),
        sd((ROWS,), jnp.uint32))


@pytest.mark.parametrize("r", [3, 5])
def test_vote_combine_compiles_for_v5e(one_chip, r):
    """The flat vote, which a batch of fewer than 8 rows (the secure
    train step's one row a rank) is flattened into."""
    flat = jax.ShapeDtypeStruct((ROWS * ELEMS,), jnp.uint32,
                                sharding=one_chip)
    _compile(lambda *xs: vote_combine(list(xs[:r]), xs[r], interpret=False),
             *[flat] * (r + 1))


@pytest.mark.parametrize("r", [3, 5])
def test_vote_combine_rows_compiles_for_v5e(one_chip, r):
    """The (rows, T) batch is voted in its own layout: the r + 2
    double-buffered operand blocks fit the scoped VMEM."""
    batch = jax.ShapeDtypeStruct((ROWS, ELEMS), jnp.uint32,
                                 sharding=one_chip)
    _compile(lambda *xs: vote_combine_rows(list(xs[:r]), xs[r],
                                           interpret=False),
             *[batch] * (r + 1))


def _entry(hlo: str) -> list:
    """The instructions of the ENTRY computation of an HLO module."""
    lines = hlo.splitlines()
    start = next(i for i, l in enumerate(lines) if l.startswith("ENTRY"))
    end = next(i for i in range(start, len(lines)) if lines[i] == "}")
    return [l.strip() for l in lines[start + 1:end]]


def test_fl_round_executable_votes_without_relayouts(one_chip):
    """The committee-256 batch executable (one session of n = 256 slots,
    Pallas, donated, as the service runs it): each of the ring's 63
    voted rounds is one vote call on the (256, T) rows as they are, and
    no op of the program relayouts a batch into flat (k, 128) tiles."""
    n, T = 256, 8192
    plan = compile_plan(AggConfig(n_nodes=n, kernel_impl="pallas"))
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = build_batch_executable(plan, donate=True).lower(
        sd((1, n, T), jnp.float32), sd((1,), jnp.uint32),
        sd((1,), jnp.uint32), {}).compile()
    ops = _entry(compiled.as_text())
    out = f"u32[{n},{T}]"
    votes = [op for op in ops if "vote_combine" in op
             and 'custom_call_target="tpu_custom_call"' in op]
    assert len(plan.rounds) == 63
    assert len(votes) == 63
    assert all(op.split(" = ", 1)[1].startswith(out + "{") for op in votes)
    tiles = [op for op in ops if re.match(r"\S+ = u32\[\d+,128\]\{", op)]
    assert not tiles, tiles[:3]


@pytest.mark.parametrize("dp", [1, 4])
def test_secure_train_step_compiles_for_v5e(topo, dp):
    """The secure step's shard_map is manual over "data" and automatic
    over "model"; Mosaic refuses a kernel that XLA would have to
    partition, so the kernels must run manual over "model" too."""
    mesh = Mesh(np.array(topo.devices[:dp]).reshape(dp, 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    cfg = dataclasses.replace(get_smoke_config("mamba2-370m"),
                              dp_mode="replicated")
    shape = ShapeConfig("compile", 64, 8, "train")
    agg = AggConfig(n_nodes=4, clip=8.0,
                    kernel_impl="pallas").derive(n_nodes=dp)
    fn, shardings, opt_cfg = ST.build_secure_train_step(
        cfg, mesh, agg, shape=shape, donate=False)
    abstract = (ST.abstract_params(cfg), ST.abstract_opt_state(cfg, opt_cfg),
                ST.input_specs(cfg, shape))
    _compile(fn, *jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        abstract, shardings))
