"""Byzantine-robust training demo: inject gradient-corrupting ranks into
the secure-aggregation ring and show the majority vote keeps training on
the exact baseline trajectory (the paper's correctness property at tensor
scale).

Runs on 8 forced host devices (re-executes itself with XLA_FLAGS set),
so it is a CPU-only demo: run it with JAX_PLATFORMS=cpu.

    PYTHONPATH=src python examples/byzantine_training.py
"""
import os
import sys

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.execv(sys.executable, [sys.executable] + sys.argv)

sys.path.insert(0, "src")

import dataclasses

import numpy as np

from repro.api import AggConfig, Security, Topology
from repro.configs import get_smoke_config
from repro.configs.base import ShapeConfig
from repro.core.byzantine import ByzantineSpec
from repro.launch.mesh import make_host_mesh
from repro.launch.train import train_loop
from repro.optim import adamw


def main():
    cfg = dataclasses.replace(get_smoke_config("olmo-1b"), dtype="float32")
    mesh = make_host_mesh(data=8, model=1)
    shape = ShapeConfig("byz", seq_len=64, global_batch=8, kind="train")
    opt = adamw.OptConfig(lr=2e-3, warmup_steps=5, total_steps=100)
    steps = 12

    print("== baseline (no adversary, plain GSPMD psum) ==")
    base = train_loop(cfg, mesh, steps=steps, shape=shape, opt_cfg=opt,
                      log_every=4)

    # 2 clusters of 4; one corrupt member per cluster (< r/2 of r=3 votes)
    corrupt = (1, 5)
    agg = AggConfig.compose(
        Topology(n_nodes=8, cluster_size=4),
        Security(redundancy=3, clip=8.0,
                 byzantine=ByzantineSpec(corrupt_ranks=corrupt,
                                         mode="garbage")))
    print(f"== secure aggregation with byzantine ranks {corrupt} ==")
    sec = train_loop(cfg, mesh, steps=steps, shape=shape, opt_cfg=opt,
                     secure=True, agg=agg, log_every=4)

    diff = np.max(np.abs(np.asarray(base["losses"])
                         - np.asarray(sec["losses"])))
    print(f"max |loss_base - loss_byzantine_secure| = {diff:.2e}")
    assert diff < 5e-3, "vote failed to correct byzantine gradients!"
    print("majority vote fully corrected the corrupted ring traffic ✓")

    print("== control: same corruption WITHOUT enough redundancy (r=1) ==")
    agg_bad = agg.replace(redundancy=1)
    bad = train_loop(cfg, mesh, steps=steps, shape=shape, opt_cfg=opt,
                     secure=True, agg=agg_bad, log_every=4)
    diff_bad = np.max(np.abs(np.asarray(base["losses"])
                             - np.asarray(bad["losses"])))
    print(f"without voting: max deviation = {diff_bad:.2e} "
          f"({'diverged' if diff_bad > 1e-2 else 'unexpectedly fine'})")


if __name__ == "__main__":
    main()
