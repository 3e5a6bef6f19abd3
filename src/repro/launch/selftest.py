"""Multi-device self-test for the distributed secure aggregation path.

Runs with forced host devices (set BEFORE jax import), so it is a
CPU-only tool; on a TPU host, ``chip_smoke.py`` is the check:

    JAX_PLATFORMS=cpu REPRO_SELFTEST_DEVICES=16 python -m repro.launch.selftest

Verifies, for every (schedule x transport x masking) combination:
  * distributed MeshTransport result == single-device SimTransport oracle
    bit-for-bit — including the digest transport, whose hops the oracle
    models faithfully (1 payload + r digests + compiled backup stream)
  * result == plain fp32 sum within the quantization error bound
  * byzantine corruption of a vote-minority is fully corrected
Exit code 0 on success (used as a subprocess test by tests/test_distributed.py).
"""
import os
import sys

_N = int(os.environ.get("REPRO_SELFTEST_DEVICES", "16"))
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={_N} "
    + os.environ.get("XLA_FLAGS", ""))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import AggConfig, Runtime, SecureAggregator  # noqa: E402
from repro.core.byzantine import ByzantineSpec  # noqa: E402
from repro.core.masking import quantization_error_bound  # noqa: E402


def check(name: str, ok: bool, detail: str = ""):
    status = "PASS" if ok else "FAIL"
    print(f"[{status}] {name} {detail}")
    if not ok:
        sys.exit(1)


def run_sim(cfg: AggConfig, xs) -> np.ndarray:
    """Single-device oracle via the facade: (n, T) -> (n, T) results."""
    agg = SecureAggregator(cfg, runtime=Runtime(backend="sim"))
    return np.asarray(agg.allreduce(jnp.asarray(xs)))


def run_mesh(cfg: AggConfig, mesh, axes, xs) -> np.ndarray:
    """Distributed: the same plan under shard_map over a real dp mesh —
    the facade's mesh backend."""
    agg = SecureAggregator(cfg, runtime=Runtime(backend="mesh", mesh=mesh,
                                                dp_axes=axes))
    return np.asarray(agg.allreduce(jnp.asarray(xs)))


def main():
    n = len(jax.devices())
    assert n == _N, (n, _N)
    shape = (n, 1024)
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=shape).astype(np.float32) * 0.3)
    true_sum = np.asarray(xs.sum(axis=0))

    # 2D dp mesh: test multi-axis flat node ids ("pod","data")
    mesh_shapes = [((n,), ("data",))]
    if n % 2 == 0:
        mesh_shapes.append(((2, n // 2), ("pod", "data")))

    for mesh_shape, axes in mesh_shapes:
        mesh = jax.make_mesh(mesh_shape, axes)
        for schedule in ("ring", "tree", "butterfly"):
            for transport in ("full", "digest"):
                for masking in ("global", "pairwise", "none"):
                    cfg = AggConfig(n_nodes=n, cluster_size=4, redundancy=3,
                                    schedule=schedule, transport=transport,
                                    masking=masking, clip=2.0)
                    got = run_mesh(cfg, mesh, axes, xs)
                    bound = quantization_error_bound(cfg.mask_cfg()) * 4
                    err = np.abs(got - true_sum[None]).max()
                    check(f"{axes} {schedule}/{transport}/{masking}",
                          err < bound, f"err={err:.2e} bound={bound:.2e}")
                    sim = run_sim(cfg, xs)
                    dd = np.abs(sim - got).max()
                    check(f"  sim-match {schedule}/{transport}/{masking}",
                          dd == 0.0, f"max|sim-dist|={dd:.2e}")

        # byzantine: corrupt one member per cluster (minority of r=3 votes)
        corrupt = tuple(range(0, n, 4))  # member 0 of each cluster of 4
        for schedule in ("ring", "tree", "butterfly"):
            for transport in ("full", "digest"):
                cfg = AggConfig(n_nodes=n, cluster_size=4, redundancy=3,
                                schedule=schedule, transport=transport,
                                masking="global", clip=2.0,
                                byzantine=ByzantineSpec(corrupt_ranks=corrupt,
                                                        mode="flip"))
                got = run_mesh(cfg, mesh, axes, xs)
                bound = quantization_error_bound(cfg.mask_cfg()) * 4
                err = np.abs(got - true_sum[None]).max()
                check(f"{axes} byzantine {schedule}/{transport}", err < bound,
                      f"err={err:.2e} (vote corrected {len(corrupt)} ranks)")

    print("selftest OK")


if __name__ == "__main__":
    main()
