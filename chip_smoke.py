"""Smoke run of the secure-aggregation service and the secure train step
on a TPU v5e, through the entry points a user calls.

    python chip_smoke.py               # phases A, B and C on one chip
    python chip_smoke.py --four-chips  # mesh == sim and the dp=4 step

Everything runs in this one process, which must be the only one using
the chip.  The phases:

  A  Federated-learning round: 4 sessions of n=256 protocol slots (64
     clusters x c=4, r=3, ring schedule, full transport, global masking:
     the config defaults) x T=2^20 float32 updates, one session per
     batch, through the ``SecureAggregator`` facade and
     ``launch.serve_agg.run_load``.  Each revealed sum must lie within
     the quantization bound of the float64 sum of the seeded updates.
  B  Private-telemetry histograms: 256 sessions of a 64-bin histogram
     over the same 256 slots, 64 per batch (``run_func_load``), each
     equal to ``np.histogram``.
  C  Secure train step: mamba2-370m at full width, seq_len 1024, global
     batch 8, 3 steps of ``launch.train.train_loop(secure=True)`` against
     the plain step from the same seed.

With ``--four-chips`` only these run, on a 2x2 host:

  (a) the facade's ``mesh`` backend at n=4 (one node per chip) against
      its ``sim`` backend on the same T=2^20 payload, full and digest
      transports: bit-identical, and the mesh result sharded over 4 chips;
  (b) the secure train step at dp=4 against the plain ``psum`` step.

The run exits non-zero, and prints no result line, unless the first
device is a TPU v5 lite, the kernel engine resolves to native Pallas and
the phase-A executable holds the Pallas kernels, every session reveals
exactly, and no retry, bisection, quarantine or degraded batch absorbed a
fault.  Lines that start with ``smoke:`` are smoke output (compile and
wall seconds, peak device bytes), not benchmark metrics.  The last line
is the JSON result.

JAX's persistent compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, and otherwise in ``.jax_cache/`` beside this file.  "cold" compile
seconds are those of a phase's first run in this process, "warm" those of
the same programs again after ``jax.clear_caches()``; each comes with the
count of programs the persistent cache served.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SLOTS = 256                 # protocol slots: 64 clusters x 4
ELEMS = 1 << 20             # float32 update length per client
ROUND_SESSIONS = 4          # phase A sessions, one per batch
HIST_SESSIONS, HIST_BATCH, HIST_BINS = 256, 64, 64
ARCH, SEQ_LEN, BATCH, STEPS = "mamba2-370m", 1024, 8, 3
# 368M parameters in 22 chunks: the engine unrolls one pipeline stage per
# chunk, and the default 2^16 would unroll about 5,600
CHUNK_ELEMS = 1 << 24
# secure vs plain loss: the tolerance the repo's distributed training
# test holds them to (activations are bf16, and the two steps reduce the
# loss and the gradients in different orders)
LOSS_ATOL = 5e-3


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileLog:
    """Seconds XLA spent compiling or loading from the persistent cache,
    and the cache hits among them, from JAX's monitoring events."""

    def __init__(self, jax):
        self.secs, self.count, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self) -> str:
        out = (f"{self.secs:.1f}s ({self.count} programs, "
               f"{self.hits} from cache)")
        self.secs, self.count, self.hits = 0.0, 0, 0
        return out


def peak_bytes(jax) -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def check_service(phase: str, out: dict) -> None:
    """All sessions revealed exactly, and the resilience layer absorbed
    nothing: a retry, bisection or fallback would hide a device fault."""
    res = out["stats"]["resilience"]
    check(out["revealed"] == out["sessions"],
          f"{phase}: revealed {out['revealed']}/{out['sessions']}")
    check(out["exact"] == out["revealed"],
          f"{phase}: exact {out['exact']}/{out['revealed']}")
    for key in ("retries", "bisections", "quarantined", "degraded_batches"):
        check(res[key] == 0, f"{phase}: {key}={res[key]}")
    check(not res["dead_letter"], f"{phase}: dead_letter {res['dead_letter']}")
    check(not out["degraded"], f"{phase}: service degraded")


def phase_round(jax, log) -> None:
    """A: the federated-learning round."""
    import jax.numpy as jnp
    from repro.api import SecureAggregator, Topology
    from repro.core.engine import build_batch_executable
    from repro.launch.serve_agg import run_load
    from repro.service import BatchingConfig, StreamConfig

    def load(sessions: int, seed: int) -> dict:
        agg = SecureAggregator(topology=Topology(n_nodes=SLOTS),
                               batching=BatchingConfig(max_batch=1))
        out = run_load(agg, None, sessions=sessions, elems=ELEMS,
                       churn_every=0, seed=seed)
        check_service("A", out)
        return out

    out = load(ROUND_SESSIONS, seed=0)
    cold = log.take()
    # the program the service dispatched, compiled again on its own to
    # read what it holds
    agg = SecureAggregator(topology=Topology(n_nodes=SLOTS))
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    compiled = build_batch_executable(
        agg.plan(), donate=StreamConfig().resolve_donate()).lower(
            sds((1, SLOTS, ELEMS), jnp.float32), sds((1,), jnp.uint32),
            sds((1,), jnp.uint32), {}).compile()
    check("tpu_custom_call" in compiled.as_text(),
          "A: the compiled executable holds no Pallas kernel")
    ma = compiled.memory_analysis()
    del compiled
    again = log.take()
    jax.clear_caches()
    load(1, seed=1)
    warm = log.take()
    print(f"smoke: A n={SLOTS} T={ELEMS} sessions={ROUND_SESSIONS} "
          f"compile cold {cold}, check {again}, warm {warm}; executable args "
          f"{ma.argument_size_in_bytes} B, temps {ma.temp_size_in_bytes} B; "
          f"wall {out['wall_s']:.2f}s; revealed {out['revealed']}/"
          f"{out['sessions']} exact {out['exact']}; peak_bytes_in_use "
          f"{peak_bytes(jax)}", flush=True)


def phase_histograms(jax, log) -> None:
    """B: the private-telemetry histograms."""
    from repro.api import SecureAggregator, Topology
    from repro.launch.serve_agg import run_func_load
    from repro.service import BatchingConfig

    def load(sessions: int, seed: int) -> dict:
        # batches flush on size only, so every batch has the same shape
        agg = SecureAggregator(
            topology=Topology(n_nodes=SLOTS),
            batching=BatchingConfig(max_batch=HIST_BATCH, max_age=3600.0))
        out = run_func_load(agg, None, sessions=sessions, fn="histogram",
                            bins=HIST_BINS, steps=256, k=1, churn_every=0,
                            seed=seed)
        check_service("B", out)
        return out

    out = load(HIST_SESSIONS, seed=1)
    cold = log.take()
    jax.clear_caches()
    load(HIST_BATCH, seed=2)
    warm = log.take()
    print(f"smoke: B n={SLOTS} bins={HIST_BINS} sessions={HIST_SESSIONS} "
          f"batch={HIST_BATCH} compile cold {cold}, warm {warm}; wall "
          f"{out['wall_s']:.2f}s; revealed {out['revealed']}/"
          f"{out['sessions']} exact {out['exact']}; peak_bytes_in_use "
          f"{peak_bytes(jax)}", flush=True)


def model_config():
    from repro.configs import get_config
    return get_config(ARCH)


def phase_train(jax, log, dp: int, tag: str) -> None:
    """C (and 4b): secure train step against the plain step."""
    from repro.configs.base import ShapeConfig
    from repro.core.plan import AggConfig
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import train_loop

    cfg = model_config()
    shape = ShapeConfig("smoke", SEQ_LEN, BATCH, "train")
    mesh = make_host_mesh(data=dp)
    agg = AggConfig(n_nodes=4, clip=8.0,
                    chunk_elems=CHUNK_ELEMS).derive(n_nodes=dp)

    def losses(secure: bool, steps: int) -> list:
        out = train_loop(cfg, mesh, steps=steps, shape=shape, secure=secure,
                         agg=agg if secure else None, log_every=1)
        return out["losses"]

    t0 = time.perf_counter()
    plain = losses(False, STEPS)
    secure = losses(True, STEPS)
    wall = time.perf_counter() - t0
    cold = log.take()
    jax.clear_caches()
    again = losses(True, 1)
    warm = log.take()
    check(all(np.isfinite(plain + secure)), f"{tag}: non-finite loss")
    check(np.allclose(secure, plain, rtol=0, atol=LOSS_ATOL),
          f"{tag}: losses secure {secure} vs plain {plain}")
    check(again[0] == secure[0],
          f"{tag}: secure step 0 gave {secure[0]}, then {again[0]}")
    print(f"smoke: {tag} {ARCH} dp={dp} seq={SEQ_LEN} batch={BATCH} "
          f"committee n={agg.n_nodes} c={agg.cluster_size} "
          f"r={agg.redundancy} chunk_elems={CHUNK_ELEMS} compile cold "
          f"{cold}, warm {warm}; wall {wall:.2f}s for {STEPS}+{STEPS} "
          f"steps; losses plain {plain} secure {secure}; "
          f"peak_bytes_in_use {peak_bytes(jax)}", flush=True)


def phase_mesh(jax, log) -> None:
    """4a: the facade's mesh backend against its sim backend at n=4."""
    from repro.api import AggConfig, Runtime, SecureAggregator
    from repro.core.masking import quantization_error_bound
    from repro.runtime import compat

    mesh = compat.node_mesh(4)
    rng = np.random.default_rng(3)
    xs = rng.random((4, ELEMS), dtype=np.float32) * np.float32(2) - 1
    want = xs.sum(0, dtype=np.float64)
    t0 = time.perf_counter()
    # cluster_size 1: three ring hops between the chips; 2: a grouped
    # psum inside each pair, then one hop (r <= c, so r=1 at n=4)
    for c in (1, 2):
        for transport in ("full", "digest"):
            cfg = AggConfig(n_nodes=4, cluster_size=c, redundancy=1,
                            transport=transport)
            got = SecureAggregator(cfg, runtime=Runtime(
                backend="mesh", mesh=mesh)).allreduce(xs)
            ref = SecureAggregator(cfg, runtime=Runtime(
                backend="sim")).allreduce(xs)
            tag = f"4a c={c} {transport}"
            shards = got.addressable_shards
            check(len({s.device for s in shards}) == 4
                  and all(s.data.shape == (1, ELEMS) for s in shards),
                  f"{tag}: mesh result not one row per chip: "
                  f"{[(s.device, s.data.shape) for s in shards]}")
            got, ref = np.asarray(got), np.asarray(ref)
            check(np.array_equal(got, ref), f"{tag}: mesh != sim")
            tol = (quantization_error_bound(cfg.mask_cfg())
                   + 4 * float(np.finfo(np.float32).eps))
            check(float(np.abs(got - want).max()) <= tol,
                  f"{tag}: off the float64 sum by {np.abs(got - want).max()}")
    print(f"smoke: 4a n=4 T={ELEMS} mesh == sim bit-identical for c in "
          f"(1, 2) x (full, digest), one row per chip; compile {log.take()};"
          f" wall {time.perf_counter() - t0:.2f}s; peak_bytes_in_use "
          f"{peak_bytes(jax)}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phases (mesh == sim, "
                         "secure step at dp=4)")
    args = ap.parse_args()

    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    dev = jax.devices()[0]
    n_dev = len(jax.devices())
    check(dev.platform == "tpu", f"no TPU: JAX found {dev.platform}")
    check(dev.device_kind == "TPU v5 lite",
          f"expected a TPU v5 lite, found {dev.device_kind}")
    check(not args.four_chips or n_dev == 4,
          f"--four-chips needs 4 chips, found {n_dev}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.kernels import backend
    check("REPRO_KERNEL_IMPL" not in os.environ,
          "REPRO_KERNEL_IMPL overrides the kernel engine")
    impl = backend.default_impl()
    check(impl == "pallas", f"kernel engine resolved to {impl}")
    print(f"smoke: device {dev.device_kind} x{n_dev}, kernel engine {impl}, "
          f"compile cache {jax.config.jax_compilation_cache_dir}", flush=True)

    log = CompileLog(jax)
    phases = ([phase_mesh, lambda j, l: phase_train(j, l, 4, "4b")]
              if args.four_chips else
              [phase_round, phase_histograms,
               lambda j, l: phase_train(j, l, 1, "C")])
    for phase in phases:
        phase(jax, log)
        gc.collect()            # free one phase's device buffers first
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"smoke: FAIL {e}", file=sys.stderr)
        sys.exit(1)
