"""Self-tuning planner benches: decision byte trajectories + the
cache-hit dispatch-overhead gate.

Two claims to pin (``repro.tune``, PR 9):

  * the DECISIONS are worth committing: per headline workload
    signature, the tuned config's exact predicted wire bytes
    (``tuner_decision_*_bytes``) next to the paper-faithful ring/full
    default (``tuner_default_*_bytes``).  The decision rows ride in
    ``BENCH_secure_agg.json`` and are guarded by ``make bench-tune`` —
    a model change that silently makes a headline decision move >10%
    MORE bytes fails the gate (``_bytes`` rows are lower-is-better);
  * resolution is FREE once cached: a facade with ``tune="auto"``
    resolves every repeat dispatch through one memo lookup, required to
    stay within 2% of a facade constructed directly with the winning
    config (same plan, same compiled executable — the only delta IS the
    resolution).  Methodology: interleaved one-dispatch rounds, min
    over rounds, S=64 batched lane.  The gate is ENFORCED: a breach raises, which
    ``benchmarks/run.py`` turns into an ERROR row and a non-zero exit.
"""
from __future__ import annotations

import time

import numpy as np

# headline signatures: (n_nodes, cluster, T, S)
DECISION_GRID = (
    (16, 4, 1024, 8),
    (16, 4, 200000, 2),
    (64, 4, 4096, 16),
)

OVERHEAD_N, OVERHEAD_T, OVERHEAD_S = 16, 1024, 64
GATE_PCT = 2.0


def run(full: bool = False) -> None:
    import jax

    from repro.api import SecureAggregator, Topology
    from repro.tune import Tuner, clear_tuner_cache

    clear_tuner_cache()
    tuner = Tuner()
    for n, cluster, T, S in DECISION_GRID:
        from repro.core.plan import AggConfig, Security, Wire
        cfg = AggConfig.compose(Topology(n_nodes=n, cluster_size=cluster),
                                Security(), Wire())
        d = tuner.resolve(cfg, T, S)
        tag = f"n{n}_T{T}_S{S}"
        pick = (f"{d.config.schedule}_{d.config.transport}"
                f"_w{d.config.digest_words}"
                f"_bk{int(d.config.digest_backup)}_pad{d.padded_elems}")
        print(f"tuner_decision_{tag}_bytes,{d.predicted_bytes},{pick};"
              f"saves_{100 * d.saving_vs_default:.1f}pct")
        print(f"tuner_default_{tag}_bytes,{d.baseline_bytes},"
              f"ring_full_default")

    # -- cache-hit resolution overhead on the S=64 batched lane -------------
    base = SecureAggregator(
        topology=Topology(n_nodes=OVERHEAD_N, cluster_size=4))
    tuned = SecureAggregator(
        topology=Topology(n_nodes=OVERHEAD_N, cluster_size=4), tune="auto")
    decision = tuned._tune_decision(OVERHEAD_T, OVERHEAD_S)
    # the control facade runs the WINNING config directly: both variants
    # dispatch the same compiled executable, so the measured delta is
    # exactly the per-dispatch resolution cost (one memo lookup)
    direct = SecureAggregator(cfg=decision.config)
    rng = np.random.default_rng(0)
    xs = (rng.normal(size=(OVERHEAD_S, OVERHEAD_N, OVERHEAD_T))
          .astype(np.float32) * 0.1)
    variants = (("tuned", tuned), ("direct", direct), ("untuned", base))
    for _, agg in variants:                      # warm every compile cache
        jax.block_until_ready(agg.allreduce_batched(xs))
    rounds = 48 if full else 24
    us = {name: float("inf") for name, _ in variants}
    for _ in range(rounds):
        for name, agg in variants:
            t0 = time.perf_counter()
            jax.block_until_ready(agg.allreduce_batched(xs))
            us[name] = min(us[name], (time.perf_counter() - t0) * 1e6)
    for name, _ in variants:
        print(f"tune_dispatch_{name}_S{OVERHEAD_S}_us,{us[name]:.0f},"
              f"batched_allreduce_T{OVERHEAD_T}")
    pct = (us["tuned"] - us["direct"]) / us["direct"] * 100
    print(f"tune_overhead_cachehit_pct,{pct:.2f},"
          f"regression_vs_direct;gate_lt_{GATE_PCT:.0f}pct")
    if pct >= GATE_PCT:
        raise RuntimeError(
            f"tuner resolution overhead gate breached — cache-hit "
            f"dispatch {pct:.2f}% >= {GATE_PCT:.0f}% over direct config")
