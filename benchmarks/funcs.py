"""Secure-function layer benches (``repro.funcs``, PR 10).

Two claims to pin:

  * a HISTOGRAM costs exactly one additive allreduce at T=bins — the
    one-hot compilation adds zero wire overhead over the sum it rides
    (``funcs_histogram_bins64_bytes`` == ``funcs_sum_T64_bytes``, both
    printed so the equality is visible in the trajectory file);
  * MEDIAN wire cost scales with ``log2(steps)``, not with the domain
    width: the ``funcs_median_steps{256,1024,4096}_bytes`` rows grow by
    two extra 1-element rounds per 4x domain refinement.  The
    steps=1024 row is the ``make bench-funcs`` regression guard — a
    protocol change that silently inflates the bisection's per-round
    bytes >10% fails the gate.

Timing rows (min over interleaved rounds):
the one-shot verb wall time, histogram vs an 8-round median — the
median's sequential reveal-between-rounds dispatches are the price of
non-additivity the README table documents.
"""
from __future__ import annotations

import time

import numpy as np

N, C, R = 16, 4, 3
BINS = 64
STEPS_GRID = (256, 1024, 4096)


def run(full: bool = False) -> None:
    from repro.api import AggConfig, SecureAggregator

    cfg = AggConfig(n_nodes=N, cluster_size=C, redundancy=R, clip=2.0)
    agg = SecureAggregator(cfg)
    rng = np.random.default_rng(0)
    vals = rng.random(N)

    # -- wire bytes: histogram == sum at the same T -------------------------
    ch = agg.cost(fn="histogram", bins=BINS)
    cs = agg.cost(BINS)
    assert ch["bytes_total"] == cs["bytes_total"]
    print(f"funcs_histogram_bins{BINS}_bytes,{ch['bytes_total']},"
          f"one_one_hot_allreduce")
    print(f"funcs_sum_T{BINS}_bytes,{cs['bytes_total']},"
          f"additive_baseline_same_T")

    # -- wire bytes: median scales with log2(steps) -------------------------
    for steps in STEPS_GRID:
        c = agg.cost(fn="median", domain=(0.0, 1.0, steps))
        print(f"funcs_median_steps{steps}_bytes,{c['bytes_total']},"
              f"{c['allreduces']}_bisection_rounds_1elem_each")

    # -- verb wall time (min over interleaved rounds) -----------------------
    timed = (
        (f"funcs_histogram_bins{BINS}_us",
         lambda: agg.histogram(vals, bins=BINS),
         "one_shot_verb"),
        ("funcs_median_steps256_us",
         lambda: agg.median(vals, domain=(0.0, 1.0, 256)),
         "8_sequential_count_rounds"),
        ("funcs_topk4_steps256_us",
         lambda: agg.topk(vals, 4, domain=(0.0, 1.0, 256)),
         "bisection_plus_readout"),
    )
    for _, fn, _ in timed:                  # warm every compile cache
        fn()
    rounds = 24 if full else 8
    best = {name: float("inf") for name, _, _ in timed}
    for _ in range(rounds):
        for name, fn, _ in timed:
            t0 = time.perf_counter()
            fn()
            best[name] = min(best[name], (time.perf_counter() - t0) * 1e6)
    for name, _, note in timed:
        print(f"{name},{best[name]:.0f},{note}")
