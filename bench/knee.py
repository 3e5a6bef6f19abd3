"""Sweep of offered load for an open-loop cell, to find its knee once.

    python3 bench/knee.py --workload committee-256.telemetry-hist \
        --rates 300,400,500 --seconds 10 --seed 1

Builds and warms the cell's service once, then offers each rate for
``--seconds`` in turn and prints, per rate, the sessions revealed per
second, the latency percentiles from due time, how late the generator ran
and how many sessions were still pending when the arrivals stopped.  The
knee is the highest rate whose backlog does not grow and whose p95 stays
under the limit fixed from the sweep; the cell runs at about four fifths
of it, written into its traffic file.  Not part of the benchmark's runs.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

from harness import device, loadgen, service, spec, stats  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args()
    import jax
    bench = spec.with_held(spec.load_spec())
    w = spec.cell(bench, a.workload)
    config, traffic = spec.config_of(bench, w), spec.traffic_of(w)
    device.setup_compile_cache(jax, spec.ROOT)
    device.require_chip(jax, w["chips"])
    agg = service.build(config, traffic, a.seed)
    first = loadgen.make_load(traffic, n_nodes=config["n_nodes"],
                              clip=config["clip"], seed=a.seed, seconds=1.0)
    service.warm(agg, first, traffic)
    span = service.spans(False)
    for r in [float(x) for x in a.rates.split(",")]:
        t = dict(traffic, rate_per_s=r)
        load = loadgen.make_load(t, n_nodes=config["n_nodes"],
                                 clip=config["clip"], seed=a.seed,
                                 seconds=a.seconds)
        win = service.run_open(agg, load, a.seconds, span)
        lat = win.latency_s
        late = sorted(win.lateness_s)
        row = {"rate_per_s": r, "sessions": win.attempted,
               "revealed_per_s": stats.rate(win.revealed, win.seconds),
               "window_s": win.seconds,
               "p50_ms": 1000 * stats.percentile(lat, 50),
               "p95_ms": 1000 * stats.percentile(lat, 95),
               "p99_ms": 1000 * stats.percentile(lat, 99),
               "late_p50_ms": 1000 * late[len(late) // 2],
               "late_max_ms": 1000 * late[-1],
               "drain_s": win.seconds - a.seconds}
        print("knee:", json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
