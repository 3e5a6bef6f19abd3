"""Aggregation-service driver: many concurrent secure-aggregation
sessions under synthetic load, batched by the admission scheduler.

    PYTHONPATH=src python -m repro.launch.serve_agg --sessions 64 \
        --batch 16 --elems 1024 --overlay-n 256 --churn-every 16

Drives everything through the ``repro.api.SecureAggregator`` facade
(one config: Topology/Security/Runtime sections; ``open_session`` /
``seal`` / ``pump`` / ``result`` verbs).
Opens ``--sessions`` sessions against a cuckoo-overlay network, feeds
every protocol slot's contribution, seals them as load arrives, and lets
the size/age watermarks of the admission queue decide when batches
flush.  ``--churn-every`` applies a join/leave burst (advancing the
churn epoch) every that-many sessions, so part of the load drains on
old-epoch committees with vote-absorbed departures.  Prints sessions/sec
and the realized batch-size histogram.

``--fn histogram|median|min|max|topk`` (with ``--bins``/``--steps``/
``--topk``) switches the load from additive sums to secure FUNCTIONS
(``repro.funcs``): each session compiles to a chain of count-payload
allreduces — one one-hot round for histograms, ``ceil(log2(steps))``
threshold-count bisection rounds for order statistics — driven across
pump cycles by the same admission scheduler, with exactness checked
against the plain-numpy oracle on the quantized domain.

Resilience knobs: ``--ttl`` puts a deadline on every session,
``--max-pending-rows`` arms the admission queue's load-shedding
watermark, ``--retry-attempts``/``--retry-backoff``/``--deadline``
shape the executor's retry policy, and ``--chaos MODE`` (with
``--chaos-p``/``--chaos-seed``/``--chaos-times``) injects deterministic
runtime faults to watch the retry/bisect/quarantine ladder work under
real load; the run report includes the resilience counters.

Observability (``repro.obs``): ``--trace-out FILE`` attaches the flight
recorder and streams the JSONL event log (per-batch / per-voted-round
wire bytes, stage spans, the retry/bisect/quarantine ladder) to FILE;
``--metrics-out FILE`` writes the final Prometheus-style snapshot of
the shared metrics registry; ``--stats-interval N`` prints the human
metrics table every N sessions while the load runs.

Mesh/compat bootstrap is shared with ``launch.serve`` via
``runtime.compat.host_mesh`` (one place for the mesh bootstrap);
``REPRO_KERNEL_IMPL`` (or ``--impl``) picks the kernel engine exactly as
in the single-query path.
"""
from __future__ import annotations

import argparse
import collections
import time

import numpy as np

from repro.api import Runtime, SecureAggregator, Security, Topology
from repro.core.masking import quantization_error_bound
from repro.core.overlay import build_overlay
from repro.launch.mesh import make_host_mesh
from repro.obs import DEFAULT_REGISTRY, TraceRecorder, stats_table
from repro.obs.export import prometheus_text
from repro.runtime.chaos import CHAOS_MODES, ChaosConfig
from repro.service import (BatchingConfig, EpochManager, RetryPolicy,
                           StreamConfig)
from repro.service.session import SessionState


def run_func_load(agg: SecureAggregator, em: EpochManager, *,
                  sessions: int, fn: str, bins: int, steps: int, k: int,
                  churn_every: int, seed: int = 0) -> dict:
    """Drive ``--sessions`` secure-FUNCTION sessions (histogram /
    quantile bisection / top-k) through the service: each one rides a
    chain of ordinary additive sessions, advanced by the same ``pump``
    that flushes the admission queue.  Exactness is checked against the
    plain-numpy oracle on the quantized domain; mid-flight churn can
    legitimately cost exactness for multi-round functions (each
    bisection round pins to the epoch current at ITS open, so a
    departure changes the visible electorate between rounds)."""
    from repro.funcs import ValueDomain
    from repro.funcs.run import quantile_rank

    rng = np.random.default_rng(seed)
    n = agg.cfg.n_nodes
    dom = ValueDomain(0.0, 1.0, steps)
    t0 = time.monotonic()
    handles: list[tuple] = []
    for i in range(sessions):
        if churn_every and i and i % churn_every == 0:
            em.churn(joins=4, leaves=4, honest_join_frac=1.0)
        if fn == "histogram":
            fs = agg.open_session(fn=fn, bins=bins, now=time.monotonic())
        elif fn == "topk":
            fs = agg.open_session(fn=fn, k=k, domain=dom,
                                  now=time.monotonic())
        else:
            fs = agg.open_session(fn=fn, domain=dom, now=time.monotonic())
        vals = rng.random(n)
        for slot in range(n):
            fs.contribute(slot, float(vals[slot]))
        fs.seal(now=time.monotonic())
        handles.append((fs, vals))
        agg.pump()
    agg.drain()
    wall = time.monotonic() - t0

    exact = done = 0
    for fs, vals in handles:
        if not fs.done:
            continue
        done += 1
        if fn == "histogram":
            want = np.histogram(np.clip(vals, 0.0, 1.0), bins=bins,
                                range=(0.0, 1.0))[0]
            exact += bool(np.array_equal(fs.result, want))
        elif fn == "topk":
            quant = np.array([dom.value(int(i))
                              for i in dom.indices(vals)])
            want = np.sort(quant)[::-1][:k]
            exact += bool(np.array_equal(np.asarray(fs.result), want))
        else:
            qq = {"median": 0.5, "min": 0.0, "max": 1.0}[fn]
            quant = np.sort([dom.value(int(i))
                             for i in dom.indices(vals)])
            want = quant[quantile_rank(qq, n) - 1]
            exact += bool(fs.result == want)
    return {"wall_s": wall, "sessions": sessions,
            "sessions_per_s": sessions / max(wall, 1e-9),
            "revealed": done, "exact": exact,
            "degraded": agg.stats().get("degraded", False),
            "stats": agg.stats()["service"]}


def run_load(agg: SecureAggregator, em: EpochManager, *, sessions: int,
             elems: int, churn_every: int, seed: int = 0,
             stats_interval: int = 0) -> dict:
    """Drive ``sessions`` additive sessions of ``elems`` float32 updates
    per slot, drawn uniformly from the clip range.  A revealed sum is
    exact when it is within the quantization bound of the float64 sum
    (plus the float32 rounding of the revealed value)."""
    rng = np.random.default_rng(seed)
    n, clip = agg.cfg.n_nodes, agg.cfg.clip
    tol = (quantization_error_bound(agg.cfg.mask_cfg())
           + n * clip * float(np.finfo(np.float32).eps))
    expected: dict[int, np.ndarray] = {}
    t0 = time.monotonic()
    for i in range(sessions):
        if churn_every and i and i % churn_every == 0:
            em.churn(joins=4, leaves=4, honest_join_frac=1.0)
        s = agg.open_session(elems, now=time.monotonic())
        vals = rng.random((n, elems), dtype=np.float32)
        vals *= np.float32(2 * clip)
        vals -= np.float32(clip)
        for slot in range(n):
            s.contribute(slot, vals[slot])
        expected[s.sid] = vals.sum(0, dtype=np.float64)
        agg.seal(s.sid, now=time.monotonic())
        agg.pump()                       # watermark-driven flushes
        if stats_interval and (i + 1) % stats_interval == 0:
            print(stats_table(agg.metrics,
                              title=f"metrics @ {i + 1} sessions"))
    agg.drain()
    wall = time.monotonic() - t0
    svc = agg.service
    revealed = [sid for sid in expected
                if svc.get(sid).state is SessionState.REVEALED]
    exact = sum(
        bool(np.abs(agg.result(sid) - expected[sid]).max() <= tol)
        for sid in revealed)
    return {"wall_s": wall, "sessions": sessions,
            "sessions_per_s": sessions / max(wall, 1e-9),
            "revealed": len(revealed), "exact": exact,
            "degraded": agg.stats().get("degraded", False),
            "stats": agg.stats()["service"]}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sessions", type=int, default=64)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--max-age", type=float, default=0.05)
    ap.add_argument("--elems", type=int, default=1024)
    ap.add_argument("--overlay-n", type=int, default=256)
    ap.add_argument("--tau", type=float, default=0.2)
    ap.add_argument("--cluster-size", type=int, default=4)
    ap.add_argument("--redundancy", type=int, default=3)
    ap.add_argument("--schedule", default="ring")
    ap.add_argument("--tune", choices=("auto", "probe"), default=None,
                    help="self-tuning planner (repro.tune): resolve "
                         "schedule/transport/digest/chunk/pad per "
                         "workload signature with the exact wire-byte "
                         "oracle ('probe' adds one measured dispatch "
                         "per finalist); --schedule becomes a hint")
    ap.add_argument("--churn-every", type=int, default=0)
    ap.add_argument("--fn", default=None,
                    choices=("histogram", "median", "min", "max", "topk"),
                    help="drive secure-FUNCTION sessions (repro.funcs) "
                         "instead of additive sums: each session is a "
                         "histogram / bisection-quantile / top-k over "
                         "one scalar per slot, multi-round fns riding "
                         "chains of service sessions across pump cycles")
    ap.add_argument("--bins", type=int, default=16,
                    help="--fn histogram: bucket count over [0, 1)")
    ap.add_argument("--steps", type=int, default=256,
                    help="--fn median/min/max/topk: value-domain grid "
                         "resolution (bisection runs ceil(log2(steps)) "
                         "rounds)")
    ap.add_argument("--topk", type=int, default=4, metavar="K",
                    help="--fn topk: how many largest values to reveal")
    ap.add_argument("--impl", default=None,
                    help="kernel engine override (pallas/pallas_interpret/jnp)")
    ap.add_argument("--transport", choices=("sim", "mesh"), default="sim",
                    help="executor backend: sim oracle or shard_map over "
                         "a dp mesh (needs one device per protocol slot; "
                         "force with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="in-flight streaming batch slots (1 = the "
                         "sequential pre-PR-8 dispatch; 2 = "
                         "double-buffered pack/device overlap)")
    # resilience: deadlines, shedding, retry, deterministic chaos
    ap.add_argument("--ttl", type=float, default=None,
                    help="session deadline in seconds (EXPIRED past it)")
    ap.add_argument("--max-pending-rows", type=int, default=None,
                    help="load-shedding high-watermark in batch rows")
    ap.add_argument("--retry-attempts", type=int, default=3)
    ap.add_argument("--retry-backoff", type=float, default=0.02)
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-attempt wall deadline (retriable)")
    ap.add_argument("--chaos", choices=CHAOS_MODES, default=None,
                    help="inject deterministic runtime faults")
    ap.add_argument("--chaos-p", type=float, default=1.0)
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--chaos-times", type=int, default=None,
                    help="cap total injections (default unbounded)")
    # observability: flight recorder + metrics export
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="stream the flight-recorder JSONL event log "
                         "(batch/round wire bytes, stage spans, the "
                         "retry/bisect/quarantine ladder) to FILE")
    ap.add_argument("--metrics-out", default=None, metavar="FILE",
                    help="write the final Prometheus-style metrics "
                         "snapshot to FILE")
    ap.add_argument("--stats-interval", type=int, default=0, metavar="N",
                    help="print the human metrics table every N "
                         "sessions (0 = off)")
    args = ap.parse_args()

    mesh = make_host_mesh(data=args.data, model=args.model)
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"on {mesh.devices.ravel()[0].platform}")

    ov = build_overlay(args.overlay_n, args.tau, seed=42)
    em = EpochManager(ov, cluster_size=args.cluster_size)
    snap = em.current()
    agg_mesh = None
    if args.transport == "mesh":
        from repro.runtime import compat
        agg_mesh = compat.node_mesh(snap.n_nodes)
    agg = SecureAggregator(
        topology=Topology(n_nodes=snap.n_nodes,
                          cluster_size=args.cluster_size,
                          schedule=args.schedule),
        security=Security(redundancy=args.redundancy),
        runtime=Runtime(kernel_impl=args.impl, backend=args.transport,
                        mesh=agg_mesh),
        epochs=em,
        batching=BatchingConfig(max_batch=args.batch, max_age=args.max_age,
                                max_pending_rows=args.max_pending_rows,
                                session_ttl=args.ttl),
        retry=RetryPolicy(max_attempts=args.retry_attempts,
                          base_backoff_s=args.retry_backoff,
                          deadline_s=args.deadline),
        chaos=None if args.chaos is None else ChaosConfig(
            mode=args.chaos, p=args.chaos_p, seed=args.chaos_seed,
            times=args.chaos_times),
        metrics=DEFAULT_REGISTRY,
        recorder=(None if args.trace_out is None
                  else TraceRecorder(sink=args.trace_out)),
        stream=StreamConfig(depth=args.pipeline_depth),
        tune=args.tune)
    print(f"service: g={snap.n_clusters} clusters x c={args.cluster_size} "
          f"-> {snap.n_nodes} slots, T={args.elems}, r={args.redundancy}, "
          f"transport={args.transport}")

    if args.fn is not None:
        cost_kw = (dict(bins=args.bins) if args.fn == "histogram" else
                   dict(domain=(0.0, 1.0, args.steps),
                        **({"k": args.topk} if args.fn == "topk" else {})))
        c = agg.cost(fn=args.fn, **cost_kw)
        print(f"func: {args.fn} -> {c['allreduces']} allreduce(s)/session "
              f"(round elems {c['round_elems']}), "
              f"{c['bytes_total']} wire bytes/session")
        out = run_func_load(agg, em, sessions=args.sessions, fn=args.fn,
                            bins=args.bins, steps=args.steps, k=args.topk,
                            churn_every=args.churn_every)
    else:
        out = run_load(agg, em, sessions=args.sessions, elems=args.elems,
                       churn_every=args.churn_every,
                       stats_interval=args.stats_interval)
    hist = collections.Counter(out["stats"]["batches"]["sizes"])
    print(f"{out['sessions']} sessions in {out['wall_s']:.2f}s "
          f"({out['sessions_per_s']:.1f} sessions/s), "
          f"revealed {out['revealed']}/{out['sessions']}, "
          f"exact results: {out['exact']}/{out['revealed']}")
    print(f"batches: {out['stats']['batches']['run']} "
          f"(size histogram {dict(sorted(hist.items()))}), "
          f"final epoch: {out['stats']['epoch']}")
    res, qm = out["stats"]["resilience"], out["stats"]["queue"]
    print(f"resilience: retries={res['retries']} "
          f"bisections={res['bisections']} "
          f"quarantined={res['quarantined']} "
          f"chaos_injected={res['chaos_injected']} "
          f"degraded_batches={res['degraded_batches']} "
          f"shed={qm['shed_sessions']} expired={qm['expired_sessions']} "
          f"degraded={out['degraded']}")
    print(f"wire: {out['stats']['wire']['bytes_sent']} modeled bytes "
          f"over {out['stats']['batches']['run']} batches")
    if args.tune is not None:
        ts = agg.stats()["tuner"]
        d = agg._tune_decision(args.elems, args.batch)
        c = d.config
        print(f"tuner: {c.schedule}/{c.transport} words={c.digest_words} "
              f"backup={c.digest_backup} pad={d.padded_elems} "
              f"predicted={d.predicted_bytes}B/batch "
              f"(-{100 * d.saving_vs_default:.1f}% vs ring/full default; "
              f"{ts['decisions']} decisions, {ts['cache_hits']} cache "
              f"hits, {ts['probes']} probes)")
    if agg.recorder is not None:
        agg.recorder.close()
        print(f"trace: {agg.recorder.events_recorded} events -> "
              f"{args.trace_out}")
    if args.metrics_out is not None:
        with open(args.metrics_out, "w") as f:
            f.write(prometheus_text(agg.metrics))
        print(f"metrics: snapshot -> {args.metrics_out}")
    if out["revealed"] < out["sessions"] or out["exact"] < out["revealed"]:
        raise SystemExit(
            f"serve_agg: revealed {out['revealed']}/{out['sessions']}, "
            f"exact {out['exact']}/{out['revealed']}")


if __name__ == "__main__":
    main()
