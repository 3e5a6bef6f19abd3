"""The ``service`` driver: the cells of a configuration without a
``kind``.  It builds the secure-aggregation service the configuration
describes, warms the shapes its traffic will use, and runs the measured
window through the facade (``SecureAggregator.open_session`` /
``contribute`` / ``seal`` / ``pump``) as clients would.  Its traffic is
made by ``loadgen``, its end-to-end metrics are ``e2e.E2E`` and its
reference and limits ``oracles``.

Every call into a layer of the program is wrapped in a harness span
(``bench.ingest``, ``bench.pump``, ``bench.wait``); with ``--trace 1``
they are ``jax.profiler.TraceAnnotation``s, so the trace reduction can say
what the host was doing while the device sat idle.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import numpy as np

from harness import e2e, loadgen, oracles
from harness.loadgen import Load

MAX_WAIT_S = 60.0       # how long past the window a late answer is awaited

E2E = e2e.E2E
LIMITS = oracles.LIMITS


def build(config: dict, traffic: dict, seed: int):
    """The facade over the service, as the configuration states it; the
    pad streams' base key comes from the run's seed."""
    from repro.api import AggConfig, SecureAggregator
    from repro.service import BatchingConfig, StreamConfig
    cfg = AggConfig(n_nodes=config["n_nodes"],
                    cluster_size=config["cluster_size"],
                    redundancy=config["redundancy"],
                    schedule=config["schedule"],
                    transport=config["transport"],
                    masking=config["masking"], clip=config["clip"],
                    guard_bits=config["guard_bits"],
                    seed=int(seed) & 0xFFFFFFFF)
    return SecureAggregator(
        cfg, batching=BatchingConfig(
            max_batch=traffic["batching"]["max_batch"]),
        stream=StreamConfig(depth=config["pipeline_depth"]))


@dataclasses.dataclass
class Window:
    """What one measured window did, for the metrics and the comparison."""
    attempted: int = 0
    seconds: float = 0.0            # window length, start to last reveal
    revealed: int = 0
    latency_s: list = dataclasses.field(default_factory=list)
    sealed_at: dict = dataclasses.field(default_factory=dict)  # closed loops
    lateness_s: list = dataclasses.field(default_factory=list)
    ingest_s: list = dataclasses.field(default_factory=list)
    results: dict = dataclasses.field(default_factory=dict)  # i -> answer
    absorbed: dict = dataclasses.field(default_factory=dict)


def _open(agg, load: Load, i: int, now: float):
    """Session i as its clients make it: open, every slot contributes,
    seal.  Returns the handle the answer is read from."""
    n = load.n_nodes
    if load.kind == "vector":
        x, own, row = load.vector(i)
        s = agg.open_session(load.elems, now=now)
        for slot in range(n):
            s.contribute(slot, row if slot == own else x[slot])
        agg.seal(s.sid, now=now)
        return s
    fs = agg.open_session(fn="histogram", bins=load.bins, now=now)
    row = load.scalars(i)
    for slot in range(n):
        fs.contribute(slot, row[slot])
    fs.seal(now=now)
    return fs


def _answer(agg, load: Load, h) -> Optional[object]:
    """The revealed answer of a finished session, or None if it failed;
    "pending" while it is still in flight."""
    from repro.service import SessionState
    if load.kind == "vector":
        if h.state is SessionState.REVEALED:
            return agg.result(h.sid, evict=True)
        if h.state in (SessionState.FAILED, SessionState.EXPIRED):
            return None
        return "pending"
    if h.state == "done":
        return h.result
    if h.state == "failed":
        return None
    return "pending"


def warm(agg, load: Load, traffic: dict) -> int:
    """Run every batch shape the traffic can flush once, outside the
    window, so that nothing compiles inside it: the age watermark can
    flush any S up to ``max_batch``, and the executor compiles each S
    exactly.  Warm-up sessions have data of their own (negative session
    indices, from streams no window session draws from), so that a
    warm-up answer served in the window reads as wrong.  Returns the
    sessions warmed."""
    warm_load = dataclasses.replace(load)
    if load.kind == "histogram":
        warm_load.values = loadgen.warm_scalars(load, 64)
    done = 0
    for S in range(1, traffic["batching"]["max_batch"] + 1):
        hs = [_open(agg, warm_load, -1 - (i % 64), time.monotonic())
              for i in range(S)]
        agg.drain()
        for h in hs:
            a = _answer(agg, load, h)
            if a is None or isinstance(a, str):
                raise RuntimeError(f"warm-up session not revealed at S={S}")
        done += S
    return done


def run_closed(agg, load: Load, seconds: float, span,
               beat: Callable = lambda: None) -> Window:
    """``clients`` populations, each of which seals its next session as
    soon as its previous one has revealed, while the window has room for
    it: the first session of every population is always sealed, a later
    one only where it and the sessions already in flight, at the last
    round's seconds a session, end within ``seconds``.  The window ends
    when the last session sealed has revealed.

    The program's ``pump`` settles every batch it issued before it
    returns, so the sessions sealed before a pump all reveal in it, and
    the populations move in rounds.  ``beat`` is not called: the loop
    waits in ``pump`` by design."""
    w = Window()
    clock = time.monotonic
    t0 = clock()
    handles: list = [None] * load.clients   # (session index, handle)
    i = 0
    per = 0.0                               # seconds a session, last round
    while True:
        ta = clock()
        opened = 0
        for p in range(load.clients):
            if handles[p] is not None:
                k, h = handles[p]
                a = _answer(agg, load, h)
                if not isinstance(a, str):  # not still queued
                    w.results[k] = a
                    w.latency_s.append(ta - w.sealed_at[k])
                    handles[p] = None
        with span("bench.ingest"):
            for p in range(load.clients):
                busy = sum(h is not None for h in handles)
                if handles[p] is not None or (
                        i >= load.clients and
                        clock() - t0 + (busy + 1) * per > seconds):
                    continue
                handles[p] = (i, _open(agg, load, i, clock()))
                w.sealed_at[i] = clock()
                i += 1
                opened += 1
        if opened:
            w.ingest_s.append((clock() - ta) / opened)
        if all(h is None for h in handles):
            break
        if clock() - t0 > seconds + MAX_WAIT_S:     # never came
            for k, _ in filter(None, handles):
                w.results[k] = None
            break
        with span("bench.pump"):
            agg.pump(now=clock())
        if opened:
            per = (clock() - ta) / opened
    w.seconds = clock() - t0
    w.attempted = i
    w.revealed = sum(a is not None for a in w.results.values())
    return w


def run_open(agg, load: Load, seconds: float, span,
             beat: Callable = lambda: None) -> Window:
    """Sessions arrive on the schedule ``load.due`` whatever the state of
    earlier ones; each is timed from its due time to the moment the
    client sees its answer.  A session that has not revealed
    ``MAX_WAIT_S`` past the window is missing, and its latency is the
    wait."""
    w = Window()
    clock = time.monotonic
    due, N = load.due, len(load.due)
    queue = agg.service.queue
    max_age = queue.batching.max_age
    pending: dict = {}
    t0 = clock()
    i = 0
    while i < N or pending:
        beat()
        now = clock() - t0
        while i < N and due[i] <= now:
            ta = clock()
            with span("bench.ingest"):
                pending[i] = _open(agg, load, i, ta)
            w.ingest_s.append(clock() - ta)
            w.lateness_s.append(ta - t0 - due[i])
            i += 1
            now = clock() - t0
        with span("bench.pump"):
            agg.pump(now=clock())
        td = clock() - t0
        for k in list(pending):
            a = _answer(agg, load, pending[k])
            if isinstance(a, str):
                continue
            w.results[k] = a
            w.latency_s.append(td - due[k])
            del pending[k]
        if td > seconds + MAX_WAIT_S:
            break
        ages = queue.oldest_ages(clock())
        wait = min(due[i] - (clock() - t0) if i < N else MAX_WAIT_S,
                   max_age - max(ages.values()) if ages else MAX_WAIT_S)
        if wait > 0:
            with span("bench.wait"):
                time.sleep(min(wait, 0.01))
    end = clock() - t0
    for k in pending:                       # never came
        w.results[k] = None
        w.latency_s.append(end - due[k])
    w.seconds = end
    w.attempted = N
    w.revealed = sum(a is not None for a in w.results.values())
    return w


def absorbed(agg) -> dict:
    """Faults the resilience layer hid: each would have turned a device
    error into a retry, a bisection, a quarantine or a degraded batch."""
    res = agg.stats()["service"]["resilience"]
    return {k: int(res[k]) for k in ("retries", "bisections", "quarantined",
                                     "degraded_batches")}


def compare(load: Load, w: Window, config: dict,
            control: bool = False) -> dict:
    """Every answer due in the window against the plain reference (or,
    with ``control``, the reference one precision lower in the program's
    place).  Returns the numbers compared; the limits are the cell's."""
    unrevealed = sum(a is None for a in w.results.values())
    out = {"unrevealed": unrevealed,
           "absorbed": sum(w.absorbed.values())}
    if load.kind == "vector":
        tol = oracles.vector_tolerance(load.n_nodes, config["clip"],
                                       config["guard_bits"])
        worst = 0.0
        ctrl = {}
        for i, a in w.results.items():
            if a is None:
                continue
            want = load.vector_sum(i)
            if control:
                x, j, row = load.vector(i)
                k = load.pool_index(i)
                if k not in ctrl:
                    ctrl[k] = oracles.control_vector(x)
                a = ctrl[k] - oracles.bf16(x[j]) + oracles.bf16(row)
            worst = max(worst, oracles.vector_error(a, want) / tol)
        out["err_over_tol"] = worst
    else:
        wrong = 0
        for i, a in w.results.items():
            if a is None:
                continue
            if control:
                a = oracles.control_histogram(load.values[i], load.bins)
            wrong += oracles.wrong_bins(a, load.values[i], load.bins)
        out["wrong_bins"] = wrong
    return out


def stage_totals(agg) -> dict:
    """(count, seconds) of every ``stage.seconds`` span in the registry."""
    hist = agg.metrics.snapshot()["histograms"]
    return {k: (v["count"], v["total"]) for k, v in hist.items()
            if k.startswith("stage.seconds")}


class Cell:
    """One run of a service cell, as ``run.run_cell`` drives it: set-up
    (the data from the seed, the service, every batch shape warmed) when
    it is made, then the window, the program's state freed, and the
    comparison."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 seconds: float, chips: int):
        self.config, self.traffic = config, traffic
        self.load = loadgen.make_load(traffic, n_nodes=config["n_nodes"],
                                      clip=config["clip"], seed=seed,
                                      seconds=seconds)
        self.agg = build(config, traffic, seed)
        self.warmed = f"{warm(self.agg, self.load, traffic)} sessions warmed"
        self._stages0 = stage_totals(self.agg)

    def window(self, seconds: float, span, beat: Callable) -> Window:
        run = run_closed if self.load.loop == "closed" else run_open
        return run(self.agg, self.load, seconds, span, beat)

    def stages(self) -> dict:
        """The registry's ``stage.seconds`` spans over the window."""
        s0 = self._stages0
        return {k: (c - s0.get(k, (0, 0.0))[0], t - s0.get(k, (0, 0.0))[1])
                for k, (c, t) in stage_totals(self.agg).items()}

    def settle(self, w: Window) -> None:
        w.absorbed = absorbed(self.agg)

    def done(self, w: Window) -> str:
        return f"{w.attempted} sessions, {w.revealed} revealed"

    def notes(self, w: Window) -> list:
        if not w.lateness_s:
            return []
        late = sorted(w.lateness_s)
        return [f"generator ran late by {1000 * late[len(late) // 2]:.3f} "
                f"ms (median), {1000 * late[-1]:.3f} ms (max)"]

    def free(self) -> None:
        del self.agg

    def compare(self, w: Window, control: bool = False) -> dict:
        return compare(self.load, w, self.config, control=control)

    def failed(self, numbers: dict) -> int:
        return numbers["unrevealed"] + numbers["absorbed"]
