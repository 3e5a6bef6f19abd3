"""Batched session executor + admission scheduler (+ resilience layer).

The executor is where the service meets the protocol core: S concurrent
sessions that share a :class:`BatchKey` are packed into one
(S, n_nodes, T_row) batch, a plan is compiled once per shape
(``core.plan.compile_plan``), and the engine executes it on the
configured transport:

  * ``transport="sim"``  — :class:`~repro.core.engine.SimTransport`,
    the single-device oracle (default);
  * ``transport="mesh"`` — :class:`~repro.core.engine.MeshTransport`,
    the same plan under ``shard_map`` over a real dp mesh (one device
    per protocol node) — bit-identical to the sim path by construction.

(The *wire* transport of the voted hops — "full" r-copy voting vs the
paper's "digest" 1-payload + r-digest hops with the compiled backup
stream — is a protocol parameter and rides in ``SessionParams.transport``
/ the batch key; both executor backends run both.)

Every protocol stage is ONE batched kernel dispatch over all S rows,
and all masking modes run batched (pairwise pads are fused in-kernel).

Dispatch is a *streaming pipeline* (:class:`StreamConfig`): up to
``depth`` batch slots are in flight at once — ``execute_async`` packs
and issues a slot without blocking on the device result (JAX async
dispatch), so packing batch k+1 overlaps the device aggregating batch
k, and the host sync moves to slot *settlement* (the next issue once
the ring is full, or ``flush()``).  Off-CPU backends donate the packed
slot buffer to the executable (``donate_argnums``), which is why the
slots are double-buffered: the slot being packed is never the one the
device owns.  An executable-cache miss warms in the background (AOT
``lower().compile()`` on a worker thread) while traffic keeps flowing
on an already-compiled larger-S shape bucket — bit-identical for the
real rows because batch rows are independent sessions.  ``depth=1``
reproduces the historical sequential dispatch exactly.

Long payloads chunk across batch *rows*: a session whose payload
exceeds ``BatchingConfig.max_row_elems`` contributes several (n, T_row)
rows whose pad-stream counter offsets continue where the previous row
stopped, so the chunked session is bit-identical to a monolithic one.

Runtime faults (a raising dispatch, a compile failure, a stalled
collective) are handled by the resilience layer rather than failing
all S rows: :meth:`BatchedExecutor.execute` retries the batch per its
:class:`~repro.runtime.resilience.RetryPolicy` (exponential backoff,
deterministic jitter, optional per-attempt deadline), then *bisects*
a still-failing batch to quarantine the poison session(s) into the
``dead_letter`` list while the healthy halves reveal normally.  With a
``transport="mesh"`` executor, a
:class:`~repro.runtime.resilience.CircuitBreaker` adds the degrade
ladder: K consecutive mesh failures fall the executor back to the sim
transport (bit-identical by construction) until a post-cooloff probe
succeeds.  ``runtime.chaos`` injects deterministic runtime faults into
exactly this machinery for tests.

The admission queue coalesces sealed sessions per batch key and flushes
on two watermarks:

  * size — a full batch of ``max_batch`` rows flushes immediately;
  * age  — a partial batch flushes once its oldest sealed session has
    waited ``max_age`` (``now`` defaults to ``time.monotonic()``; tests
    pass explicit ticks).

It also enforces two protection tiers:

  * session deadlines — a queued session past its ``expires_at`` moves
    to EXPIRED at pump time instead of aggregating;
  * load shedding — when total pending rows exceed the
    ``max_pending_rows`` high-watermark, newest-arrival sessions are
    shed (EXPIRED, flush reason ``"shed"``) with weighted-fair victim
    selection across batch keys: keys are weighted by pending rows
    discounted by their ``oldest_ages`` watermark, so large young
    floods shed first and old starving keys are protected.

Fairness/starvation telemetry rides on :attr:`AdmissionQueue.metrics`:
per-key age watermarks (``oldest_ages``), the max observed queue age,
per-reason flush counters, and the shed/expired/dropped counts.

Payload lengths are rounded up to ``pad_buckets`` so sessions with
similar (not identical) T share a compiled executable; the pad tail is
zero-contribution elements that are sliced off at reveal.
"""
from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import (MeshTransport, SimTransport,
                               build_batch_executable, execute_chunks)
from repro.core.plan import (SessionMeta, compile_plan, fault_masks_of,
                             _require)
from repro.kernels.secure_agg import VOTE_LAYOUTS
from repro.obs import metrics as M
from repro.obs.spans import span
from repro.obs.trace import TraceRecorder, record_batch_trace
from repro.runtime.chaos import (ChaosConfig, ChaosError, ChaosSchedule,
                                 ChaosTransport)
from repro.runtime.resilience import (CircuitBreaker, DeadlineExceeded,
                                      RetryPolicy)
from repro.service.session import (LifecycleError, Session, SessionState)

BatchKey = tuple

_MASK32 = 0xFFFFFFFF

# one-hot / count payloads pad to the kernel's 128-lane quantum rather
# than the coarse buckets (a 1025-bin histogram pads to 1152, not 4096)
FUNC_PAD_QUANTUM = 128


@dataclasses.dataclass(frozen=True)
class BatchingConfig:
    max_batch: int = 8            # size watermark, in batch ROWS (S)
    max_age: float = 0.05         # age watermark, in `now` units
    pad_buckets: tuple[int, ...] = (64, 256, 1024, 4096, 16384)
    # per-batch-key tuned pads: {payload elems -> padded row elems},
    # written by the facade's tuner (``SecureAggregator(tune=...)``) so
    # tuned sessions pad to the tuner's kernel-lane-tight row instead
    # of the coarse buckets above — the padded length is part of the
    # batch key, so tuned and untuned sessions never share a batch.
    # The mapping is consulted before the buckets and is deliberately a
    # plain mutable dict: decisions arrive one signature at a time
    tuned: Optional[dict] = None
    # payloads longer than this chunk across multiple batch rows (the
    # per-session counter offsets keep chunked == monolithic); None
    # keeps the historical behavior (one row, padded to a multiple of
    # the top bucket)
    max_row_elems: Optional[int] = None
    # load-shedding high-watermark: when the TOTAL pending rows across
    # all batch keys exceed this, newest-arrival sessions are shed
    # (EXPIRED, flush reason "shed") at submit time; None = unbounded
    max_pending_rows: Optional[int] = None
    # default session deadline: open() sets expires_at = now + ttl
    # unless the caller overrides it; None = sessions never expire
    session_ttl: Optional[float] = None

    def padded_elems(self, elems: int) -> int:
        if self.tuned is not None:
            hit = self.tuned.get(elems)
            if hit is not None:
                return hit
        for b in self.pad_buckets:
            if elems <= b:
                return b
        top = self.pad_buckets[-1]
        return ((elems + top - 1) // top) * top

    def register_func_elems(self, round_elems) -> None:
        """Install the secure-function pad rule (:func:`func_padded`)
        for every payload length a ``FuncPlan`` will ship, so function
        rounds batch cleanly: 1-element bisection counts stay 1 element
        (instead of ballooning to the first bucket — they all share one
        batch key anyway), and one-hot histogram rows pad to the
        128-lane quantum instead of the next coarse bucket.  Requires a
        mutable ``tuned`` map; never overwrites a tuner's decision."""
        _require(self.tuned is not None,
                 "register_func_elems needs BatchingConfig(tuned={...}) "
                 "— a mutable per-elems pad map")
        for T in round_elems:
            self.tuned.setdefault(T, func_padded(T, self.pad_buckets))

    def row_layout(self, elems: int) -> tuple[int, int]:
        """(row_elems, n_rows) a payload of ``elems`` occupies."""
        if self.max_row_elems is not None and elems > self.max_row_elems:
            row = self.padded_elems(self.max_row_elems)
            return row, -(-elems // row)
        return self.padded_elems(elems), 1


def func_padded(elems: int, pad_buckets: tuple =
                BatchingConfig.pad_buckets) -> int:
    """The secure-function (``repro.funcs``) pad rule for one payload
    length: tiny count payloads (bisection rounds, <= 8 elems) stay
    unpadded — every concurrent bisection round shares the same T so
    there is nothing to coalesce by padding — and wider one-hot rows
    round up to the 128-lane quantum, capped at whatever the default
    buckets would have picked (so the rule can only ever shrink a
    batch row, never inflate one)."""
    if elems <= 8:
        return elems
    lane = -(-elems // FUNC_PAD_QUANTUM) * FUNC_PAD_QUANTUM
    for b in pad_buckets:
        if elems <= b:
            return min(lane, b)
    top = pad_buckets[-1]
    return min(lane, -(-elems // top) * top)


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Streaming-pipeline knobs of :class:`BatchedExecutor`.

    ``depth`` is the number of in-flight batch slots: 1 reproduces the
    historical fully-sequential dispatch; 2 double-buffers (pack slot
    k+1 while the device aggregates slot k — JAX async dispatch defers
    the host sync to reveal time).  ``donate`` donates the packed
    ``(S, n, T)`` slot buffer to the executable
    (``jax.jit(donate_argnums=(0,))``); ``None`` auto-enables it off
    the CPU backend, where XLA ignores donation (with a UserWarning).
    ``async_compile`` makes an executable-cache miss warm in the
    background (AOT ``lower().compile()`` on a worker thread) while
    traffic keeps flowing on an already-compiled larger-S shape bucket
    — rows pad with zero-contribution dummies, which is bit-identical
    for the real rows because batch rows are independent sessions."""

    depth: int = 2
    donate: Optional[bool] = None
    async_compile: bool = True

    def resolve_donate(self) -> bool:
        if self.donate is None:
            return jax.default_backend() != "cpu"
        return self.donate


class _Slot:
    """One in-flight streaming dispatch: the device result future plus
    everything the deferred completion (reveal / account / retry) needs."""

    __slots__ = ("sessions", "padded", "unit", "backend", "degraded",
                 "revealed", "owner", "fresh", "rows", "masks",
                 "t_issue", "error", "buf", "votes")

    def __init__(self, sessions, padded, unit, backend, degraded):
        self.sessions = sessions
        self.padded = padded
        self.unit = unit
        self.backend = backend
        self.degraded = degraded
        self.revealed = None          # device array until _settle syncs
        self.owner = None
        self.fresh = False
        self.rows = 0
        self.masks = {}
        self.t_issue = 0.0
        self.error: Optional[Exception] = None
        self.buf = None               # pack buffer, recycled at settle
        self.votes = None             # the executable's vote tally


class BatchedExecutor:
    """Runs batches of sealed sessions through one engine execution.

    Compiled executables are cached per (batch key, row count, fault
    modes, backend) — a steady-state service replays a handful of
    shapes, so each shape compiles once and every later batch is a
    single cached call.  Failures go through the retry -> bisect ->
    quarantine ladder of ``retry`` (see module docstring); a mesh
    executor additionally degrades to the sim transport behind
    ``breaker``."""

    def __init__(self, kernel_impl: Optional[str] = None,
                 transport: str = "sim",
                 mesh: Optional[jax.sharding.Mesh] = None,
                 dp_axes: Sequence[str] = ("data",),
                 retry: Optional[RetryPolicy] = None,
                 breaker: Optional[CircuitBreaker] = None,
                 chaos=None,
                 metrics: Optional[M.MetricsRegistry] = None,
                 recorder: Optional[TraceRecorder] = None,
                 stream: Optional[StreamConfig] = None):
        _require(transport in ("sim", "mesh"),
                 f"unknown executor transport {transport!r}; pick 'sim' "
                 "(single-device oracle) or 'mesh' (shard_map over a dp "
                 "mesh)")
        _require(transport != "mesh" or mesh is not None,
                 "executor transport='mesh' needs a mesh: pass "
                 "mesh=compat.node_mesh(n_nodes) (one device per "
                 "protocol node)")
        self.kernel_impl = kernel_impl
        self.transport = transport
        self.mesh = mesh
        self.dp_axes = tuple(dp_axes)
        self.retry = retry if retry is not None else RetryPolicy()
        # the degrade ladder only applies to the distributed backend —
        # a sim executor has nothing to fall back to
        self.breaker = breaker if breaker is not None else (
            CircuitBreaker() if transport == "mesh" else None)
        if chaos is not None and isinstance(chaos, ChaosConfig):
            chaos = ChaosSchedule(chaos)
        self.chaos: Optional[ChaosSchedule] = chaos
        self.stream = stream if stream is not None else StreamConfig()
        self._donate = self.stream.resolve_donate()
        self._fns: dict = {}
        # streaming pipeline state: in-flight slots (issued, not yet
        # settled), unit errors deferred to flush(), and the background
        # AOT warm pool (lazily built on the first bucketed miss)
        self._ring: collections.deque = collections.deque()
        self._errors: list[Exception] = []
        self._warming: dict = {}
        self._pool: Optional[concurrent.futures.ThreadPoolExecutor] = None
        # recycled host pack buffers, keyed by (rows, n, padded): a
        # settled slot's buffer is refilled in place instead of
        # re-faulting megabytes of fresh pages every batch
        self._buf_pool: dict = {}
        # every counter lives on the metrics registry (one source of
        # truth obs.export can render); the legacy attribute names stay
        # as read-only properties.  A private registry by default —
        # explicit sharing (serve_agg) passes one in.
        self.metrics = M.registry_or_default(metrics)
        self.recorder = recorder
        # stage spans use the recorder's clock when one is attached
        # (deterministic replays inject a TickClock); perf_counter
        # otherwise
        self._clock = (recorder.clock if recorder is not None
                       else time.perf_counter)
        m = self.metrics
        self._c_batches = m.counter(M.M_BATCHES)
        self._c_sessions = m.counter(M.M_SESSIONS)
        self._c_fn_hits = m.counter(M.M_FN_HITS)
        self._c_fn_misses = m.counter(M.M_FN_MISSES)
        self._c_fn_bucket = m.counter(M.M_FN_BUCKET_HITS)
        self._g_depth = m.gauge(M.G_PIPELINE_DEPTH)
        self._c_retries = m.counter(M.M_RETRIES)
        self._c_bisections = m.counter(M.M_BISECTIONS)
        self._c_quarantined = m.counter(M.M_QUARANTINED)
        self._c_deadline = m.counter(M.M_DEADLINE_HITS)
        self._c_degraded = m.counter(M.M_DEGRADED)
        self._c_wire = m.counter(M.M_WIRE_BYTES)
        self._c_votes = {layout: m.counter(M.M_VOTE_CALLS, layout=layout)
                         for layout in VOTE_LAYOUTS}
        self._h_stage = {s: m.histogram(M.H_STAGE, stage=s)
                         for s in M.STAGES}
        self.dead_letter: list[tuple[int, str]] = []   # (sid, error repr)
        self._units = 0               # retry units started (jitter salt)
        self._plans: dict = {}        # params -> AggPlan (byte account)
        # executable key -> the vote tally its trace fills (see
        # ``build_batch_executable(vote_calls=...)``)
        self._votes: dict = {}

    def _plan_of(self, template: Session):
        """Compiled plan of one batch's shared params (hot-path memo in
        front of the module-wide ``compile_plan`` cache — skips the
        AggConfig construction/validation per dispatch)."""
        plan = self._plans.get(template.params)
        if plan is None:
            plan = compile_plan(template.params.agg_config(self.kernel_impl))
            self._plans[template.params] = plan
        return plan

    # -- registry-backed counter views (the pre-PR-7 attribute names) ----
    @property
    def batches_run(self) -> int:
        return self._c_batches.value

    @property
    def sessions_run(self) -> int:
        return self._c_sessions.value

    @property
    def fn_cache_hits(self) -> int:
        return self._c_fn_hits.value

    @property
    def fn_cache_misses(self) -> int:
        return self._c_fn_misses.value

    @property
    def retries(self) -> int:
        return self._c_retries.value

    @property
    def bisections(self) -> int:
        return self._c_bisections.value

    @property
    def quarantined(self) -> int:
        return self._c_quarantined.value

    @property
    def deadline_hits(self) -> int:
        return self._c_deadline.value

    @property
    def degraded_batches(self) -> int:
        return self._c_degraded.value

    @property
    def wire_bytes(self) -> int:
        """Cumulative modeled wire bytes of every executed batch —
        ``AggPlan.wire_bytes`` at the executed row count, i.e. exactly
        what the engine's trace-time ``Transport.bytes_sent`` accounted
        for those executions."""
        return self._c_wire.value

    @property
    def cache_stats(self) -> dict:
        """Compiled-executable cache account (plan compilation has its
        own shared memo — see ``core.plan.plan_cache_stats``)."""
        return {"hits": self.fn_cache_hits, "misses": self.fn_cache_misses,
                "bucket_hits": self._c_fn_bucket.value,
                "size": len(self._fns)}

    @property
    def resilience(self) -> dict:
        """Retry/quarantine/degrade account (see module docstring)."""
        return {
            "retries": self.retries,
            "bisections": self.bisections,
            "quarantined": self.quarantined,
            "deadline_hits": self.deadline_hits,
            "degraded_batches": self.degraded_batches,
            "dead_letter": tuple(self.dead_letter),
            "chaos_injected": (self.chaos.injected
                               if self.chaos is not None else 0),
            "breaker": (self.breaker.snapshot()
                        if self.breaker is not None else None),
        }

    def _build_fn(self, template: Session, backend: str, key):
        """The shared jitted batch executable (see
        ``core.engine.build_batch_executable``) with the executor's
        donation policy applied; its trace fills the vote tally of
        executable ``key``."""
        plan = self._plan_of(template)
        return build_batch_executable(
            plan, backend=backend, mesh=self.mesh, dp_axes=self.dp_axes,
            impl=self.kernel_impl, donate=self._donate,
            vote_calls=self._votes.setdefault(key, {}))

    def _drain_warmed(self) -> None:
        """Promote finished background AOT compiles into the cache (a
        failed warm is dropped — the next exact-shape miss recompiles
        synchronously and surfaces the error on the dispatch path)."""
        if not self._warming:
            return
        for key in [k for k, f in self._warming.items() if f.done()]:
            fut = self._warming.pop(key)
            try:
                self._fns[key] = fut.result()
            except Exception:
                pass

    def _warm_async(self, key, template: Session, padded: int, S: int,
                    modes: frozenset, backend: str) -> None:
        """Kick off an AOT ``lower().compile()`` of the exact shape on
        the worker thread (XLA releases the GIL during the build, so the
        pump loop keeps flowing on the bucket executable meanwhile)."""
        if key in self._warming:
            return
        if self._pool is None:
            self._pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1)
        fn = self._build_fn(template, backend, key)
        n = template.params.n_nodes
        f32, u32 = jnp.float32, jnp.uint32

        def build():
            with span("svc.compile"):
                return fn.lower(
                    jax.ShapeDtypeStruct((S, n, padded), f32),
                    jax.ShapeDtypeStruct((S,), u32),
                    jax.ShapeDtypeStruct((S,), u32),
                    {m: jax.ShapeDtypeStruct((S, n), jnp.bool_)
                     for m in modes}).compile()

        self._warming[key] = self._pool.submit(build)

    def _compiled(self, template: Session, padded: int, S: int,
                  modes: frozenset,
                  backend: str) -> tuple[Callable, bool, int, dict]:
        """(executable, fresh, S_exec, votes) — ``fresh`` marks a
        synchronous cache miss, which the stage timer attributes to
        ``plan_compile`` (jax.jit is lazy, so the XLA build cost lands on
        the miss's first dispatch).  ``S_exec >= S`` is the row count the
        returned executable was compiled for: on a miss with
        ``async_compile`` the exact shape warms in the background and the
        dispatch runs on the smallest already-compiled larger-S bucket
        (the caller pads with dummy rows and slices the first S back
        out).  ``votes`` is the returned executable's vote tally, filled
        by its trace."""
        # fault PATTERNS are runtime (S, n) masks, so churn/missing-slot
        # variation never retraces; only the set of fault MODES present
        # (<= 8 combinations) and the dispatch backend are part of the
        # executable's identity (the degrade ladder adds "sim" entries
        # next to a mesh executor's primaries)
        bk = template.params.batch_key(padded)
        key = (bk, S, modes, backend)
        self._drain_warmed()
        fn = self._fns.get(key)
        if fn is not None:
            self._c_fn_hits.inc()
            return fn, False, S, self._votes[key]
        self._c_fn_misses.inc()
        if self.stream.async_compile and self.stream.depth > 1:
            buckets = [k[1] for k in self._fns
                       if k[0] == bk and k[2] == modes and k[3] == backend
                       and k[1] > S]
            if buckets:
                self._c_fn_bucket.inc()
                self._warm_async(key, template, padded, S, modes, backend)
                S_exec = min(buckets)
                exec_key = (bk, S_exec, modes, backend)
                return (self._fns[exec_key], False, S_exec,
                        self._votes[exec_key])
        fn = self._build_fn(template, backend, key)
        self._fns[key] = fn
        return fn, True, S, self._votes[key]

    # -- one dispatch attempt ----------------------------------------------
    def _dispatch(self, sessions: Sequence[Session], padded: int,
                  backend: str, fault: Optional[ChaosConfig], unit: int):
        """Pack + issue one batch WITHOUT the host sync: returns
        ``(revealed, owner, fresh, rows, masks, buf, votes)`` where
        ``revealed`` is the (possibly still in-flight) device result of
        the first ``rows`` real rows (bucketed dispatches pad with dummy
        rows — the caller slices ``[:rows]`` after its ``np.asarray``
        sync), ``masks`` are the real rows' fault masks (what the trace
        records), ``buf`` is the pack buffer to recycle at settlement and
        ``votes`` the executable's vote tally (None for the eager chaos
        run).  Session state is untouched, so a failed attempt stays
        retriable.  The pack, the host-to-device copy and the call
        are the ``svc.pack`` / ``svc.put`` / ``svc.issue`` (or, on a
        cache miss, ``svc.compile``) spans of retry unit ``unit``."""
        if fault is not None and fault.mode == "dispatch":
            raise ChaosError(
                f"chaos: injected dispatch failure "
                f"(batch of {len(sessions)})")
        if fault is not None and fault.mode == "slow":
            time.sleep(fault.slow_s)
        n_nodes = sessions[0].params.n_nodes
        seeds, offsets, owner = [], [], []
        for i, s in enumerate(sessions):
            for j in range(s.n_rows(padded)):
                seeds.append(s.seed)
                offsets.append((s.pad_offset + j * padded) & _MASK32)
                owner.append(i)
        R = len(owner)
        owner = np.asarray(owner)
        sess_masks = fault_masks_of(
            [s.fault.specs() for s in sessions], n_nodes)
        masks = {m: v[owner] for m, v in sess_masks.items()}  # per row
        if fault is not None and fault.mode == "compile":
            raise ChaosError("chaos: injected compile failure")
        if fault is not None and fault.mode == "hop":
            fresh = False                        # eager run, no jit cache
            xs = np.stack([mat for s in sessions
                           for mat in s.payload_rows(padded)])
            revealed = self._chaos_hop_run(sessions[0], xs, seeds, offsets,
                                           masks, backend, fault)
            return revealed, owner, fresh, R, masks, None, None
        fn, fresh, S_exec, votes = self._compiled(
            sessions[0], padded, R, frozenset(masks), backend)
        # pack straight into a recycled (S_exec, n, padded) slot buffer
        # — fill_payload_rows writes every byte of the real rows, so no
        # pre-zeroing; the buffer returns to the pool once this batch
        # settles (its executable is done reading the staged copy)
        with span("svc.pack", unit=unit):
            xs = self._buf_take((S_exec, n_nodes, padded))
            r = 0
            for s in sessions:
                r += s.fill_payload_rows(xs, r, padded)
            dm = masks
            if S_exec > R:
                # shape-bucket dispatch: dummy zero rows (zero payload,
                # zero seed/offset, no faults) — batch rows are
                # independent sessions, so the real rows' outputs are
                # bit-identical and the dummies are sliced off after the
                # sync
                pad = S_exec - R
                xs[R:] = 0.0
                seeds = list(seeds) + [0] * pad
                offsets = list(offsets) + [0] * pad
                dm = {m: np.concatenate(
                    [v, np.zeros((pad, n_nodes), v.dtype)])
                    for m, v in masks.items()}
        with span("svc.put", unit=unit):
            if backend == "mesh":
                # stage the batch pre-sharded over the dp axes:
                # device_put to the executable's input sharding is one
                # strided copy, while handing jit a replicated/device-0
                # array makes XLA reshard inside the program (measurably
                # slower on a thread-starved host)
                from jax.sharding import NamedSharding, PartitionSpec
                xs_dev = jax.device_put(xs, NamedSharding(
                    self.mesh, PartitionSpec(None, self.dp_axes, None)))
            else:
                xs_dev = jnp.asarray(xs)
            args = (xs_dev, jnp.asarray(seeds, dtype=jnp.uint32),
                    jnp.asarray(offsets, dtype=jnp.uint32),
                    {k: jnp.asarray(v) for k, v in dm.items()})
        # jax.jit builds lazily: a cache miss compiles inside this call
        with span("svc.compile" if fresh else "svc.issue", unit=unit):
            revealed = fn(*args)
        return revealed, owner, fresh, R, masks, xs, votes

    def _buf_take(self, shape) -> np.ndarray:
        """A pooled float32 pack buffer (fresh if the pool is dry)."""
        pool = self._buf_pool.get(shape)
        if pool:
            return pool.pop()
        return np.empty(shape, np.float32)

    def _buf_give(self, buf) -> None:
        """Return a settled slot's pack buffer to the pool.  Only
        called after the batch's host sync — the staged device copy is
        complete by then, so refilling the buffer cannot race the
        executable.  The pool is capped per shape (depth + a retry's
        worth of slack); overflow buffers just drop to the GC."""
        if buf is not None:
            pool = self._buf_pool.setdefault(buf.shape, [])
            if len(pool) < max(self.stream.depth, 1) + 2:
                pool.append(buf)

    def _account(self, sessions: Sequence[Session], padded: int, rows: int,
                 masks: dict, unit: int, attempt: int, backend: str,
                 fresh: bool, votes: Optional[dict]) -> None:
        """Book one completed attempt's wire bytes, vote calls by layout
        (the executable's trace-time tally) and flight-recorder events —
        all host-side, after the device sync, so the jitted program is
        untouched.  The streaming path defers this to slot settlement
        (the account describes an execution that finished)."""
        plan = self._plan_of(sessions[0])
        self._c_wire.inc(plan.wire_bytes(padded, S=rows))
        for layout, calls in (votes or {}).items():
            self._c_votes[layout].inc(calls)
        if self.recorder is not None:
            record_batch_trace(
                self.recorder, plan, padded=padded, rows=rows,
                masks=masks, unit=unit, attempt=attempt, backend=backend,
                sids=tuple(s.sid for s in sessions), fresh=fresh)

    def _attempt(self, sessions: Sequence[Session], padded: int,
                 backend: str, fault: Optional[ChaosConfig],
                 unit: int = 0, attempt: int = 1):
        """One SYNCHRONOUS dispatch: pack, execute, block, account.
        Returns (revealed, owner) without touching session state (the
        caller reveals after the deadline check, so a failed/too-slow
        attempt stays retriable)."""
        t0 = self._clock()
        revealed, owner, fresh, R, masks, buf, votes = self._dispatch(
            sessions, padded, backend, fault, unit=unit)
        with span("svc.settle", unit=unit):
            revealed = np.asarray(revealed)[:R]  # host sync: span ends here
            self._buf_give(buf)
        stage = "plan_compile" if fresh else "device_dispatch"
        self._h_stage[stage].observe(self._clock() - t0)
        self._account(sessions, padded, R, masks, unit, attempt, backend,
                      fresh, votes)
        return revealed, owner

    def _chaos_hop_run(self, template: Session, xs, seeds, offsets, masks,
                       backend: str, fault: ChaosConfig):
        """Eager (unjitted) engine run with a ChaosTransport wrapped
        around the substrate, so a raise-at-hop-k fault fires on every
        armed attempt instead of only the first trace."""
        cfg = template.params.agg_config(self.kernel_impl)
        plan = compile_plan(cfg)
        meta = SessionMeta(
            seeds=jnp.asarray(seeds, dtype=jnp.uint32),
            offsets=jnp.asarray(offsets, dtype=jnp.uint32),
            fault_masks={k: jnp.asarray(v) for k, v in masks.items()})
        xj = jnp.asarray(xs)
        if backend == "mesh":
            mt = MeshTransport(self.mesh, self.dp_axes,
                               impl=self.kernel_impl,
                               wrap_inner=lambda tp: ChaosTransport(
                                   tp, fault))
            return mt.execute(plan, xj, meta, reveal_only=True)
        R, n, T = xj.shape
        tp = ChaosTransport(SimTransport(plan, S=R), fault)
        flat = xj.reshape(R * n, T).astype(jnp.float32)
        (out,) = execute_chunks(plan, tp, [flat], meta, reveal_only=True)
        return out

    # -- retry / bisect / quarantine ladder ---------------------------------
    def _run_unit(self, sessions: list[Session], padded: int,
                  start_attempt: int = 1,
                  prior_error: Optional[Exception] = None,
                  salt: Optional[int] = None) -> Optional[Exception]:
        """Drive one retry unit to a terminal state: every session ends
        REVEALED or FAILED (never AGGREGATING).  Returns the first
        triggering error if any session was quarantined, else None.

        The streaming path re-enters here after a slot's non-blocking
        attempt 1 already failed at settlement: ``start_attempt=2``
        continues the SAME unit (``salt`` keeps the backoff jitter and
        trace unit id stable) with ``prior_error`` standing in as the
        last error if the remaining budget is empty."""
        policy = self.retry
        if salt is None:
            self._units += 1
            salt = self._units
        rec = self.recorder
        sids = tuple(s.sid for s in sessions)
        last: Optional[Exception] = prior_error
        for attempt in range(start_attempt, policy.max_attempts + 1):
            backend = self.transport
            degraded = False
            if (self.breaker is not None and backend == "mesh"
                    and not self.breaker.allow_primary()):
                backend, degraded = "sim", True
            fault = (self.chaos.decide(sessions, backend)
                     if self.chaos is not None else None)
            if fault is not None and rec is not None:
                rec.event("chaos", unit=salt, attempt=attempt,
                          mode=fault.mode, backend=backend,
                          sids=list(sids))
            t0 = time.monotonic()
            try:
                revealed, owner = self._attempt(sessions, padded,
                                                backend, fault,
                                                unit=salt, attempt=attempt)
                if (policy.deadline_s is not None
                        and time.monotonic() - t0 > policy.deadline_s):
                    self._c_deadline.inc()
                    raise DeadlineExceeded(
                        f"batch attempt exceeded the "
                        f"{policy.deadline_s}s deadline")
            except Exception as e:
                last = e
                self._record_breaker(rec, backend, failed=True)
                if attempt < policy.max_attempts:
                    self._c_retries.inc()
                    delay = policy.backoff_s(attempt, salt=salt)
                    if rec is not None:
                        rec.event("retry", unit=salt, attempt=attempt,
                                  backend=backend, delay=delay,
                                  error=repr(e)[:200])
                    if delay > 0:
                        policy.sleep(delay)
                continue
            self._record_breaker(rec, backend, failed=False)
            if degraded:
                self._c_degraded.inc()
                if rec is not None:
                    rec.event("degrade", unit=salt, attempt=attempt,
                              sids=list(sids))
            with span("svc.reveal", self._h_stage["reveal"], self._clock,
                      unit=salt):
                for i, s in enumerate(sessions):
                    s.reveal(revealed[owner == i].reshape(-1))
            self._c_batches.inc()
            self._c_sessions.inc(len(sessions))
            return None
        # attempt budget exhausted: bisect to isolate the poison rows
        if policy.bisect and len(sessions) > 1:
            self._c_bisections.inc()
            mid = len(sessions) // 2
            if rec is not None:
                rec.event("bisect", unit=salt,
                          left=[s.sid for s in sessions[:mid]],
                          right=[s.sid for s in sessions[mid:]])
            e1 = self._run_unit(sessions[:mid], padded)
            e2 = self._run_unit(sessions[mid:], padded)
            return e1 if e1 is not None else e2
        # irreducible unit still failing: quarantine it
        for s in sessions:
            s.fail(repr(last))
            self.dead_letter.append((s.sid, repr(last)))
        self._c_quarantined.inc(len(sessions))
        if rec is not None:
            rec.event("quarantine", unit=salt, sids=list(sids),
                      error=repr(last)[:200])
        if len(self.dead_letter) > 4096:          # bounded history
            del self.dead_letter[:-2048]
        return last

    def _record_breaker(self, rec, backend: str, *, failed: bool) -> None:
        """Feed the breaker and trace its state transitions."""
        if self.breaker is None or backend != "mesh":
            return
        before = self.breaker.state
        if failed:
            self.breaker.record_failure()
        else:
            self.breaker.record_success()
        if rec is not None and self.breaker.state != before:
            rec.event("breaker", state=self.breaker.state)

    def execute(self, sessions: Sequence[Session],
                padded_elems: Optional[int] = None) -> None:
        """Aggregate + reveal one batch (all sessions share a batch key).

        A session may span several batch rows (long payloads); row j of
        a session reuses its pad key at counter offset ``pad_offset +
        j * padded_elems``.  Failures run the retry -> bisect ->
        quarantine ladder: surviving sessions reveal normally and the
        poison ones land in :attr:`dead_letter` as FAILED — a session is
        never left in AGGREGATING and never silently dropped.  The
        first triggering error re-raises only when NO session in the
        call survived (so the pump can account a fully-poisoned key
        without starving the rest of its sweep)."""
        if not sessions:
            return
        padded = padded_elems or max(s.params.elems for s in sessions)
        key0 = sessions[0].params.batch_key(padded)
        _require(all(s.params.batch_key(padded) == key0 for s in sessions),
                 "batch mixes incompatible sessions (distinct batch "
                 "keys); group sessions per AdmissionQueue.submit key")
        sessions = list(sessions)
        for s in sessions:
            s.mark_aggregating()
        self._g_depth.track_max(1.0)
        try:
            err = self._run_unit(sessions, padded)
        except BaseException:
            # unexpected escape (bug / KeyboardInterrupt): never leave a
            # session wedged in AGGREGATING
            for s in sessions:
                if s.state is SessionState.AGGREGATING:
                    s.fail("executor aborted mid-batch")
            raise
        if err is not None and all(s.state is SessionState.FAILED
                                   for s in sessions):
            raise err

    # -- streaming pipeline (overlapped dispatch) ---------------------------
    def execute_async(self, sessions: Sequence[Session],
                      padded_elems: Optional[int] = None) -> None:
        """Issue one batch into the streaming ring without blocking on
        its device result.

        Same batch-key/lifecycle contract as :meth:`execute`, but the
        dispatch is only *issued* here (JAX async dispatch — the packed
        slot goes to the device and the host returns immediately, timed
        as the ``pack_overlap`` stage); the host sync, the reveal, and
        the retry ladder run when the slot is settled — at the next
        issue once the ring holds ``StreamConfig.depth`` slots, or at
        :meth:`flush`.  Unit failures NEVER raise here: a failed slot
        re-enters the retry -> bisect -> quarantine ladder at
        settlement (after draining every other in-flight slot), and an
        all-failed unit's error is deferred to the next :meth:`flush`."""
        if not sessions:
            return
        padded = padded_elems or max(s.params.elems for s in sessions)
        key0 = sessions[0].params.batch_key(padded)
        _require(all(s.params.batch_key(padded) == key0 for s in sessions),
                 "batch mixes incompatible sessions (distinct batch "
                 "keys); group sessions per AdmissionQueue.submit key")
        sessions = list(sessions)
        for s in sessions:
            s.mark_aggregating()
        try:
            while len(self._ring) >= max(self.stream.depth, 1):
                self._flush_one()
        except BaseException:
            self._abort_ring()
            for s in sessions:
                if s.state is SessionState.AGGREGATING:
                    s.fail("executor aborted mid-batch")
            raise
        self._ring.append(self._issue(sessions, padded))
        self._g_depth.track_max(float(len(self._ring)))

    def flush(self) -> None:
        """Settle every in-flight streaming slot (reveal / retry /
        quarantine), then re-raise the FIRST deferred all-failed unit
        error — mirroring :meth:`execute`'s raise-only-when-no-session-
        survived contract, shifted to the drain point."""
        try:
            while self._ring:
                self._flush_one()
        except BaseException:
            self._abort_ring()
            raise
        if self._errors:
            err = self._errors[0]
            self._errors.clear()
            raise err

    def _abort_ring(self) -> None:
        """Unexpected escape mid-drain: never leave ring sessions
        wedged in AGGREGATING."""
        while self._ring:
            slot = self._ring.popleft()
            for s in slot.sessions:
                if s.state is SessionState.AGGREGATING:
                    s.fail("executor aborted mid-batch")

    def _issue(self, sessions: list, padded: int) -> _Slot:
        """Attempt 1 of a new retry unit, issued without blocking: the
        breaker/chaos decisions and the host-side pack + dispatch run
        now (the ``pack_overlap`` span — overlapped with the previous
        slot's device work), exceptions are captured on the slot."""
        self._units += 1
        salt = self._units
        backend = self.transport
        degraded = False
        if (self.breaker is not None and backend == "mesh"
                and not self.breaker.allow_primary()):
            backend, degraded = "sim", True
        fault = (self.chaos.decide(sessions, backend)
                 if self.chaos is not None else None)
        rec = self.recorder
        if fault is not None and rec is not None:
            rec.event("chaos", unit=salt, attempt=1, mode=fault.mode,
                      backend=backend, sids=[s.sid for s in sessions])
        slot = _Slot(sessions, padded, salt, backend, degraded)
        slot.t_issue = time.monotonic()
        t0 = self._clock()
        try:
            (slot.revealed, slot.owner, slot.fresh, slot.rows,
             slot.masks, slot.buf, slot.votes) = self._dispatch(
                sessions, padded, backend, fault, unit=salt)
        except Exception as e:
            slot.error = e
        self._h_stage["pack_overlap"].observe(self._clock() - t0)
        return slot

    def _settle(self, slot: _Slot) -> Optional[Exception]:
        """Complete one issued slot: host sync (the streaming
        ``device_dispatch`` span is just this blocking wait), deadline
        check, account, breaker feed, reveal.  Returns the attempt's
        error instead of raising (the caller owns the drain-then-retry
        ordering); session state is only touched on success."""
        policy = self.retry
        rec = self.recorder
        try:
            if slot.error is not None:
                raise slot.error
            stage = "plan_compile" if slot.fresh else "device_dispatch"
            with span("svc.settle", self._h_stage[stage], self._clock,
                      unit=slot.unit):
                revealed = np.asarray(slot.revealed)[:slot.rows]  # sync
                self._buf_give(slot.buf)
                slot.buf = None
            if (policy.deadline_s is not None
                    and time.monotonic() - slot.t_issue
                    > policy.deadline_s):
                self._c_deadline.inc()
                raise DeadlineExceeded(
                    f"batch attempt exceeded the "
                    f"{policy.deadline_s}s deadline")
        except Exception as e:
            self._record_breaker(rec, slot.backend, failed=True)
            return e
        self._account(slot.sessions, slot.padded, slot.rows, slot.masks,
                      slot.unit, 1, slot.backend, slot.fresh, slot.votes)
        self._record_breaker(rec, slot.backend, failed=False)
        if slot.degraded:
            self._c_degraded.inc()
            if rec is not None:
                rec.event("degrade", unit=slot.unit, attempt=1,
                          sids=[s.sid for s in slot.sessions])
        with span("svc.reveal", self._h_stage["reveal"], self._clock,
                  unit=slot.unit):
            for i, s in enumerate(slot.sessions):
                s.reveal(revealed[slot.owner == i].reshape(-1))
        self._c_batches.inc()
        self._c_sessions.inc(len(slot.sessions))
        return None

    def _retry_continuation(self, slot: _Slot,
                            e: Exception) -> Optional[Exception]:
        """Re-enter the retry ladder for a slot whose non-blocking
        attempt 1 failed: book the retry (same unit id, same jitter
        salt as a sequential attempt-1 failure would), then continue
        the unit synchronously from attempt 2."""
        policy = self.retry
        rec = self.recorder
        if policy.max_attempts > 1:
            self._c_retries.inc()
            delay = policy.backoff_s(1, salt=slot.unit)
            if rec is not None:
                rec.event("retry", unit=slot.unit, attempt=1,
                          backend=slot.backend, delay=delay,
                          error=repr(e)[:200])
            if delay > 0:
                policy.sleep(delay)
        return self._run_unit(slot.sessions, slot.padded,
                              start_attempt=2, prior_error=e,
                              salt=slot.unit)

    def _flush_one(self) -> None:
        """Settle the oldest in-flight slot.  On failure, FIRST drain
        every other in-flight slot (the retry/bisect ladder re-dispatches
        synchronously — no donated buffer or device queue state may be
        shared with still-in-flight work), then run the failed slots'
        retry continuations in issue order."""
        pending = [self._ring.popleft()]
        try:
            err = self._settle(pending[0])
            if err is None:
                return
            failures = [(pending[0], err)]
            while self._ring:        # drain in-flight before re-dispatch
                nxt = self._ring.popleft()
                pending.append(nxt)
                e2 = self._settle(nxt)
                if e2 is None:
                    pending.remove(nxt)
                else:
                    failures.append((nxt, e2))
            for sl, e in failures:
                unit_err = self._retry_continuation(sl, e)
                pending.remove(sl)
                if unit_err is not None and all(
                        s.state is SessionState.FAILED
                        for s in sl.sessions):
                    self._errors.append(unit_err)
        except BaseException:
            for sl in pending:
                for s in sl.sessions:
                    if s.state is SessionState.AGGREGATING:
                        s.fail("executor aborted mid-batch")
            raise


class AdmissionQueue:
    """Coalesces sealed sessions into fixed-size batches per batch key."""

    def __init__(self, executor: BatchedExecutor,
                 batching: BatchingConfig = BatchingConfig(),
                 pre_execute: Optional[Callable] = None):
        self.executor = executor
        self.batching = batching
        self.pre_execute = pre_execute   # e.g. epoch-departure fault merge
        self._pending: dict[BatchKey, list[Session]] = {}
        self.batch_sizes: list[int] = []
        # fairness/starvation telemetry lives on the executor's metrics
        # registry (one registry per service); the legacy attribute
        # names stay as read-only properties and ``metrics`` returns the
        # same dict shape as before
        reg = executor.metrics
        self.recorder = executor.recorder
        self._c_flush = {r: reg.counter(M.M_FLUSHES, reason=r)
                         for r in ("size", "age", "force", "shed")}
        self._g_max_age = reg.gauge(M.M_MAX_QUEUE_AGE)
        self._c_starved = reg.counter(M.M_STARVED)
        self._c_expired = reg.counter(M.M_EXPIRED)
        self._c_shed = reg.counter(M.M_SHED)
        self._c_dropped = reg.counter(M.M_DROPPED)
        self._h_wait = executor._h_stage["admission_wait"]

    # -- registry-backed counter views (the pre-PR-7 attribute names) ----
    @property
    def flush_reasons(self) -> dict:
        return {r: c.value for r, c in self._c_flush.items()}

    @property
    def max_queue_age(self) -> float:
        return self._g_max_age.value

    @property
    def starved_sessions(self) -> int:
        return self._c_starved.value    # flushed only after 2x the age mark

    @property
    def expired_sessions(self) -> int:
        return self._c_expired.value    # deadline reached while queued

    @property
    def shed_sessions(self) -> int:
        return self._c_shed.value       # dropped by the load watermark

    @property
    def dropped_sessions(self) -> int:
        return self._c_dropped.value    # left the queue already terminal

    def submit(self, session: Session,
               now: Optional[float] = None) -> BatchKey:
        if session.state is not SessionState.SEALED:
            raise LifecycleError(
                f"only SEALED sessions enter the admission queue, got "
                f"{session!r}")
        row_elems, _ = self.batching.row_layout(session.params.elems)
        key = session.params.batch_key(row_elems)
        self._pending.setdefault(key, []).append(session)
        if self.batching.max_pending_rows is not None:
            self._shed(session.sealed_at if now is None else now)
        return key

    def depth(self) -> int:
        return sum(len(q) for q in self._pending.values())

    def depth_rows(self) -> int:
        """Total pending batch rows across all keys (the unit the
        ``max_pending_rows`` load watermark is measured in)."""
        return sum(self._rows(key, q) for key, q in self._pending.items())

    def oldest_ages(self, now: Optional[float] = None) -> dict:
        """Per-key age watermark: how long each key's oldest sealed
        session has been waiting."""
        now = time.monotonic() if now is None else now
        return {key: now - min(s.sealed_at for s in q)
                for key, q in self._pending.items() if q}

    @property
    def metrics(self) -> dict:
        return {
            "flush_reasons": dict(self.flush_reasons),
            "max_queue_age": self.max_queue_age,
            "starved_sessions": self.starved_sessions,
            "expired_sessions": self.expired_sessions,
            "shed_sessions": self.shed_sessions,
            "dropped_sessions": self.dropped_sessions,
            "pending_sessions": self.depth(),
            "pending_rows": self.depth_rows(),
        }

    def _rows(self, key: BatchKey, sessions: Sequence[Session]) -> int:
        row_elems = key[-1]
        return sum(s.n_rows(row_elems) for s in sessions)

    def _shed(self, now: float) -> None:
        """Load shedding: while total pending rows exceed the
        high-watermark, drop the NEWEST arrival of the heaviest key.

        Victim selection is weighted-fair across batch keys: each key
        weighs ``pending_rows / (1 + oldest_age)`` — the key holding
        the most work, discounted by how long its oldest session has
        already waited — so a young flood sheds before an old starving
        key loses anything."""
        limit = self.batching.max_pending_rows
        while self.depth_rows() > limit:
            ages = self.oldest_ages(now)
            key = max(self._pending,
                      key=lambda k: self._rows(k, self._pending[k])
                      / (1.0 + max(ages.get(k, 0.0), 0.0)))
            victim = self._pending[key].pop()     # newest arrival
            victim.expire(
                f"shed: admission queue over max_pending_rows={limit}")
            self._c_flush["shed"].inc()
            self._c_shed.inc()
            if self.recorder is not None:
                self.recorder.event("shed", sid=victim.sid,
                                    pending_rows=self.depth_rows(),
                                    limit=limit)
            if not self._pending[key]:
                del self._pending[key]

    def _sweep(self, q: list[Session], now: float) -> list[Session]:
        """Deadline/terminal sweep of one key's queue: expired sessions
        move to EXPIRED, sessions already terminal (failed or expired
        elsewhere) are dropped; survivors stay queued."""
        alive = []
        for s in q:
            if s.state is not SessionState.SEALED:
                self._c_dropped.inc()
            elif s.expired(now):
                s.expire("deadline: session expired before aggregation")
                self._c_expired.inc()
                if self.recorder is not None:
                    self.recorder.event("expire", sid=s.sid)
            else:
                alive.append(s)
        return alive

    def _run(self, key: BatchKey, batch: list[Session], reason: str,
             now: float, account_age: bool = True) -> None:
        if account_age:
            age = now - min(s.sealed_at for s in batch)
            self._g_max_age.track_max(age)
            self._c_starved.inc(sum(
                now - s.sealed_at >= 2 * self.batching.max_age
                for s in batch))
            # the admission-wait span of this batch (oldest member's
            # queue residency, on the open/seal/pump clock)
            self._h_wait.observe(age)
        self._c_flush[reason].inc()
        if self.recorder is not None:
            self.recorder.event("flush", reason=reason,
                                sids=[s.sid for s in batch],
                                rows=self._rows(key, batch))
        if self.pre_execute is not None:
            self.pre_execute(batch)
        if self.executor.stream.depth > 1:
            # streaming: issue without blocking; pump() drains the ring
            # (and re-raises deferred unit errors) after its key sweep
            self.executor.execute_async(batch, padded_elems=key[-1])
        else:
            self.executor.execute(batch, padded_elems=key[-1])
        self.batch_sizes.append(len(batch))
        if len(self.batch_sizes) > 4096:   # bounded history
            del self.batch_sizes[:-2048]

    def pump(self, now: Optional[float] = None, force: bool = False) -> int:
        """Flush ready batches; returns the number of sessions executed
        (revealed or quarantined — expired/shed sessions don't count).

        Size watermark: every group of ``max_batch`` ready rows flushes.
        Age watermark: a partial group flushes when its oldest member
        sealed more than ``max_age`` ago (or unconditionally with
        ``force``).  ``now`` defaults to the monotonic clock.  A forced
        pump (drain/shutdown) skips ALL age accounting — callers that
        sealed with logical ticks would otherwise record bogus
        monotonic-minus-tick ages.

        Keys are isolated: a key whose batch raises out of the executor
        (a fully-poisoned batch, or a raising ``pre_execute``) is
        skipped for the rest of this pump, the sweep continues over the
        other keys, and the FIRST such error re-raises after the sweep
        completes — one poisoned key never starves the rest.  The
        whole call is the ``svc.pump`` span."""
        with span("svc.pump"):
            return self._pump(now, force)

    def _pump(self, now: Optional[float], force: bool) -> int:
        now = time.monotonic() if now is None else now
        account_age = not force
        ran = 0
        first_err: Optional[Exception] = None
        for key in list(self._pending):
            q = self._pending[key]
            q[:] = self._sweep(q, now)
            try:
                while self._rows(key, q) >= self.batching.max_batch:
                    # FIFO prefix that fits the row budget — never exceeds
                    # max_batch rows (keeping the compile-cache shape set
                    # small), except a single session wider than the budget,
                    # which flushes alone
                    take, rows = [], 0
                    row_elems = key[-1]
                    while q and rows + q[0].n_rows(row_elems) \
                            <= self.batching.max_batch:
                        s = q.pop(0)
                        take.append(s)
                        rows += s.n_rows(row_elems)
                    if not take:
                        take.append(q.pop(0))
                    self._run(key, take, "size", now,
                              account_age=account_age)
                    ran += len(take)
                if q and (force or
                          now - min(s.sealed_at for s in q)
                          >= self.batching.max_age):
                    batch, self._pending[key] = list(q), []
                    q = self._pending[key]
                    # batch already dequeued: a raising executor has
                    # already quarantined it (never re-enqueued)
                    self._run(key, batch, "force" if force else "age", now,
                              account_age=account_age)
                    ran += len(batch)
            except Exception as e:
                if first_err is None:
                    first_err = e
                q = self._pending.get(key, [])
            if not q:
                self._pending.pop(key, None)
        # drain the streaming ring: every issued batch settles (reveal /
        # retry / quarantine) before pump returns, so callers still see
        # only terminal sessions after a pump — a deferred all-failed
        # unit error joins the per-key errors under the same
        # first-error-wins contract
        try:
            self.executor.flush()
        except Exception as e:
            if first_err is None:
                first_err = e
        if first_err is not None:
            raise first_err
        return ran
