"""Observability layer: metrics registry semantics, the trace flight
recorder, the service's spans and the engine's stage scopes on the
profiler's clock, and the exactness chain

    round events  ==  batch event  ==  AggPlan.wire_bytes
                  ==  executed Transport.bytes_sent
                  ==  analytic schedule_cost

plus deterministic byte-identical JSONL replay under chaos (the
obs-lane / chaos-lane anchor).
"""
import collections
import hashlib
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import (STAGE_SCOPES, build_batch_executable,
                               sim_batch)
from repro.core.plan import (AggConfig, SessionMeta, compile_plan,
                             hop_wire_words)
from repro.core.schedules import schedule_cost
from repro.obs import (MetricsRegistry, SVC_STATS_DEPRECATED,
                       SVC_STATS_KEYS, SVC_STATS_VERSION, TickClock,
                       TraceRecorder, prometheus_text, stats_table)
from repro.obs.spans import SERVICE_SPANS, span
from repro.obs.trace import read_jsonl, to_jsonl
from repro.runtime.chaos import ChaosConfig, ChaosError
from repro.runtime.fault import SessionFaultPlan
from repro.runtime.resilience import RetryPolicy
from repro.service import (AggregationService, BatchingConfig,
                           SessionParams)
from repro.service.session import SessionState

RNG = np.random.default_rng(31)
N, ELEMS = 8, 16


def _params(**kw):
    return SessionParams(n_nodes=N, elems=ELEMS, cluster_size=4,
                         redundancy=3, **kw)


def _service(S=4, vals=None, params=None, batching=None, **kw):
    svc = AggregationService(
        params or _params(),
        batching=batching or BatchingConfig(max_batch=S, max_age=1e9),
        **kw)
    for i in range(S):
        s = svc.open(now=0.0)
        for slot in range(N):
            s.contribute(slot, vals[i, slot])
        svc.seal(s.sid, now=0.0)
    return svc


def _vals(S=4):
    return RNG.normal(size=(S, N, ELEMS)).astype(np.float32) * 0.3


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    c = reg.counter("x.count")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert reg.counter("x.count") is c          # same handle, same series
    g = reg.gauge("x.depth")
    g.set(2.0)
    g.track_max(7.0)
    g.track_max(3.0)
    assert g.value == 7.0
    h = reg.histogram("x.lat")
    for v in (1.0, 3.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["counters"] == {"x.count": 5}
    assert snap["gauges"] == {"x.depth": 7.0}
    assert snap["histograms"]["x.lat"] == {
        "count": 2, "total": 4.0, "min": 1.0, "max": 3.0, "mean": 2.0}
    reg.reset()
    assert c.value == 0 and g.value == 0.0      # handles stay live
    assert reg.snapshot()["histograms"]["x.lat"]["count"] == 0


def test_registry_labels_key_distinct_series():
    reg = MetricsRegistry()
    a = reg.counter("q.flushes", reason="size")
    b = reg.counter("q.flushes", reason="age")
    assert a is not b
    a.inc(2)
    b.inc()
    assert reg.snapshot()["counters"] == {
        "q.flushes{reason=age}": 1, "q.flushes{reason=size}": 2}


def test_exporters_render_every_series():
    reg = MetricsRegistry()
    reg.counter("executor.batches_run").inc(3)
    reg.counter("queue.flushes", reason="size").inc()
    reg.histogram("stage.seconds", stage="reveal").observe(0.001)
    prom = prometheus_text(reg)
    assert "repro_executor_batches_run 3" in prom
    assert 'repro_queue_flushes{reason="size"} 1' in prom
    assert 'repro_stage_seconds_count{stage="reveal"} 1' in prom
    table = stats_table(reg)
    assert "executor.batches_run" in table and "n=1" in table


# ---------------------------------------------------------------------------
# Trace recorder
# ---------------------------------------------------------------------------


def test_recorder_ring_jsonl_and_tick_clock(tmp_path):
    path = tmp_path / "t.jsonl"
    rec = TraceRecorder(capacity=3, clock=TickClock(), sink=str(path))
    for i in range(5):
        rec.event("tick", i=i)
    rec.event("other")
    rec.close()
    assert rec.events_recorded == 6
    ring = rec.events()
    assert len(ring) == 3                       # bounded ring, oldest out
    assert [e["ts"] for e in ring] == [3.0, 4.0, 5.0]
    assert rec.events("other") == [{"ts": 5.0, "kind": "other"}]
    # the sink saw everything (it streams; the ring only buffers)
    disk = read_jsonl(str(path))
    assert len(disk) == 6
    assert disk[0] == {"ts": 0.0, "kind": "tick", "i": 0}
    # canonical serialization round-trips byte-for-byte
    assert to_jsonl(disk) == path.read_text()


# ---------------------------------------------------------------------------
# hop_wire_words: one formula behind plan, engine, trace and analytics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("transport,backup", [("full", True),
                                              ("digest", True),
                                              ("digest", False)])
def test_hop_wire_words_matches_plan_and_schedule_cost(transport, backup):
    T = 48
    cfg = AggConfig(n_nodes=16, cluster_size=4, redundancy=3,
                    schedule="tree", transport=transport,
                    digest_backup=backup)
    plan = compile_plan(cfg)
    words = [hop_wire_words(cfg, rnd, T) for rnd in plan.rounds]
    total = 4 * sum(w["payload"] + w["digest"] + w["backup"]
                    for w in words)
    assert total == plan.wire_bytes(T)
    cost = schedule_cost("tree", 4, 4, 3, payload_bytes=4 * T,
                         digest=transport == "digest",
                         digest_bytes=4 * cfg.digest_words,
                         digest_backup=backup)
    assert total == cost["bytes_total"]


# ---------------------------------------------------------------------------
# Spans and scopes on the profiler's clock
# ---------------------------------------------------------------------------


def _profiled(tmp_path, body):
    """Run ``body`` under the JAX profiler; returns the host plane's
    events as (name, stats) pairs."""
    import glob
    from jax.profiler import ProfileData, ProfileOptions
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    with warnings.catch_warnings():
        # jaxlib's stats iterator type warns that it has no __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        return [(e.name, dict(e.stats))
                for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU" for line in p.lines
                for e in line.events]


def test_span_observes_its_histogram_over_the_span_on_the_given_clock():
    h = MetricsRegistry().histogram("stage.seconds", stage="reveal")
    clock = TickClock(step=0.25)
    with span("svc.reveal", h, clock, unit=3):
        pass
    with span("svc.pack", unit=3):              # no histogram: no clock
        pass
    assert (h.count, h.total) == (1, 0.25)
    assert clock() == 0.5                      # read twice, by the first
    with pytest.raises(RuntimeError):
        with span("svc.reveal", h, clock):
            raise RuntimeError("a failed step observes nothing")
    assert h.count == 1


def test_span_shows_on_the_host_plane_of_a_cpu_profiler_trace(tmp_path):
    h = MetricsRegistry().histogram("stage.seconds", stage="reveal")

    def body():
        with span("svc.reveal", h, unit=7):
            jnp.ones(8).block_until_ready()
    events = _profiled(tmp_path, body)
    assert ("svc.reveal", {"unit": 7}) in events
    assert h.count == 1 and h.total > 0


@pytest.mark.parametrize("depth", [1, 2])
def test_service_steps_are_spans_of_their_retry_unit(tmp_path, depth):
    """Every host step of a batch is a span carrying the batch's retry
    unit, the id the recorder's ``batch`` event carries; the first
    batch of a shape compiles, the next issues; one ``svc.seal`` per
    session."""
    from repro.service import StreamConfig
    rec = TraceRecorder(clock=TickClock())
    svc = AggregationService(_params(), recorder=rec,
                             batching=BatchingConfig(max_batch=2,
                                                     max_age=1e9),
                             stream=StreamConfig(depth=depth))
    vals = _vals(4)

    def body():
        for i in range(4):
            s = svc.open(now=0.0)
            for slot in range(N):
                s.contribute(slot, vals[i, slot])
            svc.seal(s.sid, now=0.0)
        svc.pump(now=1.0)
    events = _profiled(tmp_path, body)
    names = collections.Counter(n for n, _ in events
                                if n.startswith("svc."))
    assert set(names) == set(SERVICE_SPANS)
    assert names["svc.seal"] == 4 and names["svc.pump"] == 1
    assert names["svc.compile"] == names["svc.issue"] == 1
    units = {e["unit"] for e in rec.events("batch")}
    for step in ("svc.pack", "svc.put", "svc.settle", "svc.reveal"):
        assert names[step] == 2
        assert {st["unit"] for n, st in events if n == step} == units
    sids = {st["sid"] for n, st in events if n == "svc.seal"}
    assert sids == {0, 1, 2, 3}


def _entry_ops(fn, S, n, T):
    """(opcode, name, op_name) of every instruction of the lowered
    program's entry computation."""
    low = fn.lower(jax.ShapeDtypeStruct((S, n, T), jnp.float32),
                   jax.ShapeDtypeStruct((S,), jnp.uint32),
                   jax.ShapeDtypeStruct((S,), jnp.uint32), {})
    text = low.compiler_ir("hlo").as_hlo_module().to_string()
    entry = text[text.index("\nENTRY"):]
    ops = []
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%?(\S+) = \S+ ([\w-]+)\(", line)
        if m:
            on = re.search(r'op_name="([^"]*)"', line)
            ops.append((m.group(2), m.group(1), on.group(1) if on else ""))
    return ops, text


@pytest.mark.parametrize("impl,transport", [
    ("jnp", "full"), ("jnp", "digest"), ("pallas_interpret", "full")])
def test_every_op_of_the_batch_executable_carries_a_stage_scope(
        impl, transport):
    """In the lowered batch executable every traced op of the entry
    computation sits under an ``agg.*`` scope (calls into nested jits
    included, whose ops take the caller's scope when XLA inlines them),
    save the parameters and the entry reshape/convert of ``xs``;
    constants, and their broadcasts, carry no op_name at all.  Every
    stage scope, and a round's, appears."""
    cfg = AggConfig(n_nodes=16, cluster_size=4, redundancy=3,
                    transport=transport, kernel_impl=impl)
    fn = build_batch_executable(compile_plan(cfg), impl=impl)
    ops, text = _entry_ops(fn, 2, 16, 256)
    outside = [(code, on) for code, name, on in ops if "agg." not in on]
    for code, on in outside:
        if on:
            assert code == "parameter" or (
                code in ("reshape", "convert")
                and on.startswith("jit(raw)/")
                and on.count("/") == 1), (code, on)
        else:
            assert code in ("constant", "broadcast"), code
    assert sum(bool(on) for _, on in outside) <= 5
    for scope in STAGE_SCOPES + ("agg.round_0",):
        assert f"/{scope}/" in text, scope


def test_stage_scopes_leave_the_compiled_program_unchanged(monkeypatch):
    """The scopes change metadata only: the optimized program compiled
    with and without them is the same once metadata and instruction
    names are taken out."""
    import contextlib
    cfg = AggConfig(n_nodes=16, cluster_size=4, redundancy=3)
    args = (jax.ShapeDtypeStruct((1, 16, 512), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.uint32),
            jax.ShapeDtypeStruct((1,), jnp.uint32), {})

    def program():
        """(whether any op is scoped, the program without metadata)"""
        fn = build_batch_executable(compile_plan(cfg))
        text = fn.lower(*args).compile().as_text()
        scoped = "/agg.hop/" in text
        # the source-file and stack-frame tables, then each op's metadata
        head, _, rest = text.partition("\nFileNames\n")
        text = head + rest[re.search(r"^(%|ENTRY)", rest, re.M).start():]
        text = re.sub(r", metadata=\{[^}]*\}", "", text)
        names: dict = {}
        return scoped, re.sub(
            r"%[\w.\-]+",
            lambda m: names.setdefault(m.group(0), f"%v{len(names)}"), text)
    with_scopes, program_a = program()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without, program_b = program()
    assert with_scopes and not without
    assert program_a == program_b


# ---------------------------------------------------------------------------
# Executor integration: flight-recorder events + registry views
# ---------------------------------------------------------------------------


def test_batch_and_round_events_reconcile_with_engine_account():
    S, vals = 4, _vals(4)
    rec = TraceRecorder(clock=TickClock())
    svc = _service(S=S, vals=vals, recorder=rec)
    assert svc.pump(now=1.0) == S
    (b,) = rec.events("batch")
    rounds = rec.events("round")
    assert b["rows"] == S and b["sids"] == [0, 1, 2, 3] and b["fresh"]
    assert len(rounds) == b["rounds"]
    # summed round events == the batch event == the plan's byte account
    assert sum(r["bytes"] for r in rounds) == b["bytes"]
    for r in rounds:
        assert r["bytes"] == (r["payload_bytes"] + r["digest_bytes"]
                              + r["backup_bytes"])
    plan = compile_plan(_params().agg_config())
    assert b["bytes"] == plan.wire_bytes(b["padded"], S=S)
    # == the analytic account
    cost = schedule_cost("ring", N // 4, 4, 3,
                         payload_bytes=4 * b["padded"])
    assert b["bytes"] == S * cost["bytes_total"]
    # == the engine's executed trace-time account, bit for bit
    xs = np.zeros((S, N, b["padded"]), np.float32)
    _, tp = sim_batch(plan, xs, SessionMeta.build(S, N, seed=plan.cfg.seed))
    assert tp.bytes_sent == b["bytes"]
    # registry agrees with all of the above
    assert svc.executor.wire_bytes == b["bytes"]
    assert svc.stats["wire"]["bytes_sent"] == b["bytes"]
    # stage spans were recorded host-side around the dispatch
    hists = svc.metrics.snapshot()["histograms"]
    for stage in ("admission_wait", "plan_compile", "reveal"):
        assert hists[f"stage.seconds{{stage={stage}}}"]["count"] == 1, stage
    # flush event precedes the batch event
    kinds = [e["kind"] for e in rec.events()]
    assert kinds.index("flush") < kinds.index("batch")


def test_round_events_model_fault_population_on_digest():
    vals = _vals(1)
    rec = TraceRecorder(clock=TickClock())
    svc = _service(S=1, vals=vals, params=_params(transport="digest"),
                   recorder=rec)
    svc.get(0).inject_fault(SessionFaultPlan(byzantine_slots=(2,),
                                             byzantine_mode="mismatch"))
    svc.drain()
    assert svc.get(0).state is SessionState.REVEALED
    rounds = rec.events("round")
    assert rounds
    for r in rounds:
        assert r["fault_population"] == {"mismatch": 1}
        assert r["vote_disagreements"] == 1
        assert r["digest_mismatches"] == 1
        assert r["digest_bytes"] > 0


def test_resilience_ladder_events_retry_bisect_quarantine():
    vals = _vals(2)
    rec = TraceRecorder(clock=TickClock())
    # one injected dispatch failure -> retry -> recovery
    svc = _service(S=2, vals=vals, recorder=rec,
                   retry=RetryPolicy(max_attempts=2, base_backoff_s=0),
                   chaos=ChaosConfig(mode="dispatch", times=1))
    svc.drain()
    (chaos,) = rec.events("chaos")
    (retry,) = rec.events("retry")
    assert chaos["mode"] == "dispatch" and chaos["attempt"] == 1
    assert retry["attempt"] == 1 and "chaos" in retry["error"]
    assert [e["attempt"] for e in rec.events("batch")] == [2]
    # unbounded chaos -> the whole ladder: retries exhaust, the batch
    # bisects, both halves quarantine; the trace reconstructs it all
    rec2 = TraceRecorder(clock=TickClock())
    svc2 = _service(S=2, vals=vals, recorder=rec2,
                    retry=RetryPolicy(max_attempts=2, base_backoff_s=0),
                    chaos=ChaosConfig(mode="dispatch"))
    with pytest.raises(ChaosError):
        svc2.drain()
    (bisect,) = rec2.events("bisect")
    assert bisect["left"] == [0] and bisect["right"] == [1]
    assert [sorted(e["sids"]) for e in rec2.events("quarantine")] \
        == [[0], [1]]
    assert not rec2.events("batch")             # nothing ever executed
    assert svc2.stats["resilience"]["quarantined"] == 2


def test_queue_protection_events_shed_and_expire():
    vals = _vals(4)
    rec = TraceRecorder(clock=TickClock())
    svc = _service(S=4, vals=vals, recorder=rec,
                   batching=BatchingConfig(max_batch=2, max_age=1e9,
                                           max_pending_rows=3))
    # 4 sealed rows > watermark 3: the newest arrival was shed
    (shed,) = rec.events("shed")
    assert shed["sid"] == 3 and shed["limit"] == 3
    svc.drain()
    assert svc.get(3).state is SessionState.EXPIRED


def _vote_counters(metrics: MetricsRegistry) -> dict:
    counters = metrics.snapshot()["counters"]
    return {layout: counters[f"executor.vote_calls{{layout={layout}}}"]
            for layout in ("rows", "flat")}


def test_executor_counts_vote_calls_by_layout():
    """Each executed batch adds its executable's trace-time vote tally
    to ``executor.vote_calls``: a committee-256 session (n = 256, ring,
    one session a batch) votes its 63 rounds on the (256, T) rows as
    they are; a batch under one (8, 128) tile is flattened."""
    n, T, sessions = 256, 128, 2
    params = SessionParams(n_nodes=n, elems=T, cluster_size=4,
                           redundancy=3)
    vals = RNG.normal(size=(sessions, n, T)).astype(np.float32) * 0.3
    svc = AggregationService(params, batching=BatchingConfig(max_batch=1,
                                                             max_age=1e9))
    for i in range(sessions):
        s = svc.open(now=0.0)
        for slot in range(n):
            s.contribute(slot, vals[i, slot])
        svc.seal(s.sid, now=0.0)
    svc.drain()
    assert len(compile_plan(params.agg_config()).rounds) == 63
    assert svc.stats["batches"]["run"] == sessions
    assert _vote_counters(svc.metrics) == {"rows": 63 * sessions, "flat": 0}
    # N = 8 slots of ELEMS = 16: one batch, under a whole tile
    small = _service(S=1, vals=_vals(1))
    small.drain()
    rounds = len(compile_plan(_params().agg_config()).rounds)
    assert _vote_counters(small.metrics) == {"rows": 0, "flat": rounds}


def test_train_step_sync_votes_flat(monkeypatch):
    """The secure train step's sync (``tree_allreduce`` inside a
    shard_map manual over the dp axis) votes one row a rank, which the
    layout rule flattens into the flat kernel's tiles."""
    from jax.sharding import AbstractMesh, PartitionSpec as P
    from repro.core import engine
    made = []

    class Recording(engine.ManualTransport):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            made.append(self)

    monkeypatch.setattr(engine, "ManualTransport", Recording)
    # c = 1: a grouped psum cannot be traced over an abstract mesh
    cfg = AggConfig(n_nodes=4, cluster_size=1, redundancy=1)
    sync = jax.shard_map(
        lambda g: engine.tree_allreduce({"w": g}, cfg, ("data",))["w"],
        mesh=AbstractMesh((4,), ("data",)), in_specs=P("data"),
        out_specs=P("data"))
    jax.jit(sync).trace(jax.ShapeDtypeStruct((4 * 4096,), jnp.float32))
    (tp,) = made
    rounds = len(compile_plan(cfg).rounds)
    assert rounds > 0
    assert tp.vote_calls == {"rows": 0, "flat": rounds}


# ---------------------------------------------------------------------------
# svc.stats schema: canonical nested keys + deprecated aliases
# ---------------------------------------------------------------------------


def test_svc_stats_schema_and_aliases():
    vals = _vals(2)
    svc = _service(S=2, vals=vals)
    svc.drain()
    st = svc.stats
    assert st["schema"] == SVC_STATS_VERSION
    # schema v2: the flat pre-PR-7 aliases are gone — the nested keys
    # ARE the stats surface
    assert SVC_STATS_DEPRECATED == ()
    assert set(st) == set(SVC_STATS_KEYS)
    assert st["sessions"] == {"opened": 2, "run": 2, "failed": 0,
                              "pending": 0}
    assert st["batches"] == {"run": 1, "sizes": (2,)}
    assert set(st["caches"]) == {"executor", "plan"}
    assert st["wire"]["bytes_sent"] == svc.executor.wire_bytes > 0
    assert set(st["metrics"]) == {"counters", "gauges", "histograms"}


# ---------------------------------------------------------------------------
# Deterministic byte-identical replay under chaos (chaos-lane anchor)
# ---------------------------------------------------------------------------


def _chaos_run(path, vals):
    rec = TraceRecorder(clock=TickClock(), sink=str(path))
    svc = _service(
        S=8, vals=vals, recorder=rec,
        batching=BatchingConfig(max_batch=4, max_age=1e9),
        retry=RetryPolicy(max_attempts=2, base_backoff_s=0),
        chaos=ChaosConfig(mode="dispatch", p=0.35, seed=0))
    try:
        svc.drain()
    except ChaosError:
        pass
    rec.close()
    return rec


@pytest.mark.chaos
def test_chaos_trace_replays_byte_identical(tmp_path):
    """Same chaos seed + TickClock + zero backoff => the two runs write
    byte-for-byte identical JSONL (pinned by digest), and every executed
    batch's summed round events reconcile with the engine + analytic
    byte accounts."""
    vals = _vals(8)
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    rec = _chaos_run(a, vals)
    _chaos_run(b, vals)
    assert rec.events_recorded > 0
    da = hashlib.sha256(a.read_bytes()).hexdigest()
    db = hashlib.sha256(b.read_bytes()).hexdigest()
    assert da == db
    events = read_jsonl(str(a))
    batches = [e for e in events if e["kind"] == "batch"]
    assert batches                              # some dispatches executed
    assert any(e["kind"] == "retry" for e in events)  # and chaos fired
    for bt in batches:
        rsum = sum(e["bytes"] for e in events
                   if e["kind"] == "round" and e["unit"] == bt["unit"]
                   and e["attempt"] == bt["attempt"])
        assert rsum == bt["bytes"]
        # unfaulted cells: the analytic account holds exactly
        cost = schedule_cost("ring", N // 4, 4, 3,
                             payload_bytes=4 * bt["padded"])
        assert bt["bytes"] == bt["rows"] * cost["bytes_total"]
