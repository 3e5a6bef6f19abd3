"""The trace reduction on synthetic traces: busy union, kernel match and
bytes, gap attribution; and the reader of a real (CPU) profiler trace."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import tracefold as tf  # noqa: E402

VOTE = ("%raw.66 = u32[1024,128]{1,0:T(8,128)} custom-call(u32[1024,128]"
        "{1,0:T(8,128)} %reshape.323, u32[1024,128]{1,0:T(8,128)} %b.1, "
        "u32[1024,128]{1,0:T(8,128)} %b.2, u32[1024,128]{1,0:T(8,128)} "
        "%reshape.326), custom_call_target=\"tpu_custom_call\", "
        "operand_layout_constraints={}")
RESHAPE = ("%reshape.11 = u32[1024,128]{1,0:T(8,128)} reshape(u32[1,8,16384]"
           "{2,1,0:T(8,128)} %fusion.1)")
ALLREDUCE = "%all-reduce.3 = f32[4096]{0} all-reduce(f32[4096]{0} %p)"


def synthetic(ms=1_000_000):
    """A 100 ms window: device ops at 10-30 (two overlapping), 50-60
    (the vote kernel) and 70-75 ms (an all-reduce); host spans: ingest
    0-40, pump 40-100 with a nested wait 80-90."""
    ops = {0: [(RESHAPE, 10 * ms, 15 * ms), (RESHAPE, 20 * ms, 10 * ms),
               (VOTE, 50 * ms, 10 * ms), (ALLREDUCE, 70 * ms, 5 * ms),
               (RESHAPE, 120 * ms, 5 * ms)]}          # after the window
    spans = [(tf.WINDOW_SPAN, 0, 100 * ms), ("bench.ingest", 0, 40 * ms),
             ("bench.pump", 40 * ms, 60 * ms), ("bench.wait", 80 * ms, 10 * ms)]
    return tf.Trace(ops=ops, spans=spans)


def test_union_merges_overlaps_and_touching():
    assert tf.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_gaps_are_the_complement_within_the_window():
    assert tf.gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]
    assert tf.gaps([], 0, 10) == [(0, 10)]


def test_busy_kernels_and_collectives_of_a_synthetic_trace():
    r = tf.reduce(synthetic(), chips=1)
    assert r.window_s == pytest.approx(0.1)
    # 10-30 ms (union of the overlapping reshapes), 50-60, 70-75
    assert r.busy_s == pytest.approx(0.035)
    assert r.kernel_s == {"vote": pytest.approx(0.010)}
    assert r.kernel_bytes["vote"] == 5 * 1024 * 128 * 4
    assert r.collective_s == pytest.approx(0.005)
    labels = dict((n, s) for n, s in r.device_ops)
    assert labels["reshape u32[1024,128]"] == pytest.approx(0.025)
    assert labels["vote_kernel"] == pytest.approx(0.010)


def test_idle_gaps_go_to_the_innermost_host_span():
    r = tf.reduce(synthetic(), chips=1)
    gaps = dict((n, s) for n, s in r.idle_gaps)
    # idle: 0-10 and 30-40 under ingest; 40-50, 60-70, 75-80 and 90-100
    # under pump; 80-90 under the nested wait
    assert gaps["bench.ingest"] == pytest.approx(0.020)
    assert gaps["bench.pump"] == pytest.approx(0.035)
    assert gaps["bench.wait"] == pytest.approx(0.010)
    assert sum(gaps.values()) == pytest.approx(r.window_s - r.busy_s)


def test_idle_outside_every_span_is_named_so():
    t = synthetic()
    t.spans = [s for s in t.spans if s[0] == tf.WINDOW_SPAN]
    r = tf.reduce(t, chips=1)
    assert r.idle_gaps == [[tf.NO_SPAN, pytest.approx(0.065)]]


def test_busy_is_averaged_over_chips():
    t = synthetic()
    t.ops[1] = [(RESHAPE, 0, 100_000_000)]             # chip 1 always busy
    r = tf.reduce(t, chips=2)
    assert r.busy_s == pytest.approx((0.035 + 0.1) / 2)


def test_roofline_share_is_bytes_at_peak_over_time():
    r = tf.reduce(synthetic(), chips=1)
    want = 100 * (5 * 1024 * 128 * 4 / 819e9) / 0.010
    assert tf.roofline_pct(r, "vote", 819e9) == pytest.approx(want)
    assert tf.roofline_pct(r, "mask", 819e9) is None


def test_a_trace_without_its_window_span_is_refused():
    t = synthetic()
    t.spans = t.spans[1:]
    with pytest.raises(ValueError):
        tf.reduce(t, chips=1)


def test_reads_a_profiler_trace_written_on_this_host(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileOptions, TraceAnnotation
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with TraceAnnotation(tf.WINDOW_SPAN):
        with TraceAnnotation("bench.pump"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tf.load(tf.latest_xplane(str(tmp_path)), chips=1)
    names = [n for n, _, _ in t.spans]
    assert tf.WINDOW_SPAN in names and "bench.pump" in names
    lo, hi = tf.window(t)
    assert hi > lo
    assert t.ops == {}            # no TPU plane on this host


def test_stall_watch_names_where_the_loop_was_blocked():
    import time
    from harness.stalls import Stalls

    def blocked_call():
        time.sleep(0.3)

    with Stalls(every=0.01, after=0.1) as watch:
        watch.beat()
        blocked_call()
        watch.beat()
        time.sleep(0.05)
    (t, kind, ms, where), = [n for n in watch.notes if n[1] == "loop blocked"]
    assert 250 <= ms < 1000 and where.startswith("blocked_call")
    assert watch.lines()[0].startswith("stall at")
