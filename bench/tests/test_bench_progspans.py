"""The program's scopes and spans in a trace, on synthetic traces: device
time by protocol stage, idle by the innermost service span, the metadata
reader, and the four readers that use them, which give None where the
program ran no such scope or span."""
import json
import os
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import progspans as ps  # noqa: E402
from harness import spec  # noqa: E402
from harness import tracefold as tf  # noqa: E402

MS = 1_000_000
HOP = ("%reshape.104 = u32[1024,128]{1,0:T(8,128)} reshape(u32[1,8,16384]"
       "{2,1,0:T(8,128)} %pad_add_fusion.79)")
VOTE = ("%vote_combine.65 = u32[1024,128]{1,0:T(8,128)} custom-call(u32"
        "[1024,128]{1,0:T(8,128)} %a, u32[1024,128]{1,0:T(8,128)} %b), "
        "custom_call_target=\"tpu_custom_call\"")
BACK = ("%reshape.333 = u32[1,8,16384]{2,1,0:T(8,128)} reshape(u32[1024,128]"
        "{1,0:T(8,128)} %vote_combine.64)")
COPY = "%copy = f32[1,8,16384]{2,1,0} copy(f32[1,8,16384]{2,1,0} %xs.1)"
SCOPES = {
    HOP: "jit(raw)/agg.round_3/agg.hop/jit(_roll_static)/concatenate:",
    VOTE: "jit(raw)/agg.round_3/agg.vote/vote_combine/pallas_call:",
    BACK: ("jit(raw)/agg.round_3/agg.select/reshape;"
           "jit(raw)/agg.round_3/agg.vote/reshape:"),
}


def synthetic():
    """A 100 ms window.  Device: the unscoped input copy 0-5, hop 20-40,
    vote 40-50, select 50-55, hop again 70-80 and 120-130 (after the
    window).  Host: ingest 0-10 holding a seal 8-9; a pump 10-100 whose
    svc.pump 11-99 holds pack 12-16, put 16-17, issue 17-18, pack 18-30
    and settle 30-95."""
    ops = {0: [(COPY, 0, 5 * MS), (HOP, 20 * MS, 20 * MS),
               (VOTE, 40 * MS, 10 * MS), (BACK, 50 * MS, 5 * MS),
               (HOP, 70 * MS, 10 * MS), (HOP, 120 * MS, 10 * MS)]}
    bench = [(tf.WINDOW_SPAN, 0, 100 * MS), ("bench.ingest", 0, 10 * MS),
             ("bench.pump", 10 * MS, 90 * MS)]
    svc = [("svc.seal", 8 * MS, 1 * MS), ("svc.pump", 11 * MS, 88 * MS),
           ("svc.pack", 12 * MS, 4 * MS), ("svc.put", 16 * MS, 1 * MS),
           ("svc.issue", 17 * MS, 1 * MS), ("svc.pack", 18 * MS, 12 * MS),
           ("svc.settle", 30 * MS, 65 * MS)]
    return tf.Trace(ops=ops, spans=bench), svc


class FakeRun:
    def __init__(self, revealed=2):
        self.window = type("W", (), {"revealed": revealed})()


@pytest.fixture
def folded(monkeypatch):
    trace, svc = synthetic()
    f = ps.fold(trace, SCOPES, svc)
    monkeypatch.setattr(ps, "trace_file", lambda: "synthetic.xplane.pb")
    monkeypatch.setattr(ps, "fold_file", lambda path: f)
    return f


@pytest.mark.parametrize("op_name,stage", [
    ("jit(raw)/agg.encrypt/pallas_call:", "agg.encrypt"),
    ("jit(raw)/agg.round_0/agg.hop/jit(_where)/select_n", "agg.hop"),
    ("jit(raw)/agg.round_9/agg.vote/reshape;jit(raw)/agg.round_9/agg.hop/"
     "reshape", "agg.vote"),
    ("jit(raw)/reshape;jit(raw)/agg.cluster_sum/reduce_sum",
     "agg.cluster_sum"),
    ("jit(raw)/agg.round_2", ps.NO_STAGE),
    ("", ps.NO_STAGE),
])
def test_stage_of_names_the_innermost_stage_scope(op_name, stage):
    assert ps.stage_of(op_name) == stage


def test_device_time_folds_by_stage_within_the_window():
    trace, svc = synthetic()
    f = ps.fold(trace, SCOPES, svc)
    assert f.stage_s == {"agg.hop": pytest.approx(0.030),
                         "agg.vote": pytest.approx(0.010),
                         "agg.select": pytest.approx(0.005),
                         ps.NO_STAGE: pytest.approx(0.005)}
    assert f.busy_s == pytest.approx(0.050)


def test_idle_goes_to_the_innermost_service_span_inside_the_pump():
    trace, svc = synthetic()
    f = ps.fold(trace, SCOPES, svc)
    # idle: 5-10 (ingest, with the seal 8-9); 10-20 (the harness's pump,
    # the service's, pack, put, issue, pack); 55-70 and 80-95 (settle);
    # 95-100 (the service's pump, then the harness's)
    assert f.idle_s == {"bench.ingest": pytest.approx(0.004),
                        "svc.seal": pytest.approx(0.001),
                        "bench.pump": pytest.approx(0.002),
                        "svc.pump": pytest.approx(0.005),
                        "svc.pack": pytest.approx(0.006),
                        "svc.put": pytest.approx(0.001),
                        "svc.issue": pytest.approx(0.001),
                        "svc.settle": pytest.approx(0.030)}
    assert sum(f.idle_s.values()) == pytest.approx(0.1 - f.busy_s)
    assert f.spans["svc.pack"] == (2, pytest.approx(0.016))


READS = {
    "hop_device_ms.fl": 1000 * 0.035 / 2,     # hop 30 ms + select 5 ms
    "vote_device_ms.fl": 1000 * 0.010 / 2,
    "pack_idle_ms.fl": 1000 * 0.007 / 2,      # pack 6 ms + put 1 ms
    "put_ms.fl": 1.0,
}


@pytest.mark.parametrize("name", sorted(READS))
def test_reader_on_a_synthetic_trace(name, folded):
    assert spec.reader(name)(FakeRun()) == pytest.approx(READS[name])


@pytest.mark.parametrize("name", sorted(READS))
def test_reader_gives_none_where_the_program_has_no_scope_or_span(
        name, monkeypatch, tmp_path):
    trace, _ = synthetic()
    bare = ps.fold(trace, {}, [])             # a program without either
    monkeypatch.setattr(ps, "trace_file", lambda: "synthetic.xplane.pb")
    monkeypatch.setattr(ps, "fold_file", lambda path: bare)
    assert spec.reader(name)(FakeRun()) is None
    monkeypatch.undo()
    monkeypatch.setattr(ps, "TRACES", tmp_path / "no-trace-yet")
    assert spec.reader(name)(FakeRun()) is None


def test_trace_file_is_the_newest_trace_of_any_cell(monkeypatch, tmp_path):
    monkeypatch.setattr(ps, "TRACES", tmp_path)
    assert ps.trace_file() is None
    paths = []
    for i, cell in enumerate(["b.cell", "a.cell"]):
        d = tmp_path / cell / "plugins" / "profile" / f"2026_{i}"
        d.mkdir(parents=True)
        paths.append(d / "host.xplane.pb")
        paths[-1].write_bytes(b"")
        os.utime(paths[-1], (1000 + i, 1000 + i))
    (tmp_path / "b.cell" / "later.txt").write_bytes(b"")
    assert ps.trace_file() == str(paths[1])


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _entry(key, value):
    return _field(1, key) + _field(2, value)


def test_op_names_reads_tf_op_from_the_event_metadata(tmp_path):
    """An XSpace as the TPU profiler writes it: each XLA op's event
    metadata holds its HLO text and a ``tf_op`` stat, as a string or as
    a reference to a stat metadata entry; host planes are skipped."""
    stat_md = (_field(5, _entry(7, _field(1, 7) + _field(2, "tf_op")))
               + _field(5, _entry(8, _field(1, 8) + _field(2, "hlo_op")))
               + _field(5, _entry(9, _field(1, 9) + _field(2, SCOPES[VOTE]))))
    ev = (_field(4, _entry(1, _field(1, 1) + _field(2, HOP)
                           + _field(5, _field(1, 8) + _field(5, "reshape"))
                           + _field(5, _field(1, 7)
                                    + _field(5, SCOPES[HOP]))))
          + _field(4, _entry(2, _field(1, 2) + _field(2, VOTE)
                             + _field(5, _field(1, 7) + _field(7, 9))))
          + _field(4, _entry(3, _field(1, 3) + _field(2, COPY))))
    device = _field(1, 3) + _field(2, "/device:TPU:0") + stat_md + ev
    host = (_field(2, "/host:CPU") + stat_md
            + _field(4, _entry(1, _field(2, "svc.pack")
                               + _field(5, _field(1, 7) + _field(5, "x")))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, device) + _field(1, host))
    assert ps.op_names(str(path)) == {HOP: SCOPES[HOP], VOTE: SCOPES[VOTE]}


def test_a_traced_tiny_run_reports_the_span_metrics(tmp_path, monkeypatch):
    """A whole traced fl-round run at a tiny size on the CPU, reporting
    these four metrics (the rooflines need a chip): the host spans give
    ``put_ms.fl`` and ``pack_idle_ms.fl``; the CPU trace has no TPU
    plane, so the device-stage metrics are left out."""
    import importlib.util
    bench = Path(__file__).resolve().parents[1]
    mod_spec = importlib.util.spec_from_file_location("bench_run",
                                                      bench / "run.py")
    run = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(run)
    s = spec.with_held(spec.load_spec())
    s["per_layer"] = [m for m in s["per_layer"] if m["name"] in READS]
    w = spec.cell(s, "committee-256.fl-round")
    config = dict(spec.config_of(s, w), n_nodes=16)
    traffic = json.loads(json.dumps(spec.traffic_of(w)))
    traffic["payload"]["elems"] = 1024
    root = tmp_path / "checkout"
    (root / "bench").mkdir(parents=True)
    monkeypatch.setattr(ps, "TRACES", root / "bench" / "out" / "trace")
    out = run.run_cell("committee-256.fl-round", 2**40 + 3, 0.5, True,
                       check_device=False, root=root, config=config,
                       traffic=traffic, bench=s)
    assert out["correct"]
    m = out["metrics"]
    assert m["put_ms.fl"]["value"] > 0
    assert m["pack_idle_ms.fl"]["value"] >= 0
    assert "hop_device_ms.fl" not in m and "vote_device_ms.fl" not in m
