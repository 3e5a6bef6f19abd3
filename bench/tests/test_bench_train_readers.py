"""The training cell's per-layer readers on a synthetic trace: device time
under the engine's scopes is a union, so an op nested in a loop's event
counts once; the whole step's and the model's shares of the bf16 peak,
the sync's milliseconds a step and the idle share follow from it, and
each reader gives None where there is nothing to read."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import counts  # noqa: E402
from harness import progspans as ps  # noqa: E402
from harness import spec  # noqa: E402
from harness import tracefold as tf  # noqa: E402

MS = 1_000_000
WHILE = "%while.193 = (s32[], f32[8,2048,1024]) while(%tuple.5)"
INNER = "%fusion.811 = bf16[8,2048,2048]{2,1,0} fusion(%param.3)"
NESTED = "%mask_encrypt_batch.1 = u32[1,16777216]{1,0} custom-call(%p)"
ENC = "%mask_encrypt_batch.2 = u32[1,16777216]{1,0} custom-call(%q)"
SUM = "%all-reduce.7 = u32[1,16777216]{1,0} all-reduce(%r)"
SCOPES = {NESTED: "jit(step)/agg.encrypt/pallas_call:",
          ENC: "jit(step)/agg.encrypt/pallas_call:",
          SUM: "jit(step)/agg.cluster_sum/psum:"}
MODEL = {"d_model": 64, "n_layers": 2, "vocab_size": 128,
         "ssm": {"d_state": 32, "d_conv": 4, "expand": 2, "head_dim": 16,
                 "chunk": 32}}
PEAK = 1e12


def synthetic():
    """A 100 ms window on chip 0: a layer loop 0-60 holding a fusion
    10-50 and an engine op 50-55, then encrypt 60-70 and the cluster sum
    70-80; idle 80-100.  Chip 1 is busy 0-90."""
    ops = {0: [(WHILE, 0, 60 * MS), (INNER, 10 * MS, 40 * MS),
               (NESTED, 50 * MS, 5 * MS), (ENC, 60 * MS, 10 * MS),
               (SUM, 70 * MS, 10 * MS)],
           1: [(WHILE, 0, 90 * MS)]}
    return tf.Trace(ops=ops, spans=[(tf.WINDOW_SPAN, 0, 100 * MS)])


class FakeRun:
    def __init__(self, steps=4, chips=2):
        trace = synthetic()
        self.window = type("W", (), {"steps": steps})()
        self.load = type("L", (), {"batch": 8, "seq_len": 64,
                                   "chips": chips})()
        self.config = {"model": MODEL}
        self.peaks = {"bf16_flops_per_s": PEAK}
        self.trace = tf.reduce(trace, chips)


@pytest.fixture
def folded(monkeypatch):
    f = ps.fold(synthetic(), SCOPES, [])
    monkeypatch.setattr(ps, "trace_file", lambda: "synthetic.xplane.pb")
    monkeypatch.setattr(ps, "fold_file", lambda path: f)
    return f


def test_scoped_time_is_the_union_of_the_stages_ops(folded):
    # the loop and its fusion overlap, and the nested engine op lies in
    # the loop: summed by stage they read 100 ms of unscoped time
    assert folded.stage_s[ps.NO_STAGE] == pytest.approx(0.100)
    assert folded.busy_s == pytest.approx(0.080)
    assert folded.scoped_s == pytest.approx(0.025)


FLOPS = counts.train_step_flops(MODEL, 8, 64) * 4     # four steps
READS = {
    # two chips busy 80 and 90 ms: 85 ms a chip
    "mfu.train": 100 * FLOPS / (2 * PEAK * 0.085),
    # chip 0 busy 80 ms, 25 of them under a scope
    "model_mfu.train": 100 * FLOPS / (2 * PEAK * 0.055),
    "sync_device_ms.train": 25.0 / 4,
    "idle_share.train": 20.0,
}


@pytest.mark.parametrize("name", sorted(READS))
def test_train_reader_on_a_synthetic_trace(name, folded):
    assert spec.reader(name)(FakeRun()) == pytest.approx(READS[name])


@pytest.mark.parametrize("name", ["model_mfu.train", "sync_device_ms.train",
                                  "idle_share.train"])
def test_train_reader_gives_none_without_a_trace(name, monkeypatch,
                                                 tmp_path):
    monkeypatch.setattr(ps, "TRACES", tmp_path / "no-trace-yet")
    assert spec.reader(name)(FakeRun()) is None


@pytest.mark.parametrize("name", ["mfu.train", "model_mfu.train",
                                  "sync_device_ms.train"])
def test_train_reader_gives_none_without_a_step(name, folded):
    assert spec.reader(name)(FakeRun(steps=0)) is None


def test_sync_reader_gives_none_without_engine_scopes(monkeypatch):
    bare = ps.fold(synthetic(), {}, [])
    monkeypatch.setattr(ps, "trace_file", lambda: "synthetic.xplane.pb")
    monkeypatch.setattr(ps, "fold_file", lambda path: bare)
    assert spec.reader("sync_device_ms.train")(FakeRun()) is None
