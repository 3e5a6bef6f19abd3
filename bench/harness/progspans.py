"""The program's own scopes and spans in a profiler trace: device time by
protocol stage, and device idle by the service's host step.

The engine runs every protocol stage under a ``jax.named_scope``
(``agg.encrypt``, ``agg.cluster_sum``, per voted round ``agg.round_<i>``
holding ``agg.hop``, ``agg.vote`` and ``agg.select``, ``agg.reveal_rows``,
``agg.unmask``).  XLA keeps the scope path in each op's ``op_name``, and
the TPU profiler writes it into the metadata of the op's ``XLA Ops``
events as the stat ``tf_op``.  ``jax.profiler.ProfileData`` shows an
event's own stats but not its metadata's, so :func:`op_names` reads them
from the ``.xplane.pb`` itself with a small protobuf wire-format reader.
The service's host steps are ``svc.*`` annotations (``repro.obs.spans``)
on the host plane, beside the harness's ``bench.*`` spans.

Both reductions work on plain tuples, as ``tracefold``'s do, so that they
can be checked on a synthetic trace:

* device seconds by stage over the window, on chip 0.  An op counts
  toward the stage its ``op_name`` names; where XLA merged several ops
  into one, the ``op_name`` lists theirs joined by ``;`` and the first
  that names a stage decides;
* chip 0's idle seconds by the innermost host span open at the time,
  over the harness's spans and the service's together.

A reader finds the window's trace by itself: ``run.py`` writes it under
``out/trace/<cell>/``, after clearing that directory, and stops the
profiler before the readers run, so it is the newest ``.xplane.pb`` there.
A trace of a program without the scopes or the spans has no stage and no
``svc.*`` span to report, and the readers then give None.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import glob
import os
from pathlib import Path
from typing import Optional

from harness import tracefold

TRACES = Path(__file__).resolve().parent.parent / "out" / "trace"
SPAN_PREFIX = "svc."
NO_STAGE = "(no agg scope)"


def _varint(b, i: int) -> tuple:
    out = shift = 0
    while True:
        x = b[i]
        i += 1
        out |= (x & 0x7F) << shift
        if x < 0x80:
            return out, i
        shift += 7


def _fields(b):
    """(field number, value) of every field of one protobuf message: an
    int for a varint, a memoryview for every other wire type."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_names(path: str) -> dict:
    """HLO text of every op in the device planes' event metadata (the
    name its ``XLA Ops`` events carry) -> its ``op_name``.

    XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map to
    XEventMetadata: name = 2, stats = 5), stat_metadata = 5 (map to
    XStatMetadata: name = 2); XStat: metadata_id = 1, str_value = 5,
    ref_value = 7 (a stat metadata id whose name is the string)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        fs = list(_fields(plane))
        name = next((_text(v) for g, v in fs if g == 2), "")
        if not name.startswith("/device:"):
            continue
        stat_names = {}
        for g, v in fs:
            if g == 5:
                entry = dict(_fields(v))
                md = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = _text(md.get(2, b""))
        tf_op = {k for k, n in stat_names.items() if n == "tf_op"}
        for g, v in fs:
            if g != 4:
                continue
            md = list(_fields(dict(_fields(v)).get(2, b"")))
            text = next((_text(x) for h, x in md if h == 2), "")
            for h, x in md:
                if h != 5:
                    continue
                stat = dict(_fields(x))
                if stat.get(1) not in tf_op:
                    continue
                if 5 in stat:
                    out.setdefault(text, _text(stat[5]))
                elif 7 in stat:
                    out.setdefault(text, stat_names.get(stat[7], ""))
    return out


def stage_of(op_name: str) -> str:
    """The protocol stage an ``op_name`` names: its innermost ``agg.*``
    scope other than a round's, from the first of its ``;``-joined
    names that has one; ``NO_STAGE`` where none does."""
    for part in op_name.rstrip(":").split(";"):
        scopes = [c for c in part.split("/")
                  if c.startswith("agg.") and not c.startswith("agg.round_")]
        if scopes:
            return scopes[-1]
    return NO_STAGE


def host_spans(path: str) -> list:
    """[(name, start_ns, dur_ns)] of the service's spans on the host."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.duration_ns)
            for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith(SPAN_PREFIX)]


@dataclasses.dataclass
class Folded:
    stage_s: dict       # stage -> device seconds in the window, chip 0
    busy_s: float       # chip 0's busy union in the window
    scoped_s: float     # chip 0's union of the ops under any stage
    idle_s: dict        # innermost host span -> chip 0's idle seconds
    spans: dict         # svc span -> (count, seconds) within the window


def fold(trace: tracefold.Trace, scopes: dict, svc: list) -> Folded:
    """``trace`` as ``tracefold.load`` gives it, ``scopes`` as
    :func:`op_names`, ``svc`` as :func:`host_spans`."""
    lo, hi = tracefold.window(trace)
    ops = tracefold.clip(trace.ops.get(0, []), lo, hi)
    stage: dict = collections.Counter()
    scoped = []
    for name, a, b in ops:
        k = stage_of(scopes.get(name, ""))
        stage[k] += b - a
        if k != NO_STAGE:
            scoped.append((a, b))
    busy = tracefold.union((a, b) for _, a, b in ops)
    idle = tracefold.attribute(tracefold.gaps(busy, lo, hi),
                               trace.spans + svc)
    count: dict = collections.Counter()
    total: dict = collections.Counter()
    for name, a, b in tracefold.clip(svc, lo, hi):
        count[name] += 1
        total[name] += b - a
    return Folded(
        stage_s={k: v * 1e-9 for k, v in stage.items()},
        busy_s=sum(b - a for a, b in busy) * 1e-9,
        scoped_s=sum(b - a for a, b in tracefold.union(scoped)) * 1e-9,
        idle_s={k: v * 1e-9 for k, v in idle.items()},
        spans={k: (count[k], total[k] * 1e-9) for k in count})


@functools.lru_cache(maxsize=4)
def fold_file(path: str) -> Folded:
    return fold(tracefold.load(path, 1), op_names(path), host_spans(path))


def trace_file() -> Optional[str]:
    """The newest ``.xplane.pb`` under ``TRACES``: the one of the traced
    run whose readers ask for it; None where there is none."""
    files = glob.glob(os.path.join(str(TRACES), "*", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def of(run) -> Optional[Folded]:
    """The folded trace of a traced run, read once for all its readers."""
    path = trace_file()
    return fold_file(path) if path else None


def per_session_ms(run, seconds: float) -> Optional[float]:
    n = run.window.revealed
    return 1000.0 * seconds / n if n else None


def stage_ms(run, *stages) -> Optional[float]:
    """Device milliseconds per revealed session under the given stages;
    None where the trace has none of them."""
    f = of(run)
    if f is None or not any(s in f.stage_s for s in stages):
        return None
    return per_session_ms(run, sum(f.stage_s.get(s, 0.0) for s in stages))


def idle_ms(run, *spans) -> Optional[float]:
    """Chip 0's idle milliseconds per revealed session whose innermost
    host span is one of ``spans``; None where none of them ran."""
    f = of(run)
    if f is None or not any(s in f.spans for s in spans):
        return None
    return per_session_ms(run, sum(f.idle_s.get(s, 0.0) for s in spans))
