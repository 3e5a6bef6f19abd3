"""Reduction of a profiler trace to the numbers the per-layer metrics
read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
In it, each chip is a plane ``/device:TPU:<i>`` whose line ``XLA Ops``
holds one event per op executed on the device, named by the op's HLO text;
the host is the plane ``/host:CPU``, whose thread lines hold the harness's
own spans (``jax.profiler.TraceAnnotation``, names starting ``bench.``).
Device and host events share one clock.

The reduction works on plain tuples, so that it can be checked on a
synthetic trace: ``Trace.ops[chip]`` is a list of ``(name, start_ns,
dur_ns)``, ``Trace.spans`` a list of ``(name, start_ns, dur_ns)``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import glob
import os
from typing import Callable, Optional

from harness import counts

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
NO_SPAN = "(outside any harness span)"


@dataclasses.dataclass
class Trace:
    ops: dict            # chip index -> [(name, start_ns, dur_ns)]
    spans: list          # [(name, start_ns, dur_ns)] harness spans


def spans(trace: bool) -> Callable:
    """What a harness span is opened with: a profiler annotation in a
    traced run, nothing otherwise."""
    if trace:
        from jax.profiler import TraceAnnotation
        return TraceAnnotation
    return lambda name: contextlib.nullcontext()


def latest_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str, chips: int) -> Trace:
    """Read the device ops of chips 0..chips-1 and the harness spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: dict = {}
    spans: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = int(plane.name.rsplit(":", 1)[1])
            if chip >= chips:
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[chip] = [(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.duration_ns))
    return Trace(ops=ops, spans=spans)


def window(trace: Trace) -> tuple:
    """(start_ns, end_ns) of the measured window's span."""
    w = [(s, s + d) for n, s, d in trace.spans if n == WINDOW_SPAN]
    if not w:
        raise ValueError(f"the trace has no {WINDOW_SPAN} span")
    return w[0]


def clip(events, lo: float, hi: float) -> list:
    """Events cut to [lo, hi): (name, start, end), empty ones dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b))
    return out


def union(intervals) -> list:
    """Sorted disjoint (start, end) covering the given intervals."""
    merged: list = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def gaps(busy: list, lo: float, hi: float) -> list:
    """The idle (start, end) stretches of [lo, hi) between busy ones."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(idle: list, spans: list) -> dict:
    """Idle nanoseconds by the innermost harness span open on the host at
    the time (the one that began last); the rest under ``NO_SPAN``."""
    spans = sorted((s, s + d, n) for n, s, d in spans if n != WINDOW_SPAN)
    out: dict = collections.Counter()
    for a, b in idle:
        # cut [a, b) at every span edge inside it, then name each piece
        cuts = sorted({a, b} | {x for s, e, _ in spans for x in (s, e)
                                if a < x < b})
        for lo, hi in zip(cuts, cuts[1:]):
            mid = (lo + hi) / 2
            inner = [(s, n) for s, e, n in spans if s <= mid < e]
            out[max(inner)[1] if inner else NO_SPAN] += hi - lo
    return out


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                 # mean over chips of the busy union
    kernel_s: dict                # kernel -> device seconds (chip mean)
    kernel_bytes: dict            # kernel -> HBM bytes (chip mean)
    collective_s: float           # chip mean
    device_ops: list              # [[label, seconds]] top 10, chip mean
    idle_gaps: list               # [[host span, seconds]] top 10, chip 0


def reduce(trace: Trace, chips: int, top: int = 10) -> Reduced:
    lo, hi = window(trace)
    busy = 0.0
    ksec: dict = collections.Counter()
    kbytes: dict = collections.Counter()
    labels: dict = collections.Counter()
    coll = 0.0
    idle0: list = []
    for chip in range(chips):
        ops = clip(trace.ops.get(chip, []), lo, hi)
        u = union((a, b) for _, a, b in ops)
        busy += sum(b - a for a, b in u)
        if chip == 0:
            idle0 = gaps(u, lo, hi)
        for name, a, b in ops:
            labels[counts.opcode(name)] += b - a
            k = counts.kernel_of(name)
            if k is not None:
                ksec[k] += b - a
                kbytes[k] += counts.kernel_bytes(name)
            elif counts.is_collective(name):
                coll += b - a
    ns = 1e-9 / chips
    by_span = attribute(idle0, trace.spans)
    return Reduced(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy * ns,
        kernel_s={k: v * ns for k, v in ksec.items()},
        kernel_bytes={k: v / chips for k, v in kbytes.items()},
        collective_s=coll * ns,
        device_ops=[[n, v * ns] for n, v in labels.most_common(top)],
        idle_gaps=[[n, v * 1e-9] for n, v in by_span.most_common(top)])


def roofline_pct(r: Reduced, kernel: str, hbm_bytes_per_s: float
                 ) -> Optional[float]:
    """Share (%) of the HBM-bytes roofline a kernel reached: the time its
    bytes need at peak bandwidth over the time it took.  None where the
    window ran no such kernel."""
    t = r.kernel_s.get(kernel, 0.0)
    if t <= 0:
        return None
    return 100.0 * r.kernel_bytes[kernel] / hbm_bytes_per_s / t
