"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for.  The cell ``<config>.<traffic>`` is looked up in
``BENCHMARK.json``; its configuration, traffic mix and per-layer metrics are
files of their own under ``bench/`` (see ``harness/spec.py``).

A configuration's ``kind`` picks the driver that runs its cells:
``harness/<kind>.py``, ``service`` where the file names none.  The driver
brings set-up, the measured window, the comparison with its plain
reference, its end-to-end metrics (``E2E``) and the limits of the numbers
it compares (``LIMITS``); this file brings what every cell shares: the
chip and the compile cache, the clock, the profiler, the count of
compiles, and the result line.

Set-up (``setup_s``) runs from process start to the first timed request
or step: the data and weights are made from the seed, the system under
test is built, and every shape the traffic uses is run once, from JAX's
persistent compile cache after a cell's first run.  Then the window runs
for ``--seconds``; nothing may compile inside it.  After the window, the
program's state is freed and what the window produced is compared with
the plain reference.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window is traced by the JAX profiler and the result
carries its per-layer metrics, the device's busy and window seconds, and
a breakdown.  ``--control 1`` compares the reference computed one
precision lower in the program's place, which has to come out not
correct (the program's own numbers of the run are printed before it);
the benchmark's own runs never set it.

Earlier lines of output are free; the last line of standard output is
the result, and the last lines of standard error are the numbers compared
beside their limits.  A run that finds no TPU v5 lite, fewer chips than
the cell asks for, or a kernel engine other than native Pallas exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT / "src"))

from harness import device, oracles, spec  # noqa: E402
from harness import tracefold  # noqa: E402
from harness.stalls import Stalls  # noqa: E402
from harness.peaks import peaks_for  # noqa: E402


def say(*parts) -> None:
    print("bench:", *parts, file=sys.stderr, flush=True)


class GcPauses:
    """Collections of the interpreter's garbage collector and the time
    they took, so that a stall of the host is not read as the service's."""

    def __init__(self):
        self.count, self.total, self.longest, self._t = 0, 0.0, 0.0, None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            dt = time.perf_counter() - self._t
            self.count += 1
            self.total += dt
            self.longest = max(self.longest, dt)

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


class Run:
    """What a per-layer metric's reader sees of one traced run."""

    def __init__(self, *, window, stages, trace, peaks, load, config,
                 traffic):
        self.window = window            # the driver's record of the window
        self.stages = stages            # series -> (count, seconds), window
        self.trace = trace              # tracefold.Reduced
        self.peaks = peaks              # harness.peaks entry of the chip
        self.load = load                # the driver's inputs of the run
        self.config = config
        self.traffic = traffic

    def stage_mean_ms(self, stage: str):
        """Mean milliseconds of a ``stage.seconds`` span over the window,
        None where it never ran."""
        count, total = self.stages.get(f"stage.seconds{{stage={stage}}}",
                                       (0, 0.0))
        return 1000.0 * total / count if count else None


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             control: bool = False, check_device: bool = True,
             root: Path = ROOT, config: dict = None,
             traffic: dict = None, bench: dict = None) -> dict:
    """One run of one cell; returns the result object.  ``check_device``
    is off only in the harness's own tests, which run on the CPU;
    ``config``, ``traffic`` and ``bench`` (the spec, held cells included)
    replace the cell's files there."""
    import jax
    bench = bench or spec.load_spec(root)
    w = spec.cell(bench, workload)
    chips = w["chips"]
    config = config or spec.config_of(bench, w, root)
    traffic = traffic or spec.traffic_of(w)
    driver = spec.driver(config)
    if check_device:
        cache = device.setup_compile_cache(jax, root)
        dev = device.require_chip(jax, chips)
    else:
        cache, dev = None, device.device_record(jax, chips)
    say(f"device {dev['kind']} x{len(jax.devices())} (cell uses {chips}), "
        f"compile cache {cache}")
    log = device.CompileLog(jax)

    length = float(traffic.get("trace_seconds", seconds) if trace
                   else seconds)
    cell = driver.Cell(config, traffic, seed, length, chips)
    # set-up's garbage, and its writes to the compile cache, are settled
    # here, so that neither is paid for inside the window
    gc.collect()
    os.sync()
    span = tracefold.spans(trace)
    trace_dir = root / "bench" / "out" / "trace" / workload
    if trace:
        from jax.profiler import ProfileOptions
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    before = log.snapshot()
    compiled = (log.compiles, log.compile_s, log.hits)
    setup_s = time.monotonic() - T_START
    with span(tracefold.WINDOW_SPAN), GcPauses() as pauses, \
            Stalls() as stalls:
        win = cell.window(length, span, stalls.beat)
    in_window = log.since(before)
    stages = cell.stages()
    if trace:
        jax.profiler.stop_trace()
    cell.settle(win)
    dev["memory_peak_bytes"] = device.peak_bytes(jax, chips)
    say(f"set-up {setup_s:.3f} s ({cell.warmed}; "
        f"{compiled[0]} programs compiled in {compiled[1]:.3f} s, "
        f"{compiled[2]} loaded from the cache); window "
        f"{win.seconds:.3f} s, {cell.done(win)}; compiles in the window "
        f"{in_window}")
    for line in cell.notes(win):
        say(line)
    say(f"garbage collections in the window: {pauses.count}, "
        f"{1000 * pauses.total:.3f} ms, longest {1000 * pauses.longest:.3f} "
        f"ms")
    for line in stalls.lines():
        say(line)
    say(f"peak device bytes {dev['memory_peak_bytes']}")
    cell.free()
    gc.collect()                # the program's state goes before the check

    numbers = cell.compare(win)
    numbers["window_compiles"] = sum(in_window.values())
    if control:
        # the program's own reading of this seed first, then the control
        for name, v in numbers.items():
            say(f"sound check {name} = {v}")
        numbers.update(cell.compare(win, control=True))
        say("control: the reference one precision lower stands in for "
            "the program")
    checks = {k: {"value": v, "limit": driver.LIMITS[k]}
              for k, v in numbers.items()}
    failed = cell.failed(numbers)
    metrics = {}
    breakdown = None
    if not trace:
        for m in spec.metrics_of(bench, w, "end_to_end"):
            metrics[m["name"]] = {"value": driver.E2E[m["name"]](win,
                                                                 setup_s),
                                  "unit": m["unit"]}
    else:
        red = tracefold.reduce(
            tracefold.load(tracefold.latest_xplane(str(trace_dir)), chips),
            chips)
        ctx = Run(window=win, stages=stages, trace=red,
                  peaks=peaks_for(dev["kind"]) if check_device else None,
                  load=cell.load, config=config, traffic=traffic)
        for m in spec.metrics_of(bench, w, "per_layer"):
            v = spec.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev["busy_s"] = red.busy_s
        dev["window_s"] = red.window_s
        breakdown = {"device_ops": red.device_ops,
                     "idle_gaps": red.idle_gaps}
    for name, m in metrics.items():
        say(f"metric {name} = {m['value']} {m['unit']}")
    out = {"correct": oracles.verdict(checks), "attempted": win.attempted,
           "failed": failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for name, c in checks.items():
        say(f"check {name} = {c['value']} (limit {c['limit']})")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    a = ap.parse_args()
    out = run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                   control=bool(a.control))
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except device.BenchFailure as e:
        say(f"FAIL {e}")
        sys.exit(1)
