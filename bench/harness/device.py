"""What the harness asks of the machine before it measures anything: a
TPU v5 lite with enough chips, the native Pallas kernel engine, and JAX's
persistent compile cache at a fixed path inside the checkout."""
from __future__ import annotations

import os
from pathlib import Path

CHIP_KIND = "TPU v5 lite"
CACHE_DIR = ".jax_cache"        # under the checkout's root, as chip_smoke.py


class BenchFailure(Exception):
    """The run cannot give a result: no chip, the wrong engine, or a
    configuration the harness does not know."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise BenchFailure(msg)


def setup_compile_cache(jax, root: Path) -> str:
    """Honour ``JAX_COMPILATION_CACHE_DIR``; otherwise keep the cache in
    ``<root>/.jax_cache``.  Every program is written to it, however fast
    it compiled, so a cell's second run loads all of them."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / CACHE_DIR)
    os.makedirs(path, exist_ok=True)    # JAX writes no entry into a missing one
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chip(jax, chips: int) -> dict:
    """The device record of the result line; fails unless JAX sees at
    least ``chips`` TPU v5 lite chips and the kernel engine resolves to
    native Pallas (no CPU fallback, no override)."""
    devs = jax.devices()
    dev = devs[0]
    check(dev.platform == "tpu", f"no TPU: JAX found {dev.platform}")
    check(dev.device_kind == CHIP_KIND,
          f"expected a {CHIP_KIND}, found {dev.device_kind}")
    check(len(devs) >= chips, f"the cell needs {chips} chips, JAX found "
                              f"{len(devs)}")
    check("REPRO_KERNEL_IMPL" not in os.environ,
          "REPRO_KERNEL_IMPL overrides the kernel engine")
    from repro.kernels import backend
    impl = backend.default_impl()
    check(impl == "pallas", f"kernel engine resolved to {impl}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def device_record(jax, chips: int) -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def peak_bytes(jax, chips: int) -> int:
    """``peak_bytes_in_use`` of the fullest chip the cell uses (-1 where
    the backend keeps no statistics, as the CPU)."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
             for d in jax.devices()[:chips]]
    return max(peaks)


class CompileLog:
    """Programs traced, compiled or loaded from the persistent cache, from
    JAX's monitoring events (as ``chip_smoke.py`` counts them).  The
    harness reads it at the window's edges: inside the window every
    count has to stay 0."""

    def __init__(self, jax):
        self.traces = self.compiles = self.hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> tuple:
        return (self.traces, self.compiles, self.hits)

    def since(self, snap: tuple) -> dict:
        t, c, h = snap
        return {"traces": self.traces - t, "compiles": self.compiles - c,
                "cache_loads": self.hits - h}
