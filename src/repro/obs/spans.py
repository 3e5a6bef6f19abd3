"""Service spans on the profiler's clock.

:func:`span` is the one instrument the service's host steps use: it
opens a ``jax.profiler.TraceAnnotation`` of the given name, so a
profiler trace shows the step on the host's timeline beside the device
ops, and, when a ``stage.seconds`` histogram is given, observes that
histogram over exactly the same interval with the caller's clock (the
executor's: ``perf_counter``, or a recorder's injected clock).  The
registry stage and the trace span are then one measurement.

With the profiler off a ``TraceAnnotation`` costs about a microsecond,
so spans go per batch (and one ``svc.seal`` per session), never per
``contribute``.  The spans the service opens, and the ``agg.*`` scopes
the engine puts on its device ops, are listed in :data:`SERVICE_SPANS`
and ``core.engine.STAGE_SCOPES``.
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable

from jax.profiler import TraceAnnotation

# name -> the host step it covers (README "Observability")
SERVICE_SPANS = {
    "svc.pump": "AdmissionQueue.pump: the key sweep, its batches and the "
                "ring drain",
    "svc.pack": "Session.fill_payload_rows into the batch slot",
    "svc.put": "the slot's host-to-device copy",
    "svc.issue": "the call of a cached batch executable",
    "svc.compile": "a build of a batch executable on a cache miss",
    "svc.settle": "the blocking wait for a batch's device result",
    "svc.reveal": "the batch's reveals",
    "svc.seal": "one session's seal and admission",
}


@contextlib.contextmanager
def span(name: str, hist=None,
         clock: Callable[[], float] = time.perf_counter, **meta):
    """A named host span on the profiler's timeline; ``meta`` (the retry
    ``unit``, a session ``sid``) rides on the trace event.  ``hist``, a
    registry histogram, observes the span's seconds on ``clock`` when
    the body returns normally (a raising body observes nothing, as the
    stage timers always did)."""
    with TraceAnnotation(name, **meta):
        if hist is None:
            yield
            return
        t0 = clock()
        yield
        hist.observe(clock() - t0)
