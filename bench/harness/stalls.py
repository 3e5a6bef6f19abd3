"""A watch on the host during the measured window, so that a run whose
tail reads far off says why.

A thread wakes every ``every`` seconds.  Where it wakes more than
``after`` seconds late, the whole process was held up: descheduled by
the host, or the interpreter's lock held in native code.  Where it wakes
on time but the window's loop has not called ``beat`` for ``after``
seconds, the loop is blocked in a call, and the thread notes the main
thread's innermost frames.  Nothing is noted for a loop that never
beats, such as one blocked in ``pump`` by design.
"""
from __future__ import annotations

import sys
import threading
import time
import traceback


class Stalls:
    def __init__(self, every: float = 0.02, after: float = 0.25):
        self.every, self.after = every, after
        self.notes: list = []           # (seconds into window, kind, ms, where)
        self._beat = None
        self._noted = None
        self._stop = threading.Event()
        self._main = threading.main_thread().ident
        self._t0 = 0.0

    def beat(self) -> None:
        self._beat = time.monotonic()

    def _watch(self) -> None:
        clock = time.monotonic
        due = clock() + self.every
        blocked = None                  # index of the open "loop blocked" note
        while not self._stop.wait(max(0.0, due - clock())):
            now = clock()
            if now - due > self.after:
                self.notes.append([now - self._t0, "process held",
                                   1000 * (now - due), ""])
            beat = self._beat
            if blocked is not None and beat != self._noted:
                # the loop moved again: the note gets the stall's length
                self.notes[blocked][2] = 1000 * (beat - self._noted)
                blocked = None
            if beat is not None and now - beat > self.after \
                    and self._noted != beat:
                self._noted = beat
                frame = sys._current_frames().get(self._main)
                where = " < ".join(
                    f"{f.name} ({f.filename.rsplit('/', 1)[-1]}:{f.lineno})"
                    for f in reversed(traceback.extract_stack(frame)[-4:])
                ) if frame is not None else ""
                blocked = len(self.notes)
                self.notes.append([now - self._t0, "loop blocked",
                                   1000 * (now - beat), where])
            due = now + self.every

    def __enter__(self):
        self._t0 = time.monotonic()
        self._thread = threading.Thread(target=self._watch, daemon=True)
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()

    def lines(self, most: int = 8) -> list:
        return [f"stall at {t:.3f} s: {kind} {ms:.1f} ms {where}".rstrip()
                for t, kind, ms, where in self.notes[:most]]
