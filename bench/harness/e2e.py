"""The end-to-end metrics: what a user of the service sees, taken by the
harness on the host clock over the whole window and over all requests."""
from __future__ import annotations

from harness import stats


def sessions_per_s(w, setup_s: float) -> float:
    """Sessions revealed over the whole window."""
    return stats.rate(w.revealed, w.seconds)


def reveal_p95_ms(w, setup_s: float) -> float:
    """95th percentile, over every session due in the window, of the time
    from its due time to the client seeing its answer; a session that
    never revealed counts with the whole wait."""
    return 1000.0 * stats.percentile(w.latency_s, 95)


def setup_s(w, setup_s: float) -> float:
    """Process start to the first timed request."""
    return setup_s


E2E = {"sessions_per_s": sessions_per_s, "reveal_p95_ms": reveal_p95_ms,
       "setup_s": setup_s}
