"""Share (%) of the traced window in which no op ran on chip 0: one less
chip 0's union of device-op intervals over the window."""
from harness import progspans


def read(run):
    f = progspans.of(run)
    if f is None or f.busy_s <= 0:
        return None
    return 100.0 * (1.0 - f.busy_s / run.trace.window_s)
