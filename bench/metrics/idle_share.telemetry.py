"""Share (%) of the traced window in which no op ran on the device: one
less the union of the device-op intervals over the window, averaged over
the chips the cell uses."""


def read(run):
    t = run.trace
    return 100.0 * (1.0 - t.busy_s / t.window_s)
