"""Mean milliseconds a batch's oldest session waited in the admission
queue before its batch flushed, over the window, read from the registry's
``stage.seconds{stage=admission_wait}``."""


def read(run):
    return run.stage_mean_ms("admission_wait")
