"""Mean milliseconds of the executor's ``pack_overlap`` span over the
window: packing a batch into its slot buffer and issuing it to the device
(``Session.fill_payload_rows``, ``BatchedExecutor._dispatch``), read from
the registry's ``stage.seconds{stage=pack_overlap}``."""


def read(run):
    return run.stage_mean_ms("pack_overlap")
