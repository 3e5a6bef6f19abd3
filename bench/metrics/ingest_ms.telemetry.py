"""Mean milliseconds, over the sessions of the window, of the harness's
span around one session's ingest: open, every slot's ``contribute`` and
``seal``."""


def read(run):
    s = run.window.ingest_s
    return 1000.0 * sum(s) / len(s) if s else None
