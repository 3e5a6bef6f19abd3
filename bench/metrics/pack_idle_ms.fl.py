"""Milliseconds per revealed session in which chip 0 sat idle while the
innermost host span was the service's ``svc.pack`` (filling the batch
slot) or ``svc.put`` (its host-to-device copy), over the traced window."""
from harness import progspans


def read(run):
    return progspans.idle_ms(run, "svc.pack", "svc.put")
