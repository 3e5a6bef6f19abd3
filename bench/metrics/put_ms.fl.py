"""Mean milliseconds of the service's ``svc.put`` span, the batch slot's
host-to-device copy (``jnp.asarray`` / ``device_put``), over the traced
window."""
from harness import progspans


def read(run):
    f = progspans.of(run)
    if f is None or "svc.put" not in f.spans:
        return None
    count, seconds = f.spans["svc.put"]
    return 1000.0 * seconds / count
