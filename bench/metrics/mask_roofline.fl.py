"""Share (%) of the HBM roofline reached by the encrypt kernel
(``mask_encrypt_batch``): float32 payload read and uint32 written, counted
from the shapes the trace prints, at 819 GB/s, over its device time.
HBM-bound by construction: no VPU peak of the v5e is published."""
from harness import tracefold


def read(run):
    return tracefold.roofline_pct(run.trace, "mask",
                                  run.peaks["hbm_bytes_per_s"])
