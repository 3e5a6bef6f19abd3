"""Share (%) of the HBM roofline reached by the vote kernel
(``vote_combine``): its operands' and result's bytes, counted from the
shapes the trace prints, at 819 GB/s, over its device time.  HBM-bound by
construction: no VPU peak of the v5e is published."""
from harness import tracefold


def read(run):
    return tracefold.roofline_pct(run.trace, "vote",
                                  run.peaks["hbm_bytes_per_s"])
