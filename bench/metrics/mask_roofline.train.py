"""Share (%) of the HBM roofline reached by the mask kernel
(``mask_encrypt_batch``) in the train step's gradient sync: each chunk's
float32 payload and seed table in, its uint32 ciphertext out, counted
from the shapes the trace prints, at 819 GB/s, over its device time,
averaged over the chips."""
from harness import tracefold


def read(run):
    if run.peaks is None:
        return None
    return tracefold.roofline_pct(run.trace, "mask",
                                  run.peaks["hbm_bytes_per_s"])
