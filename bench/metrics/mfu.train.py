"""Share (%) of the chips' bf16 peak that the window's training steps
reached on the device: the model FLOPs of a step
(``counts.train_step_flops``, from the configuration's widths,
rematerialisation not counted) times the steps completed, over the
chips' device seconds in the traced window (each chip's union of
device-op intervals, from the trace, chip mean) times the chips times
197 TFLOP/s.  The whole step's share: the model, the sync and every
kernel count in its time; the device's idle does not
(``idle_share.train`` reads that)."""
from harness import counts


def read(run):
    if run.peaks is None or not run.window.steps or run.trace.busy_s <= 0:
        return None
    flops = counts.train_step_flops(run.config["model"], run.load.batch,
                                    run.load.seq_len) * run.window.steps
    return 100.0 * flops / (run.load.chips * run.peaks["bf16_flops_per_s"]
                            * run.trace.busy_s)
