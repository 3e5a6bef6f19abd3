"""Share (%) of chip 0's bf16 peak that the step's own model work
reached: a step's model FLOPs on one chip (``counts.train_step_flops``
over the chips) times the steps completed, over chip 0's device seconds
outside every ``agg.*`` op (its busy union less the union of the ops
under an engine scope: the forward, the backward and AdamW, with the
gradients' secure sum left out), times 197 TFLOP/s."""
from harness import counts, progspans


def read(run):
    f = progspans.of(run)
    if f is None or run.peaks is None or not run.window.steps:
        return None
    model_s = f.busy_s - f.scoped_s
    if model_s <= 0:
        return None
    flops = counts.train_step_flops(run.config["model"], run.load.batch,
                                    run.load.seq_len) * run.window.steps
    return 100.0 * flops / (run.load.chips * run.peaks["bf16_flops_per_s"]
                            * model_s)
