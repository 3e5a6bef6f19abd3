"""Device milliseconds per revealed session under the engine's
``agg.vote`` scope: the vote kernel (``vote_combine``) and the relayouts
of its operands and result that carry the vote's name, on chip 0, over
the traced window."""
from harness import progspans


def read(run):
    return progspans.stage_ms(run, "agg.vote")
