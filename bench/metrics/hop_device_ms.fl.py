"""Device milliseconds per revealed session of the voted hops: the ops
under the engine's ``agg.hop`` and ``agg.select`` scopes (the rolls of
the node axis, the relayouts XLA folds into them, and the select of the
nodes that take part in a round), on chip 0, over the traced window."""
from harness import progspans


def read(run):
    return progspans.stage_ms(run, "agg.hop", "agg.select")
