"""Device milliseconds a step under the engine's ``agg.*`` scopes, which
``tree_allreduce`` -> ``execute_chunks`` opens for the gradients' secure
sum (encrypt, cluster sum, any voted rounds' hops, votes and selects,
reveal, unmask), on chip 0, over the traced window: the union of those
ops' intervals, so that an op nested in another is counted once."""
from harness import progspans


def read(run):
    f = progspans.of(run)
    if f is None or not run.window.steps or f.scoped_s <= 0:
        return None
    return 1000.0 * f.scoped_s / run.window.steps
