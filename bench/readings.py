"""The readings that the limits of a training cell's comparison are set
from: for each seed, at the cell's own size and in one process, the
numbers of a sound run of the program (set-up and one window step), of
the control, and of the faults a training cell can have, planted in the
reference put in the program's place.

    python3 bench/readings.py --workload mamba2-370m-dp4.secure-train \\
        --seeds 11,12,13 --control 1 --faults 1

prints one JSON line a seed.  The faults change the first gradient as
the optimizer gets it, read by ``grad_norm_gap`` and ``grad_err``:

* ``rank_left_out``: rank 0's gradient does not reach the sum;
* ``no_exchange``: rank 0 keeps its own gradient;
* ``half_left_out``: every rank's first half of its rows, as their mean;
* ``wrong_batch``: the gradient and loss of the next step's batch;
* ``altered_answer``: one leaf's sum (the embedding's) with its sign
  turned;
* ``state_unchanged``: the optimizer never moves (a gap of 1, by the
  measure itself).

Not part of the benchmark's runs (PERF.md gives the readings).
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import math
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

from harness import device, spec, train, tracefold  # noqa: E402


def rank_grads(cell, params, b: dict, rows: int) -> tuple:
    """Each rank's gradient of its first ``rows`` rows of batch ``b`` at
    ``params`` (unsummed), as host arrays stacked over the ranks: the
    reference's own per-rank pass."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as Ps
    load = cell.load
    mesh = jax.make_mesh((load.chips,), ("ranks",),
                         devices=jax.devices()[:load.chips])
    fn = cell.ref.rank_grads(cell.model, mesh, "ranks", load.tokens_per_step,
                             train.REF_ROWS, summed=False)
    per = load.batch // load.chips

    def put(x):
        x = x.reshape(load.chips, per, -1)[:, :rows].reshape(
            load.chips * rows, -1)
        return jax.device_put(x, NamedSharding(mesh, Ps("ranks")))
    with jax.default_matmul_precision("highest"):
        g, _ = fn(params, put(b["tokens"]), put(b["labels"]))
    return train.to_host(g)


def clipped(cell, leaves: list) -> list:
    """``leaves`` as the optimizer gets them: clipped to the global
    norm the configuration states."""
    n = math.sqrt(sum(train.norm(x) ** 2 for x in leaves))
    s = min(1.0, cell.opt["grad_clip"] / (n + 1e-9))
    return [x * s for x in leaves]


def planted(cell) -> dict:
    """Gradient numbers of each fault, planted in the reference."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as Ps
    mesh = jax.make_mesh((cell.load.chips,), ("ranks",),
                         devices=jax.devices()[:cell.load.chips])
    p0 = jax.jit(functools.partial(cell.ref.init, cell.model),
                 out_shardings=NamedSharding(mesh, Ps()))(
                     cell.ref.key_of(cell.seed))
    per = cell.load.batch // cell.load.chips
    b0, b1 = (train.batch(cell.model, cell.load, cell.seed, k)
              for k in (0, 1))
    ranks = rank_grads(cell, p0, b0, per)
    half = rank_grads(cell, p0, b0, per // 2)
    nxt = rank_grads(cell, p0, b1, per)
    del p0
    split = functools.partial(train.layer_leaves, stacked=cell.stacked)
    ref = cell._ref
    want = split(ref.grad)
    norms = [train.norm(x) for x in want]
    grads = {
        "rank_left_out": [g[1:].sum(0) for g in ranks],
        "no_exchange": [g[0] for g in ranks],
        "half_left_out": [2 * g.sum(0) for g in half],
        "wrong_batch": [g.sum(0) for g in nxt],
        "altered_answer": [-ref.grad[0]] + ref.grad[1:],
        "state_unchanged": [np.zeros_like(x) for x in ref.grad],
    }
    out = {}
    for name, g in grads.items():
        g = split(clipped(cell, g))
        out[name] = {"grad_norm_gap": train.worst_gap(g, norms),
                     "grad_err": train.worst_err(g, want, norms)}
    out["state_unchanged"]["update_norm_gap"] = 1.0
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=1)
    ap.add_argument("--faults", type=int, default=0)
    a = ap.parse_args()
    import jax
    bench = spec.load_spec()
    w = spec.cell(bench, a.workload)
    config, traffic = spec.config_of(bench, w), spec.traffic_of(w)
    device.setup_compile_cache(jax, spec.ROOT)
    device.require_chip(jax, w["chips"])
    for seed in (int(s) for s in a.seeds.split(",")):
        cell = train.Cell(config, traffic, seed, 0.0, w["chips"])
        win = cell.window(0.0, tracefold.spans(False), lambda: None)
        cell.free()
        gc.collect()
        sound = cell.compare(win)
        rec = {"seed": seed, "setup_losses": cell.setup.losses,
               "ref_losses": cell._ref.losses, "sound": sound}
        if a.control:
            rec["control"] = cell.compare(win, control=True)
        if a.faults:
            rec["faults"] = planted(cell)
        print(json.dumps(rec), flush=True)
        del cell
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
