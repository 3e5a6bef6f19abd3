"""One run of the training cell at smoke widths on the CPU, for
``test_bench_train.py``, which starts it in a process of its own with
forced host devices:

    python bench/tests/train_tiny.py <scenario>

``sound`` runs the cell as it is; ``control`` puts the reference one
precision lower in the program's place; ``ranks`` checks the reference's
per-rank pass instead of running the cell; ``another`` adds a further
training configuration (other widths, two ranks) through ``run_cell``'s
``config``, ``traffic`` and ``bench`` alone (float32 activations,
whose control is bfloat16); every other scenario breaks
the timed path underneath in one way (``FAULTS``).  The last line of
standard output is the run's result.
"""
import copy
import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

CELL = "mamba2-370m-dp4.secure-train"
SMOKE = {"d_model": 64, "n_layers": 2, "vocab_size": 128,
         "ssm": {"d_state": 32, "d_conv": 4, "expand": 2, "head_dim": 16,
                 "chunk": 32}}
SEED = 2**40 + 11


def _state_unchanged():
    # the optimizer hands back the parameters and its state as they came
    from repro.optim import adamw
    adamw.apply_updates = lambda cfg, p, g, state, grad_norm=None: (
        p, state, {"grad_norm": grad_norm, "lr": grad_norm * 0})


def _half_left_out():
    # each rank's loss over the first half of its rows, as their mean
    from repro.models import model
    loss_fn = model.loss_fn

    def half(cfg, params, batch, total_tokens=None):
        rows = batch["tokens"].shape[0] // 2
        return loss_fn(cfg, params, {k: v[:rows] for k, v in batch.items()},
                       total_tokens=total_tokens // 2)
    model.loss_fn = half


def _no_exchange():
    # every rank keeps its own gradient
    from repro.launch import steps
    steps.tree_allreduce = lambda tree, cfg, axes: tree


def _rank_left_out():
    # rank 0's gradient does not reach the sum
    import jax
    import jax.numpy as jnp
    from repro.launch import steps
    allreduce = steps.tree_allreduce

    def without_rank_0(tree, cfg, axes):
        first = jax.lax.axis_index(axes[0]) == 0
        return allreduce(jax.tree.map(
            lambda g: jnp.where(first, jnp.zeros_like(g), g), tree), cfg, axes)
    steps.tree_allreduce = without_rank_0


def _altered_answer():
    # the revealed sum of one leaf comes back with its sign turned
    from repro.launch import steps
    allreduce = steps.tree_allreduce

    def altered(tree, cfg, axes):
        out = allreduce(tree, cfg, axes)
        first = sorted(out)[0]
        return dict(out, **{first: -out[first]})
    steps.tree_allreduce = altered


def _wrong_batch():
    # each step is fed the batch of the step after it
    from harness import train
    feed = train.Cell.feed
    train.Cell.feed = lambda self, k: feed(self, k + 1)


FAULTS = {"state_unchanged": _state_unchanged,
          "half_left_out": _half_left_out, "no_exchange": _no_exchange,
          "rank_left_out": _rank_left_out, "altered_answer": _altered_answer,
          "wrong_batch": _wrong_batch}


def ranks() -> dict:
    """The reference's per-rank pass, as ``readings.py`` takes it: the
    ranks' own gradients add up to the summed pass, and each rank's
    first row gives the gradient of those rows alone."""
    import types
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as Ps
    import readings
    from harness import ref_mamba2 as R
    from harness import train
    model = dict(SMOKE, tie_embeddings=True, norm_eps=1e-6)
    load = train.Load(batch=8, seq_len=64, chips=4, setup_steps=1)
    cell = types.SimpleNamespace(ref=R, model=model, load=load)
    mesh = jax.make_mesh((4,), ("ranks",))
    put = lambda x: jax.device_put(x, NamedSharding(mesh, Ps("ranks")))
    first = lambda x: x.reshape(4, 2, -1)[:, 0]
    params = jax.jit(lambda k: R.init(model, k))(R.key_of(SEED))
    b = train.batch(model, load, SEED, 0)
    summed = R.rank_grads(model, mesh, "ranks", load.tokens_per_step, 1)
    with jax.default_matmul_precision("highest"):
        whole, _ = summed(params, put(b["tokens"]), put(b["labels"]))
        firsts, _ = summed(params, put(first(b["tokens"])),
                           put(first(b["labels"])))

    def err(each, want):
        return max(float(np.abs(g.sum(0) - np.asarray(x)).max()
                         / np.abs(np.asarray(x)).max())
                   for g, x in zip(each, jax.tree.leaves(want)))
    each = readings.rank_grads(cell, params, b, 2)
    return {"ranks": len(each[0]), "sum_err": err(each, whole),
            "first_rows_err": err(readings.rank_grads(cell, params, b, 1),
                                  firsts)}


def _another(s: dict, config: dict, traffic: dict) -> tuple:
    """A further training configuration, as a later change would add it:
    its own configuration, traffic and entries, no other file."""
    name = "mamba2-tiny-dp2"
    config = copy.deepcopy(config)
    config.update(name=name, dp=2, n_nodes=2, cluster_size=2, redundancy=1)
    config["model"].update(d_model=64, n_layers=3, vocab_size=96,
                           ssm=dict(SMOKE["ssm"], d_state=16, chunk=16),
                           dtype="float32")
    traffic = dict(traffic, seq_len=48, global_batch=4, setup_steps=2)
    cell = f"{name}.secure-train-small"
    s = copy.deepcopy(s)
    s["configs"].append({"name": name, "source": "test", "reduced": [],
                         "file": f"bench/configs/{name}.json", "why": "test"})
    s["workloads"].append({"name": cell, "config": name, "chips": 2,
                           "traffic": "secure-train-small", "why": "test"})
    for m in s["end_to_end"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append(cell)
    return cell, s, config, traffic


def main(scenario: str) -> dict:
    spec_ = importlib.util.spec_from_file_location("bench_run",
                                                   BENCH / "run.py")
    run = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(run)
    from harness import spec
    s = spec.with_held(spec.load_spec())
    w = spec.cell(s, CELL)
    config = spec.config_of(s, w)
    config["model"].update(SMOKE)
    traffic = dict(spec.traffic_of(w), seq_len=64, global_batch=8)
    cell = CELL
    if scenario == "another":
        cell, s, config, traffic = _another(s, config, traffic)
    elif scenario in FAULTS:
        FAULTS[scenario]()
    return run.run_cell(cell, SEED, 0.01, False,
                        control=scenario == "control", check_device=False,
                        config=config, traffic=traffic, bench=s)


if __name__ == "__main__":
    out = ranks() if sys.argv[1] == "ranks" else main(sys.argv[1])
    print(json.dumps(out), flush=True)
