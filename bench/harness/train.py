"""The ``train`` driver: cells of a configuration with ``"kind":
"train"``, which train a model data-parallel through the program's
secure train step (``launch/steps.build_secure_train_step``), the
gradients of the ranks summed every step by the paper's masked
committee.

Set-up builds one object, the compiled step with its state: the weights
are made from the seed by the configuration's plain reference
(``harness/<reference>.py``, one jitted call, in the program's parameter
layout), the Adam state is the program's own, and the step runs the
traffic's ``setup_steps`` first steps through the window's own call and
feed.  The window then goes on with the same object, step after step,
each step's loss read back as ``launch/train.train_loop`` does.  Every
step has a batch of its own: token ids drawn uniformly from the whole
vocabulary, from the seed and the step's index.

The comparison follows the set-up steps with the reference, from the
same weights and batches, once the program's state is freed:

* ``grad_norm_gap``: the first gradient as the optimizer got it, the
  program's worked out from its Adam state after one step (``m / (1 -
  b1)``, the clipped revealed sum), against the reference's clipped sum
  of the ranks' gradients: the worst leaf's gap of norms;
* ``update_norm_gap``: the parameters' change over the set-up steps, as
  the window's first step takes them, against the reference's: the
  worst leaf's gap of norms;
* ``grad_err``: the worst leaf's norm of the difference of the two first
  gradients, which a gradient of the wrong batch or sign cannot pass as
  a gap of norms can;
* ``nonfinite_losses``: window steps whose loss is not finite.

A leaf is one layer's tensor (the program stacks the layers).  Each gap
is taken against the larger of the reference leaf's norm and the median
leaf's; leaves whose reference gradient is under a thousandth of the
median leaf's are left out of ``update_norm_gap``, since Adam moves them
by round-off alone.  The control puts the reference, computed with its
activations and their cotangents in the next precision below the
configuration's activation dtype (``CONTROL``), in the program's place.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
import math
import time
from typing import Callable

import numpy as np

from harness import e2e, stats
from harness.device import BenchFailure

# The limits of the numbers compared, set from the readings of sound runs
# of the program (the lower reading, over a dozen seeds) and of the
# control (the upper reading), on four v5e chips at the cell's own size;
# PERF.md gives the readings.
# The set-up steps' gap of loss from the reference's is not compared: the
# control reads under three times, and the faults under ten times, what
# sound runs read, so no limit holds between them (PERF.md gives the
# readings); the gradients' numbers catch the control and every fault.
LIMITS = {"grad_norm_gap": 0.16, "update_norm_gap": 0.024, "grad_err": 0.4,
          "nonfinite_losses": 0, "window_compiles": 0}

# the next precision below a configuration's activation dtype
CONTROL = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}
REF_ROWS = 1            # sequences a rank's reference pass takes at a time
QUIET = 1e-3            # of the median leaf's gradient: moved by round-off


def train_tokens_per_s(w, setup_s: float) -> float:
    """Tokens of the steps completed over the whole window."""
    return stats.rate(w.tokens, w.seconds)


E2E = {"train_tokens_per_s": train_tokens_per_s, "setup_s": e2e.setup_s}


@dataclasses.dataclass
class Load:
    """The sizes of a run's steps, for the per-layer readers."""
    batch: int
    seq_len: int
    chips: int
    setup_steps: int

    @property
    def tokens_per_step(self) -> int:
        return self.batch * self.seq_len


@dataclasses.dataclass
class Window:
    attempted: int = 0              # steps started in the window
    steps: int = 0                  # steps completed
    seconds: float = 0.0            # window start to the last step's end
    tokens: int = 0
    losses: list = dataclasses.field(default_factory=list)


def batch(model: dict, load: Load, seed: int, step: int) -> dict:
    """Step ``step``'s batch: ``batch`` rows of ``seq_len + 1`` token ids
    drawn uniformly from the vocabulary; each row's labels are its
    tokens shifted by one."""
    rng = np.random.default_rng([int(seed) & (2**64 - 1), 7, step])
    ids = rng.integers(0, model["vocab_size"],
                       size=(load.batch, load.seq_len + 1), dtype=np.int32)
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}


def program_config(config: dict):
    """The program's model configuration: its registered architecture
    with the widths, depth and numerics the file states."""
    from repro.configs import get_config
    from repro.configs.base import SSMConfig
    m = config["model"]
    return dataclasses.replace(
        get_config(config["arch"]), d_model=m["d_model"],
        n_units=m["n_layers"], vocab_size=m["vocab_size"],
        tie_embeddings=m["tie_embeddings"], ssm=SSMConfig(**m["ssm"]),
        dtype=m["dtype"], param_dtype=m["param_dtype"],
        opt_state_dtype=m["opt_state_dtype"], remat=m["remat"],
        dp_mode="replicated")


def committee(config: dict):
    """The committee that sums the gradients: ``AggConfig`` as the file
    states it, derived for the data-parallel extent as the train step
    derives its per-axis committees."""
    from repro.core.plan import AggConfig
    keys = ("cluster_size", "redundancy", "schedule", "transport", "masking",
            "clip", "guard_bits", "chunk_elems")
    return AggConfig(n_nodes=config["n_nodes"],
                     **{k: config[k] for k in keys}).derive(
                         n_nodes=config["dp"])


def to_host(tree) -> list:
    """Every leaf of ``tree`` as a float32 NumPy array, in tree order."""
    import jax
    return [np.asarray(x, np.float32) for x in jax.tree.leaves(tree)]


def layer_leaves(leaves: list, stacked: list) -> list:
    """One entry per layer's tensor: the leaves the program stacks over
    the layers (``stacked[i]`` true) split along their first axis."""
    out = []
    for x, s in zip(leaves, stacked):
        out.extend(list(x) if s else [x])
    return out


def norm(x) -> float:
    return math.sqrt(float(np.sum(np.square(x, dtype=np.float64))))


def worst_gap(got: list, norms: list, keep=None) -> float:
    """The worst leaf's gap between the norms of ``got`` and the
    reference's ``norms``, against the larger of the reference leaf's
    norm and the median leaf's; ``keep`` leaves out the others."""
    floor = float(np.median(norms))
    return max(abs(norm(g) - n) / max(n, floor)
               for i, (g, n) in enumerate(zip(got, norms))
               if keep is None or keep[i])


def worst_err(got: list, want: list, norms: list) -> float:
    """The worst leaf's norm of ``got - want``, against the larger of
    ``want``'s norm (``norms``) and the median leaf's."""
    floor = float(np.median(norms))
    return max(norm(np.subtract(g, x, dtype=np.float64)) / max(n, floor)
               for g, x, n in zip(got, want, norms))


@dataclasses.dataclass
class Steps:
    """What the set-up steps gave, as host arrays: each step's loss, the
    first step's gradient as the optimizer got it, and the parameters
    after the last."""
    losses: list
    grad: list
    params: list


class Cell:
    """One run of a training cell, as ``run.run_cell`` drives it."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 seconds: float, chips: int):
        import jax
        from repro.configs.base import ShapeConfig
        from repro.launch import steps as ST
        from repro.launch.mesh import make_host_mesh
        from repro.optim import adamw

        self.seed = seed
        self.model, self.opt = config["model"], config["optimizer"]
        self.ref = importlib.import_module(f"harness.{config['reference']}")
        dp = config["dp"]
        if dp != chips:
            raise BenchFailure(f"the configuration is {dp}-way data "
                               f"parallel, the cell has {chips} chips")
        self.load = Load(batch=traffic["global_batch"],
                         seq_len=traffic["seq_len"], chips=chips,
                         setup_steps=traffic["setup_steps"])
        cfg = program_config(config)
        opt_cfg = adamw.OptConfig(
            lr=self.opt["lr"], betas=tuple(self.opt["betas"]),
            eps=self.opt["eps"], weight_decay=self.opt["weight_decay"],
            grad_clip=self.opt["grad_clip"],
            warmup_steps=self.opt["warmup_steps"],
            total_steps=self.opt["total_steps"],
            min_lr_frac=self.opt["min_lr_frac"],
            state_dtype=self.model["opt_state_dtype"])
        shape = ShapeConfig("bench", self.load.seq_len, self.load.batch,
                            "train")
        self.step_fn, (p_sh, o_sh, self.b_sh), _ = \
            ST.build_secure_train_step(cfg, make_host_mesh(data=dp),
                                       committee(config),
                                       opt_cfg=opt_cfg, shape=shape)
        make = jax.jit(functools.partial(self.ref.init, self.model),
                       out_shardings=p_sh)
        params = make(self.ref.key_of(seed))
        want = ST.abstract_params(cfg)
        if (jax.tree.structure(params) != jax.tree.structure(want)
                or any(a.shape != b.shape for a, b in
                       zip(jax.tree.leaves(params), jax.tree.leaves(want)))):
            raise BenchFailure("the reference's weights do not have the "
                               "program's parameter layout")
        self.stacked = [x.ndim > 0 and x.shape[0] == self.model["n_layers"]
                        and p[0].key == "units" for p, x in
                        jax.tree_util.tree_leaves_with_path(params)]
        self.state = (params, jax.jit(
            functools.partial(adamw.init_opt_state, opt_cfg),
            out_shardings=o_sh)(params))
        losses, grad = [], None
        b1 = self.opt["betas"][0]
        for k in range(self.load.setup_steps):
            losses.append(self._step(k))
            if k == 0:
                grad = [m / np.float32(1 - b1)
                        for m in to_host(self.state[1]["m"])]
        self.setup = Steps(losses=losses, grad=grad,
                           params=to_host(self.state[0]))
        self.warmed = f"{self.load.setup_steps} steps warmed"
        self._ref = None

    def feed(self, k: int) -> dict:
        """Step ``k``'s batch on the chips, as the step takes it."""
        import jax
        return jax.device_put(batch(self.model, self.load, self.seed, k),
                              self.b_sh)

    def _step(self, k: int) -> float:
        params, opt_state, metrics = self.step_fn(*self.state, self.feed(k))
        self.state = (params, opt_state)
        return float(metrics["loss"])

    def window(self, seconds: float, span, beat: Callable) -> Window:
        """Steps one after another until ``seconds`` have passed; the
        window ends with the last step's loss.  ``beat`` is not called:
        the loop waits on each step by design."""
        w = Window()
        clock = time.monotonic
        t0 = clock()
        k = self.load.setup_steps
        while True:
            w.attempted += 1
            with span("bench.step"):
                w.losses.append(self._step(k))
            k += 1
            if clock() - t0 >= seconds:
                break
        w.seconds = clock() - t0
        w.steps = len(w.losses)
        w.tokens = w.steps * self.load.tokens_per_step
        return w

    def stages(self) -> dict:
        return {}

    def settle(self, w: Window) -> None:
        pass

    def done(self, w: Window) -> str:
        return f"{w.steps} steps, {w.tokens} tokens"

    def notes(self, w: Window) -> list:
        return [f"losses: set-up {self.setup.losses}, window first "
                f"{w.losses[0]}, last {w.losses[-1]}"]

    def free(self) -> None:
        del self.state

    def _follow(self, act=None) -> Steps:
        """The reference's set-up steps from the run's weights and
        batches; with ``act``, in that precision (the control)."""
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as Ps
        mesh = jax.make_mesh((self.load.chips,), ("ranks",),
                             devices=jax.devices()[:self.load.chips])
        rep = NamedSharding(mesh, Ps())
        rows = NamedSharding(mesh, Ps("ranks"))
        step = self.ref.make_step(self.model, self.opt, mesh, "ranks",
                                  self.load.tokens_per_step, REF_ROWS, act)
        params = jax.jit(functools.partial(self.ref.init, self.model),
                         out_shardings=rep)(self.ref.key_of(self.seed))
        p0 = to_host(params)
        zeros = jax.jit(lambda p: jax.tree.map(jax.numpy.zeros_like, p),
                        out_shardings=rep)
        mo, vo = zeros(params), zeros(params)
        losses, grad = [], None
        for k in range(self.load.setup_steps):
            b = batch(self.model, self.load, self.seed, k)
            params, mo, vo, val, g = step(
                k, params, mo, vo, jax.device_put(b["tokens"], rows),
                jax.device_put(b["labels"], rows))
            losses.append(float(val))
            if k == 0:
                grad = to_host(g)
            del g
        out = Steps(losses=losses, grad=grad, params=to_host(params))
        self.p0 = p0
        return out

    def compare(self, w: Window, control: bool = False) -> dict:
        if self._ref is None:
            self._ref = self._follow()
        ref = self._ref
        got = (self._follow(CONTROL[self.model["dtype"]]) if control
               else self.setup)
        split = functools.partial(layer_leaves, stacked=self.stacked)
        g_ref = split(ref.grad)
        n_grad = [norm(x) for x in g_ref]
        floor = float(np.median(n_grad))
        keep = [n >= QUIET * floor for n in n_grad]
        moved = lambda s: [p - p0 for p, p0 in zip(split(s.params),
                                                   split(self.p0))]
        g_got = split(got.grad)
        out = {
            "grad_norm_gap": worst_gap(g_got, n_grad),
            "update_norm_gap": worst_gap(
                moved(got), [norm(x) for x in moved(ref)], keep),
            "grad_err": worst_err(g_got, g_ref, n_grad),
        }
        if not control:
            out["nonfinite_losses"] = sum(not math.isfinite(x)
                                          for x in w.losses)
        return out

    def failed(self, numbers: dict) -> int:
        return numbers["nonfinite_losses"]
