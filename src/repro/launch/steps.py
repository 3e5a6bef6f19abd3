"""Step builders: train (baseline GSPMD / secure paper-path), prefill,
decode — plus ``input_specs`` (ShapeDtypeStruct stand-ins, no allocation).

The SECURE path runs the whole fwd/bwd inside a ``shard_map`` that is
manual over the DP axes and auto over "model" (DESIGN §2.2): backward
then yields *local* per-rank gradients (no hidden GSPMD psum on the DP
axes), which are aggregated by the paper's voted cluster schedule.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES, ModelConfig, ShapeConfig
from repro.core.engine import tree_allreduce
from repro.core.plan import AggConfig
from repro.launch import sharding as SH
from repro.launch.mesh import dp_axes_of
from repro.models import model as M
from repro.optim import adamw
from repro.runtime.context import DistCtx, use_ctx

# ---------------------------------------------------------------------------
# input_specs: ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    B, S = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    out: dict[str, Any] = {}
    if shape.kind == "decode":
        out["tokens"] = sds((B, 1), jnp.int32)
    elif cfg.frontend == "audio_frames":
        out["frames"] = sds((B, S, cfg.d_model), jnp.float32)
    else:
        out["tokens"] = sds((B, S), jnp.int32)
    if shape.kind == "train":
        out["labels"] = sds((B, S), jnp.int32)
    if cfg.frontend == "vision_patches" and shape.kind != "decode":
        out["media"] = sds((B, cfg.n_media_tokens, cfg.d_model), jnp.float32)
    return out


def abstract_params(cfg: ModelConfig) -> Any:
    return jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))


def abstract_opt_state(cfg: ModelConfig, opt_cfg: adamw.OptConfig) -> Any:
    params = abstract_params(cfg)
    return jax.eval_shape(lambda: adamw.init_opt_state(opt_cfg, params))


def abstract_cache(cfg: ModelConfig, shape: ShapeConfig) -> Any:
    return jax.eval_shape(lambda: M.init_cache(
        cfg, shape.global_batch, shape.seq_len,
        media_len=cfg.n_media_tokens))


# ---------------------------------------------------------------------------
# Baseline train step (pure GSPMD)
# ---------------------------------------------------------------------------


def build_train_step(cfg: ModelConfig, mesh: jax.sharding.Mesh,
                     opt_cfg: Optional[adamw.OptConfig] = None,
                     shape: Optional[ShapeConfig] = None,
                     donate: bool = True):
    """Returns (jitted step, (param_shardings, opt_shardings, batch_shardings))."""
    opt_cfg = opt_cfg or adamw.OptConfig(
        state_dtype=cfg.opt_state_dtype)
    shape = shape or SHAPES["train_4k"]
    total_tokens = shape.global_batch * shape.seq_len
    ctx = DistCtx(mesh=mesh, dp_axes=dp_axes_of(mesh), tp_axis="model",
                  ep_axis="data" if cfg.moe else None, manual_dp=False)

    params_abs = abstract_params(cfg)
    pspecs = SH.param_specs(cfg, params_abs, mesh)
    ospecs = SH.opt_specs(cfg, None, pspecs, mesh)
    bspecs = SH.batch_specs(cfg, shape, mesh)
    p_sh = SH.to_shardings(pspecs, mesh)
    o_sh = SH.to_shardings(ospecs, mesh)
    b_sh = SH.to_shardings(bspecs, mesh)

    def step(params, opt_state, batch):
        with use_ctx(ctx):
            def loss_of(p):
                return M.loss_fn(cfg, p, batch, total_tokens=total_tokens)
            loss, grads = jax.value_and_grad(loss_of)(params)
            new_params, new_opt, metrics = adamw.apply_updates(
                opt_cfg, params, grads, opt_state)
            metrics["loss"] = loss
            return new_params, new_opt, metrics

    jitted = jax.jit(
        step,
        in_shardings=(p_sh, o_sh, b_sh),
        out_shardings=(p_sh, o_sh, None),
        donate_argnums=(0, 1) if donate else (),
    )
    return jitted, (p_sh, o_sh, b_sh), opt_cfg


# ---------------------------------------------------------------------------
# Secure train step (paper path: shard_map manual over DP axes)
# ---------------------------------------------------------------------------


def _dp_leaf_axes(cfg: ModelConfig, pspecs: Any,
                  dp_axes: tuple[str, ...]) -> Any:
    """Per-leaf tuple of dp axes the leaf is SHARDED over (EP leaves) —
    those must NOT be part of its gradient sync axes."""
    def one(spec):
        used = set()
        for e in spec:
            for a in (e if isinstance(e, tuple) else (e,)):
                if a in dp_axes:
                    used.add(a)
        return tuple(a for a in dp_axes if a not in used)
    return jax.tree.map(one, pspecs, is_leaf=lambda x: isinstance(x, P))


def _project_specs(specs: Any, axes: tuple[str, ...]) -> Any:
    """Keep only the given axis names in every PartitionSpec (for the
    partial-manual shard_map whose in/out_specs may reference only the
    manual axes)."""
    aset = set(axes)

    def one(spec):
        def keep(e):
            if e is None:
                return None
            if isinstance(e, tuple):
                t = tuple(a for a in e if a in aset)
                return t if t else None
            return e if e in aset else None
        return P(*(keep(e) for e in spec))

    return jax.tree.map(one, specs, is_leaf=lambda x: isinstance(x, P))


def build_secure_train_step(cfg: ModelConfig, mesh: jax.sharding.Mesh,
                            agg: AggConfig,
                            opt_cfg: Optional[adamw.OptConfig] = None,
                            shape: Optional[ShapeConfig] = None,
                            donate: bool = True):
    """The paper's aggregation as the gradient-sync layer.

    Requires cfg.dp_mode == "replicated" (params DP-replicated; EP expert
    leaves stay sharded over "data" and sync over the remaining dp axes).
    """
    opt_cfg = opt_cfg or adamw.OptConfig(state_dtype=cfg.opt_state_dtype)
    shape = shape or SHAPES["train_4k"]
    total_tokens = shape.global_batch * shape.seq_len
    dp_axes = dp_axes_of(mesh)
    ctx = DistCtx(mesh=mesh, dp_axes=dp_axes, tp_axis="model",
                  ep_axis="data" if cfg.moe else None, manual_dp=True,
                  manual_axes=dp_axes)

    params_abs = abstract_params(cfg)
    pspecs = SH.param_specs(cfg, params_abs, mesh, fsdp=None)
    ospecs = {"m": pspecs, "v": pspecs, "step": P()}
    bspecs = SH.batch_specs(cfg, shape, mesh)
    sync_axes = _dp_leaf_axes(cfg, pspecs, dp_axes)

    def dp_body(params, opt_state, batch):
        with use_ctx(ctx):
            def loss_of(p):
                return M.loss_fn(cfg, p, batch, total_tokens=total_tokens)
            loss, grads = jax.value_and_grad(loss_of)(params)

            # --- the paper's protocol, leaf-grouped by sync axes ---
            groups: dict[tuple, list] = {}
            flat, treedef = jax.tree.flatten(grads)
            axes_flat = jax.tree.leaves(
                sync_axes, is_leaf=lambda x: isinstance(x, tuple))
            for i, (g, ax) in enumerate(zip(flat, axes_flat)):
                groups.setdefault(ax, []).append(i)
            out = list(flat)
            for ax, idxs in groups.items():
                if not ax:  # fully consumed by EP: already correct locally
                    continue
                n_ax = 1
                for a in ax:
                    n_ax *= mesh.shape[a]
                sub = {str(i): flat[i] for i in idxs}
                # per-sync-axis committee: derive() reclamps the cluster
                # size / vote redundancy to whatever the axis supports
                summed = tree_allreduce(sub, agg.derive(n_nodes=n_ax), ax)
                for i in idxs:
                    out[i] = summed[str(i)]
            grads = jax.tree.unflatten(treedef, out)
            # per-rank loss is local_CE / total_global_tokens: global mean
            # loss is the SUM over ranks (matches the gradient convention)
            loss = jax.lax.psum(loss, dp_axes)

            # grad norm: EP-sharded leaves contribute across their axes
            sq = jnp.zeros((), jnp.float32)
            for g, ax in zip(out, axes_flat):
                s = jnp.sum(jnp.square(g.astype(jnp.float32)))
                missing = tuple(a for a in dp_axes if a not in ax)
                if missing:
                    s = jax.lax.psum(s, missing)
                sq = sq + s
            gnorm = jnp.sqrt(sq)

            new_params, new_opt, metrics = adamw.apply_updates(
                opt_cfg, params, grads, opt_state, grad_norm=gnorm)
            metrics["loss"] = loss
            return new_params, new_opt, metrics

    in_specs = (_project_specs(pspecs, dp_axes),
                _project_specs(ospecs, dp_axes),
                _project_specs(bspecs, dp_axes))
    out_specs = (_project_specs(pspecs, dp_axes),
                 _project_specs(ospecs, dp_axes),
                 {"loss": P(), "grad_norm": P(), "lr": P()})
    smapped = jax.shard_map(
        dp_body, mesh=mesh,
        in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
        axis_names=frozenset(dp_axes),
    )
    jitted = jax.jit(smapped, donate_argnums=(0, 1) if donate else ())
    p_sh = SH.to_shardings(pspecs, mesh)
    o_sh = SH.to_shardings(ospecs, mesh)
    b_sh = SH.to_shardings(bspecs, mesh)
    return jitted, (p_sh, o_sh, b_sh), opt_cfg


# ---------------------------------------------------------------------------
# Serving steps
# ---------------------------------------------------------------------------


def build_prefill_step(cfg: ModelConfig, mesh: jax.sharding.Mesh,
                       shape: ShapeConfig):
    ctx = DistCtx(mesh=mesh, dp_axes=dp_axes_of(mesh), tp_axis="model",
                  ep_axis="data" if cfg.moe else None, manual_dp=False)
    params_abs = abstract_params(cfg)
    pspecs = SH.param_specs(cfg, params_abs, mesh)
    bspecs = SH.batch_specs(cfg, shape, mesh)
    cache_abs = abstract_cache(cfg, shape)
    cspecs = SH.cache_specs(cfg, cache_abs, shape, mesh)

    if not cfg.decoder:
        # encoder-only: inference forward = logits
        def step(params, batch):
            with use_ctx(ctx):
                return M.forward(cfg, params, batch)
        jitted = jax.jit(step, in_shardings=(SH.to_shardings(pspecs, mesh),
                                             SH.to_shardings(bspecs, mesh)),
                         out_shardings=None)
        return jitted, (pspecs, bspecs, None)

    def step(params, batch):
        with use_ctx(ctx):
            return M.prefill(cfg, params, batch, max_seq=shape.seq_len)

    jitted = jax.jit(
        step,
        in_shardings=(SH.to_shardings(pspecs, mesh),
                      SH.to_shardings(bspecs, mesh)),
        out_shardings=(None, SH.to_shardings(cspecs, mesh)),
    )
    return jitted, (pspecs, bspecs, cspecs)


def build_decode_step(cfg: ModelConfig, mesh: jax.sharding.Mesh,
                      shape: ShapeConfig, donate: bool = True):
    """serve_step: one new token against a seq_len cache."""
    ctx = DistCtx(mesh=mesh, dp_axes=dp_axes_of(mesh), tp_axis="model",
                  ep_axis="data" if cfg.moe else None, manual_dp=False)
    params_abs = abstract_params(cfg)
    pspecs = SH.param_specs(cfg, params_abs, mesh)
    cache_abs = abstract_cache(cfg, shape)
    cspecs = SH.cache_specs(cfg, cache_abs, shape, mesh)
    dp = SH._trim(P(SH.DP), mesh)
    dp_size = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            dp_size *= mesh.shape[a]
    tok_spec = P(*dp, None) if shape.global_batch % dp_size == 0 and \
        shape.global_batch >= dp_size else P(None, None)

    def step(params, cache, tokens, t):
        with use_ctx(ctx):
            return M.decode_step(cfg, params, cache, tokens, t)

    c_sh = SH.to_shardings(cspecs, mesh)
    jitted = jax.jit(
        step,
        in_shardings=(SH.to_shardings(pspecs, mesh), c_sh,
                      NamedSharding(mesh, tok_spec), None),
        out_shardings=(None, c_sh),
        donate_argnums=(1,) if donate else (),
    )
    return jitted, (pspecs, cspecs, tok_spec)
