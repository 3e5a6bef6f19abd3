"""Dispatch layer for the secure-aggregation hot path.

Every protocol stage goes through one of these ops; ``impl`` selects the
execution engine (``pallas`` / ``pallas_interpret`` / ``jnp``), defaulting
to :func:`repro.kernels.backend.default_impl` — native Pallas on TPU, the
bit-identical jnp reference elsewhere.  The un-jitted ``*_fn`` variants
are for callers that are already inside jit/shard_map (the protocol); the
``*_op`` wrappers are jitted entry points for tests and benchmarks.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Union

import jax
from jax.sharding import PartitionSpec as P

from repro.kernels import backend
from repro.kernels.secure_agg import ref as R
from repro.kernels.secure_agg.secure_agg import (LANES, SUBLANES,
                                                 mask_encrypt,
                                                 mask_encrypt_batch,
                                                 unmask_decrypt,
                                                 unmask_decrypt_batch,
                                                 vote_combine,
                                                 vote_combine_rows)

# The two layouts ``vote_combine_batch_fn`` votes a (rows, T) batch in.
VOTE_LAYOUTS = ("rows", "flat")


def _interp(impl: str) -> bool:
    return impl != "pallas"


def _kernel(kernel, *args, **kwargs):
    """Call a Pallas kernel.  XLA cannot partition a Mosaic kernel, so
    inside a ``shard_map`` that is manual over only some mesh axes (the
    secure train step: manual over the dp axes, automatic over "model")
    the call is made manual over the other axes too, with its array
    operands replicated over them."""
    mesh = jax.sharding.get_abstract_mesh()
    rest = frozenset(mesh.axis_names) - frozenset(mesh.manual_axes)
    if not mesh.manual_axes or not rest:
        return kernel(*args, **kwargs)
    leaves, tree = jax.tree.flatten((args, kwargs))
    dyn = [i for i, leaf in enumerate(leaves) if isinstance(leaf, jax.Array)]

    def body(*arrays):
        filled = list(leaves)
        for i, a in zip(dyn, arrays):
            filled[i] = a
        a, kw = jax.tree.unflatten(tree, filled)
        return kernel(*a, **kw)

    return jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                         axis_names=rest, check_vma=False)(
                             *(leaves[i] for i in dyn))


def mask_encrypt_fn(x, node_id, seed, scale: float, clip: float,
                    mode: str = "mask", offset=0, cluster_size: int = 0,
                    impl: Optional[str] = None) -> jax.Array:
    """Fused clip+quantize(+pad) of a flat float payload -> uint32.
    Mode "pairwise" fuses the cluster-cancelling pad (in-kernel loop
    over ``cluster_size`` members) instead of the global pad."""
    impl = backend.resolve(impl)
    if impl == "jnp":
        return R.mask_encrypt_ref(x, node_id, seed, scale, clip, mode=mode,
                                  offset=offset, cluster_size=cluster_size)
    return _kernel(mask_encrypt, x, node_id, seed, scale, clip, mode=mode,
                   offset=offset, cluster_size=cluster_size,
                   interpret=_interp(impl))


def unmask_decrypt_fn(agg, n_nodes: int, seed, scale: float,
                      mode: str = "mask", offset=0,
                      impl: Optional[str] = None) -> jax.Array:
    """Fused n-way total-pad removal + dequantize -> float32."""
    impl = backend.resolve(impl)
    if impl == "jnp":
        return R.unmask_decrypt_ref(agg, n_nodes, seed, scale, mode=mode,
                                    offset=offset)
    return _kernel(unmask_decrypt, agg, n_nodes, seed, scale, mode=mode,
                   offset=offset, interpret=_interp(impl))


def vote_combine_fn(copies: Union[jax.Array, Sequence[jax.Array]], acc,
                    impl: Optional[str] = None) -> jax.Array:
    """acc + majority(copies); copies is a list of r flat uint32 arrays
    (or a stacked (r, T) array for back-compat)."""
    impl = backend.resolve(impl)
    if impl == "jnp":
        return R.vote_combine_ref(copies, acc)
    return _kernel(vote_combine, copies, acc, interpret=_interp(impl))


# ---------------------------------------------------------------------------
# Batched variants (leading session axis) — one dispatch covers S sessions
# with per-row (seed, node_id, offset).  The multi-session service's
# executor packs concurrent sessions into these instead of looping.
# ---------------------------------------------------------------------------


def mask_encrypt_batch_fn(x, node_ids, seeds, scale: float, clip: float,
                          mode: str = "mask", offsets=None,
                          cluster_size: int = 0,
                          impl: Optional[str] = None) -> jax.Array:
    """(B, T) float rows -> (B, T) uint32, row b keyed by
    (seeds[b], node_ids[b]) at counter offset ``offsets[b]``."""
    impl = backend.resolve(impl)
    if impl == "jnp":
        return R.mask_encrypt_batch_ref(x, node_ids, seeds, scale, clip,
                                        mode=mode, offsets=offsets,
                                        cluster_size=cluster_size)
    return _kernel(mask_encrypt_batch, x, node_ids, seeds, scale, clip,
                   mode=mode, offsets=offsets, cluster_size=cluster_size,
                   interpret=_interp(impl))


def unmask_decrypt_batch_fn(agg, n_nodes: int, seeds, scale: float,
                            mode: str = "mask", offsets=None,
                            impl: Optional[str] = None) -> jax.Array:
    """(B, T) uint32 aggregates -> (B, T) float32 per-row decryptions."""
    impl = backend.resolve(impl)
    if impl == "jnp":
        return R.unmask_decrypt_batch_ref(agg, n_nodes, seeds, scale,
                                          mode=mode, offsets=offsets)
    return _kernel(unmask_decrypt_batch, agg, n_nodes, seeds, scale,
                   mode=mode, offsets=offsets, interpret=_interp(impl))


def vote_layout(shape: tuple) -> str:
    """The layout ``vote_combine_batch_fn`` votes a ``shape`` batch in:
    ``"rows"`` where the (rows, T) operands hold at least one whole
    (8, 128) tile, else ``"flat"``."""
    rows, T = shape
    return "rows" if rows >= SUBLANES and T >= LANES else "flat"


def vote_combine_batch_fn(copies: Sequence[jax.Array], acc,
                          impl: Optional[str] = None) -> jax.Array:
    """acc + majority(copies) over (rows, T) rows, bit-identical to
    voting each row separately (the vote is elementwise).  The layout
    follows the shape (``vote_layout``): ``"rows"`` votes the operands
    as they are, with ``vote_combine_rows`` (the jnp engine votes them
    elementwise); ``"flat"`` flattens the batch into one call of the
    flat kernel."""
    copies = R.as_copy_list(copies)
    if vote_layout(acc.shape) == "flat":
        return vote_combine_fn([c.reshape(-1) for c in copies],
                               acc.reshape(-1),
                               impl=impl).reshape(acc.shape)
    impl = backend.resolve(impl)
    if impl == "jnp":
        return R.vote_combine_ref(copies, acc)
    return _kernel(vote_combine_rows, copies, acc, interpret=_interp(impl))


@functools.partial(jax.jit,
                   static_argnames=("scale", "clip", "mode", "cluster_size",
                                    "impl"))
def mask_encrypt_batch_op(x, node_ids, seeds, scale, clip, mode="mask",
                          offsets=None, cluster_size: int = 0,
                          impl: Optional[str] = None):
    return mask_encrypt_batch_fn(x, node_ids, seeds, scale, clip, mode=mode,
                                 offsets=offsets, cluster_size=cluster_size,
                                 impl=impl)


@functools.partial(jax.jit,
                   static_argnames=("n_nodes", "scale", "mode", "impl"))
def unmask_decrypt_batch_op(agg, n_nodes, seeds, scale, mode="mask",
                            offsets=None, impl: Optional[str] = None):
    return unmask_decrypt_batch_fn(agg, n_nodes, seeds, scale, mode=mode,
                                   offsets=offsets, impl=impl)


@functools.partial(jax.jit, static_argnames=("impl",))
def vote_combine_batch_op(copies, acc, impl: Optional[str] = None):
    return vote_combine_batch_fn(copies, acc, impl=impl)


@functools.partial(jax.jit,
                   static_argnames=("scale", "clip", "mode", "cluster_size",
                                    "impl"))
def mask_encrypt_op(x, node_id, seed, scale, clip, mode="mask", offset=0,
                    cluster_size: int = 0, impl: Optional[str] = None):
    return mask_encrypt_fn(x, node_id, seed, scale, clip, mode=mode,
                           offset=offset, cluster_size=cluster_size,
                           impl=impl)


@functools.partial(jax.jit,
                   static_argnames=("n_nodes", "scale", "mode", "impl"))
def unmask_decrypt_op(agg, n_nodes, seed, scale, mode="mask", offset=0,
                      impl: Optional[str] = None):
    return unmask_decrypt_fn(agg, n_nodes, seed, scale, mode=mode,
                             offset=offset, impl=impl)


@functools.partial(jax.jit, static_argnames=("impl",))
def vote_combine_op(copies, acc, impl: Optional[str] = None):
    return vote_combine_fn(copies, acc, impl=impl)
