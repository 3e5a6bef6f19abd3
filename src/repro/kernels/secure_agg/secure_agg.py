"""Secure-aggregation Pallas TPU kernels (the paper's per-step hot path,
DESIGN §2.2) — one fused VMEM pass per protocol stage:

  * ``mask_encrypt``   — clip + fixed-point quantize + PRF pad-add over
    Z_{2^32}.  The pad is a counter-based splitmix32 stream keyed by
    (seed, node_id) and indexed by the global element position, so the
    same stream can be produced chunk-by-chunk (``offset``) and the
    aggregate pad can be regenerated without per-node state.
  * ``unmask_decrypt`` — the "threshold decryption": subtract the n-way
    total pad (in-kernel ``fori_loop`` over node ids — O(1) program size,
    one VMEM pass regardless of n_nodes) fused with dequantize.
  * ``vote_combine``   — element-wise majority (odd-even sort network)
    over r redundant uint32 copies fused with the ring accumulate add.
    Copies arrive as r *separate* operands so no (r, T) buffer is ever
    materialized by the caller.

  * ``vote_combine_rows`` — the same vote over ``(rows, T)`` operands in
    their own layout: one ``(tb, tt)`` block per grid step, ragged edge
    blocks masked by Pallas, so a batch of rows is voted with no
    relayout into flat tiles and back.

Blocks are (8, 128)-aligned (the float32/uint32 VPU tile) so every kernel
compiles natively on TPU.  The flat kernels view a flat length as
``(rows, 128)`` tiles, with internal padding and a final slice for
arbitrary lengths; ``vote_combine_rows`` needs neither.
``interpret=None`` defers to ``repro.kernels.backend`` (native on TPU,
interpreter elsewhere).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend

# numpy literals (not traced arrays) so pallas kernels don't capture consts
GOLDEN = np.uint32(0x9E3779B9)
MIX1 = np.uint32(0x85EBCA6B)
MIX2 = np.uint32(0xC2B2AE35)

LANES = 128      # TPU lane count (last tile dim)
SUBLANES = 8     # float32/uint32 sublane count (second-to-last tile dim)

# keys for pairwise pads live in a disjoint space from per-node keys
# (shared with core/masking.py, which re-exports it)
PAIRWISE_KEY_BASE = np.uint32(1 << 20)


def splitmix32(x: jax.Array) -> jax.Array:
    """Counter-based PRF core (uint32 -> uint32)."""
    x = x + GOLDEN
    x = (x ^ (x >> 16)) * MIX1
    x = (x ^ (x >> 13)) * MIX2
    return x ^ (x >> 16)


def pad_stream(seed, key_id, ctr: jax.Array) -> jax.Array:
    """The masking one-time pad: PRF(seed, key_id) evaluated at counter
    positions ``ctr`` (all uint32).  Shared by the Pallas kernels and the
    jnp reference/masking layer so both paths are bit-identical.

    Two independent subkeys are derived per (seed, key_id) and the second
    is added *outside* the mixer: a single known plaintext element yields
    one equation in two unknowns, and differencing two known elements
    still leaves a nonlinear relation in ``k1`` — no algebraic inversion,
    only a 2^32 key search (the entropy bound of this 32-bit toy scale;
    see masking.py for the trust-model caveat)."""
    k1 = splitmix32(seed ^ key_id * MIX1)
    k2 = splitmix32(k1 ^ MIX2)
    return splitmix32(ctr ^ k1) + k2


# ---------------------------------------------------------------------------
# 2-D tiling helpers: flat (T,) -> (rows, 128) padded to whole tiles
# ---------------------------------------------------------------------------


def _tile_rows(T: int, block_rows: int) -> tuple[int, int]:
    """(rows_per_tile, padded_rows) for a flat length T."""
    rows = pl.cdiv(T, LANES)
    tr = min(block_rows, pl.cdiv(rows, SUBLANES) * SUBLANES)
    tr = max(SUBLANES, (tr // SUBLANES) * SUBLANES)
    rows_p = pl.cdiv(rows, tr) * tr
    return tr, rows_p


def _to_tiles(x: jax.Array, rows_p: int) -> jax.Array:
    T = x.shape[0]
    pad = rows_p * LANES - T
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    return x.reshape(rows_p, LANES)


def _ctr_tile(meta_off, ib, tr: int) -> jax.Array:
    """Global flat element index of every lane in tile ``ib`` (uint32)."""
    base = meta_off + jnp.uint32(ib * tr * LANES)
    row = jax.lax.broadcasted_iota(jnp.uint32, (tr, LANES), 0)
    col = jax.lax.broadcasted_iota(jnp.uint32, (tr, LANES), 1)
    return base + row * jnp.uint32(LANES) + col


def pairwise_total(seed, node_id, ctr: jax.Array,
                   cluster_size: int) -> jax.Array:
    """SecAgg-style pairwise-cancelling pad of ``node_id`` within its
    cluster, evaluated at counter positions ``ctr`` — an in-kernel
    ``fori_loop`` over the ``cluster_size`` members (O(1) program size in
    the cluster size), shared by the Pallas kernels and the jnp
    reference so both are bit-identical to ``core.masking.pairwise_pad``:

        mask_i = sum_{j in cluster, j>i} PRF(ij) - sum_{j<i} PRF(ij)

    so the pads cancel inside the intra-cluster modular sum."""
    c = jnp.uint32(cluster_size)
    node = jnp.asarray(node_id).astype(jnp.uint32)
    cluster = node // c
    member = node % c

    def body(other, acc):
        o = jnp.uint32(other)
        lo = jnp.minimum(member, o)
        hi = jnp.maximum(member, o)
        pair_id = cluster * c * c + lo * c + hi + PAIRWISE_KEY_BASE
        p = pad_stream(seed, pair_id, ctr)
        contrib = jnp.where(member < o, p, jnp.uint32(0) - p)
        contrib = jnp.where(member == o, jnp.uint32(0), contrib)
        return acc + contrib

    return jax.lax.fori_loop(0, cluster_size, body,
                             jnp.zeros(ctr.shape, jnp.uint32))


# ---------------------------------------------------------------------------
# mask_encrypt: clip + quantize + pad-add
# ---------------------------------------------------------------------------


def _mask_kernel(x_ref, meta_ref, o_ref, *, tr: int, scale: float,
                 clip: float, mode: str, cluster_size: int):
    ib = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    xq = jnp.clip(x, -jnp.float32(clip), jnp.float32(clip)) * jnp.float32(scale)
    q = jnp.round(xq).astype(jnp.int32).astype(jnp.uint32)
    if mode == "mask":
        ctr = _ctr_tile(meta_ref[2], ib, tr)
        q = q + pad_stream(meta_ref[0], meta_ref[1], ctr)
    elif mode == "pairwise":
        ctr = _ctr_tile(meta_ref[2], ib, tr)
        q = q + pairwise_total(meta_ref[0], meta_ref[1], ctr, cluster_size)
    o_ref[...] = q


def mask_encrypt(x: jax.Array, node_id, seed, scale: float, clip: float,
                 *, mode: str = "mask", offset=0, cluster_size: int = 0,
                 block_rows: int = 256,
                 interpret: Optional[bool] = None) -> jax.Array:
    """x: flat (T,) float -> quantized(+masked) uint32 (T,), any T.

    ``offset`` shifts the PRF counter so chunked calls reproduce the same
    stream as one monolithic call over the concatenated payload.  Mode
    "pairwise" adds the in-kernel pairwise-cancelling pad instead of the
    global pad (``cluster_size`` required).
    """
    (T,) = x.shape
    if mode == "pairwise":
        assert cluster_size >= 1, "pairwise mode needs cluster_size"
    tr, rows_p = _tile_rows(T, block_rows)
    x2 = _to_tiles(x.astype(jnp.float32), rows_p)
    meta = jnp.stack([jnp.asarray(seed).astype(jnp.uint32),
                      jnp.asarray(node_id).astype(jnp.uint32),
                      jnp.asarray(offset).astype(jnp.uint32)])
    out = pl.pallas_call(
        functools.partial(_mask_kernel, tr=tr, scale=scale, clip=clip,
                          mode=mode, cluster_size=cluster_size),
        grid=(rows_p // tr,),
        in_specs=[
            pl.BlockSpec((tr, LANES), lambda ib: (ib, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((tr, LANES), lambda ib: (ib, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, LANES), jnp.uint32),
        name="mask_encrypt",
        interpret=backend.interpret_default(interpret),
    )(x2, meta)
    return out.reshape(-1)[:T]


# ---------------------------------------------------------------------------
# unmask_decrypt: subtract n-way total pad (fori_loop) + dequantize
# ---------------------------------------------------------------------------


def _unmask_kernel(agg_ref, meta_ref, o_ref, *, tr: int, n_nodes: int,
                   scale: float, mode: str):
    ib = pl.program_id(0)
    agg = agg_ref[...]
    if mode == "mask":
        seed = meta_ref[0]
        ctr = _ctr_tile(meta_ref[1], ib, tr)

        def body(i, acc):
            return acc + pad_stream(seed, jnp.uint32(i), ctr)

        total_pad = jax.lax.fori_loop(
            0, n_nodes, body, jnp.zeros((tr, LANES), jnp.uint32))
        agg = agg - total_pad
    o_ref[...] = agg.astype(jnp.int32).astype(jnp.float32) / jnp.float32(scale)


def unmask_decrypt(agg: jax.Array, n_nodes: int, seed, scale: float,
                   *, mode: str = "mask", offset=0, block_rows: int = 256,
                   interpret: Optional[bool] = None) -> jax.Array:
    """agg: flat (T,) uint32 aggregate -> float32 (T,) decrypted sum.

    mode "mask" removes the n-way global pad then dequantizes; mode
    "dequantize" only dequantizes (pairwise pads cancel / no masking).
    """
    (T,) = agg.shape
    tr, rows_p = _tile_rows(T, block_rows)
    a2 = _to_tiles(agg, rows_p)
    meta = jnp.stack([jnp.asarray(seed).astype(jnp.uint32),
                      jnp.asarray(offset).astype(jnp.uint32)])
    out = pl.pallas_call(
        functools.partial(_unmask_kernel, tr=tr, n_nodes=int(n_nodes),
                          scale=scale, mode=mode),
        grid=(rows_p // tr,),
        in_specs=[
            pl.BlockSpec((tr, LANES), lambda ib: (ib, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((tr, LANES), lambda ib: (ib, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, LANES), jnp.float32),
        name="unmask_decrypt",
        interpret=backend.interpret_default(interpret),
    )(a2, meta)
    return out.reshape(-1)[:T]


# ---------------------------------------------------------------------------
# Batched variants: leading session axis with *per-row* (seed, node_id,
# offset) — the multi-session service packs S concurrent aggregation
# sessions into one (S, T) dispatch instead of S kernel launches.  The
# grid gains a session dimension; per-session metadata lives in SMEM and
# is indexed by the session program id, so one pallas_call covers every
# session natively (no vmap over the Mosaic kernel).
# ---------------------------------------------------------------------------


def _to_tiles_b(x: jax.Array, rows_p: int) -> jax.Array:
    """(B, T) -> (B, rows_p, LANES) with zero padding per row."""
    B, T = x.shape
    pad = rows_p * LANES - T
    if pad:
        x = jnp.concatenate([x, jnp.zeros((B, pad), x.dtype)], axis=1)
    return x.reshape(B, rows_p, LANES)


def _mask_batch_kernel(x_ref, meta_ref, o_ref, *, tr: int, scale: float,
                       clip: float, mode: str, cluster_size: int):
    ib = pl.program_id(0)   # session row
    it = pl.program_id(1)   # tile within the row
    x = x_ref[0].astype(jnp.float32)
    xq = jnp.clip(x, -jnp.float32(clip), jnp.float32(clip)) * jnp.float32(scale)
    q = jnp.round(xq).astype(jnp.int32).astype(jnp.uint32)
    if mode == "mask":
        ctr = _ctr_tile(meta_ref[2, ib], it, tr)
        q = q + pad_stream(meta_ref[0, ib], meta_ref[1, ib], ctr)
    elif mode == "pairwise":
        ctr = _ctr_tile(meta_ref[2, ib], it, tr)
        q = q + pairwise_total(meta_ref[0, ib], meta_ref[1, ib], ctr,
                               cluster_size)
    o_ref[0] = q


def mask_encrypt_batch(x: jax.Array, node_ids, seeds, scale: float,
                       clip: float, *, mode: str = "mask", offsets=None,
                       cluster_size: int = 0, block_rows: int = 256,
                       interpret: Optional[bool] = None) -> jax.Array:
    """x: (B, T) float -> quantized(+masked) uint32 (B, T); row b is padded
    with the stream keyed by (seeds[b], node_ids[b]) starting at counter
    ``offsets[b]`` — bit-identical to B separate ``mask_encrypt`` calls."""
    B, T = x.shape
    if mode == "pairwise":
        assert cluster_size >= 1, "pairwise mode needs cluster_size"
    tr, rows_p = _tile_rows(T, block_rows)
    x3 = _to_tiles_b(x.astype(jnp.float32), rows_p)
    if offsets is None:
        offsets = jnp.zeros((B,), jnp.uint32)
    meta = jnp.stack([
        jnp.broadcast_to(jnp.asarray(seeds).astype(jnp.uint32), (B,)),
        jnp.broadcast_to(jnp.asarray(node_ids).astype(jnp.uint32), (B,)),
        jnp.broadcast_to(jnp.asarray(offsets).astype(jnp.uint32), (B,)),
    ])
    out = pl.pallas_call(
        functools.partial(_mask_batch_kernel, tr=tr, scale=scale, clip=clip,
                          mode=mode, cluster_size=cluster_size),
        grid=(B, rows_p // tr),
        in_specs=[
            pl.BlockSpec((1, tr, LANES), lambda ib, it: (ib, it, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, tr, LANES), lambda ib, it: (ib, it, 0)),
        out_shape=jax.ShapeDtypeStruct((B, rows_p, LANES), jnp.uint32),
        name="mask_encrypt_batch",
        interpret=backend.interpret_default(interpret),
    )(x3, meta)
    return out.reshape(B, -1)[:, :T]


def _unmask_batch_kernel(agg_ref, meta_ref, o_ref, *, tr: int, n_nodes: int,
                         scale: float, mode: str):
    ib = pl.program_id(0)
    it = pl.program_id(1)
    agg = agg_ref[0]
    if mode == "mask":
        seed = meta_ref[0, ib]
        ctr = _ctr_tile(meta_ref[1, ib], it, tr)

        def body(i, acc):
            return acc + pad_stream(seed, jnp.uint32(i), ctr)

        total_pad = jax.lax.fori_loop(
            0, n_nodes, body, jnp.zeros((tr, LANES), jnp.uint32))
        agg = agg - total_pad
    o_ref[0] = agg.astype(jnp.int32).astype(jnp.float32) / jnp.float32(scale)


def unmask_decrypt_batch(agg: jax.Array, n_nodes: int, seeds, scale: float,
                         *, mode: str = "mask", offsets=None,
                         block_rows: int = 256,
                         interpret: Optional[bool] = None) -> jax.Array:
    """agg: (B, T) uint32 aggregates -> (B, T) float32; row b removes the
    n-way total pad of stream ``seeds[b]`` at counter ``offsets[b]`` —
    bit-identical to B separate ``unmask_decrypt`` calls."""
    B, T = agg.shape
    tr, rows_p = _tile_rows(T, block_rows)
    a3 = _to_tiles_b(agg, rows_p)
    if offsets is None:
        offsets = jnp.zeros((B,), jnp.uint32)
    meta = jnp.stack([
        jnp.broadcast_to(jnp.asarray(seeds).astype(jnp.uint32), (B,)),
        jnp.broadcast_to(jnp.asarray(offsets).astype(jnp.uint32), (B,)),
    ])
    out = pl.pallas_call(
        functools.partial(_unmask_batch_kernel, tr=tr, n_nodes=int(n_nodes),
                          scale=scale, mode=mode),
        grid=(B, rows_p // tr),
        in_specs=[
            pl.BlockSpec((1, tr, LANES), lambda ib, it: (ib, it, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, tr, LANES), lambda ib, it: (ib, it, 0)),
        out_shape=jax.ShapeDtypeStruct((B, rows_p, LANES), jnp.float32),
        name="unmask_decrypt_batch",
        interpret=backend.interpret_default(interpret),
    )(a3, meta)
    return out.reshape(B, -1)[:, :T]


# ---------------------------------------------------------------------------
# vote_combine: majority over r separate copies + accumulate add
# ---------------------------------------------------------------------------


def as_copy_list(copies: Union[jax.Array, Sequence[jax.Array]]
                 ) -> list[jax.Array]:
    """Normalize vote input: a stacked (r, T) array (back-compat) or a
    sequence of r flat arrays -> list of r rows.  The single definition
    both vote engines share, so their contracts can't drift."""
    if isinstance(copies, jax.Array):
        return [copies[i] for i in range(copies.shape[0])]
    return list(copies)


def median_network(rows: list[jax.Array]) -> jax.Array:
    """Odd-even transposition sort over a tiny list; returns the median.

    Each compare-exchange is one comparison and two selects, not
    ``jnp.minimum``/``jnp.maximum``: Mosaic has no lowering for unsigned
    vector min/max (``arith.minui``), while an unsigned compare and a
    select lower natively and give the same result."""
    rows = list(rows)
    r = len(rows)
    for phase in range(r):
        for i in range(phase % 2, r - 1, 2):
            a, b = rows[i], rows[i + 1]
            swap = b < a
            rows[i], rows[i + 1] = (jnp.where(swap, b, a),
                                    jnp.where(swap, a, b))
    return rows[r // 2]


def _vote_kernel(*refs, r: int):
    acc_ref, o_ref = refs[r], refs[r + 1]
    o_ref[...] = acc_ref[...] + median_network([refs[i][...]
                                                for i in range(r)])


def vote_combine(copies: Union[jax.Array, Sequence[jax.Array]],
                 acc: jax.Array, *, block_rows: int = 256,
                 interpret: Optional[bool] = None) -> jax.Array:
    """acc + elementwise-majority(copies) over Z_{2^32}.

    ``copies`` is a sequence of r flat (T,) uint32 arrays (r odd) — each
    copy is a separate kernel operand, so the caller never stacks an
    (r, T) buffer.  A stacked (r, T) array is also accepted for
    benchmarks/back-compat and is split into rows.
    """
    copies = as_copy_list(copies)
    r = len(copies)
    assert r % 2 == 1, "vote redundancy must be odd"
    (T,) = acc.shape
    tr, rows_p = _tile_rows(T, block_rows)
    spec = pl.BlockSpec((tr, LANES), lambda ib: (ib, 0))
    out = pl.pallas_call(
        functools.partial(_vote_kernel, r=r),
        grid=(rows_p // tr,),
        in_specs=[spec] * (r + 1),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows_p, LANES), jnp.uint32),
        name="vote_combine",
        interpret=backend.interpret_default(interpret),
    )(*[_to_tiles(c, rows_p) for c in copies], _to_tiles(acc, rows_p))
    return out.reshape(-1)[:T]


# ---------------------------------------------------------------------------
# vote_combine_rows: the same vote over (rows, T) operands as they are
# ---------------------------------------------------------------------------

# The r + 2 operand blocks (r copies, the accumulator and the result) are
# double-buffered in the 16 MiB of scoped VMEM: (256, 1024) uint32 blocks
# at r = 3 take 10 MiB, (256, 2048) ones 20 MiB and do not compile.
VOTE_VMEM_BUDGET = 12 << 20
VOTE_BLOCK_ROWS = 256
VOTE_BLOCK_ELEMS = 1 << 18          # 1 MiB of uint32 per operand block


def _vote_rows_block(rows: int, T: int, r: int) -> tuple[int, int]:
    """(tb, tt) block of the (rows, T) vote over r copies: tb a multiple
    of 8 (``rows >= 8``), tt a power of two of at least 128 or all of T,
    so that the r + 2 double-buffered operand blocks fit
    ``VOTE_VMEM_BUDGET``."""
    tb = min(VOTE_BLOCK_ROWS, rows // SUBLANES * SUBLANES)
    elems = min(VOTE_BLOCK_ELEMS, VOTE_VMEM_BUDGET // (2 * (r + 2) * 4))
    tt = max(LANES, 1 << ((elems // tb).bit_length() - 1))
    return tb, min(tt, T)


def vote_combine_rows(copies: Sequence[jax.Array], acc: jax.Array, *,
                      interpret: Optional[bool] = None) -> jax.Array:
    """acc + elementwise-majority(copies) over r separate (rows, T)
    uint32 operands (``rows >= 8``, r odd), voted in their own layout:
    one ``(tb, tt)`` block per grid step over ``(cdiv(rows, tb),
    cdiv(T, tt))``.  Pallas masks the ragged edge blocks, so there is no
    padding and no final slice: on the TPU the operands keep their
    (8, 128) tiling over (rows, T) and no relayout is made on the way in
    or out.  Bit-identical to ``vote_combine`` of the flattened
    operands."""
    r = len(copies)
    assert r % 2 == 1, "vote redundancy must be odd"
    rows, T = acc.shape
    tb, tt = _vote_rows_block(rows, T, r)
    spec = pl.BlockSpec((tb, tt), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_vote_kernel, r=r),
        grid=(pl.cdiv(rows, tb), pl.cdiv(T, tt)),
        in_specs=[spec] * (r + 1),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, T), jnp.uint32),
        name="vote_combine",
        interpret=backend.interpret_default(interpret),
    )(*copies, acc)
