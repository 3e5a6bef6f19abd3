"""Kernel/jnp equivalence for the secure-aggregation hot path, and the
program-size guarantees the dispatch-layer rewrite exists for: the traced
protocol has O(1) PRF calls (no unrolled per-node pad chain), no stacked
(r, T) vote buffer, and a constant number of collectives per round.

No hypothesis dependency — deterministic sweeps only."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.byzantine import ByzantineSpec, majority_vote, \
    majority_vote_list
from repro.core.masking import MaskConfig, reference_aggregate
from repro.api import SecureAggregator
from repro.core.plan import AggConfig
from repro.kernels import backend
from repro.kernels.secure_agg import (mask_encrypt_batch_op, mask_encrypt_op,
                                      mask_encrypt_ref,
                                      unmask_decrypt_batch_op,
                                      unmask_decrypt_op, unmask_decrypt_ref,
                                      vote_combine_batch_fn,
                                      vote_combine_batch_op, vote_combine_op,
                                      vote_combine_ref, vote_layout)

PALLAS = backend.pallas_impl()
RNG = np.random.default_rng(7)
ODD_SIZES = [1, 77, 128, 1000, 1024, 8193]


@pytest.mark.parametrize("T", ODD_SIZES)
@pytest.mark.parametrize("mode", ["mask", "quantize"])
def test_mask_encrypt_kernel_matches_jnp(T, mode):
    """Pallas kernel == jnp reference bit-for-bit, any length (internal
    tile padding), negative values included."""
    x = jnp.asarray((RNG.normal(size=(T,)) - 0.3).astype(np.float32))
    got = mask_encrypt_op(x, 5, 1234, 2.0 ** 20, 1.0, mode=mode, impl=PALLAS)
    ref = mask_encrypt_op(x, 5, 1234, 2.0 ** 20, 1.0, mode=mode, impl="jnp")
    oracle = mask_encrypt_ref(x, 5, 1234, 2.0 ** 20, 1.0, mode=mode)
    assert got.shape == (T,)
    assert bool(jnp.all(got == oracle)) and bool(jnp.all(ref == oracle))


@pytest.mark.parametrize("T", ODD_SIZES)
@pytest.mark.parametrize("mode", ["mask", "dequantize"])
def test_unmask_decrypt_kernel_matches_jnp(T, mode):
    agg = jnp.asarray(RNG.integers(0, 2 ** 32, size=(T,), dtype=np.uint32))
    got = unmask_decrypt_op(agg, 64, 1234, 2.0 ** 20, mode=mode, impl=PALLAS)
    ref = unmask_decrypt_op(agg, 64, 1234, 2.0 ** 20, mode=mode, impl="jnp")
    oracle = unmask_decrypt_ref(agg, 64, 1234, 2.0 ** 20, mode=mode)
    assert got.dtype == jnp.float32
    assert bool(jnp.all(got == oracle)) and bool(jnp.all(ref == oracle))


@pytest.mark.parametrize("T", [1, 129, 4096])
@pytest.mark.parametrize("r", [1, 3, 5])
def test_vote_combine_kernel_matches_jnp(T, r):
    copies = [jnp.asarray(RNG.integers(0, 2 ** 32, size=(T,),
                                       dtype=np.uint32)) for _ in range(r)]
    acc = jnp.asarray(RNG.integers(0, 2 ** 32, size=(T,), dtype=np.uint32))
    got = vote_combine_op(tuple(copies), acc, impl=PALLAS)
    ref = vote_combine_op(tuple(copies), acc, impl="jnp")
    oracle = vote_combine_ref(copies, acc)
    assert bool(jnp.all(got == oracle)) and bool(jnp.all(ref == oracle))
    # list-median path == stacked-median path
    stacked = jnp.stack(copies)
    assert bool(jnp.all(majority_vote_list(copies)
                        == majority_vote(stacked)))


@pytest.mark.parametrize("r", [3, 5])
def test_vote_median_is_unsigned_across_sign_bit(r):
    """The compare-and-select median network keeps the unsigned order
    of the min/max network it replaced: copies that straddle 2^31 (where
    a signed compare would flip the order) vote to the numpy unsigned
    median on the jnp engine and in the Pallas kernel alike."""
    edge = np.array([0, 1, 0x7FFFFFFE, 0x7FFFFFFF, 0x80000000, 0x80000001,
                     0xFFFFFFFE, 0xFFFFFFFF], np.uint32)
    T = 4096
    rng = np.random.default_rng(r)
    host = np.where(rng.random((r, T)) < 0.75,
                    rng.choice(edge, size=(r, T)),
                    rng.integers(0, 2 ** 32, size=(r, T), dtype=np.uint32))
    copies = [jnp.asarray(row) for row in host]
    acc = jnp.asarray(rng.integers(0, 2 ** 32, size=(T,), dtype=np.uint32))

    def minmax_median(rows):       # the network as it was written before
        rows = list(rows)
        for phase in range(r):
            for i in range(phase % 2, r - 1, 2):
                rows[i], rows[i + 1] = (jnp.minimum(rows[i], rows[i + 1]),
                                        jnp.maximum(rows[i], rows[i + 1]))
        return rows[r // 2]

    want = np.sort(host, axis=0)[r // 2]
    assert np.array_equal(np.asarray(minmax_median(copies)), want)
    assert np.array_equal(np.asarray(majority_vote_list(copies)), want)
    for impl in ("jnp", "pallas_interpret"):
        got = np.asarray(vote_combine_op(tuple(copies), acc, impl=impl))
        assert np.array_equal(got, np.asarray(acc) + want), impl


# --- pairwise masking fused in-kernel (fori_loop over cluster members) ----


@pytest.mark.parametrize("T", [1, 77, 1000])
@pytest.mark.parametrize("c", [2, 4])
def test_pairwise_mask_kernel_matches_oracle(T, c):
    """mode="pairwise" (in-kernel loop over cluster members) ==
    quantize + the unrolled ``masking.pairwise_pad`` oracle, bit for
    bit, on both the Pallas kernel and the jnp reference — and the pads
    still cancel within each cluster."""
    from repro.core.masking import pairwise_pad, quantize
    n = 4 * c
    mcfg = MaskConfig(n_nodes=n, clip=2.0, mode="pairwise", cluster_size=c,
                      seed=99)
    x = jnp.asarray((RNG.normal(size=(T,)) * 0.4).astype(np.float32))
    offset = 321
    for nid in (0, 1, c, n - 1):
        want = quantize(mcfg, x) + pairwise_pad(mcfg, nid, (T,),
                                                offset=offset)
        for impl in (PALLAS, "jnp"):
            got = mask_encrypt_op(x, nid, mcfg.seed, mcfg.scale, mcfg.clip,
                                  mode="pairwise", offset=offset,
                                  cluster_size=c, impl=impl)
            assert bool(jnp.all(got == want)), (impl, nid)
    # cluster members' pads cancel: the modular sum is the plain
    # quantized sum
    rows = [mask_encrypt_op(x, i, mcfg.seed, mcfg.scale, mcfg.clip,
                            mode="pairwise", offset=offset, cluster_size=c,
                            impl=PALLAS) for i in range(c)]
    total = rows[0]
    for rw in rows[1:]:
        total = total + rw
    plain = quantize(mcfg, x) * jnp.uint32(c)
    assert bool(jnp.all(total == plain))


@pytest.mark.parametrize("T", [1, 8 * 128, 8 * 128 + 1])
@pytest.mark.parametrize("c", [1, 2])
def test_pairwise_mask_kernel_edge_shapes(T, c):
    """Edge shapes PR 3's round-number sweeps missed: cluster size 1 (a
    degenerate pairwise group — the pad must vanish, leaving pure
    quantization), and lengths at/over the (8, 128) tile boundary.
    Pallas-interpret == jnp == the unrolled masking oracle, bit-exact."""
    from repro.core.masking import pairwise_pad, quantize
    mcfg = MaskConfig(n_nodes=4 * c, clip=2.0, mode="pairwise",
                      cluster_size=c, seed=55)
    x = jnp.asarray((RNG.normal(size=(T,)) * 0.4).astype(np.float32))
    for nid in (0, c - 1):
        want = quantize(mcfg, x) + pairwise_pad(mcfg, nid, (T,))
        for impl in (PALLAS, "jnp"):
            got = mask_encrypt_op(x, nid, mcfg.seed, mcfg.scale, mcfg.clip,
                                  mode="pairwise", cluster_size=c, impl=impl)
            assert bool(jnp.all(got == want)), (impl, nid)
        if c == 1:   # no pairs: the pad is identically zero
            assert bool(jnp.all(want == quantize(mcfg, x)))


@pytest.mark.parametrize("T", [1, 8 * 128 + 1])
def test_pairwise_mask_batch_edge_shapes(T):
    """S=1 batches (a single-session service flush) and tile-boundary
    lengths through the batched pairwise kernel: one (1, T) dispatch ==
    the single-row kernel, Pallas-interpret == jnp bit-exact."""
    c = 4
    x = jnp.asarray((RNG.normal(size=(1, T)) * 0.4).astype(np.float32))
    want = mask_encrypt_op(x[0], 2, 77, 2.0 ** 20, 1.0, mode="pairwise",
                           offset=13, cluster_size=c, impl="jnp")[None]
    for impl in (PALLAS, "jnp"):
        got = mask_encrypt_batch_op(
            x, jnp.asarray([2], jnp.uint32), jnp.asarray([77], jnp.uint32),
            2.0 ** 20, 1.0, mode="pairwise",
            offsets=jnp.asarray([13], jnp.uint32), cluster_size=c, impl=impl)
        assert got.shape == (1, T)
        assert bool(jnp.all(got == want)), impl


def test_pairwise_mask_batch_matches_per_row():
    B, T, c = 6, 129, 4
    x = jnp.asarray(RNG.normal(size=(B, T)).astype(np.float32) * 0.4)
    nids = jnp.asarray(RNG.integers(0, 16, B).astype(np.uint32))
    seeds = jnp.asarray(RNG.integers(0, 2 ** 32, B, dtype=np.uint32))
    offs = jnp.asarray(RNG.integers(0, 9999, B).astype(np.uint32))
    want = jnp.stack([
        mask_encrypt_op(x[b], nids[b], seeds[b], 2.0 ** 20, 1.0,
                        mode="pairwise", offset=offs[b], cluster_size=c,
                        impl="jnp") for b in range(B)])
    for impl in (PALLAS, "jnp"):
        got = mask_encrypt_batch_op(x, nids, seeds, 2.0 ** 20, 1.0,
                                    mode="pairwise", offsets=offs,
                                    cluster_size=c, impl=impl)
        assert bool(jnp.all(got == want)), impl


# --- batched (multi-session) variants: leading S axis, per-row meta -------


@pytest.mark.parametrize("T", [1, 77, 1000])
@pytest.mark.parametrize("mode", ["mask", "quantize"])
def test_mask_encrypt_batch_matches_per_row(T, mode):
    """One (B, T) batched dispatch == B single-session calls bit-for-bit,
    with per-row seed / node_id / counter offset — on both the native
    batched kernel and the vmap'd jnp reference."""
    B = 5
    x = jnp.asarray(RNG.normal(size=(B, T)).astype(np.float32) - 0.2)
    nids = jnp.asarray(RNG.integers(0, 64, B).astype(np.uint32))
    seeds = jnp.asarray(RNG.integers(0, 2 ** 32, B, dtype=np.uint32))
    offs = jnp.asarray(RNG.integers(0, 9999, B).astype(np.uint32))
    want = jnp.stack([
        mask_encrypt_op(x[b], nids[b], seeds[b], 2.0 ** 20, 1.0, mode=mode,
                        offset=offs[b], impl="jnp") for b in range(B)])
    for impl in (PALLAS, "jnp"):
        got = mask_encrypt_batch_op(x, nids, seeds, 2.0 ** 20, 1.0,
                                    mode=mode, offsets=offs, impl=impl)
        assert got.shape == (B, T)
        assert bool(jnp.all(got == want)), impl


@pytest.mark.parametrize("T", [1, 77, 1000])
@pytest.mark.parametrize("mode", ["mask", "dequantize"])
def test_unmask_decrypt_batch_matches_per_row(T, mode):
    B = 5
    agg = jnp.asarray(RNG.integers(0, 2 ** 32, (B, T), dtype=np.uint32))
    seeds = jnp.asarray(RNG.integers(0, 2 ** 32, B, dtype=np.uint32))
    offs = jnp.asarray(RNG.integers(0, 9999, B).astype(np.uint32))
    want = jnp.stack([
        unmask_decrypt_op(agg[b], 16, seeds[b], 2.0 ** 20, mode=mode,
                          offset=offs[b], impl="jnp") for b in range(B)])
    for impl in (PALLAS, "jnp"):
        got = unmask_decrypt_batch_op(agg, 16, seeds, 2.0 ** 20, mode=mode,
                                      offsets=offs, impl=impl)
        assert got.dtype == jnp.float32
        assert bool(jnp.all(got == want)), impl


def _pallas_out_shapes(fn, *args) -> list:
    """Result shapes of the Pallas calls in ``fn``'s jaxpr."""
    eqns = jax.make_jaxpr(fn)(*args).jaxpr.eqns
    return [e.outvars[0].aval.shape for e in eqns
            if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("B,T,r,layout", [
    (4, 129, 1, "flat"), (4, 129, 3, "flat"), (4, 1000, 5, "flat"),
    (256, 64, 3, "flat"), (12, 64, 1, "flat"),
    (8, 129, 3, "rows"), (8, 1000, 5, "rows"), (8, 65536, 1, "rows"),
    (12, 129, 1, "rows"), (12, 1000, 3, "rows"), (12, 65536, 5, "rows"),
    (256, 1000, 5, "rows"), (256, 65536, 3, "rows"),
    (8, 40000, 3, "rows"),                      # ragged T block
])
def test_vote_combine_batch_matches_per_row(B, T, r, layout):
    """The batched vote equals the per-row jnp vote, bit for bit, in
    either layout; the layout is the shape rule's (at least one whole
    (8, 128) tile votes the rows as they are, 12 rows leave a ragged row
    block), and the Pallas call it makes has the batch's own shape only
    in the ``rows`` layout."""
    copies = [jnp.asarray(RNG.integers(0, 2 ** 32, (B, T), dtype=np.uint32))
              for _ in range(r)]
    acc = jnp.asarray(RNG.integers(0, 2 ** 32, (B, T), dtype=np.uint32))
    want = jnp.stack([
        vote_combine_op(tuple(c[b] for c in copies), acc[b], impl="jnp")
        for b in range(B)])
    for impl in (PALLAS, "jnp"):
        got = vote_combine_batch_op(tuple(copies), acc, impl=impl)
        assert bool(jnp.all(got == want)), impl
    assert vote_layout((B, T)) == layout
    (shape,) = _pallas_out_shapes(
        lambda c, a: vote_combine_batch_fn(c, a, impl=PALLAS), copies, acc)
    assert (shape == (B, T)) == (layout == "rows"), shape


def test_chunked_stream_equals_monolithic():
    """offset makes chunked encrypt/decrypt reproduce the whole-payload
    pad stream exactly — what the pipelined tree transport relies on."""
    T, C = 4096, 1024
    x = jnp.asarray(RNG.normal(size=(T,)).astype(np.float32))
    whole = mask_encrypt_ref(x, 9, 77, 2.0 ** 18, 1.0)
    parts = [
        np.asarray(mask_encrypt_op(x[o:o + C], 9, 77, 2.0 ** 18, 1.0,
                                   impl=PALLAS, offset=o))
        for o in range(0, T, C)
    ]
    assert np.array_equal(np.concatenate(parts), np.asarray(whole))
    agg = jnp.asarray(RNG.integers(0, 2 ** 32, size=(T,), dtype=np.uint32))
    whole_u = unmask_decrypt_ref(agg, 16, 77, 2.0 ** 18)
    parts_u = [
        np.asarray(unmask_decrypt_op(agg[o:o + C], 16, 77, 2.0 ** 18,
                                     impl=PALLAS, offset=o))
        for o in range(0, T, C)
    ]
    assert np.array_equal(np.concatenate(parts_u), np.asarray(whole_u))


def test_tree_pack_unpack_handles_zero_size_leaves():
    """Chunk packing round-trips pytrees containing 0-element leaves."""
    from repro.core.engine import pack_chunks as _pack_chunks
    from repro.core.engine import unpack_chunks as _unpack_chunks
    leaves = [jnp.arange(3, dtype=jnp.float32),
              jnp.zeros((0,), jnp.float32),
              jnp.arange(5, dtype=jnp.float32) * 2,
              jnp.zeros((0, 4), jnp.float32)]
    chunks = _pack_chunks(leaves, 4)
    assert all(c.shape == (4,) for c in chunks)
    back = _unpack_chunks(chunks, leaves)
    for l, b in zip(leaves, back):
        assert b.shape == l.shape and b.dtype == l.dtype
        assert np.array_equal(np.asarray(b), np.asarray(l))
    assert _pack_chunks([jnp.zeros((0,), jnp.float32)], 4) == []


@pytest.mark.parametrize("masking", ["global", "pairwise", "none"])
@pytest.mark.parametrize("schedule", ["ring", "butterfly"])
def test_simulate_matches_reference_under_byzantine(masking, schedule):
    """The full protocol (vote r=3, one corrupt member per cluster) equals
    the single-device masked-sum oracle bit-for-bit."""
    n, c = 16, 4
    xs = jnp.asarray(RNG.normal(size=(n, 333)).astype(np.float32) * 0.2)
    corrupt = tuple(cl * c + (cl % c) for cl in range(n // c))
    cfg = AggConfig(n_nodes=n, cluster_size=c, redundancy=3,
                    schedule=schedule, masking=masking, clip=2.0,
                    byzantine=ByzantineSpec(corrupt_ranks=corrupt,
                                            mode="garbage"))
    out = np.asarray(SecureAggregator(cfg).allreduce(xs))
    want = np.asarray(reference_aggregate(cfg.mask_cfg(), xs))
    assert np.array_equal(out, np.tile(want, (n, 1)))


_JAXPR_PROBE = """
import json, numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.engine import manual_allreduce
from repro.core.plan import AggConfig

def count_eqns(jaxpr, counts):
    for eqn in jaxpr.eqns:
        counts["total"] = counts.get("total", 0) + 1
        name = eqn.primitive.name
        counts[name] = counts.get(name, 0) + 1
        if name == "concatenate" and eqn.outvars[0].aval.size > 1024:
            # payload-sized concat (tiny SMEM meta stacks are fine)
            counts["concat_payload"] = counts.get("concat_payload", 0) + 1
        for v in eqn.params.values():
            vals = v if isinstance(v, (list, tuple)) else [v]
            for sub in vals:
                if hasattr(sub, "eqns"):          # plain Jaxpr
                    count_eqns(sub, counts)
                elif hasattr(sub, "jaxpr"):       # ClosedJaxpr
                    count_eqns(sub.jaxpr, counts)
    return counts

def trace(n_nodes, cluster_size):
    cfg = AggConfig(n_nodes=n_nodes, cluster_size=cluster_size,
                    redundancy=3, schedule="tree")
    mesh = Mesh(np.array(jax.devices()[:n_nodes]), ("data",))
    fn = jax.shard_map(
        lambda x: manual_allreduce(x[0], cfg, ("data",))[None],
        mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
        check_vma=False)
    x = jax.ShapeDtypeStruct((n_nodes, 2048), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.jit(fn))(x)
    return count_eqns(jaxpr.jaxpr, {})

small = trace(16, 4)    # g=4 clusters -> 4 tree rounds
big = trace(64, 16)     # same 4 clusters, 4x the nodes
print(json.dumps({"small": small, "big": big}))
"""


def test_traced_program_size_independent_of_n_nodes():
    """make_jaxpr at n_nodes=64, r=3: collective count is r*rounds (+1
    intra-cluster psum), zero threefry PRF calls, no (r, T) stack — and
    the whole program is the same size as the n_nodes=16 trace."""
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=64"
    r = subprocess.run([sys.executable, "-c", _JAXPR_PROBE], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    counts = json.loads(r.stdout.strip().splitlines()[-1])
    small, big = counts["small"], counts["big"]
    rounds, redundancy = 4, 3  # tree over g=4 clusters
    for trace in (small, big):
        assert trace.get("ppermute", 0) == rounds * redundancy, trace
        assert trace.get("psum", 0) <= 2, trace  # 1 intra-cluster (+axis id)
        assert trace.get("threefry2x32", 0) == 0, trace
        # no payload-sized concat anywhere (scalar meta stacks are fine —
        # the kernel-interpreter lane emits a (3,)-elem stack per call)
        assert trace.get("concat_payload", 0) == 0, trace
    # O(1) PRF / O(1) program size: 4x the nodes, same traced program
    assert small["total"] == big["total"], (small["total"], big["total"])
