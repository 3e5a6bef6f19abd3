"""One protocol engine, pluggable transports.

``execute_chunks`` runs a compiled :class:`~repro.core.plan.AggPlan`
stage-by-stage — encrypt, intra-cluster aggregate, voted schedule
rounds, threshold decrypt — against a :class:`Transport` that supplies
the communication substrate.  The engine is the ONLY place the protocol
control flow lives; the transports only move bits:

  * :class:`SimTransport`    — single-device oracle with the node axis
    explicit, including the batched S-session path (hops are static
    rolls of the node axis).  This is what tests pin everything else
    against.
  * :class:`ManualTransport` — per-rank execution inside a ``shard_map``
    that is manual over the dp axes (hops are ``lax.ppermute``, the
    intra-cluster sum is a grouped ``lax.psum``).  The training step's
    gradient allreduce runs here.
  * :class:`MeshTransport`   — builds the ``shard_map`` itself over a
    real dp mesh and runs :class:`ManualTransport` inside: the
    distributed backend of the service's ``BatchedExecutor``.

Both wire *transports* of ``AggConfig.transport`` run on every
substrate: "full" ships r redundant payload copies per hop and
median-votes them; "digest" ships ONE payload plus r short digests
(the paper's O(n log^3 n) bandwidth mechanism) with the plan-compiled
backup stream (``HopRound.backup_perm``) as the static fallback for a
rejected payload.  The fault model is applied inside :meth:`Transport.hop`
per *wire view* — payload bytes, digest source, per-copy-stream
equivocation — so digest-specific adversaries (equivocation,
digest/payload mismatch, crash-at-hop-k) are modeled identically by the
oracle and the mesh.  Every hop also feeds ``Transport.bytes_sent``, a
trace-time bandwidth account the conformance tests pin against
``schedules.schedule_cost``, and every full-transport vote feeds
``Transport.vote_calls``, a trace-time tally of the votes by the layout
the kernel dispatch picks for their shape (``vote_layout``).

The value container is uniform: every chunk is a ``(rows, T)`` array
where ``rows = S`` sessions times the transport's local node slots (all
``n`` for the sim oracle, 1 per rank on a mesh).  All tensor compute
goes through the batched kernel dispatch ops with per-row metadata, so
every transport is bit-identical by construction — the acceptance tests
pin ``MeshTransport == SimTransport`` exactly, crash + Byzantine +
digest-adversary sessions included.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.byzantine import (digest_rows, digest_vote_combine,
                                  equivocate_digest, equivocate_payload,
                                  parse_mode, sent_value)
from repro.core.plan import (AggPlan, HopRound, SessionMeta, compile_plan,
                             hop_wire_words)
from repro.kernels import backend
from repro.kernels.secure_agg import (VOTE_LAYOUTS, mask_encrypt_batch_fn,
                                      unmask_decrypt_batch_fn,
                                      vote_combine_batch_fn, vote_layout)

_ENC_MODE = {"global": "mask", "pairwise": "pairwise", "none": "quantize"}

# The ``jax.named_scope`` of each protocol stage in ``execute_chunks``;
# voted round i wraps its hop, vote and select in ``agg.round_<i>``.
STAGE_SCOPES = ("agg.encrypt", "agg.cluster_sum", "agg.hop", "agg.vote",
                "agg.select", "agg.reveal_rows", "agg.unmask")


def flat_node_id(dp_axes: Sequence[str]) -> jax.Array:
    """Row-major flat rank over the dp mesh axes (inside shard_map)."""
    nid = jnp.zeros((), jnp.int32)
    for ax in dp_axes:
        nid = nid * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    return nid


def _active_bases(items, rnd_idx: int) -> set:
    """Base fault modes in effect at voted round ``rnd_idx``."""
    out = set()
    for mode, _ in items:
        base, frm = parse_mode(mode)
        if rnd_idx >= frm:
            out.add(base)
    return out


class Transport:
    """Communication substrate an :class:`AggPlan` executes against.

    ``S`` is the session count; values are ``(rows, T)`` uint32 arrays
    with ``rows = S * local_nodes``.  Subclasses define who the local
    rows belong to (``node_ids``) and how bits move between nodes."""

    S: int
    impl: str
    plan: AggPlan
    # bytes this transport instance has shipped across hops (trace-time
    # account over the plan's static pair lists; see ``_account``)
    bytes_sent: int = 0
    # full-transport vote calls this instance has traced, by the layout
    # the kernel dispatch picks ("rows" or "flat"; see ``vote``)
    vote_calls: dict
    _static_faults: Optional[list] = None

    def _fault_items(self, meta: SessionMeta) -> list:
        """Ordered fault sources shared by every transport (the
        bit-equality contract): the plan's static specs first, lowered
        ONCE per transport to constant (n,) numpy masks, then the
        per-session runtime masks ((S, n), possibly traced), in
        ``meta.fault_masks`` insertion order."""
        if self._static_faults is None:
            items = []
            n = self.plan.n_nodes
            for spec in self.plan.faults:
                m = np.zeros((n,), bool)
                m[list(spec.corrupt_ranks)] = True
                items.append((spec.mode, m))
            self._static_faults = items
        return self._static_faults + list(meta.fault_masks.items())

    def node_ids(self) -> jax.Array:
        """(rows,) uint32 protocol node id of every row."""
        raise NotImplementedError

    def expand(self, per_session: jax.Array) -> jax.Array:
        """(S,) per-session metadata -> (rows,) per-row metadata."""
        raise NotImplementedError

    def cluster_sum(self, q: jax.Array) -> jax.Array:
        """Intra-cluster modular sum, replicated to every member."""
        raise NotImplementedError

    # -- per-transport primitives the shared hop/fault logic runs on ----
    def _wire(self, acc: jax.Array) -> jax.Array:
        """Row array -> the transport's fault-model view (the sim oracle
        exposes the node axis; per-rank transports are identity)."""
        return acc

    def _sel(self, m) -> jax.Array:
        """(n,) static or (S, n) runtime fault mask -> a bool selector
        broadcastable over the wire view."""
        raise NotImplementedError

    def _digest(self, x: jax.Array) -> jax.Array:
        """Row-wise digests of a wire-view array."""
        raise NotImplementedError

    def _move(self, rnd: HopRound, stream: int, x: jax.Array) -> jax.Array:
        """Ship ``x`` (wire view) along copy stream ``stream``; returns
        the received rows."""
        raise NotImplementedError

    def _move_backup(self, rnd: HopRound, x: jax.Array) -> jax.Array:
        """Ship ``x`` along the compiled shift-1 backup stream."""
        raise NotImplementedError

    # -- shared fault application + hop assembly (bit-equality contract:
    # every transport runs EXACTLY this code against its primitives) ----
    def _sent(self, items, rnd_idx: int, honest: jax.Array, view: str,
              stream: Optional[int] = None) -> jax.Array:
        """Apply the fault model to the honest wire view for one wire
        (``stream`` set = full-transport per-stream equivocation)."""
        sent = honest
        for mode, m in items:
            base, frm = parse_mode(mode)
            if rnd_idx < frm:
                continue
            if base == "equivocate" and stream is not None:
                bad = equivocate_payload(honest, stream)
            else:
                bad = sent_value(base, view, honest)
            sent = jnp.where(self._sel(m), bad, sent)
        return sent

    def _equiv_sel(self, items, rnd_idx: int):
        """Union selector of active equivocating nodes, or None."""
        sel = None
        for mode, m in items:
            base, frm = parse_mode(mode)
            if base != "equivocate" or rnd_idx < frm:
                continue
            sel = self._sel(m) if sel is None else sel | self._sel(m)
        return sel

    def hop(self, rnd: HopRound, rnd_idx: int, meta: SessionMeta,
            acc: jax.Array):
        """Apply the fault model to the SENT wire views and move one
        round's redundant copies; returns opaque in-flight state consumed
        by :meth:`vote` — a list of r payload copies for the full
        transport, ``(payload, digest_copies, backup)`` for digest."""
        self._account(rnd, acc.shape[-1])
        cfg = self.plan.cfg
        r = self.plan.redundancy
        items = self._fault_items(meta)
        w = self._wire(acc)
        if cfg.transport == "full":
            if "equivocate" not in _active_bases(items, rnd_idx):
                sent = self._sent(items, rnd_idx, w, "payload")
                return [self._move(rnd, s, sent) for s in range(r)]
            return [self._move(rnd, s,
                               self._sent(items, rnd_idx, w, "payload",
                                          stream=s)) for s in range(r)]
        # digest transport: 1 full payload + r row-wise digests + the
        # compiled backup stream — each wire view faulted independently
        pay = self._sent(items, rnd_idx, w, "payload")
        dg = self._digest(self._sent(items, rnd_idx, w, "digest"))
        em = self._equiv_sel(items, rnd_idx)
        payload = self._move(rnd, 0, pay)
        dg_copies = [
            self._move(rnd, s, dg if em is None
                       else jnp.where(em, equivocate_digest(dg, s), dg))
            for s in range(r)]
        backup = (self._move_backup(rnd, pay)
                  if cfg.digest_backup else None)
        return payload, dg_copies, backup

    def vote(self, rnd: HopRound, inflight, base: jax.Array) -> jax.Array:
        """base + majority(inflight) — one fused pass per transport."""
        if self.plan.cfg.transport == "full":
            self.vote_calls[vote_layout(base.shape)] += 1
            return vote_combine_batch_fn(inflight, base, impl=self.impl)
        payload, dg_copies, backup = inflight
        return digest_vote_combine(payload, dg_copies, base, backup=backup,
                                   n_words=self.plan.cfg.digest_words)

    def select(self, rnd: HopRound, voted: jax.Array,
               acc: jax.Array) -> jax.Array:
        """Keep ``voted`` on nodes that participate this round."""
        raise NotImplementedError

    def reveal_rows(self, accs: list, meta: SessionMeta):
        """Narrow to one revealed row per session (the service path) ->
        (accs', row_seeds', row_offsets')."""
        raise NotImplementedError

    def _account(self, rnd: HopRound, T: int) -> None:
        """Bandwidth account for one hop of one chunk, per the plan's
        static pair lists: full ships r payload copies; digest ships one
        payload + r digests (+ the backup payload when compiled in).
        Accumulated at trace time — the conformance suite pins this
        against the analytic ``schedules.schedule_cost``, and the
        flight recorder's per-round events sum the same
        ``plan.hop_wire_words`` split, so trace == executed exactly."""
        w = hop_wire_words(self.plan.cfg, rnd, T)
        words = w["payload"] + w["digest"] + w["backup"]
        self.bytes_sent += 4 * words * self.S


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


def _vote_base(rnd: HopRound, acc: jax.Array, local: jax.Array) -> jax.Array:
    if rnd.combine == "add":
        return acc
    if rnd.combine == "local_plus":
        return local
    return jnp.zeros_like(acc)  # replace (tree broadcast-down)


def execute_chunks(plan: AggPlan, tp: Transport, chunks: list,
                   meta: SessionMeta, *, reveal_only: bool = False) -> list:
    """Run the full protocol over equal-size float32 chunks.

    ``chunks[k]`` is (rows, Tc) and covers pad-stream positions
    ``[k*Tc, (k+1)*Tc)`` past each session's counter offset, so chunked
    and monolithic payloads produce identical streams.  Per round, chunk
    k+1's hop is issued before chunk k's vote (double-buffered software
    pipeline — communication overlaps vote compute).

    Every stage runs under a ``jax.named_scope`` (see ``STAGE_SCOPES``),
    so each op of every transport's program carries its protocol stage
    in its ``op_name`` metadata, where a profiler trace can read it; the
    scopes change metadata only, never the compiled program."""
    mcfg = plan.mask_cfg()
    c = plan.cluster_size
    K = len(chunks)
    Tc = chunks[0].shape[-1]

    # --- Step 1: encrypt (fused clip+quantize+pad, incl. pairwise) ---
    with jax.named_scope("agg.encrypt"):
        node_ids = tp.node_ids()
        row_seeds = tp.expand(meta.seeds)
        row_offs = tp.expand(meta.offsets)

        def off(k):
            delta = plan.chunk_offset(k, Tc)
            return row_offs if not delta else row_offs + jnp.uint32(delta)

        qs = [mask_encrypt_batch_fn(ch, node_ids, row_seeds, mcfg.scale,
                                    mcfg.clip, mode=_ENC_MODE[mcfg.mode],
                                    offsets=off(k), cluster_size=c,
                                    impl=tp.impl)
              for k, ch in enumerate(chunks)]

    # --- Steps 1-2: intra-cluster modular sum (pairwise pads cancel) ---
    with jax.named_scope("agg.cluster_sum"):
        accs = [tp.cluster_sum(q) for q in qs]

    # --- Step 3: voted schedule; hops pipelined over chunks ---
    locals_ = list(accs)
    for ri, rnd in enumerate(plan.rounds):
        with jax.named_scope(f"agg.round_{ri}"):
            with jax.named_scope("agg.hop"):
                inflight = tp.hop(rnd, ri, meta, accs[0])
            new_accs = []
            for k in range(K):
                with jax.named_scope("agg.hop"):
                    nxt = (tp.hop(rnd, ri, meta, accs[k + 1])
                           if k + 1 < K else None)
                with jax.named_scope("agg.vote"):
                    voted = tp.vote(rnd, inflight,
                                    _vote_base(rnd, accs[k], locals_[k]))
                with jax.named_scope("agg.select"):
                    new_accs.append(tp.select(rnd, voted, accs[k]))
                inflight = nxt
            accs = new_accs

    # --- Step 4: threshold decryption (fused unmask+dequantize) ---
    if reveal_only:
        # ``off`` closes over row_offs, so it now yields per-revealed-row
        # offsets automatically
        with jax.named_scope("agg.reveal_rows"):
            accs, row_seeds, row_offs = tp.reveal_rows(accs, meta)
    umode = "mask" if mcfg.mode == "global" else "dequantize"
    with jax.named_scope("agg.unmask"):
        return [unmask_decrypt_batch_fn(a, mcfg.n_nodes, row_seeds,
                                        mcfg.scale, mode=umode,
                                        offsets=off(k), impl=tp.impl)
                for k, a in enumerate(accs)]


# ---------------------------------------------------------------------------
# Pytree payloads: pack leaves into fixed-size chunks (no giant concat)
# ---------------------------------------------------------------------------


def pack_chunks(leaves: list, chunk_elems: int) -> list:
    """Flatten leaves into equal chunks of ``chunk_elems`` float32 elements
    (last chunk zero-padded).  The max live buffer is one chunk — the
    whole gradient is never concatenated into a single payload."""
    pieces = [l.reshape(-1).astype(jnp.float32) for l in leaves
              if l.size > 0]
    total = sum(p.shape[0] for p in pieces)
    chunk_elems = min(chunk_elems, total)
    chunks, cur, cur_n = [], [], 0
    for p in pieces:
        pos = 0
        while pos < p.shape[0]:
            take = min(chunk_elems - cur_n, p.shape[0] - pos)
            cur.append(p[pos:pos + take])
            cur_n += take
            pos += take
            if cur_n == chunk_elems:
                chunks.append(cur[0] if len(cur) == 1
                              else jnp.concatenate(cur))
                cur, cur_n = [], 0
    if cur_n:
        cur.append(jnp.zeros((chunk_elems - cur_n,), jnp.float32))
        chunks.append(jnp.concatenate(cur))
    return chunks


def unpack_chunks(chunks: list, leaves: list) -> list:
    """Inverse of ``pack_chunks``: re-slice summed chunks into leaves."""
    size = chunks[0].shape[0]
    outs, off = [], 0
    for l in leaves:
        if l.size == 0:
            outs.append(jnp.zeros(l.shape, l.dtype))
            continue
        need, parts = l.size, []
        while need:
            k, j = divmod(off, size)
            take = min(need, size - j)
            parts.append(chunks[k][j:j + take])
            off += take
            need -= take
        flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        outs.append(flat.reshape(l.shape).astype(l.dtype))
    return outs


def sim_batch(plan: AggPlan, xs: jax.Array, meta: SessionMeta, *,
              reveal_only: bool = False, impl: Optional[str] = None):
    """Engine-native single-device oracle run: (S, n_nodes, T) per-
    session/per-node payloads -> ((S, n_nodes, T) per-node results — or
    (S, T) with ``reveal_only`` — , the SimTransport, whose
    ``bytes_sent`` carries the hop bandwidth account).  The one sim
    invocation recipe the conformance harness, the facade's sim backend
    and the benchmarks all share."""
    S, n, T = xs.shape
    assert n == plan.n_nodes, (n, plan.n_nodes)
    tp = SimTransport(plan, S=S, impl=impl)
    flat = jnp.asarray(xs).reshape(S * n, T).astype(jnp.float32)
    (out,) = execute_chunks(plan, tp, [flat], meta, reveal_only=reveal_only)
    return out.reshape((S, T) if reveal_only else (S, n, T)), tp


def build_batch_executable(plan: AggPlan, *, backend: str = "sim",
                           mesh=None, dp_axes: Sequence[str] = ("data",),
                           impl: Optional[str] = None,
                           donate: bool = False,
                           vote_calls: Optional[dict] = None):
    """The one jitted batch-reveal executable the service executor and
    the facade's batched one-shot share:

        fn(xs, seeds, offsets, fault_masks) -> (S, T) revealed rows

    with ``xs`` (S, n, T) per-session/per-node payloads.  ``backend``
    picks the substrate (sim oracle or ``MeshTransport`` over a real dp
    mesh with the distributed reveal).  ``donate=True`` donates the
    ``xs`` batch-slot buffer to the computation
    (``jax.jit(donate_argnums=(0,))``) so XLA reuses it for
    intermediates — callers must re-stage ``xs`` per call (the
    streaming executor's double-buffered slots exist exactly so packing
    the next slot never touches a donated buffer).  Donation is a
    no-op (with a UserWarning) on the CPU backend, so callers gate it
    on ``jax.default_backend()``.

    ``vote_calls``, where given, is a dict that every trace of the
    executable refills with its transport's ``vote_calls`` tally (of
    the one program; on a mesh, of each rank's)."""
    def traced(tally: dict) -> None:
        if vote_calls is not None:
            vote_calls.clear()
            vote_calls.update(tally)

    if backend == "mesh":
        mt = MeshTransport(mesh, dp_axes, impl=impl)

        def raw(xs, seeds, offsets, fault_masks):
            meta = SessionMeta(seeds=seeds, offsets=offsets,
                               fault_masks=fault_masks)
            out = mt.execute(plan, xs, meta, reveal_only=True)
            traced(mt.last_vote_calls)
            return out
    else:
        def raw(xs, seeds, offsets, fault_masks):
            meta = SessionMeta(seeds=seeds, offsets=offsets,
                               fault_masks=fault_masks)
            S, n, T = xs.shape
            tp = SimTransport(plan, S=S, impl=impl)
            flat = xs.reshape(S * n, T).astype(jnp.float32)
            (out,) = execute_chunks(plan, tp, [flat], meta,
                                    reveal_only=True)
            traced(tp.vote_calls)
            return out

    return jax.jit(raw, donate_argnums=(0,) if donate else ())


def manual_allreduce(x: jax.Array, cfg, dp_axes: Sequence[str]) -> jax.Array:
    """Exact-sum allreduce of ``x`` over ``dp_axes`` via the paper
    schedule; call inside a shard_map manual over ``dp_axes``.  The
    engine-native entry the training step and the facade's "manual"
    backend use."""
    dp_axes = tuple(dp_axes)
    plan = compile_plan(cfg)
    tp = ManualTransport(plan, dp_axes)
    flat = x.reshape(-1).astype(jnp.float32)
    (out,) = execute_chunks(plan, tp, [flat[None]],
                            SessionMeta.single(cfg.seed))
    return out[0].reshape(x.shape)


def tree_allreduce(tree, cfg, dp_axes: Sequence[str]):
    """Apply to a pytree.  Leaves are packed into fixed-size chunks
    (``cfg.chunk_elems``) and the voted hops are software-pipelined over
    the chunks, so hop communication overlaps vote compute and no
    gradient-sized payload is ever materialized."""
    dp_axes = tuple(dp_axes)
    leaves, treedef = jax.tree.flatten(tree)
    chunks = pack_chunks(leaves, cfg.chunk_elems)
    if not chunks:  # every leaf zero-size: nothing to aggregate
        return tree
    plan = compile_plan(cfg)
    tp = ManualTransport(plan, dp_axes)
    outs = execute_chunks(plan, tp, [ch[None] for ch in chunks],
                          SessionMeta.single(cfg.seed))
    return jax.tree.unflatten(treedef, unpack_chunks([o[0] for o in outs],
                                                     leaves))


# ---------------------------------------------------------------------------
# Simulation transport: node axis explicit, hops are static rolls
# ---------------------------------------------------------------------------


class SimTransport(Transport):
    """Single-device oracle over (S * n, T) rows, row = s * n + node."""

    def __init__(self, plan: AggPlan, S: int = 1,
                 impl: Optional[str] = None):
        self.plan = plan
        self.S = S
        self.bytes_sent = 0
        self.vote_calls = dict.fromkeys(VOTE_LAYOUTS, 0)
        self.impl = backend.resolve(
            impl if impl is not None else plan.cfg.kernel_impl)

    def _3d(self, x: jax.Array) -> jax.Array:
        return x.reshape(self.S, self.plan.n_nodes, x.shape[-1])

    def node_ids(self) -> jax.Array:
        return jnp.tile(jnp.arange(self.plan.n_nodes, dtype=jnp.uint32),
                        self.S)

    def expand(self, per_session: jax.Array) -> jax.Array:
        return jnp.repeat(jnp.asarray(per_session).astype(jnp.uint32),
                          self.plan.n_nodes)

    def cluster_sum(self, q: jax.Array) -> jax.Array:
        S, (g, c) = self.S, (self.plan.cfg.n_clusters, self.plan.cluster_size)
        T = q.shape[-1]
        acc = q.reshape(S, g, c, T).sum(axis=2, dtype=jnp.uint32)
        return jnp.repeat(acc[:, :, None], c, axis=2).reshape(q.shape)

    # wire view: (S, n, T) with the node axis explicit; hops are rolls
    def _wire(self, acc: jax.Array) -> jax.Array:
        return self._3d(acc)

    def _sel(self, m) -> jax.Array:
        m = jnp.asarray(m)
        if m.ndim == 1:
            m = m[None]
        return m[:, :, None]                    # (·, n, 1)

    def _digest(self, x3: jax.Array) -> jax.Array:
        S, n = self.S, self.plan.n_nodes
        dg = digest_rows(x3.reshape(S * n, -1), self.plan.cfg.digest_words)
        return dg.reshape(S, n, -1)

    def _shift(self, x3: jax.Array, rnd: HopRound, shift: int) -> jax.Array:
        """Node (cluster i, member m) receives the rows of node
        (``recv_from[i]``, (m + shift) % c).  Where the cluster map is a
        rotation by k (every ring round) this is a roll of the node axis
        by k*c - s (s = shift % c), with a second roll for the members
        whose m + s wraps past c: XLA:TPU compiles a gather of
        payload-sized rows in time that grows with the payload (over
        three minutes at n=256, T=2^20), and rolls in seconds.  Clusters
        that do not receive this round take their own rows, which
        :meth:`select` then discards."""
        S, n, T = x3.shape
        c = self.plan.cluster_size
        g = n // c
        src = [i if f is None else f for i, f in enumerate(rnd.recv_from)]
        k = -src[0] % g
        if any(f != (i - k) % g for i, f in enumerate(src)):
            y = x3.reshape(S, g, c, T)[:, np.asarray(src)]
            return jnp.roll(y, -shift, axis=2).reshape(S * n, T)
        s = shift % c
        y = jnp.roll(x3, k * c - s, axis=1)
        if s:
            wraps = np.arange(n) % c + s >= c
            y = jnp.where(wraps[None, :, None],
                          jnp.roll(x3, k * c - s + c, axis=1), y)
        return y.reshape(S * n, T)

    def _move(self, rnd: HopRound, stream: int, x: jax.Array) -> jax.Array:
        return self._shift(x, rnd, stream)

    def _move_backup(self, rnd: HopRound, x: jax.Array) -> jax.Array:
        return self._shift(x, rnd, 1)

    def select(self, rnd: HopRound, voted: jax.Array,
               acc: jax.Array) -> jax.Array:
        part = jnp.asarray(np.asarray(rnd.participates))[None, :, None]
        return jnp.where(part, self._3d(voted), self._3d(acc)
                         ).reshape(acc.shape)

    def reveal_rows(self, accs: list, meta: SessionMeta):
        # every cluster member holds the identical aggregate: reveal
        # member 0's copy per session
        return ([self._3d(a)[:, 0] for a in accs],
                jnp.asarray(meta.seeds).astype(jnp.uint32),
                jnp.asarray(meta.offsets).astype(jnp.uint32))


# ---------------------------------------------------------------------------
# Manual transport: per-rank inside an existing shard_map over dp axes
# ---------------------------------------------------------------------------


class ManualTransport(Transport):
    """Per-rank rows (S, T) inside a shard_map manual over ``dp_axes``:
    hops are ``ppermute``, the intra-cluster sum a grouped ``psum``.
    The traced program is O(1) in ``n_nodes`` (participation and fault
    masks are constant-array lookups, the unmask loop lives in-kernel)."""

    def __init__(self, plan: AggPlan, dp_axes: Sequence[str], S: int = 1,
                 impl: Optional[str] = None, shard_reveal: bool = False):
        self.plan = plan
        self.dp_axes = tuple(dp_axes)
        self.S = S
        self.bytes_sent = 0
        self.vote_calls = dict.fromkeys(VOTE_LAYOUTS, 0)
        self.impl = backend.resolve(
            impl if impl is not None else plan.cfg.kernel_impl)
        # distributed reveal: each rank decrypts only its 1/n slice of
        # the revealed sessions (see ``reveal_rows``) instead of all S
        self.shard_reveal = shard_reveal
        self._nid = flat_node_id(self.dp_axes)

    def node_ids(self) -> jax.Array:
        return jnp.broadcast_to(self._nid.astype(jnp.uint32), (self.S,))

    def expand(self, per_session: jax.Array) -> jax.Array:
        return jnp.asarray(per_session).astype(jnp.uint32)

    def cluster_sum(self, q: jax.Array) -> jax.Array:
        if self.plan.cluster_size == 1:
            return q
        groups = [list(g) for g in self.plan.groups]
        return jax.lax.psum(q, self.dp_axes, axis_index_groups=groups)

    # wire view: this rank's (S, T) rows; hops are ppermute
    def _sel(self, m) -> jax.Array:
        m = jnp.asarray(m)
        if m.ndim == 1:
            return jnp.broadcast_to(m[self._nid], (self.S,))[:, None]
        return m[:, self._nid][:, None]         # (S, 1) this-rank column

    def _digest(self, x: jax.Array) -> jax.Array:
        return digest_rows(x, self.plan.cfg.digest_words)

    def _move(self, rnd: HopRound, stream: int, x: jax.Array) -> jax.Array:
        return jax.lax.ppermute(x, self.dp_axes, list(rnd.perms[stream]))

    def _move_backup(self, rnd: HopRound, x: jax.Array) -> jax.Array:
        return jax.lax.ppermute(x, self.dp_axes, list(rnd.backup_perm))

    def select(self, rnd: HopRound, voted: jax.Array,
               acc: jax.Array) -> jax.Array:
        part = jnp.asarray(np.asarray(rnd.participates))[self._nid]
        return jnp.where(part, voted, acc)

    def reveal_rows(self, accs: list, meta: SessionMeta):
        seeds = self.expand(meta.seeds)
        offs = self.expand(meta.offsets)
        if not self.shard_reveal:
            # SPMD: every rank decrypts its own (identical) copy
            return accs, seeds, offs
        # Distributed reveal: after the voted rounds every rank holds the
        # identical (S, T) aggregate, so decrypting all S rows on every
        # rank is n-fold redundant work.  Unmask is elementwise per row,
        # so each rank decrypts only rows [nid*S_loc, (nid+1)*S_loc) with
        # the matching seed/offset slice — bit-identical per row — and
        # the shard_map concatenates the slices back ((n*S_loc, T); the
        # caller slices off the zero-pad tail past S).
        n = self.plan.n_nodes
        s_loc = -(-self.S // n)
        pad = n * s_loc - self.S
        start = self._nid.astype(jnp.int32) * s_loc

        def sl(a):
            if pad:
                a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            return jax.lax.dynamic_slice_in_dim(a, start, s_loc, axis=0)

        return [sl(a) for a in accs], sl(seeds), sl(offs)


# ---------------------------------------------------------------------------
# Mesh transport: shard_map over a real dp mesh, ManualTransport inside
# ---------------------------------------------------------------------------


class MeshTransport:
    """Distributed plan execution: one device per protocol node.

    ``execute`` shard_maps the engine over the mesh's dp axes — inside,
    each rank runs :class:`ManualTransport` on its (S, T) slice, so a
    sealed service batch runs the *same* engine code the oracle runs,
    over real collectives.  Bit-identical to ``SimTransport`` for the
    same plan (pinned by tests/test_engine.py and the conformance grid
    on a forced-8-device host).  ``last_bytes`` and ``last_vote_calls``
    hold the inner transport's bandwidth account and vote tally after a
    (re)traced ``execute``."""

    def __init__(self, mesh: jax.sharding.Mesh,
                 dp_axes: Sequence[str] = ("data",),
                 impl: Optional[str] = None, wrap_inner=None):
        self.mesh = mesh
        self.dp_axes = tuple(dp_axes)
        self.impl = impl
        # optional hook wrapping the per-rank ManualTransport inside the
        # shard_map body (e.g. runtime.chaos.ChaosTransport injecting a
        # raise-at-hop-k fault); must preserve the Transport protocol
        self.wrap_inner = wrap_inner
        self.last_bytes: Optional[int] = None
        self.last_vote_calls: Optional[dict] = None
        n = 1
        for ax in self.dp_axes:
            n *= mesh.shape[ax]
        self.n_devices = n

    def execute(self, plan: AggPlan, xs: jax.Array, meta: SessionMeta,
                *, reveal_only: bool = False) -> jax.Array:
        """xs: (S, n_nodes, T) per-session/per-node payloads ->
        (S, n_nodes, T) per-node results, or (S, T) with
        ``reveal_only`` (one revealed copy per session).

        ``reveal_only`` runs the *distributed* reveal: after the voted
        rounds every rank holds the identical (S, T) aggregate, so each
        rank threshold-decrypts only its 1/n slice of the sessions
        (``ManualTransport.shard_reveal``) and the out_specs concatenate
        the slices — n-fold less unmask work than replicated decrypt,
        bit-identical per row to the sim oracle."""
        S, n, T = xs.shape
        assert n == plan.n_nodes == self.n_devices, \
            (n, plan.n_nodes, self.n_devices)
        mask_keys = tuple(meta.fault_masks)
        inner: list = []

        def body(xl, seeds, offsets, masks):
            tp = ManualTransport(plan, self.dp_axes, S=S, impl=self.impl,
                                 shard_reveal=reveal_only)
            inner.append(tp)
            run_tp = tp if self.wrap_inner is None else self.wrap_inner(tp)
            m = SessionMeta(seeds=seeds, offsets=offsets,
                            fault_masks=dict(masks))
            (out,) = execute_chunks(plan, run_tp, [xl[:, 0, :]], m,
                                    reveal_only=reveal_only)
            return out if reveal_only else out[:, None, :]

        shard = P(None, self.dp_axes, None)
        rep = P(None)
        fn = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(shard, rep, rep, {k: P(None, None)
                                        for k in mask_keys}),
            # reveal_only: each rank returns its (S_loc, T) decrypted
            # slice; concatenating over the dp axes gives (n*S_loc, T)
            # with the real sessions in rows [:S]
            out_specs=P(self.dp_axes, None) if reveal_only else shard,
            check_vma=False)
        out = fn(xs.astype(jnp.float32), meta.seeds, meta.offsets,
                 dict(meta.fault_masks))
        if inner:
            self.last_bytes = inner[-1].bytes_sent
            self.last_vote_calls = dict(inner[-1].vote_calls)
        return out[:S] if reveal_only else out
