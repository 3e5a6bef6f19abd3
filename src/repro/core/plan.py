"""Config model + plan compiler for the secure-allreduce protocol core.

The paper's algorithm is one protocol, but the repo used to run it
through four diverging code paths (manual/shard_map, chunked pytree,
single-device oracle, batched oracle).  The plan/engine/transport split
makes the committee logic independent of the communication substrate
(the architectural point of Dani et al.'s quorum MPC line): everything
*static* about a run is compiled here, once, into an :class:`AggPlan`
that ``core/engine.py`` executes stage-by-stage against any
``Transport``.

This module also owns the *config model* the whole system is
parameterized by.  One run is described by four small frozen sections —

  * :class:`Topology` — who aggregates: ``n_nodes``, ``cluster_size``,
    the voted ``schedule``;
  * :class:`Security` — what the protocol defends: vote ``redundancy``,
    ``masking`` mode (+ quantization ``clip``/``guard_bits``), the pad
    ``seed``, the static ``byzantine`` fault model;
  * :class:`Wire`     — what the hops ship: ``transport`` (full r-copy
    vs digest), ``digest_words``/``digest_backup``, ``chunk_elems``;
  * :class:`Runtime`  — where it executes: kernel engine override and
    the transport ``backend`` (sim oracle / manual-in-shard_map / mesh)
    with its mesh + dp axes —

that compose into the flat :class:`AggConfig` the compiler consumes
(``AggConfig.compose`` / the ``.topology``/``.security``/``.wire``
section views).  Invalid knob combinations raise :class:`ConfigError`
with an actionable message (never a bare ``assert``, which would vanish
under ``python -O``); ``cfg.replace(...)`` re-validates and
``cfg.derive(n_nodes=...)`` reclamps the committee shape for per-axis /
per-session overrides.  ``compile_plan`` memoizes per config, so every
caller — facade, service executor, training step — shares one plan per
shape (see :func:`plan_cache_stats`).

A plan captures:

  * the voted schedule as explicit :class:`HopRound`\\ s — for every
    round, its cluster-level ``recv_from`` map, the r ``ppermute`` pair
    lists (mesh transports), and the per-node participation mask;
  * the intra-cluster ``psum`` groups;
  * the static fault model (``AggConfig.byzantine`` plus an optional
    ``SessionFaultPlan``, e.g. churn departures from an overlay epoch
    snapshot);
  * the per-chunk pad-stream offset rule (``chunk_offset``).

Everything *per-session* (pad-stream keys, counter offsets, runtime
fault masks) rides separately in :class:`SessionMeta`, so one compiled
plan serves any number of batched sessions and fault patterns without
retracing.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import schedules as SCH
from repro.core.byzantine import ByzantineSpec
from repro.core.masking import MaskConfig

_DEFAULT_SEED = 0x5EC0A66


# ---------------------------------------------------------------------------
# Config model: four composable sections -> one flat AggConfig
# ---------------------------------------------------------------------------


# ConfigError/_require live in core.schedules (the import root of the
# config stack — schedules cannot import this module back) and are
# re-exported here: `from repro.core.plan import ConfigError` stays the
# canonical spelling for the facade, the service, and the tests.
ConfigError = SCH.ConfigError
_require = SCH._require


@dataclasses.dataclass(frozen=True)
class Topology:
    """Who aggregates: the committee layout of one protocol run."""
    n_nodes: int                  # total DP ranks (g * c)
    cluster_size: int = 4         # c  (paper: O(log n))
    schedule: str = "ring"        # ring | tree | butterfly

    def __post_init__(self):
        _require(self.n_nodes >= 1,
                 f"n_nodes must be >= 1, got {self.n_nodes}")
        _require(self.cluster_size >= 1,
                 f"cluster_size must be >= 1, got {self.cluster_size}")
        _require(self.n_nodes % self.cluster_size == 0,
                 f"n_nodes={self.n_nodes} must be a multiple of "
                 f"cluster_size={self.cluster_size} (clusters are "
                 "contiguous rank groups); pick a dividing cluster_size "
                 "or use cfg.derive(n_nodes=...) to reclamp")
        _require(self.schedule in SCH.SCHEDULES,
                 f"unknown schedule {self.schedule!r}; pick one of "
                 f"{sorted(SCH.SCHEDULES)}")
        g = self.n_nodes // self.cluster_size
        _require(self.schedule not in ("tree", "butterfly") or g == 1
                 or g & (g - 1) == 0,
                 f"schedule={self.schedule!r} needs a power-of-two "
                 f"cluster count, got g={g} (= n_nodes/cluster_size); "
                 "use 'ring', or adjust the committee shape")

    @property
    def n_clusters(self) -> int:
        return self.n_nodes // self.cluster_size


@dataclasses.dataclass(frozen=True)
class Security:
    """What the protocol defends: voting, masking, the fault model."""
    redundancy: int = 3           # r odd: copies per vote
    masking: str = "global"       # global | pairwise | none
    clip: float = 1.0             # quantization range [-clip, clip]
    guard_bits: int = 2           # summation headroom beyond ceil(log2 n)
    seed: int = _DEFAULT_SEED     # pad-stream base key
    byzantine: ByzantineSpec = ByzantineSpec()

    def __post_init__(self):
        _require(self.redundancy >= 1,
                 f"redundancy must be >= 1, got {self.redundancy}")
        _require(self.redundancy % 2 == 1,
                 f"redundancy={self.redundancy} must be odd — the "
                 "element-wise majority vote needs an unambiguous median")
        _require(self.masking in ("global", "pairwise", "none"),
                 f"unknown masking {self.masking!r}; pick one of "
                 "['global', 'pairwise', 'none']")
        _require(self.clip > 0,
                 f"clip must be > 0 (quantization range), got {self.clip}")
        _require(self.guard_bits >= 0,
                 f"guard_bits must be >= 0, got {self.guard_bits}")


@dataclasses.dataclass(frozen=True)
class Wire:
    """What the voted hops ship over the wire."""
    transport: str = "full"       # full | digest
    digest_words: int = 16        # words per row digest (digest transport)
    # digest transport: the plan compiles a shift-1 full-payload backup
    # stream (``HopRound.backup_perm``) shipped eagerly as a second
    # static ppermute, so a digest-rejected payload is replaced in-band
    # (SPMD cannot fetch lazily).  On by default — it is what lets the
    # digest cells absorb payload corruption in the conformance grid.
    # Set False for the honest-path bandwidth (1 payload + r digests);
    # the unhappy path then costs one retransmission round, accounted
    # analytically in ``schedules.schedule_cost``.
    digest_backup: bool = True
    # chunked transport: pytree payloads are packed into equal chunks of
    # this many float32 elements; each hop is pipelined chunk-by-chunk.
    chunk_elems: int = 1 << 16

    def __post_init__(self):
        _require(self.transport in ("full", "digest"),
                 f"unknown transport {self.transport!r}; pick 'full' "
                 "(r payload copies per hop) or 'digest' (1 payload + "
                 "r digests)")
        _require(self.transport != "digest" or self.digest_words >= 1,
                 f"transport='digest' needs digest_words >= 1 (got "
                 f"{self.digest_words}) — zero-width digests cannot "
                 "vote; use transport='full' if you want no digests")
        _require(self.chunk_elems >= 1,
                 f"chunk_elems must be >= 1, got {self.chunk_elems}")


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Where the protocol executes (facade-level; never part of a plan).

    ``backend`` picks the engine transport the one-shot facade verbs
    run on: ``"sim"`` (single-device oracle), ``"manual"`` (call inside
    an existing shard_map manual over ``dp_axes``), ``"mesh"`` (the
    facade builds the shard_map over ``mesh``), or ``"auto"`` (mesh
    when one is given, sim otherwise)."""
    kernel_impl: Optional[str] = None   # pallas | pallas_interpret | jnp
    backend: str = "auto"               # auto | sim | manual | mesh
    mesh: Optional[object] = None       # jax.sharding.Mesh for "mesh"
    dp_axes: tuple = ("data",)

    def __post_init__(self):
        _require(self.backend in ("auto", "sim", "manual", "mesh"),
                 f"unknown backend {self.backend!r}; pick one of "
                 "['auto', 'sim', 'manual', 'mesh']")
        _require(self.kernel_impl in (None, "pallas", "pallas_interpret",
                                      "jnp"),
                 f"unknown kernel_impl {self.kernel_impl!r}; pick one of "
                 "[None, 'pallas', 'pallas_interpret', 'jnp']")
        _require(self.backend != "mesh" or self.mesh is not None,
                 "backend='mesh' needs a mesh: pass "
                 "Runtime(backend='mesh', mesh=compat.node_mesh(n))")
        object.__setattr__(self, "dp_axes", tuple(self.dp_axes))

    def resolve(self) -> str:
        """The effective backend ('auto' resolved)."""
        if self.backend != "auto":
            return self.backend
        return "mesh" if self.mesh is not None else "sim"


@dataclasses.dataclass(frozen=True)
class AggConfig:
    """Flat, hashable protocol config the plan compiler consumes.

    The four sections above are the *public* composition story
    (``AggConfig.compose(topology, security, wire, runtime)``; the
    ``.topology``/``.security``/``.wire`` properties give the section
    views back); the flat field list keeps the config a plain hashable
    dataclass — the plan-cache key.  Validation happens once, in the
    sections, plus the cross-section checks below; every path raises
    :class:`ConfigError`."""
    n_nodes: int
    cluster_size: int = 4
    redundancy: int = 3
    schedule: str = "ring"
    transport: str = "full"
    digest_words: int = 16
    digest_backup: bool = True
    masking: str = "global"
    clip: float = 1.0
    guard_bits: int = 2
    seed: int = _DEFAULT_SEED
    byzantine: ByzantineSpec = ByzantineSpec()
    chunk_elems: int = 1 << 16
    # kernel engine override (None = auto per backend; see kernels/backend)
    kernel_impl: Optional[str] = None

    def __post_init__(self):
        # section validation (each raises ConfigError with the fix)
        self.topology, self.security, self.wire  # noqa: B018
        _require(self.kernel_impl in (None, "pallas", "pallas_interpret",
                                      "jnp"),
                 f"unknown kernel_impl {self.kernel_impl!r}")
        # cross-section: a vote's r copies come from distinct members of
        # one cluster, so r cannot exceed the cluster size
        _require(self.redundancy <= self.cluster_size,
                 f"redundancy={self.redundancy} > cluster_size="
                 f"{self.cluster_size}: the r redundant copies are "
                 "distinct member shifts within one cluster; lower "
                 "redundancy or grow the cluster")

    # -- section views ------------------------------------------------------
    @property
    def topology(self) -> Topology:
        return Topology(n_nodes=self.n_nodes, cluster_size=self.cluster_size,
                        schedule=self.schedule)

    @property
    def security(self) -> Security:
        return Security(redundancy=self.redundancy, masking=self.masking,
                        clip=self.clip, guard_bits=self.guard_bits,
                        seed=self.seed, byzantine=self.byzantine)

    @property
    def wire(self) -> Wire:
        return Wire(transport=self.transport, digest_words=self.digest_words,
                    digest_backup=self.digest_backup,
                    chunk_elems=self.chunk_elems)

    @classmethod
    def compose(cls, topology: Topology, security: Security = Security(),
                wire: Wire = Wire(),
                runtime: Optional[Runtime] = None) -> "AggConfig":
        """The four config sections -> one flat plan-cacheable config.
        Only ``runtime.kernel_impl`` rides along — backend/mesh stay at
        the facade (they never change the compiled plan)."""
        return cls(
            n_nodes=topology.n_nodes, cluster_size=topology.cluster_size,
            schedule=topology.schedule,
            redundancy=security.redundancy, masking=security.masking,
            clip=security.clip, guard_bits=security.guard_bits,
            seed=security.seed, byzantine=security.byzantine,
            transport=wire.transport, digest_words=wire.digest_words,
            digest_backup=wire.digest_backup, chunk_elems=wire.chunk_elems,
            kernel_impl=runtime.kernel_impl if runtime is not None else None)

    # -- override story -----------------------------------------------------
    def replace(self, **kw) -> "AggConfig":
        """Validated ``dataclasses.replace`` accepting flat knobs and/or
        whole sections (``topology=`` / ``security=`` / ``wire=``).
        Sections expand first, explicit flat knobs win — so
        ``replace(security=Security(redundancy=1), clip=9.0)`` keeps
        ``clip=9.0``."""
        base = {}
        for name in ("topology", "security", "wire"):
            sec = kw.pop(name, None)
            if sec is not None:
                for f in dataclasses.fields(sec):
                    base[f.name] = getattr(sec, f.name)
        base.update(kw)
        return dataclasses.replace(self, **base)

    def derive(self, **kw) -> "AggConfig":
        """Per-axis / per-session override that *reclamps* the committee
        shape: shrinking ``n_nodes`` pulls ``cluster_size`` down to the
        largest divisor and ``redundancy`` down to the largest odd value
        that fits (unless explicitly overridden), and drops static
        byzantine ranks that fall out of range — the training step's
        per-sync-axis configs derive this way."""
        if "n_nodes" in kw:
            n = kw["n_nodes"]
            _require(n >= 1, f"n_nodes must be >= 1, got {n}")
            c = kw.get("cluster_size", min(self.cluster_size, n))
            if "cluster_size" not in kw:
                while n % c:
                    c -= 1
                kw["cluster_size"] = c
            if "redundancy" not in kw:
                r = min(self.redundancy, c)
                kw["redundancy"] = max(r - (1 - r % 2), 1)
            if "byzantine" not in kw and self.byzantine.corrupt_ranks:
                keep = tuple(x for x in self.byzantine.corrupt_ranks
                             if x < n)
                kw["byzantine"] = dataclasses.replace(
                    self.byzantine, corrupt_ranks=keep)
        return self.replace(**kw)

    # -- derived views ------------------------------------------------------
    @property
    def n_clusters(self) -> int:
        return self.n_nodes // self.cluster_size

    def mask_cfg(self) -> MaskConfig:
        return MaskConfig(n_nodes=self.n_nodes, clip=self.clip,
                          guard_bits=self.guard_bits, mode=self.masking,
                          cluster_size=self.cluster_size, seed=self.seed)


# ---------------------------------------------------------------------------
# Static round layout
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HopRound:
    """One voted schedule round, fully resolved to node granularity.

    ``perms[s]`` are the ``ppermute`` (src, dst) pairs of redundant copy
    stream s: receiver (cluster i, member m) takes the copy of
    (``recv_from[i]``, (m + s) % c); ``participates[i]`` says whether
    node i receives this round; ``backup_perm`` is the shift-1
    full-payload stream the digest transport's compiled fallback rides
    (a rejected payload is replaced by it in the same vote pass)."""
    combine: str                                      # add|local_plus|replace
    recv_from: tuple[Optional[int], ...]              # cluster-level round
    perms: tuple[tuple[tuple[int, int], ...], ...]    # (r, pairs)
    participates: tuple[bool, ...]                    # (n,)
    backup_perm: tuple[tuple[int, int], ...]          # digest fallback hops


def _hop_perm(n_clusters: int, cluster_size: int,
              recv_from: Sequence[Optional[int]],
              shift: int) -> list[tuple[int, int]]:
    """ppermute pairs for one redundant copy stream: receiver (cl, m)
    receives from (recv_from[cl], (m + shift) % c)."""
    c = cluster_size
    perm = []
    for cl in range(n_clusters):
        src_cl = recv_from[cl]
        if src_cl is None:
            continue
        for m in range(c):
            perm.append((src_cl * c + (m + shift) % c, cl * c + m))
    return perm


# ---------------------------------------------------------------------------
# Per-session runtime metadata
# ---------------------------------------------------------------------------


def fault_masks_of(faults: Sequence[Sequence[ByzantineSpec]],
                   n_nodes: int) -> dict[str, np.ndarray]:
    """Per-session fault specs -> {mode: (S, n) bool mask} (static numpy).

    ``faults[s]`` is a sequence of ByzantineSpec for session s; a rank may
    appear under at most one mode per session (disjointness keeps the
    sequential application order-independent)."""
    masks: dict[str, np.ndarray] = {}
    for s_idx, specs in enumerate(faults):
        for sp in specs:
            if not sp.corrupt_ranks:
                continue
            m = masks.setdefault(
                sp.mode, np.zeros((len(faults), n_nodes), bool))
            m[s_idx, list(sp.corrupt_ranks)] = True
    return masks


@dataclasses.dataclass(frozen=True)
class SessionMeta:
    """Everything per-session a plan execution needs at runtime: pad
    stream keys, counter offsets, and fault masks.  All fields may be
    traced arrays — the compiled program is independent of the values
    (the executor's compile-cache relies on that; only the *set* of
    fault modes present changes the program)."""
    seeds: jax.Array                       # (S,) uint32 pad-stream keys
    offsets: jax.Array                     # (S,) uint32 counter offsets
    fault_masks: dict[str, jax.Array] = dataclasses.field(
        default_factory=dict)              # mode -> (S, n) bool

    @property
    def S(self) -> int:
        return self.seeds.shape[0]

    @classmethod
    def build(cls, S: int, n_nodes: int, *, seed: int = 0, seeds=None,
              offsets=None,
              faults: Optional[Sequence[Sequence[ByzantineSpec]]] = None,
              fault_masks=None) -> "SessionMeta":
        """Normalize the historical entry-point kwargs: default seeds /
        offsets, and either static per-session ``faults`` (lowered to
        masks here) or already-traced ``fault_masks``."""
        if seeds is None:
            seeds = jnp.full((S,), seed, jnp.uint32)
        seeds = jnp.asarray(seeds).astype(jnp.uint32)
        if offsets is None:
            offsets = jnp.zeros((S,), jnp.uint32)
        offsets = jnp.asarray(offsets).astype(jnp.uint32)
        if fault_masks is not None:
            assert faults is None, "pass faults or fault_masks, not both"
            masks = dict(fault_masks)
        elif faults is not None:
            assert len(faults) == S, (len(faults), S)
            masks = fault_masks_of(faults, n_nodes)
        else:
            masks = {}
        return cls(seeds=seeds, offsets=offsets, fault_masks=masks)

    @classmethod
    def single(cls, seed, offset=0) -> "SessionMeta":
        return cls(seeds=jnp.asarray([seed]).astype(jnp.uint32),
                   offsets=jnp.asarray([offset]).astype(jnp.uint32))


# ---------------------------------------------------------------------------
# The compiled plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AggPlan:
    """Compiled, transport-independent form of one protocol run."""
    cfg: AggConfig
    groups: tuple[tuple[int, ...], ...]       # intra-cluster psum groups
    rounds: tuple[HopRound, ...]
    faults: tuple[ByzantineSpec, ...]         # static per-run fault model

    @property
    def n_nodes(self) -> int:
        return self.cfg.n_nodes

    @property
    def cluster_size(self) -> int:
        return self.cfg.cluster_size

    @property
    def redundancy(self) -> int:
        return self.cfg.redundancy

    def mask_cfg(self):
        return self.cfg.mask_cfg()

    def chunk_offset(self, chunk_idx: int, chunk_elems: int) -> int:
        """Pad-stream counter offset of chunk k relative to the session
        offset — chunk k covers flat positions [k*size, (k+1)*size), so
        chunked streams reproduce the monolithic stream exactly."""
        return chunk_idx * chunk_elems

    def wire_bytes(self, T: int, S: int = 1, chunks: int = 1) -> int:
        """Bytes this plan moves for ``S`` sessions of ``T`` float32
        elements shipped as ``chunks`` equal hops — the same per-hop
        account ``Transport._account`` accumulates at trace time (the
        conformance suite pins both against ``schedules.schedule_cost``).
        Note the digest transport ships one digest set *per chunk*."""
        words = 0
        for rnd in self.rounds:
            w = hop_wire_words(self.cfg, rnd, T)
            words += w["payload"] + w["backup"] + w["digest"] * chunks
        return 4 * words * S


def hop_wire_words(cfg: AggConfig, rnd: HopRound, T: int) -> dict:
    """Uint32 words ONE voted hop of ONE chunk of ``T`` elements moves
    for one session, split by wire view: ``{"payload", "digest",
    "backup"}``.

    This is the single definition of the protocol's byte account —
    ``AggPlan.wire_bytes``, the engine's trace-time
    ``Transport._account``, and the flight recorder's per-round events
    all sum exactly these words, so "summed trace events == executed
    ``bytes_sent`` == analytic ``schedule_cost``" holds by construction
    rather than by three parallel formulas agreeing."""
    if cfg.transport == "full":
        return {"payload": sum(len(p) for p in rnd.perms) * T,
                "digest": 0, "backup": 0}
    return {"payload": len(rnd.perms[0]) * T,
            "digest": sum(len(p) for p in rnd.perms) * cfg.digest_words,
            "backup": len(rnd.backup_perm) * T if cfg.digest_backup else 0}


# ---------------------------------------------------------------------------
# Multi-round secure functions (repro.funcs): the static round schedule
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FuncPlan:
    """Compiled form of one *secure function* — a non-additive
    aggregation (histogram / quantile / top-k) expressed as a static
    sequence of engine allreduces over derived {0, 1} payloads.

    Everything dynamic about a function run (the bisection interval,
    the revealed counts) lives in ``repro.funcs.FuncRun``; everything
    *static* is pinned here at compile time, exactly like
    :class:`AggPlan` pins the hop layout:

      * ``round_elems[i]`` — the payload length T of engine allreduce
        ``i``, in execution order.  Every quantile-bisection round ships
        the same 1-element threshold count, so one compiled executable
        serves all rounds and nothing retraces;
      * ``bisect_rounds``  — the static bisection depth
        ``ceil(log2(steps))`` derived from the value-domain width: the
        round count is a function of the DOMAIN, never of the data.

    The wire cost of a function run is therefore exact before it
    executes: :meth:`wire_bytes` sums the additive engine's own
    ``AggPlan.wire_bytes`` account over ``round_elems`` — the same
    per-hop ``hop_wire_words`` arithmetic every transport books at
    trace time, so multi-round ``cost()`` == executed bytes by
    construction.

    Count payloads are {0, 1} indicators whose aggregates are node
    counts <= n_nodes; the fixed-point headroom rule
    (``masking.MaskConfig.frac_bits``) makes their sums exact as long
    as ``clip >= 1.0`` — validated here so a mis-clipped config fails
    at compile time, not with a silently wrong histogram."""
    cfg: AggConfig
    fn: str                     # histogram | quantile | topk
    bins: int = 0               # histogram width (payload elems)
    lo: float = 0.0             # value range [lo, hi]
    hi: float = 1.0
    steps: int = 0              # value-domain width (bisection grid)
    q: float = 0.5              # quantile (0 -> minimum, 1 -> maximum)
    k: int = 0                  # top-k
    bisect_rounds: int = 0      # static: ceil(log2(steps))
    round_elems: tuple[int, ...] = ()   # payload T per engine allreduce

    @property
    def n_allreduces(self) -> int:
        return len(self.round_elems)

    def wire_bytes(self, S: int = 1) -> int:
        """Exact wire bytes of one full function run (``S`` concurrent
        runs): the additive plan's account summed over the static round
        schedule."""
        plan = compile_plan(self.cfg)
        return sum(plan.wire_bytes(T, S=S) for T in self.round_elems)


FUNC_NAMES = ("histogram", "quantile", "topk")


def _bisect_rounds(steps: int) -> int:
    """Static bisection depth of a ``steps``-wide value domain: the
    number of halvings that pin the search interval to one value."""
    rounds = 0
    while (1 << rounds) < steps:
        rounds += 1
    return rounds


_FUNC_PLAN_CACHE: dict = {}


def compile_func_plan(cfg: AggConfig, fn: str, *, bins: int = 0,
                      lo: float = 0.0, hi: float = 1.0, steps: int = 0,
                      q: float = 0.5, k: int = 0) -> FuncPlan:
    """Validate + compile one secure function onto ``cfg``'s additive
    engine (memoized module-wide like :func:`compile_plan`).

    ``fn='histogram'`` wants ``bins`` (+ the ``[lo, hi]`` range);
    ``fn='quantile'`` wants the value domain (``lo``/``hi``/``steps``)
    and ``q`` (0 = minimum, 1 = maximum, 0.5 = median);
    ``fn='topk'`` wants the domain and ``k`` — it compiles to the
    quantile bisection for the k-th largest threshold plus one final
    full-domain thresholded histogram."""
    _require(fn in FUNC_NAMES,
             f"unknown secure function {fn!r}; pick one of "
             f"{list(FUNC_NAMES)}")
    _require(cfg.clip >= 1.0,
             f"secure functions ship {{0, 1}} count payloads, which need "
             f"clip >= 1.0 to quantize exactly — got clip={cfg.clip}; "
             "use Security(clip=1.0) (or larger) for function configs")
    key = (cfg, fn, bins, lo, hi, steps, q, k)
    hit = _FUNC_PLAN_CACHE.get(key)
    if hit is not None:
        return hit
    if fn == "histogram":
        _require(bins >= 1, f"histogram needs bins >= 1, got {bins}")
        _require(hi > lo, f"histogram range needs hi > lo, got "
                 f"[{lo}, {hi}]")
        rounds, round_elems = 0, (bins,)
    else:
        _require(steps >= 1,
                 f"fn={fn!r} needs a value domain with steps >= 1, got "
                 f"{steps} (pass domain=ValueDomain(lo, hi, steps))")
        _require(steps == 1 or hi > lo,
                 f"value domain needs hi > lo for steps > 1, got "
                 f"[{lo}, {hi}] with steps={steps}")
        rounds = _bisect_rounds(steps)
        if fn == "quantile":
            _require(0.0 <= q <= 1.0,
                     f"quantile q must be in [0, 1], got {q}")
            round_elems = (1,) * rounds
        else:
            _require(1 <= k <= cfg.n_nodes,
                     f"topk needs 1 <= k <= n_nodes={cfg.n_nodes}, "
                     f"got {k}")
            # bisection to the k-th-largest threshold, then one
            # full-domain thresholded histogram (static shape: the
            # threshold gates the one-hot rows, never the payload width)
            round_elems = (1,) * rounds + (steps,)
    fp = FuncPlan(cfg=cfg, fn=fn, bins=bins, lo=lo, hi=hi, steps=steps,
                  q=q, k=k, bisect_rounds=rounds, round_elems=round_elems)
    if len(_FUNC_PLAN_CACHE) > 256:
        _FUNC_PLAN_CACHE.clear()
    _FUNC_PLAN_CACHE[key] = fp
    return fp


_PLAN_CACHE: dict[AggConfig, AggPlan] = {}
_PLAN_STATS = {"hits": 0, "misses": 0}


def plan_cache_stats() -> dict:
    """Hit/miss/size counters of the shared ``compile_plan`` memo —
    surfaced by ``SecureAggregator.stats()`` / ``AggregationService``."""
    return dict(_PLAN_STATS, size=len(_PLAN_CACHE))


def clear_plan_cache() -> None:
    _PLAN_CACHE.clear()
    _PLAN_STATS.update(hits=0, misses=0)


def compile_plan(cfg: AggConfig, *, epoch=None, fault=None) -> AggPlan:
    """AggConfig + overlay snapshot + fault plan -> executable AggPlan.

    ``epoch`` (optional): an object with ``n_nodes`` / ``cluster_size``
    (e.g. ``service.epochs.EpochSnapshot``) pinning the committee layout
    this plan aggregates over — validated against ``cfg``.  ``fault``
    (optional): a ``runtime.fault.SessionFaultPlan`` whose crash /
    Byzantine slots are folded into the plan's static fault model (the
    service instead passes *runtime* masks via :class:`SessionMeta`, so
    fault-pattern churn never retraces)."""
    cacheable = epoch is None and fault is None
    if cacheable:
        hit = _PLAN_CACHE.get(cfg)
        if hit is not None:
            _PLAN_STATS["hits"] += 1
            return hit
        _PLAN_STATS["misses"] += 1
    n, c, g, r = cfg.n_nodes, cfg.cluster_size, cfg.n_clusters, cfg.redundancy
    if epoch is not None:
        assert epoch.n_nodes == n, (epoch.n_nodes, n)
        assert epoch.cluster_size == c, (epoch.cluster_size, c)

    rounds = []
    for rnd in SCH.get_schedule(cfg.schedule, g):
        perms = tuple(tuple(_hop_perm(g, c, rnd.recv_from, s))
                      for s in range(r))
        participates = tuple(src_cl is not None
                             for src_cl in rnd.recv_from for _ in range(c))
        if not any(participates):
            continue
        rounds.append(HopRound(
            combine=rnd.combine, recv_from=tuple(rnd.recv_from), perms=perms,
            participates=participates,
            backup_perm=tuple(_hop_perm(g, c, rnd.recv_from, 1))))

    faults = []
    if cfg.byzantine.corrupt_ranks:
        faults.append(cfg.byzantine)
    if fault is not None:
        faults.extend(fault.specs())
    # a rank may appear under at most one static spec: disjointness keeps
    # the sequential spec application order-independent, so every
    # transport corrupts identically (the bit-equality contract)
    seen: set[int] = set()
    for sp in faults:
        overlap = seen & set(sp.corrupt_ranks)
        assert not overlap, f"rank(s) {sorted(overlap)} in multiple specs"
        seen |= set(sp.corrupt_ranks)

    groups = tuple(tuple(range(cl * c, (cl + 1) * c)) for cl in range(g))
    plan = AggPlan(cfg=cfg, groups=groups, rounds=tuple(rounds),
                   faults=tuple(faults))
    if cacheable:
        _PLAN_CACHE[cfg] = plan
    return plan
