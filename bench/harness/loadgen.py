"""The one traffic generator.  A traffic mix is a data file under
``traffic/`` (JSON); this module turns it, with the run's seed, into the
inputs of one run.  The system under test sees only what is made here.

Keys of a traffic file (all read here, none elsewhere):

``loop``
    ``"closed"``: ``clients`` independent populations, each of which seals
    its next session only when its previous one has revealed (an FL
    coordinator needs the aggregate before it broadcasts the next model).
    ``"open"``: sessions arrive on a schedule at ``rate_per_s``, whether
    or not earlier ones are done (independent telemetry clients).
``payload``
    ``{"kind": "vector", "elems": T, "pool": K}``: every slot contributes T
    float32 values drawn uniformly from [-clip, clip].  The bulk of a
    session is one of K seeded (n, T) matrices, made once in set-up with
    their float64 sums, in an order drawn from the seed; one slot of every
    session (slot ``i mod n`` of session i) contributes a row of its own,
    drawn from the seed and the session's index, so that no two sessions,
    warm-up ones included, share a sum.
    ``{"kind": "histogram", "bins": B}``: every slot contributes one
    scalar drawn uniformly from [0, 1); the session reveals its B-bin
    histogram.
``batching``
    The service's admission settings for this traffic: ``max_batch``
    rows; the age watermark is the service's default.
``arrival_seed`` (open loops)
    Seed of the one set of inter-arrival gaps, exponential at
    ``rate_per_s`` and scaled to fill the window exactly: every run seed
    gets the same gaps in another order, so the amount of work and its
    span never change with the seed.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    # any whole seed, however large, gives its own streams
    return np.random.default_rng([int(seed) & (2**64 - 1), *stream])


@dataclasses.dataclass
class Load:
    loop: str                           # "closed" | "open"
    kind: str                           # "vector" | "histogram"
    n_nodes: int
    clients: int = 0                    # closed loops
    due: Optional[np.ndarray] = None    # open loops: seconds from window start
    elems: int = 0                      # vector payloads
    pool: Optional[list] = None         # [(x (n, T) float32, sum float64)]
    order: Optional[np.ndarray] = None  # pool index of session i (cycled)
    seed: int = 0
    clip: float = 1.0
    rows: dict = dataclasses.field(default_factory=dict)  # i -> own row
    bins: int = 0                       # histogram payloads
    values: Optional[np.ndarray] = None  # (sessions, n) float64

    def vector(self, i: int) -> tuple:
        """(bulk (n, T), slot, own row) of session i: its contributions are
        the bulk matrix with row ``slot`` replaced by ``own row``.  Window
        sessions have i >= 0; warm-up sessions i < 0, from a stream of
        their own."""
        x, _ = self.pool[self.pool_index(i)]
        return x, i % self.n_nodes, self.own_row(i)

    def pool_index(self, i: int) -> int:
        return int(self.order[i % len(self.order)])

    def own_row(self, i: int) -> np.ndarray:
        if i not in self.rows:
            stream = (4, i) if i >= 0 else (5, -1 - i)
            row = _rng(self.seed, *stream).random(self.elems,
                                                  dtype=np.float32)
            row *= np.float32(2 * self.clip)
            row -= np.float32(self.clip)
            self.rows[i] = row
        return self.rows[i]

    def vector_sum(self, i: int) -> np.ndarray:
        """The float64 sum of session i's contributions."""
        x, total = self.pool[self.pool_index(i)]
        j = i % self.n_nodes
        return (total - x[j].astype(np.float64)
                + self.own_row(i).astype(np.float64))

    def scalars(self, i: int) -> list:
        return self.values[i].tolist()


def warm_scalars(load: Load, sessions: int) -> np.ndarray:
    """Histogram inputs of the warm-up sessions, from a stream that no
    window session draws from."""
    return _rng(load.seed, 5).random((sessions, load.n_nodes))


def arrivals(rate_per_s: float, seconds: float, arrival_seed: int,
             seed: int) -> np.ndarray:
    """Due times of an open loop: ``round(rate * seconds)`` arrivals whose
    gaps are one fixed exponential set, scaled to span ``seconds``, in an
    order drawn from ``seed``."""
    count = max(1, int(round(rate_per_s * seconds)))
    gaps = _rng(arrival_seed, 0).exponential(1.0, count)
    gaps *= seconds / gaps.sum()
    gaps = gaps[_rng(seed, 1).permutation(count)]
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def make_load(traffic: dict, *, n_nodes: int, clip: float, seed: int,
              seconds: float) -> Load:
    loop, payload = traffic["loop"], traffic["payload"]
    kind = payload["kind"]
    load = Load(loop=loop, kind=kind, n_nodes=n_nodes, seed=seed, clip=clip)
    if loop == "closed":
        load.clients = int(traffic["clients"])
        sessions = None
    elif loop == "open":
        load.due = arrivals(float(traffic["rate_per_s"]), seconds,
                            int(traffic["arrival_seed"]), seed)
        sessions = len(load.due)
    else:
        raise ValueError(f"unknown loop {loop!r}")
    if kind == "vector":
        load.elems = int(payload["elems"])
        rng = _rng(seed, 2)
        load.pool = []
        for _ in range(int(payload["pool"])):
            x = rng.random((n_nodes, load.elems), dtype=np.float32)
            x *= np.float32(2 * clip)
            x -= np.float32(clip)
            load.pool.append((x, x.sum(0, dtype=np.float64)))
        load.order = rng.integers(0, len(load.pool),
                                  size=sessions or 4096)
        # the own rows of the sessions a window can hold at the real size
        # (two rounds of every population: a session of 2^20 elements
        # takes 6.7 s of a v5e, a window at most 51 s) are made here; a
        # window of the tests' tiny sizes makes later ones as it opens them
        for i in range(sessions or 2 * load.clients):
            load.own_row(i)
    elif kind == "histogram":
        load.bins = int(payload["bins"])
        if sessions is None:
            raise ValueError("histogram payloads need an open loop")
        load.values = _rng(seed, 3).random((sessions, n_nodes))
    else:
        raise ValueError(f"unknown payload kind {kind!r}")
    return load
