"""Plan/engine/transport architecture: plan compilation invariants,
SimTransport == reference, and the acceptance pin — MeshTransport under
``shard_map`` on a forced-8-device host is bit-identical to the
SimTransport oracle for the same AggPlan, crash + Byzantine sessions
included, for a sealed service batch (pairwise masking too)."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.plan import (AggConfig, SessionMeta, compile_plan,
                            fault_masks_of)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ---------------------------------------------------------------------------
# Plan compilation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("schedule,n_rounds", [("ring", 3), ("tree", 4),
                                               ("butterfly", 2)])
def test_plan_round_layout(schedule, n_rounds):
    cfg = AggConfig(n_nodes=16, cluster_size=4, redundancy=3,
                    schedule=schedule)
    plan = compile_plan(cfg)
    assert len(plan.rounds) == n_rounds
    assert plan.groups == ((0, 1, 2, 3), (4, 5, 6, 7), (8, 9, 10, 11),
                           (12, 13, 14, 15))
    for rnd in plan.rounds:
        assert len(rnd.perms) == 3
        src_of = [{dst: src for src, dst in rnd.perms[s]} for s in range(3)]
        # every participating node receives exactly one copy per stream
        for s in range(3):
            assert sorted(src_of[s]) == [d for d in range(16)
                                         if rnd.participates[d]]
        # shift-s copies come from distinct members of the same cluster
        for dst in src_of[0]:
            srcs = {src_of[s][dst] for s in range(3)}
            assert len(srcs) == 3
            assert {src // 4 for src in srcs} == {rnd.recv_from[dst // 4]}


@pytest.mark.parametrize("schedule,n,c", [("ring", 16, 4), ("ring", 12, 3),
                                          ("ring", 8, 1), ("tree", 16, 4),
                                          ("butterfly", 16, 2)])
def test_sim_hop_moves_rows_along_ppermute_pairs(schedule, n, c):
    """The sim transport's rolled hop delivers, on every copy stream and
    the backup stream, exactly the rows the mesh transport's ppermute
    pairs deliver."""
    import jax.numpy as jnp
    from repro.core.engine import SimTransport
    r = 3 if c >= 3 else 1
    plan = compile_plan(AggConfig(n_nodes=n, cluster_size=c, redundancy=r,
                                  schedule=schedule))
    tp = SimTransport(plan, S=2, impl="jnp")
    x = np.arange(2 * n * 5, dtype=np.uint32).reshape(2, n, 5)
    for rnd in plan.rounds:
        moves = [(tp._move(rnd, s, jnp.asarray(x)), rnd.perms[s])
                 for s in range(r)]
        moves.append((tp._move_backup(rnd, jnp.asarray(x)),
                      rnd.backup_perm))
        for got, pairs in moves:
            got = np.asarray(got).reshape(2, n, 5)
            for src, dst in pairs:
                assert np.array_equal(got[:, dst], x[:, src]), (src, dst)


def test_plan_folds_static_faults_and_epoch_layout():
    from repro.runtime.fault import SessionFaultPlan
    from repro.service.epochs import EpochSnapshot
    cfg = AggConfig(n_nodes=8, cluster_size=4, redundancy=3)
    snap = EpochSnapshot(epoch=0, cluster_size=4,
                         slot_uids=tuple(range(8)), honest=(True,) * 8)
    plan = compile_plan(cfg, epoch=snap,
                        fault=SessionFaultPlan(crashed_slots=(2,),
                                               byzantine_slots=(5,)))
    assert {(f.mode, f.corrupt_ranks) for f in plan.faults} == \
        {("drop", (2,)), ("flip", (5,))}
    bad = EpochSnapshot(epoch=0, cluster_size=2,
                        slot_uids=tuple(range(8)), honest=(True,) * 8)
    with pytest.raises(AssertionError):
        compile_plan(cfg, epoch=bad)


def test_session_meta_build_normalizes():
    import jax.numpy as jnp
    from repro.core.byzantine import ByzantineSpec
    meta = SessionMeta.build(3, 8, seed=7)
    assert meta.S == 3 and not meta.fault_masks
    assert np.all(np.asarray(meta.seeds) == 7)
    faults = [(), (ByzantineSpec(corrupt_ranks=(1, 3), mode="drop"),), ()]
    meta = SessionMeta.build(3, 8, faults=faults)
    m = meta.fault_masks["drop"]
    assert m.shape == (3, 8) and m[1, 1] and m[1, 3] and m.sum() == 2
    with pytest.raises(AssertionError):
        SessionMeta.build(3, 8, faults=faults,
                          fault_masks={"drop": jnp.zeros((3, 8), bool)})
    assert fault_masks_of([()], 8) == {}


# ---------------------------------------------------------------------------
# MeshTransport == SimTransport (forced multi-device subprocess)
# ---------------------------------------------------------------------------


_MESH_EQUIV = """
import numpy as np, jax, jax.numpy as jnp
from repro.core.byzantine import ByzantineSpec
from repro.core.engine import MeshTransport, sim_batch
from repro.core.plan import AggConfig, SessionMeta, compile_plan
from repro.runtime import compat

rng = np.random.default_rng(5)
n, c, S, T = 8, 4, 5, 257
mesh = compat.make_mesh((n,), ("data",))
seeds = jnp.arange(S, dtype=jnp.uint32) + 11
faults = [() for _ in range(S)]
faults[1] = (ByzantineSpec(corrupt_ranks=(2,), mode="drop"),)   # crash
faults[3] = (ByzantineSpec(corrupt_ranks=(6,), mode="flip"),)   # byzantine
xs = jnp.asarray(rng.normal(size=(S, n, T)).astype(np.float32) * 0.2)
for masking in ("global", "pairwise", "none"):
    cfg = AggConfig(n_nodes=n, cluster_size=c, redundancy=3,
                    masking=masking, clip=2.0)
    plan = compile_plan(cfg)
    meta = SessionMeta.build(S, n, seed=cfg.seed, seeds=seeds, faults=faults)
    mt = MeshTransport(mesh, ("data",))
    got = np.asarray(mt.execute(plan, xs, meta))
    want = np.asarray(sim_batch(plan, xs, meta)[0])
    assert np.array_equal(got, want), masking
    ro = np.asarray(mt.execute(plan, xs, meta, reveal_only=True))
    assert np.array_equal(ro, want[:, 0]), masking
    # faults were vote-absorbed: the revealed sums stay exact
    assert np.abs(ro - np.asarray(xs).sum(1)).max() < 1e-3, masking
print("MESH==SIM")
"""


_SERVICE_MESH = """
import numpy as np, jax
from repro.runtime import compat
from repro.runtime.fault import SessionFaultPlan
from repro.service import AggregationService, BatchingConfig, SessionParams

n, elems, S = 8, 100, 6
rng = np.random.default_rng(9)
vals = rng.normal(size=(S, n, elems)).astype(np.float32) * 0.3
params = SessionParams(n_nodes=n, elems=elems, cluster_size=4, redundancy=3,
                       masking="pairwise", clip=2.0)

def run(transport):
    mesh = compat.make_mesh((n,), ("data",)) if transport == "mesh" else None
    svc = AggregationService(
        params, batching=BatchingConfig(max_batch=S, max_age=1e9),
        transport=transport, mesh=mesh)
    for i in range(S):
        s = svc.open(now=0.0)
        for slot in range(n):
            if (i, slot) != (2, 1):          # one missing slot -> crash
                s.contribute(slot, vals[i, slot])
        if i == 4:
            s.inject_fault(SessionFaultPlan(byzantine_slots=(3,)))
        svc.seal(s.sid, now=0.0)
    assert svc.pump(force=True) == S
    return np.stack([svc.result(sid) for sid in range(S)])

sim, mesh = run("sim"), run("mesh")
assert np.array_equal(sim, mesh)
want = vals.sum(1); want[2] -= vals[2, 1]
assert np.abs(sim - want).max() < 1e-3
print("SERVICE MESH==SIM")
"""


def _run_sub(code: str, devices: int = 8, timeout: int = 900):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.mesh
@pytest.mark.slow
def test_mesh_transport_bit_identical_to_sim_8dev():
    """The acceptance pin: MeshTransport (shard_map + ppermute over a dp
    mesh) == SimTransport oracle bit-for-bit for the same AggPlan, with
    one crashed and one Byzantine session, all masking modes."""
    r = _run_sub(_MESH_EQUIV)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    assert "MESH==SIM" in r.stdout


def test_service_batch_on_mesh_matches_sim_executor_8dev():
    """A sealed service batch (pairwise masking, missing contributor,
    mid-session Byzantine slot) through BatchedExecutor(transport="mesh")
    == the sim executor, bit for bit."""
    r = _run_sub(_SERVICE_MESH)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    assert "SERVICE MESH==SIM" in r.stdout


# ---------------------------------------------------------------------------
# Wire-account reset semantics (Transport.bytes_sent / last_bytes)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("transport", ["full", "digest"])
def test_sim_wire_account_resets_per_transport(transport):
    """``bytes_sent`` starts at 0, accumulates while ONE transport
    instance executes, and never leaks across executions — every
    ``sim_batch`` call builds a fresh SimTransport, so its account is
    exactly one execution's bytes."""
    import jax.numpy as jnp
    from repro.core.engine import (SimTransport, execute_chunks, sim_batch)
    rng = np.random.default_rng(0)
    n, S, T = 8, 3, 64
    cfg = AggConfig(n_nodes=n, cluster_size=4, redundancy=3,
                    transport=transport)
    plan = compile_plan(cfg)
    xs = rng.normal(size=(S, n, T)).astype(np.float32) * 0.1
    want = plan.wire_bytes(T, S=S)
    for _ in range(2):               # fresh account on every invocation
        _, tp = sim_batch(plan, xs, SessionMeta.build(S, n, seed=cfg.seed))
        assert tp.bytes_sent == want
    # a REUSED instance accumulates across executions instead
    tp = SimTransport(plan, S=S)
    assert tp.bytes_sent == 0        # nothing dispatched yet
    flat = jnp.asarray(xs).reshape(S * n, T)
    for k in (1, 2):
        execute_chunks(plan, tp, [flat],
                       SessionMeta.build(S, n, seed=cfg.seed))
        assert tp.bytes_sent == k * want


def test_wire_account_accumulates_across_chunks():
    """A chunked execution books every chunk on one account: two Tc
    chunks through one digest transport equal the analytic
    ``wire_bytes(2*Tc, chunks=2)`` (the digest set ships per chunk)."""
    import jax.numpy as jnp
    from repro.core.engine import SimTransport, execute_chunks
    rng = np.random.default_rng(1)
    n, S, Tc = 8, 2, 32
    cfg = AggConfig(n_nodes=n, cluster_size=4, redundancy=3,
                    transport="digest")
    plan = compile_plan(cfg)
    tp = SimTransport(plan, S=S)
    chunks = [jnp.asarray(rng.normal(size=(S * n, Tc)).astype(np.float32))
              for _ in range(2)]
    execute_chunks(plan, tp, chunks, SessionMeta.build(S, n, seed=cfg.seed))
    assert tp.bytes_sent == plan.wire_bytes(2 * Tc, S=S, chunks=2)
    assert tp.bytes_sent != plan.wire_bytes(2 * Tc, S=S)  # digest set x2


_MESH_WIRE = """
import numpy as np, jax, jax.numpy as jnp
from repro.core.engine import MeshTransport, sim_batch
from repro.core.plan import AggConfig, SessionMeta, compile_plan
from repro.runtime import compat

rng = np.random.default_rng(2)
n, S, T = 8, 3, 64
mesh = compat.make_mesh((n,), ("data",))
for transport in ("full", "digest"):
    cfg = AggConfig(n_nodes=n, cluster_size=4, redundancy=3,
                    transport=transport)
    plan = compile_plan(cfg)
    mt = MeshTransport(mesh, ("data",))
    assert mt.last_bytes is None        # no dispatch yet -> no account
    xs = jnp.asarray(rng.normal(size=(S, n, T)).astype(np.float32) * 0.1)
    want = plan.wire_bytes(T, S=S)
    for _ in range(2):                  # per-execution, not cumulative
        mt.execute(plan, xs, SessionMeta.build(S, n, seed=cfg.seed))
        assert mt.last_bytes == want, (transport, mt.last_bytes, want)
    _, tp = sim_batch(plan, xs, SessionMeta.build(S, n, seed=cfg.seed))
    assert tp.bytes_sent == want        # mesh account == sim account
print("MESH WIRE OK")
"""


@pytest.mark.mesh
@pytest.mark.slow
def test_mesh_wire_account_none_before_dispatch_8dev():
    """``MeshTransport.last_bytes`` is None until the first execute,
    then carries exactly one execution's account (equal to the sim
    transport's for the same plan), on both wire transports."""
    r = _run_sub(_MESH_WIRE)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    assert "MESH WIRE OK" in r.stdout
