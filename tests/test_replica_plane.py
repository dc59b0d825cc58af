"""Replica-axis collective commit plane (VERDICT r1 #6): co-located
replicas on a 2D (replica, groups) CPU mesh, commits computed by
tpuraft.parallel.collective's all_gather + order-statistic from each
replica's DURABLE log state over many real protocol steps."""

import asyncio

import numpy as np
import pytest

from tpuraft.entity import Task
from tpuraft.errors import RaftError
from tpuraft.parallel.replica_cluster import ReplicaPlaneCluster
from tpuraft.parallel.replica_plane import ReplicatedClusterPlane


async def _apply(node, data, t=10.0):
    fut = asyncio.get_running_loop().create_future()
    await node.apply(Task(data=data, done=fut.set_result))
    return await asyncio.wait_for(fut, t)


async def _apply_ok(node, data, t=10.0):
    st = await _apply(node, data, t)
    assert st.is_ok(), st


async def _apply_through_leader(c, gid, data, t=10.0):
    """Commit ``data`` in ``gid`` through whoever leads it NOW.  A
    propose-time rejection (EPERM: not leader, nothing was appended) is
    offered again to the group's next leader, as a client's route
    refresh does; any other failure is the test's."""
    while True:
        st = await _apply(await c.wait_leader(gid), data, t)
        if st.raft_error != RaftError.EPERM:
            assert st.is_ok(), st
            return


def _mesh_2d():
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    return Mesh(np.array(devs[:8]).reshape(2, 4), ("replica", "groups"))


async def test_multi_step_commits_through_collectives():
    """The VERDICT done-when: a MULTI-STEP CPU-mesh cluster commits real
    entries through collective.py — 4 replicas x 8 groups, 3 waves of
    writes, every commit decided by the replica-axis all_gather."""
    mesh = _mesh_2d()
    # every plane tick blocks the loop thread in a rendezvous of eight
    # CPU device threads; beside five other busy workers that can hold
    # the loop past the default 400 ms election timeout, and leaders
    # then move (the protocol at work, not this test's subject).  So:
    # a timeout such stalls do not cross, and every write goes through
    # the group's leader of that moment
    c = ReplicaPlaneCluster(4, 8, mesh=mesh, election_timeout_ms=2000)
    await c.start_all()
    try:
        for wave in range(3):
            await asyncio.gather(*(
                _apply_through_leader(
                    c, g, b"%s-w%d-%d" % (g.encode(), wave, i))
                for g in c.groups for i in range(5)))
        # the plane's collective tick drove the commits over many steps
        assert c.plane.ticks >= 3
        assert c.plane.commit_advances >= len(c.groups)
        # all replicas converge
        deadline = asyncio.get_running_loop().time() + 10
        while asyncio.get_running_loop().time() < deadline:
            if all(len(f.logs) >= 15 for f in c.fsms.values()):
                break
            await asyncio.sleep(0.05)
        for g in c.groups:
            logs = [c.fsms[(g, ep)].logs for ep in c.endpoints]
            assert all(lg == logs[0] for lg in logs)
            assert len(logs[0]) == 15
    finally:
        await c.stop_all()


async def test_commits_survive_replica_loss_quorum_math():
    """Kill one of 4 replicas: the collective order statistic still
    finds a 3/4 quorum; kill two: commits stall (no quorum)."""
    mesh = _mesh_2d()
    c = ReplicaPlaneCluster(4, 4, mesh=mesh)
    await c.start_all()
    try:
        gid = c.groups[0]
        leader = await c.wait_leader(gid)
        await _apply_ok(leader, b"before")
        # crash a non-leader replica endpoint entirely
        victim = next(ep for ep in c.endpoints if ep != leader.server_id)
        c.net.stop_endpoint(victim.endpoint)
        for (g, ep) in [k for k in c.nodes if k[1] == victim]:
            await c.nodes.pop((g, ep)).shutdown()
        await _apply_ok(leader, b"with-3-of-4", t=10)
        # second loss: 2/4 cannot commit
        victim2 = next(ep for ep in c.endpoints
                       if ep != leader.server_id and ep != victim)
        c.net.stop_endpoint(victim2.endpoint)
        for (g, ep) in [k for k in c.nodes if k[1] == victim2]:
            await c.nodes.pop((g, ep)).shutdown()
        fut = asyncio.get_running_loop().create_future()
        await leader.apply(Task(data=b"stalls", done=fut.set_result))
        try:
            st = await asyncio.wait_for(fut, 1.5)
            # the dead-quorum step-down may fail the entry first —
            # either way it must NOT commit
            assert not st.is_ok(), f"committed without quorum: {st}"
        except asyncio.TimeoutError:
            pass
    finally:
        await c.stop_all()


async def test_unattested_rows_never_count():
    """SAFETY: a replica whose accepted_term does not match the leader's
    lineage is masked out of the reduce even with a high durable tip
    (the stale-divergent-suffix hazard)."""
    plane = ReplicatedClusterPlane(3, 2, mesh=None)

    committed = []
    factory = plane.ballot_box_factory("g0", 0)
    box = factory(committed.append)
    box.note_attested(5)                 # leader at term 5
    box.reset_pending_index(11)          # own entries start at 11
    plane.match[0, 0] = 15               # leader durable through 15
    # replica 1: attested to term 5, durable through 15 -> quorum of 2
    plane.accepted_term[1, 0] = 5
    plane.match[1, 0] = 15
    # replica 2: STALE-HIGH row from a divergent suffix, attested to an
    # older term -> must not count
    plane.accepted_term[2, 0] = 3
    plane.match[2, 0] = 40
    plane.tick_once()
    assert committed and committed[-1] == 15, committed
    # now break replica 1's attestation too: commit must NOT advance
    plane.accepted_term[1, 0] = 0
    plane.match[0, 0] = 20
    plane.match[1, 0] = 20
    before = list(committed)
    plane.tick_once()
    assert committed == before, "unattested rows advanced the commit"
    # re-attest -> advances
    plane.accepted_term[1, 0] = 5
    plane.tick_once()
    assert committed[-1] == 20


async def test_truncation_lowers_match_row():
    """SAFETY regression: a suffix truncation must LOWER the plane row —
    exact-tip on_stable semantics, not a monotone max (else the reduce
    counts truncated entries toward the quorum)."""
    from tpuraft.entity import EntryType, LogEntry, LogId
    from tpuraft.storage.log_manager import LogManager
    from tpuraft.storage.log_storage import MemoryLogStorage

    plane = ReplicatedClusterPlane(3, 1, mesh=None)
    box = plane.ballot_box_factory("g0", 1)(lambda i: None)
    lm = LogManager(MemoryLogStorage())
    await lm.init()
    box.attach_log_manager(lm)
    entries = [LogEntry(type=EntryType.DATA, id=LogId(i, 2), data=b"x")
               for i in range(1, 41)]
    await lm.append_entries_leader(entries, term=2)
    await lm.flush_staged(40)
    assert plane.match[1, 0] == 40
    # new leader truncates the divergent suffix via a follower append
    ok = await lm.append_entries_follower(
        10, 2, [LogEntry(type=EntryType.DATA, id=LogId(11, 3), data=b"y")])
    assert ok
    assert plane.match[1, 0] == 11, plane.match[1, 0]
    await lm.shutdown()


async def test_numpy_fallback_matches_mesh_path():
    """The plane without a mesh (numpy oracle) and with the CPU mesh
    produce identical commit points on random state."""
    mesh = _mesh_2d()
    rng = np.random.default_rng(0)
    R, G = 4, 8
    for trial in range(5):
        match = rng.integers(0, 100, (R, G))
        p_np = ReplicatedClusterPlane(R, G, mesh=None)
        p_mx = ReplicatedClusterPlane(R, G, mesh=mesh)
        from tpuraft.parallel.collective import replicated_tick

        p_mx._fn = replicated_tick(mesh, R)
        for p in (p_np, p_mx):
            p.match[:, :] = match
            p.accepted_term[:, :] = 7
            p.leader_replica[:] = 0
        commits = []
        for p in (p_np, p_mx):
            # leader boxes on replica 0 for every group
            for g in range(G):
                b = p.ballot_box_factory(f"t{trial}g{g}", 0)(lambda i: None)
                b.pending_index = 1
            p.tick_once()
            commits.append(p.commit_abs.copy())
        np.testing.assert_array_equal(commits[0], commits[1])


async def test_transport_seam_tcp():
    """The protocol plane above the replica-axis collective rides real
    sockets (VERDICT r3 #8): same cluster, loopback TCP transport,
    including a replica crash + failover."""
    c = ReplicaPlaneCluster(3, 4, election_timeout_ms=600,
                            transport="tcp", base_port=7750)
    await c.start_all()
    try:
        leaders = {g: await c.wait_leader(g) for g in c.groups}
        await asyncio.gather(*(
            c.apply_ok(leaders[g], b"%s-tcp" % g.encode())
            for g in c.groups))
        # crash one replica endpoint; groups fail over over TCP
        lead_count = {ep.endpoint: 0 for ep in c.endpoints}
        for g in c.groups:
            lead_count[leaders[g].server_id.endpoint] += 1
        victim = min(c.endpoints, key=lambda ep: lead_count[ep.endpoint])
        await c.stop_replica(victim)
        for g in c.groups:
            n = await c.wait_leader(g, timeout_s=20)
            await c.apply_ok(n, b"%s-post" % g.encode())
    finally:
        await c.stop_all()
