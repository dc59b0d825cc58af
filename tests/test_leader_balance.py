"""Leader balance (ISSUE 36): ``CliService.rebalance`` over hundreds of
groups on engine-backed stores, what a transfer leaves behind, and the two
counters a balanced benchmark cell reads: ``leader_transfers`` (a leadership
GAINED through TimeoutNow, on the gaining store's engine only) and the
``leader_transfer`` span on a sampled group."""

import asyncio

from tests.test_engine import MultiRaftCluster
from tpuraft.core.cli_service import CliProcessors, CliService
from tpuraft.core.node import State
from tpuraft.rpc.transport import InProcTransport
from tpuraft.util.trace import TRACER


def _leaders(c) -> dict:
    """endpoint -> groups it leads right now."""
    out = {ep.endpoint: [] for ep in c.endpoints}
    for (gid, ep), node in c.nodes.items():
        if node.state == State.LEADER:
            out[ep.endpoint].append(gid)
    return out


def _transfers(c) -> list:
    return [c.engines[ep.endpoint].tick_hists["leader_transfers"].count
            for ep in c.endpoints]


def _watchdogs() -> list:
    return [t for t in asyncio.all_tasks()
            if getattr(t.get_coro(), "__name__", "") == "_transfer_watchdog"]


async def _until(cond, timeout_s: float, what: str) -> None:
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout_s
    while not cond():
        assert loop.time() < deadline, what
        await asyncio.sleep(0.02)


async def _pile_on_first(c) -> None:
    """Every leadership to the first endpoint, as a cluster whose stores
    boot one after another elects."""
    first = c.endpoints[0]
    for gid in c.groups:
        await c.wait_leader(gid)
    for (gid, ep), node in c.nodes.items():
        if ep != first and node.state == State.LEADER:
            assert (await node.transfer_leadership_to(first)).is_ok()
    await _until(lambda: len(_leaders(c)[first.endpoint]) == len(c.groups),
                 20.0, f"piling: {[len(v) for v in _leaders(c).values()]}")


async def test_leader_transfers_counts_on_the_gaining_store_only():
    c = MultiRaftCluster(3, 6, election_timeout_ms=1000)
    await c.start_all()
    try:
        for gid in c.groups:
            await c.wait_leader(gid)
        # the boot's elections came from timeouts: nothing gained by transfer
        assert _transfers(c) == [0, 0, 0]
        elections0 = [c.engines[ep.endpoint].tick_hists[
            "elections_started"].count for ep in c.endpoints]
        assert sum(elections0) >= 6
        leader = await c.wait_leader("g0")
        src = c.endpoints.index(leader.server_id)
        dst = (src + 1) % 3
        TRACER.configure(enabled=True, sample_rate=1.0, slow_trigger=False)
        TRACER.reset()
        try:
            assert (await leader.transfer_leadership_to(
                c.endpoints[dst])).is_ok()
            target = c.nodes[("g0", c.endpoints[dst])]
            await _until(lambda: target.state == State.LEADER, 5.0,
                         "the transferee never led")
            spans = [s for s in TRACER.spans()
                     if s["name"] == "leader_transfer"]
        finally:
            TRACER.enabled = False
            TRACER.reset()
        want = [0, 0, 0]
        want[dst] = 1
        assert _transfers(c) == want
        # it is one of the gaining engine's elections too, and no other's
        elections = [c.engines[ep.endpoint].tick_hists[
            "elections_started"].count for ep in c.endpoints]
        assert [b - a for a, b in zip(elections0, elections)] == want
        # the old leader's acceptance to the transferee's become-leader,
        # recorded once, under the old leader's store
        assert len(spans) == 1 and spans[0]["dur_s"] > 0.0
        assert spans[0]["proc"] == f"store:{c.endpoints[src]}"
        # the step-down that completed the transfer took its watchdog along
        assert not _watchdogs()
        assert leader._transfer_watchdog_task is None
        assert leader.state == State.FOLLOWER
    finally:
        await c.stop_all()


async def test_a_transfer_that_is_never_taken_up_resumes_and_leaves_nothing():
    c = MultiRaftCluster(3, 1, election_timeout_ms=400)
    await c.start_all()
    try:
        leader = await c.wait_leader("g0")
        target = next(ep for ep in c.endpoints if ep != leader.server_id)
        c.net.stop_endpoint(target.endpoint)      # TimeoutNow cannot land
        assert (await leader.transfer_leadership_to(target)).is_ok()
        assert leader.state == State.TRANSFERRING and len(_watchdogs()) == 1
        await _until(lambda: leader.state == State.LEADER, 3.0,
                     "the watchdog never resumed the leader")
        await asyncio.sleep(0)
        assert not _watchdogs()
        assert _transfers(c) == [0, 0, 0]
    finally:
        c.net.start_endpoint(target.endpoint)
        await c.stop_all()


async def test_rebalance_spreads_hundreds_of_groups_and_leaves_no_watchdog():
    n = 300
    c = MultiRaftCluster(3, n, election_timeout_ms=3000, tick_ms=10)
    await c.start_all()
    try:
        for ep in c.endpoints:
            CliProcessors(c.nodes[(c.groups[0], ep)].node_manager)
        await _pile_on_first(c)
        gained0 = _transfers(c)
        cli = CliService(InProcTransport(c.net, "cli:0"))
        st = await cli.rebalance(list(c.groups), c.conf)
        assert st.is_ok(), st
        ceiling = (n + 2) // 3
        await _until(
            lambda: sum(len(v) for v in _leaders(c).values()) == n
            and max(len(v) for v in _leaders(c).values()) <= ceiling,
            30.0, f"leaders {[len(v) for v in _leaders(c).values()]}")
        assert sorted(len(v) for v in _leaders(c).values()) == [100, 100, 100]
        # every transfer was gained by the store it was aimed at, once
        gained = [b - a for a, b in zip(gained0, _transfers(c))]
        assert gained == [0, 100, 100]
        # 200 transfers done, none pending: no task sleeps out an election
        # timeout for a leadership that has already moved
        assert not _watchdogs()
        # and the layout stays: a second call finds nothing to move
        st = await cli.rebalance(list(c.groups), c.conf)
        assert st.is_ok(), st
        await asyncio.sleep(0.5)
        assert [b - a for a, b in zip(gained0, _transfers(c))] == gained
        assert sorted(len(v) for v in _leaders(c).values()) == [100, 100, 100]
    finally:
        await c.stop_all()


def test_timeout_now_carries_the_trace_and_older_frames_still_decode():
    from tpuraft.rpc.messages import (TimeoutNowRequest, decode_message,
                                      encode_message)

    req = TimeoutNowRequest(group_id="g", server_id="127.0.0.1:1",
                            peer_id="127.0.0.1:2", term=7, trace_ctx=41)
    frame = encode_message(req)
    assert decode_message(frame) == req
    # a sender that predates the trailing field: its frame is 8 bytes
    # shorter, and reads as "not traced"
    old = decode_message(frame[:-8])
    assert (old.group_id, old.term, old.trace_ctx) == ("g", 7, 0)
