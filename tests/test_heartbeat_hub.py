"""HeartbeatHub: coalesced cross-group heartbeats (SURVEY.md §3.5
batched send-matrix plane — a TPU-native scaling feature with no
reference counterpart)."""

import asyncio

import pytest

from tests.cluster import TestCluster
from tests.test_engine import MultiRaftCluster
from tpuraft.core.node import State
from tpuraft.entity import Task


async def test_coalesced_cluster_stable_and_applies():
    """Leadership must stay stable on hub heartbeats alone (no per-group
    heartbeat loops), and replication/commit still works."""
    c = TestCluster(3, coalesce_heartbeats=True)
    await c.start_all()
    try:
        leader = await c.wait_leader()
        term0 = leader.current_term
        st = await c.apply_ok(leader, b"hub-1")
        assert st.is_ok()
        # several election timeouts of quiet time: followers must keep
        # receiving (coalesced) heartbeats, so no re-election happens
        await asyncio.sleep(1.2)
        assert leader.state == State.LEADER
        assert leader.current_term == term0
        st = await c.apply_ok(leader, b"hub-2")
        assert st.is_ok()
        await c.wait_applied(2)
        hub = c.managers[leader.server_id].heartbeat_hub
        assert hub.rpcs_sent > 0
    finally:
        await c.stop_all()


async def test_coalesced_leader_detects_dead_quorum():
    """Hub silence must feed dead-node detection exactly like direct
    heartbeats: an isolated leader steps down."""
    c = TestCluster(3, election_timeout_ms=200, coalesce_heartbeats=True)
    await c.start_all()
    try:
        leader = await c.wait_leader()
        c.net.isolate(leader.server_id.endpoint)
        deadline = asyncio.get_running_loop().time() + 5
        while asyncio.get_running_loop().time() < deadline:
            if leader.state != State.LEADER:
                break
            await asyncio.sleep(0.02)
        assert leader.state != State.LEADER
        c.net.heal()
    finally:
        await c.stop_all()


class CoalescedMultiRaftCluster(MultiRaftCluster):
    coalesce_heartbeats = True


async def test_multi_group_idle_rpc_reduction():
    """The point of the hub: G groups x P peers idle heartbeats collapse
    to one multi_heartbeat RPC per endpoint pair per interval."""
    c = CoalescedMultiRaftCluster(3, 16, election_timeout_ms=400)
    calls: list[str] = []
    orig_call = c.net.call

    async def counting_call(src, dst, method, request, timeout_ms=None):
        calls.append(method)
        return await orig_call(src, dst, method, request, timeout_ms)

    c.net.call = counting_call
    await c.start_all()
    try:
        for gid in c.groups:
            await c.wait_leader(gid, timeout_s=20.0)
        # one write per group so every group has a leader with followers
        async def put(gid):
            leader = await c.wait_leader(gid)
            fut = asyncio.get_running_loop().create_future()
            await leader.apply(Task(data=b"x", done=fut.set_result))
            assert (await asyncio.wait_for(fut, 10)).is_ok()
        await asyncio.gather(*[put(g) for g in c.groups])

        # quiet window: count idle-traffic RPCs.  Hub counters are
        # cumulative, so snapshot them and assert on window DELTAS —
        # the boot/apply phases legitimately produce small unaligned
        # pulses that would dilute a lifetime ratio (observed flake:
        # lifetime 3.98 vs the 4x bound under full-suite contention).
        hubs = [m.heartbeat_hub for m in
                (c.nodes[(c.groups[0], ep)].node_manager
                 for ep in c.endpoints)]
        rpcs0 = sum(h.rpcs_sent for h in hubs)
        beats0 = sum(h.beats_sent + h.fast_beats_sent for h in hubs)
        calls.clear()
        await asyncio.sleep(1.0)
        n_multi = calls.count("multi_heartbeat") + calls.count(
            "multi_beat_fast")
        n_append = calls.count("append_entries")
        assert n_multi > 0
        # without coalescing, idle heartbeats would be ~16 groups x 2
        # followers per interval per endpoint; with the hub, per-group
        # append_entries RPCs in a quiet window stay far below that
        assert n_append < n_multi * 4, (n_append, n_multi)
        # and the hub batched many beats per RPC while idle (deadlines
        # phase-align to the hb grid, so due groups pulse together);
        # steady state rides the beat-plane fast path almost entirely
        d_rpcs = sum(h.rpcs_sent for h in hubs) - rpcs0
        d_beats = sum(h.beats_sent + h.fast_beats_sent
                      for h in hubs) - beats0
        assert d_beats > d_rpcs * 4, (d_beats, d_rpcs)
        assert sum(h.fast_beats_sent for h in hubs) > 0
    finally:
        await c.stop_all()


async def test_coalesced_failover_and_recovery():
    """Leader crash with coalescing on: survivors elect, the new
    leader's beats flow through the hub, and the restarted node is
    re-suppressed (no dueling elections)."""
    c = TestCluster(3, coalesce_heartbeats=True)
    await c.start_all()
    try:
        leader = await c.wait_leader()
        st = await c.apply_ok(leader, b"a")
        assert st.is_ok()
        dead = leader.server_id
        await c.stop(dead)
        leader2 = await c.wait_leader()
        assert leader2.server_id != dead
        st = await c.apply_ok(leader2, b"b")
        assert st.is_ok()
        # fresh recorder: the memory:// log restarts empty and full
        # re-replication would double-count into a reused one
        from tests.cluster import MockStateMachine
        await c.start(dead, fsm=MockStateMachine())
        await c.wait_applied(2)
        assert c.fsms[dead].logs == [b"a", b"b"]
        # stability after recovery: term holds for several timeouts
        term = leader2.current_term
        await asyncio.sleep(1.0)
        assert leader2.state == State.LEADER
        assert leader2.current_term == term
    finally:
        await c.stop_all()


# -- fast-beat failure-path unit tests (ADVICE r4) ---------------------------

from types import SimpleNamespace  # noqa: E402

from tpuraft.core.heartbeat_hub import HeartbeatHub  # noqa: E402


def _fake_beat_rep(transport, peer_ep="dst:1"):
    node = SimpleNamespace(
        group_id="g",
        server_id="srv:1",
        current_term=3,
        transport=transport,
        options=SimpleNamespace(
            election_timeout_ms=400,
            raft_options=SimpleNamespace(election_heartbeat_factor=10)),
        ballot_box=SimpleNamespace(last_committed_index=7),
        is_leader=lambda: True,
        on_peer_ack=lambda peer, when: None,
    )
    return SimpleNamespace(
        _node=node,
        _running=True,
        _matched=True,
        peer_multi_hb=True,
        peer=SimpleNamespace(endpoint=peer_ep),
        match_index=7,
        last_rpc_ack=0.0,
        _beats_inflight=0,
    )


async def test_fast_beat_short_ack_list_falls_back_classic():
    """A response with fewer acks than beats must NOT silently drop the
    trailing replicators (zip truncation): the whole chunk deviates and
    gets the classic-beat follow-up."""

    class ShortTransport:
        async def call(self, dst, method, request, timeout_ms=None):
            from tpuraft.rpc.messages import BatchResponse
            return BatchResponse(items=[SimpleNamespace(ok=True)])

    hub = HeartbeatHub()
    tr = ShortTransport()
    reps = [_fake_beat_rep(tr) for _ in range(3)]
    fell_back: list = []
    hub._pulse_classic = lambda rs: fell_back.extend(rs)
    hub.pulse(reps)
    await asyncio.sleep(0.05)
    assert len(fell_back) == 3
    assert hub.fast_fallbacks == 3


async def test_fast_beat_crash_is_reaped_and_falls_back_classic():
    """A non-RpcError escaping _beat_fast must be retrieved by the done
    callback (no 'exception was never retrieved' spam) AND fall back to
    classic beats — a persistent codec failure must not silently starve
    those groups of heartbeats until their followers elect."""

    class ExplodingTransport:
        async def call(self, dst, method, request, timeout_ms=None):
            raise ValueError("codec blew up")

    hub = HeartbeatHub()
    tr = ExplodingTransport()
    reps = [_fake_beat_rep(tr) for _ in range(2)]
    fell_back: list = []
    hub._pulse_classic = lambda rs: fell_back.extend(rs)
    hub.pulse(reps)
    await asyncio.sleep(0.05)
    assert len(fell_back) == 2
    assert hub.fast_fallbacks == 2
    assert not hub._inflight  # chunk slot released for the next pulse


def test_compact_beat_decodes_old_wire_format():
    """Mixed-version fleets: a CompactBeat encoded BEFORE the quiesce
    handshake fields existed is 9 bytes shorter (bool + i64).  The
    positional field-stream decode must fill the missing trailing
    defaulted fields from their defaults instead of raising — an
    upgraded receiver behind an old sender would otherwise fail every
    fast-beat batch, and the old sender (seeing a generic error, not
    ENOMETHOD) would never fall back to classic beats."""
    import pytest

    from tpuraft.rpc.messages import CompactBeat, decode_message, \
        encode_message

    beat = CompactBeat(group_id="g0", server_id="127.0.0.1:1",
                       peer_id="127.0.0.2:2", term=3, committed_index=17,
                       quiesce=True, lease_ms=4000)
    wire = encode_message(beat)
    assert decode_message(wire) == beat          # new <-> new round trip
    got = decode_message(wire[:-9])              # strip quiesce+lease_ms
    assert got == CompactBeat(group_id="g0", server_id="127.0.0.1:1",
                              peer_id="127.0.0.2:2", term=3,
                              committed_index=17)  # defaults: no handshake
    # a genuinely truncated REQUIRED field still fails loudly
    with pytest.raises(Exception):
        decode_message(wire[:-10])


async def test_fast_beat_enomethod_counts_fallbacks_and_pins_classic():
    """ENOMETHOD (receiver predates the beat plane) must count one
    fallback per affected replicator, pin the dst to classic beats, and
    re-pulse the chunk classically — and the counters must surface
    through the hub's MetricRegistry gauges (util/metrics.py)."""
    from tpuraft.errors import RaftError, Status
    from tpuraft.rpc.transport import RpcError

    class NoMethodTransport:
        async def call(self, dst, method, request, timeout_ms=None):
            raise RpcError(Status.error(RaftError.ENOMETHOD,
                                        f"no handler {method}"))

    hub = HeartbeatHub()
    tr = NoMethodTransport()
    reps = [_fake_beat_rep(tr) for _ in range(3)]
    fell_back: list = []
    hub._pulse_classic = lambda rs: fell_back.extend(rs)
    hub.pulse(reps)
    await asyncio.sleep(0.05)
    assert hub.fast_fallbacks == 3
    assert hub._fast_ok["dst:1"] is False
    assert len(fell_back) == 3        # the re-pulse went classic
    snap = hub.metrics.snapshot()["gauges"]
    assert snap["hub.fast_fallbacks"] == 3
    assert snap["hub.rpcs_sent"] == hub.rpcs_sent
    # counters() (the soak stats line's view) agrees with the gauges
    assert hub.counters()["fast_fallbacks"] == 3


class _HeldTransport:
    """Beat RPCs that stay on the wire until released; records what each
    carried."""

    def __init__(self):
        self.sent: list = []            # (method, number of beats)
        self.release = asyncio.Event()

    async def call(self, dst, method, request, timeout_ms=None):
        from tpuraft.rpc.messages import (BatchResponse,
                                          MultiHeartbeatResponse)

        fast = method == "multi_beat_fast"
        n = len(request.items if fast else request.beats)
        self.sent.append((method, n))
        await self.release.wait()
        if fast:
            return BatchResponse(items=[SimpleNamespace(ok=True)] * n)
        return MultiHeartbeatResponse(acks=[])      # short: read as silence


def _classic_beat_rep(transport):
    from tpuraft.rpc.messages import AppendEntriesRequest

    r = _fake_beat_rep(transport)
    r._matched = False                  # not probed yet: classic beats
    r.build_heartbeat_request = lambda: AppendEntriesRequest(
        group_id="g", server_id="srv:1", peer_id="dst:1", term=3,
        prev_log_index=7, prev_log_term=3, committed_index=7)
    return r


@pytest.mark.parametrize("make_rep, per_rpc, method", [
    (_fake_beat_rep, "max_fast_beats_per_rpc", "multi_beat_fast"),
    (_classic_beat_rep, "max_beats_per_rpc", "multi_heartbeat"),
])
async def test_a_beat_in_flight_silences_its_own_replicators_only(
        make_rep, per_rpc, method):
    """What kept 4,096 leaders from holding (ISSUE 29): the in-flight guard
    was keyed by a chunk's POSITION in the pulse, so while one slow RPC was
    out, whichever groups came to sit at that position next pulse were
    dropped without a beat, pulse after pulse, until their followers
    started elections and their leaders read a dead quorum.  It is counted
    per replicator: a pulse leaves out exactly those whose last beat is
    unanswered."""
    hub = HeartbeatHub()
    setattr(hub, per_rpc, 2)
    tr = _HeldTransport()
    first = [make_rep(tr) for _ in range(2)]
    second = [make_rep(tr) for _ in range(2)]
    hub.pulse(first)
    await asyncio.sleep(0)
    assert tr.sent == [(method, 2)]
    hub.pulse(second)                   # same destination, same position
    await asyncio.sleep(0)
    assert tr.sent == [(method, 2), (method, 2)]
    hub.pulse(first + second)           # all four still unanswered
    await asyncio.sleep(0)
    assert len(tr.sent) == 2 and hub.beats_skipped == 4
    tr.release.set()
    await asyncio.sleep(0.02)
    assert not hub._inflight
    assert all(r._beats_inflight == 0 for r in first + second)
    hub.pulse(first + second)
    await asyncio.sleep(0.02)
    assert tr.sent[2:] == [(method, 2), (method, 2)]


class AutoMultiRaftCluster(MultiRaftCluster):
    coalesce_heartbeats = None  # the RaftOptions DEFAULT: auto


async def test_auto_coalescing_by_default():
    """VERDICT r2 #6 done-when: with DEFAULT options, an idle
    multi-group cluster's heartbeat RPC rate is O(endpoints) — peers
    advertise multi_heartbeat in AppendEntries responses (they all run
    NodeManagers) and the engine's beat fan-out auto-coalesces."""
    c = AutoMultiRaftCluster(3, 16, election_timeout_ms=400)
    calls: list[str] = []
    orig_call = c.net.call

    async def counting_call(src, dst, method, request, timeout_ms=None):
        calls.append(method)
        return await orig_call(src, dst, method, request, timeout_ms)

    c.net.call = counting_call
    await c.start_all()
    try:
        for gid in c.groups:
            await c.wait_leader(gid, timeout_s=20.0)

        async def put(gid):
            leader = await c.wait_leader(gid)
            fut = asyncio.get_running_loop().create_future()
            await leader.apply(Task(data=b"x", done=fut.set_result))
            assert (await asyncio.wait_for(fut, 10)).is_ok()
        await asyncio.gather(*[put(g) for g in c.groups])

        # every leader's replicators learned the capability from probes
        for (gid, ep), n in c.nodes.items():
            if n.is_leader():
                for r in n.replicators.all():
                    assert r.peer_multi_hb, (gid, str(r.peer))

        calls.clear()
        await asyncio.sleep(1.0)
        n_multi = calls.count("multi_heartbeat")
        n_append = calls.count("append_entries")
        assert n_multi > 0, "auto mode never coalesced"
        # idle per-group beats ride the hub BY DEFAULT: direct
        # append_entries stays far under the uncoalesced 16 groups x 2
        # followers per interval per endpoint
        assert n_append < n_multi * 4, (n_append, n_multi)
    finally:
        await c.stop_all()
