"""ReadOnlyService + the amortized read plane (ISSUE 10).

Unit coverage for the service itself (none existed before): the
batch-drain invariant, shutdown cancelling an in-flight round, the
term-first-index safety gate, the witness guard, and the retryable
forward path with leader-hint re-probe.  Plus the store-wide
ReadConfirmBatcher (one beat-plane round confirms many groups), the
kv_command_batch read-fence dedupe, lease reads not waking hibernating
groups, and ReadIndexResponse wire compatibility both directions.
"""

from __future__ import annotations

import asyncio
from types import SimpleNamespace

import pytest

from tpuraft.core.read_only import ReadIndexError, ReadOnlyService
from tpuraft.entity import PeerId
from tpuraft.errors import RaftError, Status
from tpuraft.options import NodeOptions, ReadOnlyOption
from tpuraft.rpc.messages import (
    BatchResponse,
    BeatAck,
    ReadIndexResponse,
    decode_message,
    encode_message,
)
from tpuraft.rpc.transport import RpcError


# ---------------------------------------------------------------------------
# stubs
# ---------------------------------------------------------------------------


class _Ctrl:
    def __init__(self, eto_ms: int):
        self._eto_ms = eto_ms
        self.activity = 0

    def note_activity(self) -> None:
        self.activity += 1


class _Fsm:
    def __init__(self):
        self.applied = 1 << 50   # everything applied unless a test lowers it

    async def wait_applied(self, idx: int) -> None:
        while self.applied < idx:
            await asyncio.sleep(0.005)


class _Replicators:
    def __init__(self, acks: int = 2):
        self.acks = acks
        self.rounds = 0
        self.gate: asyncio.Event | None = None

    async def heartbeat_round(self) -> int:
        self.rounds += 1
        if self.gate is not None:
            await self.gate.wait()
        return self.acks


class _Transport:
    """read_index forward stub: endpoint -> response or exception."""

    def __init__(self, answers: dict):
        self.answers = answers
        self.calls: list[str] = []

    async def read_index(self, endpoint, req, timeout_ms=None):
        self.calls.append(endpoint)
        ans = self.answers[endpoint]
        if isinstance(ans, Exception):
            raise ans
        return ans


def _stub_node(leader: bool = True, voters: int = 3, eto_ms: int = 200,
               witness: bool = False,
               read_opt: ReadOnlyOption = ReadOnlyOption.SAFE):
    opts = NodeOptions(election_timeout_ms=eto_ms)
    opts.witness = witness
    opts.raft_options.read_only_option = read_opt
    peers = [PeerId.parse(f"127.0.0.1:{7100 + i}") for i in range(voters)]
    node = SimpleNamespace(
        group_id="g0",
        server_id=peers[0],
        options=opts,
        is_leader=lambda: leader,
        ballot_box=SimpleNamespace(last_committed_index=10),
        _term_first_index=5,
        fsm_caller=_Fsm(),
        _ctrl=_Ctrl(eto_ms),
        conf_entry=SimpleNamespace(
            conf=SimpleNamespace(peers=peers),
            old_conf=SimpleNamespace(peers=[])),
        replicators=_Replicators(acks=voters - 1),
        leader_id=peers[1],
        transport=None,
        leader_lease_is_valid=lambda: False,
        current_term=3,
    )
    return node


# ---------------------------------------------------------------------------
# ReadOnlyService units
# ---------------------------------------------------------------------------


async def test_batch_drain_invariant_follow_up_round():
    """Readers enqueued WHILE a round is resolving must get a follow-up
    round — and must NOT share the in-flight round's confirmation (their
    RPCs must be sent after their invoke)."""
    node = _stub_node()
    node.replicators.gate = asyncio.Event()
    svc = ReadOnlyService(node)
    r1 = asyncio.ensure_future(svc.leader_confirm_read_index())
    await asyncio.sleep(0.02)       # round 1 is blocked on the gate
    assert node.replicators.rounds == 1
    r2 = asyncio.ensure_future(svc.leader_confirm_read_index())
    await asyncio.sleep(0.02)
    assert node.replicators.rounds == 1, "r2 must wait for the NEXT round"
    node.replicators.gate.set()
    assert await asyncio.wait_for(r1, 2) == 10
    assert await asyncio.wait_for(r2, 2) == 10
    assert node.replicators.rounds == 2, "mid-round reader needs its own round"


async def test_shutdown_cancels_in_flight_round_and_fails_readers():
    node = _stub_node()
    node.replicators.gate = asyncio.Event()   # never set: round hangs
    svc = ReadOnlyService(node)
    r1 = asyncio.ensure_future(svc.leader_confirm_read_index())
    await asyncio.sleep(0.02)
    round_task = svc._round_task
    assert round_task is not None and not round_task.done()
    await svc.shutdown()
    with pytest.raises(ReadIndexError) as ei:
        await asyncio.wait_for(r1, 2)
    assert ei.value.status.code == int(RaftError.ENODESHUTTING)
    await asyncio.sleep(0.02)
    assert round_task.done(), "in-flight round must be cancelled"


async def test_term_first_index_gate_fails_closed():
    """A fresh leader whose commit index still lags its own term's no-op
    must NOT serve reads (they could miss acked writes of the previous
    leadership)."""
    node = _stub_node(eto_ms=80)
    node.ballot_box.last_committed_index = 4   # < _term_first_index = 5
    node.fsm_caller.applied = 0                # the no-op never applies
    svc = ReadOnlyService(node)
    with pytest.raises(ReadIndexError) as ei:
        await asyncio.wait_for(svc.leader_confirm_read_index(), 5)
    assert ei.value.status.code == int(RaftError.ERAFTTIMEDOUT)
    # once the term's first entry commits, the same service serves
    node.ballot_box.last_committed_index = 6
    node.fsm_caller.applied = 1 << 50
    assert await asyncio.wait_for(svc.leader_confirm_read_index(), 5) == 6


async def test_witness_never_serves_reads():
    node = _stub_node(witness=True)
    svc = ReadOnlyService(node)
    with pytest.raises(ReadIndexError) as ei:
        await svc.read_index()
    assert ei.value.status.code == int(RaftError.EPERM)


async def test_forward_rejection_is_retryable_and_follows_hint():
    """Satellite: a leader-rejected forward must re-probe the hinted
    leader inside the round, and exhaustion must surface a RETRYABLE
    status (EAGAIN) — not the old terminal EPERM."""
    node = _stub_node(leader=False)
    stale = node.leader_id                    # believed leader (stale)
    real = node.conf_entry.conf.peers[2]      # where it actually moved
    node.transport = _Transport({
        stale.endpoint: ReadIndexResponse(index=0, success=False, term=4,
                                          leader_hint=str(real)),
        real.endpoint: ReadIndexResponse(index=42, success=True, term=4),
    })
    svc = ReadOnlyService(node)
    assert await asyncio.wait_for(svc.read_index(), 5) == 42
    assert svc.fwd_redirects == 1
    assert node.transport.calls == [stale.endpoint, real.endpoint]

    # no hint anywhere -> retryable EAGAIN after the bounded chain
    node.transport = _Transport({
        stale.endpoint: ReadIndexResponse(index=0, success=False, term=4),
    })
    svc2 = ReadOnlyService(node)
    with pytest.raises(ReadIndexError) as ei:
        await asyncio.wait_for(svc2.read_index(), 5)
    assert ei.value.status.code == int(RaftError.EAGAIN)


async def test_forward_rpc_error_stays_timeout():
    node = _stub_node(leader=False)
    node.transport = _Transport({
        node.leader_id.endpoint: RpcError(
            Status.error(RaftError.EHOSTDOWN, "down")),
    })
    svc = ReadOnlyService(node)
    with pytest.raises(ReadIndexError) as ei:
        await asyncio.wait_for(svc.read_index(), 5)
    assert ei.value.status.code == int(RaftError.ETIMEDOUT)


async def test_lease_read_serves_without_wake_and_safe_wakes():
    """LEASE_BASED + valid lease: no quorum round, no note_activity (a
    hibernating leader stays hibernated).  Lease lapsed: the SAFE
    fallback round runs and wakes the group with its followers."""
    node = _stub_node(read_opt=ReadOnlyOption.LEASE_BASED)
    node.leader_lease_is_valid = lambda: True
    svc = ReadOnlyService(node)
    assert await asyncio.wait_for(svc.leader_confirm_read_index(), 5) == 10
    assert node.replicators.rounds == 0
    assert node._ctrl.activity == 0, "lease read must not wake the group"
    assert svc.lease_serves == 1

    node.leader_lease_is_valid = lambda: False
    assert await asyncio.wait_for(svc.leader_confirm_read_index(), 5) == 10
    assert node.replicators.rounds == 1, "lapsed lease falls back to SAFE"
    assert node._ctrl.activity == 1, "SAFE round must wake with followers"


async def test_safe_mode_read_wakes_exactly_on_quorum_round():
    node = _stub_node(read_opt=ReadOnlyOption.SAFE)
    svc = ReadOnlyService(node)
    assert await asyncio.wait_for(svc.leader_confirm_read_index(), 5) == 10
    assert node._ctrl.activity == 1
    assert node.replicators.rounds == 1
    assert svc.safe_rounds == 1


async def test_budget_tracks_density_floor_adopted_eto():
    """Satellite: the post-election wait budget must derive from the
    ADOPTED election timeout (engine density floor), not the value the
    options were constructed with."""
    node = _stub_node(eto_ms=100)
    node._ctrl._eto_ms = 4000    # density floor raised it after init
    svc = ReadOnlyService(node)
    assert svc._effective_eto_ms() == 4000
    node.options.election_timeout_ms = 8000   # host-side adoption wins too
    assert svc._effective_eto_ms() == 8000


# ---------------------------------------------------------------------------
# ReadConfirmBatcher (store-wide amortization)
# ---------------------------------------------------------------------------


class _Rep:
    def __init__(self, peer: PeerId, fast: bool = True):
        self.peer = peer
        self.peer_multi_hb = fast
        self._matched = True
        self.match_index = 1 << 40
        self.last_rpc_ack = 0.0
        self.classic_beats = 0
        self.classic_ok = True

    async def send_heartbeat(self) -> bool:
        self.classic_beats += 1
        return self.classic_ok


class _BatchTransport:
    """multi_beat_fast stub: per-dst scripted acks (or exceptions)."""

    def __init__(self, ok_by_dst=None, fail_dst=None):
        self.ok_by_dst = ok_by_dst or {}
        self.fail_dst = fail_dst or set()
        self.calls: list[tuple[str, int]] = []

    async def call(self, dst, method, request, timeout_ms=None):
        assert method == "multi_beat_fast"
        self.calls.append((dst, len(request.items)))
        if dst in self.fail_dst:
            raise RpcError(Status.error(RaftError.EHOSTDOWN, "dead"))
        ok = self.ok_by_dst.get(dst, True)
        return BatchResponse(items=[BeatAck(ok=ok, term=b.term)
                                    for b in request.items])


def _batcher_node(gid: str, transport, voters: list[PeerId],
                  fast: bool = True):
    opts = NodeOptions(election_timeout_ms=200)
    reps = [_Rep(p, fast=fast) for p in voters[1:]]
    node = SimpleNamespace(
        group_id=gid,
        server_id=voters[0],
        options=opts,
        is_leader=lambda: True,
        current_term=7,
        ballot_box=SimpleNamespace(last_committed_index=3),
        conf_entry=SimpleNamespace(
            conf=SimpleNamespace(peers=list(voters)),
            old_conf=SimpleNamespace(peers=[])),
        replicators=SimpleNamespace(all=lambda reps=reps: list(reps)),
        transport=transport,
        on_peer_ack=lambda peer, when: None,
        acked_log=[],
    )
    node.on_peer_ack = lambda peer, when: node.acked_log.append(peer)
    return node


def _voters(base: int) -> list[PeerId]:
    return [PeerId.parse(f"127.0.0.1:{base + i}") for i in range(3)]


async def test_batcher_amortizes_many_groups_into_one_beat_round():
    """The tentpole: N groups' SAFE confirmations sharing the same two
    follower endpoints cost ONE multi_beat_fast RPC per endpoint, not
    one heartbeat round per group."""
    from tpuraft.rheakv.store_engine import ReadConfirmBatcher

    transport = _BatchTransport()
    voters = _voters(7200)
    nodes = [_batcher_node(f"g{i}", transport, voters) for i in range(8)]
    b = ReadConfirmBatcher()
    outs = await asyncio.wait_for(
        asyncio.gather(*(b.confirm(n) for n in nodes)), 5)
    assert all(outs)
    assert b.confirms == 8
    assert b.rounds == 1
    # one RPC per distinct follower endpoint, each carrying 8 fences
    assert sorted(transport.calls) == sorted(
        [(voters[1].endpoint, 8), (voters[2].endpoint, 8)])
    assert b.beat_rpcs == 2
    assert b.beats == 16


async def test_batcher_quorum_failure_returns_false():
    from tpuraft.rheakv.store_engine import ReadConfirmBatcher

    voters = _voters(7300)
    transport = _BatchTransport(
        fail_dst={voters[1].endpoint, voters[2].endpoint})
    node = _batcher_node("g0", transport, voters)
    # classic fallback also fails (dead followers)
    for r in node.replicators.all():
        r.classic_ok = False
    b = ReadConfirmBatcher()
    assert await asyncio.wait_for(b.confirm(node), 5) is False
    assert b.failed == 1


async def test_batcher_ok_false_falls_back_to_classic_beat():
    """A deviating fast ack (follower restarted / committed behind) must
    get the full-semantics classic beat, whose in-term ack still counts
    toward the fence."""
    from tpuraft.rheakv.store_engine import ReadConfirmBatcher

    voters = _voters(7400)
    transport = _BatchTransport(ok_by_dst={voters[1].endpoint: False,
                                           voters[2].endpoint: False})
    node = _batcher_node("g0", transport, voters)
    b = ReadConfirmBatcher()
    assert await asyncio.wait_for(b.confirm(node), 5) is True
    assert b.classic_beats == 2
    assert all(r.classic_beats == 1 for r in node.replicators.all())


async def test_batcher_deposed_mid_round_voids_fence():
    """Acks landing after a step-down (or a term change) must not
    confirm the old fence."""
    from tpuraft.rheakv.store_engine import ReadConfirmBatcher

    voters = _voters(7500)

    class DeposingTransport(_BatchTransport):
        def __init__(self, node_box):
            super().__init__()
            self.node_box = node_box

        async def call(self, dst, method, request, timeout_ms=None):
            self.node_box[0].is_leader = lambda: False   # deposed mid-RPC
            return await super().call(dst, method, request, timeout_ms)

    box: list = [None]
    transport = DeposingTransport(box)
    node = _batcher_node("g0", transport, voters)
    box[0] = node
    b = ReadConfirmBatcher()
    assert await asyncio.wait_for(b.confirm(node), 5) is False


async def test_batcher_joint_conf_requires_both_quorums():
    from tpuraft.rheakv.store_engine import ReadConfirmBatcher

    voters = _voters(7600)
    old = [voters[0]] + [PeerId.parse(f"127.0.0.1:{7650 + i}")
                         for i in range(2)]
    # new-config followers ack; old-config followers are DEAD
    transport = _BatchTransport(fail_dst={p.endpoint for p in old[1:]})
    node = _batcher_node("g0", transport, voters)
    node.conf_entry.old_conf = SimpleNamespace(peers=list(old))
    node.replicators = SimpleNamespace(
        all=lambda: [_Rep(p) for p in voters[1:]]
        + [_Rep(p) for p in old[1:]])
    for r in node.replicators.all():
        r.classic_ok = False
    b = ReadConfirmBatcher()
    assert await asyncio.wait_for(b.confirm(node), 5) is False, \
        "a new-config-only majority must not confirm a joint-conf fence"


class _StallTransport(_BatchTransport):
    """multi_beat_fast stub where one destination is STALLED (not
    dead): its RPCs block on an event and only answer after release —
    the gray-failure shape a timeout never sees in time."""

    def __init__(self, stalled: set[str]):
        super().__init__()
        self.stalled = stalled
        self.release = asyncio.Event()

    async def call(self, dst, method, request, timeout_ms=None):
        if dst in self.stalled:
            await self.release.wait()
        return await super().call(dst, method, request, timeout_ms)


async def test_batcher_stalled_endpoint_delays_only_its_own_round():
    """The max_inflight_rounds windowing claim, proven under a STALLED
    (not dead) endpoint: the round whose destination stalls keeps only
    ITS stragglers waiting — fences for groups on healthy endpoints
    submitted afterwards keep resolving round after round, they never
    convoy behind the stalled RPC."""
    from tpuraft.rheakv.store_engine import ReadConfirmBatcher

    slow_voters = [PeerId.parse("127.0.0.1:7801"),
                   PeerId.parse("127.0.0.1:7898"),
                   PeerId.parse("127.0.0.1:7899")]
    fast_voters = _voters(7810)
    transport = _StallTransport({p.endpoint for p in slow_voters[1:]})
    slow_node = _batcher_node("slow", transport, slow_voters)
    b = ReadConfirmBatcher()

    stalled_fut = asyncio.ensure_future(b.confirm(slow_node))
    await asyncio.sleep(0.05)   # round 1 is now in flight, stalled
    assert not stalled_fut.done()

    # healthy-endpoint fences submitted AFTER the stall keep resolving
    for i in range(5):
        fast_node = _batcher_node(f"fast{i}", transport, fast_voters)
        ok = await asyncio.wait_for(b.confirm(fast_node), 1.0)
        assert ok, f"healthy fence {i} failed behind a stalled round"
    assert not stalled_fut.done(), "stalled round resolved early?"

    transport.release.set()
    assert await asyncio.wait_for(stalled_fut, 2.0) is True
    assert b.rounds >= 6


async def test_batcher_window_bounds_concurrent_stalled_rounds():
    """With max_inflight_rounds stalled rounds already in flight, the
    NEXT fence waits for a slot (bounded task pileup) — and gets it the
    moment any round completes."""
    from tpuraft.rheakv.store_engine import ReadConfirmBatcher

    voters_sets = [[PeerId.parse(f"127.0.0.1:{7900 + 10 * i}"),
                    PeerId.parse(f"127.0.0.1:{7901 + 10 * i}"),
                    PeerId.parse(f"127.0.0.1:{7902 + 10 * i}")]
                   for i in range(5)]
    stalled_eps = {p.endpoint for vs in voters_sets[:4] for p in vs[1:]}
    transport = _StallTransport(stalled_eps)
    b = ReadConfirmBatcher()
    assert b.max_inflight_rounds == 4
    stalled = []
    for i in range(4):
        node = _batcher_node(f"s{i}", transport, voters_sets[i])
        stalled.append(asyncio.ensure_future(b.confirm(node)))
        await asyncio.sleep(0.02)   # one round each, all stalled
    assert len(b._rounds_inflight) == 4
    fast_node = _batcher_node("fast", transport, voters_sets[4])
    waiting = asyncio.ensure_future(b.confirm(fast_node))
    await asyncio.sleep(0.05)
    assert not waiting.done(), "5th round ran past the window bound"
    transport.release.set()   # frees the stalled rounds -> slot opens
    assert await asyncio.wait_for(waiting, 2.0) is True
    for fut in stalled:
        assert await asyncio.wait_for(fut, 2.0) is True


@pytest.mark.parametrize("loop_tick", [None, "began_before_the_acks"])
async def test_rounds_closing_in_one_turn_share_one_forced_tick(loop_tick):
    """Three rounds over one engine, their RPCs answered in one turn:
    TWO device calls, where every round used to force its own.  The
    first destination's acks begin a tick at once (``tick_soon``), or
    find the engine loop's own in flight; whatever lands after that
    snapshot, the other rounds' acks, rides ONE tick after it, which the
    three rounds' closes share."""
    from tests.test_tick_overlap import device_fence_node, overlap_engine
    from tpuraft.rheakv.store_engine import ReadConfirmBatcher

    eng = overlap_engine()
    voters = _voters(8200)
    transport = _StallTransport({p.endpoint for p in voters[1:]})
    b = ReadConfirmBatcher()
    futs = []
    for i in range(3):
        node = device_fence_node(eng, f"g{i}", transport, voters)
        futs.append(asyncio.ensure_future(b.confirm(node)))
        await asyncio.sleep(0.01)       # a round each, all waiting
    assert b.rounds == 3 and len(b._rounds_inflight) == 3
    assert eng.ticks == 0
    loops = asyncio.ensure_future(eng.tick()) if loop_tick else None
    transport.release.set()
    assert await asyncio.wait_for(asyncio.gather(*futs), 5) == [True] * 3
    if loops is not None:
        assert await loops == 0
    assert eng.ticks == 2       # the first snapshot's, and one for the rest
    assert eng.log == ["call", "fetch"] * eng.ticks
    assert b.failed == 0 and b.device_fences == 3
    assert eng.fence_lane_resolves == 3 and eng._fence_waiters == {}


async def test_a_destinations_acks_begin_the_fences_tick_at_once():
    """The tick that confirms a round's device fences is begun in the
    turn its first destination answered, not when the engine loop has
    woken up or the round has closed, and the close then asks for
    none."""
    from tests.test_tick_overlap import device_fence_node, overlap_engine
    from tpuraft.rheakv.store_engine import ReadConfirmBatcher

    eng = overlap_engine()
    calls = []
    tick_soon = eng.tick_soon
    eng.tick_soon = lambda: calls.append(len(eng.log)) or tick_soon()
    nodes = [device_fence_node(eng, f"g{i}", _BatchTransport(), _voters(8400))
             for i in range(4)]
    b = ReadConfirmBatcher()
    outs = await asyncio.wait_for(
        asyncio.gather(*(b.confirm(n) for n in nodes)), 5)
    assert outs == [True] * 4 and b.rounds == 1 and b.failed == 0
    assert calls[0] == 0                # asked before any device call
    assert eng.ticks == 1 and eng.log == ["call", "fetch"]
    assert eng.fence_lane_resolves == 4 and eng._fence_waiters == {}


async def test_a_round_over_three_engines_enqueues_all_before_collecting():
    from tests.test_tick_overlap import device_fence_node, overlap_engine
    from tpuraft.rheakv.store_engine import ReadConfirmBatcher

    log: list = []
    engines = [overlap_engine(ready=False) for _ in range(3)]
    transport = _BatchTransport()
    nodes = []
    for i, eng in enumerate(engines):
        eng.log = log                   # one order over the three
        nodes.append(device_fence_node(eng, f"g{i}", transport,
                                       _voters(8300 + 10 * i)))
    b = ReadConfirmBatcher()
    outs = await asyncio.wait_for(
        asyncio.gather(*(b.confirm(n) for n in nodes)), 5)
    assert outs == [True] * 3 and b.rounds == 1
    assert log == ["call"] * 3 + ["fetch"] * 3
    assert [e.ticks for e in engines] == [1, 1, 1]
    assert all(e.tick_hists["tick_overlapped"].count == 1 for e in engines)


# ---------------------------------------------------------------------------
# integration: fence dedupe + batcher through the KV stack
# ---------------------------------------------------------------------------


async def test_kv_batch_reads_share_one_fence():
    """A kv_command_batch with N GETs for one region costs ONE
    read_index confirmation, not N."""
    from tests.kv_cluster import KVTestCluster
    from tpuraft.rheakv.kv_operation import KVOp, KVOperation
    from tpuraft.rheakv.kv_service import (
        KVCommandBatchRequest,
        decode_batch_reply,
        decode_result,
        encode_batch_item,
    )

    c = KVTestCluster(3)
    await c.start_all()
    try:
        leader_engine = await c.wait_region_leader(1)
        store = leader_engine.store_engine
        rs = leader_engine.raft_store
        for i in range(6):
            await rs.put(b"rf-%d" % i, b"v%d" % i)
        region = leader_engine.region
        items = [encode_batch_item(
            region.id, region.epoch.conf_ver, region.epoch.version,
            KVOperation(KVOp.GET, b"rf-%d" % i).encode())
            for i in range(6)]
        fences0 = store.kv_processor.read_fences
        resp = await store.kv_processor.handle_batch(
            KVCommandBatchRequest(items=items))
        assert len(resp.items) == 6
        for i, blob in enumerate(resp.items):
            code, _msg, result, _meta = decode_batch_reply(blob)
            assert code == 0
            assert decode_result(result) == b"v%d" % i
        assert store.kv_processor.read_fences == fences0 + 1
        assert store.kv_processor.fenced_reads >= 6
        # and the store-level batcher carried the confirmation
        assert store.read_batcher is not None
        assert store.read_batcher.confirms >= 1
    finally:
        await c.stop_all()


async def test_read_from_follower_serves_without_touching_leader_cache():
    """read_from='follower': GETs route to a follower store (served
    there after a forwarded-ReadIndex fence) and the client's leader
    cache is not poisoned by read routing."""
    from tests.kv_cluster import KVTestCluster
    from tpuraft.rheakv.client import BatchingOptions, RheaKVStore
    from tpuraft.rheakv.pd_client import FakePlacementDriverClient

    c = KVTestCluster(3)
    await c.start_all()
    pd = FakePlacementDriverClient([r.copy() for r in c.region_template])
    kv = RheaKVStore(pd, c.client_transport(),
                     batching=BatchingOptions(enabled=True),
                     read_from="follower")
    await kv.start()
    try:
        await c.wait_region_leader(1)
        for i in range(4):
            assert await kv.put(b"ff-%d" % i, b"w%d" % i)
        for _ in range(3):
            for i in range(4):
                assert await kv.get(b"ff-%d" % i) == b"w%d" % i
        served = kv.read_serves
        assert served["follower"] > 0, served
        # writes kept committing through the leader the whole time
        assert await kv.put(b"ff-last", b"z")
        assert await kv.get(b"ff-last") == b"z"
    finally:
        await kv.shutdown()
        await c.stop_all()


async def test_lease_reads_leave_hibernating_groups_hibernated():
    """Tentpole: with LEASE_BASED reads, a pure-read load against a
    hibernated engine-backed group serves linearizably while every
    replica STAYS quiescent (hub wake counters flat)."""
    from tests.test_quiescence import QuiesceCluster, _all_quiescent, \
        _commit, _wait
    from tpuraft.options import ReadOnlyOption as RO

    c = QuiesceCluster(3, 2, election_timeout_ms=400)
    await c.start_all()
    for node in c.nodes.values():
        node.options.raft_options.read_only_option = RO.LEASE_BASED
    try:
        gid = c.groups[0]
        leader = await c.wait_leader(gid)
        await _commit(leader, b"seed")
        await _wait(lambda: _all_quiescent(c, gid), 10.0, "group quiescent")
        hubs = [c.nodes[(gid, ep)].node_manager.heartbeat_hub
                for ep in c.endpoints]
        woken0 = sum(h.groups_woken for h in hubs)
        for _ in range(20):
            idx = await asyncio.wait_for(leader.read_index(), 5)
            assert idx >= 1
        assert _all_quiescent(c, gid), \
            "lease reads must not wake a hibernating group"
        assert sum(h.groups_woken for h in hubs) == woken0
        assert leader.read_only_service.lease_serves >= 1
    finally:
        await c.stop_all()


# ---------------------------------------------------------------------------
# wire compatibility (trailing read-plane extensions)
# ---------------------------------------------------------------------------


def test_read_index_response_wire_compat_both_directions():
    """ReadIndexResponse gained trailing (term, leader_hint).  Old
    frames (index, success only) must decode on new receivers with the
    defaults; new frames must be a strict extension an old decoder
    would simply stop before."""
    new = ReadIndexResponse(index=9, success=False, term=4,
                            leader_hint="127.0.0.1:7001")
    wire = encode_message(new)
    assert decode_message(wire) == new            # new <-> new
    # old sender -> new receiver: the old format is exactly
    # tid (u8) + index (i64) + success (u8); trailing term/leader_hint
    # take their defaults on decode
    old_wire = wire[:1 + 8 + 1]
    got = decode_message(old_wire)
    assert got == ReadIndexResponse(index=9, success=False,
                                    term=0, leader_hint="")
    # new -> old receiver: the old field prefix is byte-identical, so an
    # old decoder (which stops after success) reads the same values
    assert wire[:len(old_wire)] == old_wire
    # a genuinely truncated REQUIRED field still fails loudly
    with pytest.raises(Exception):
        decode_message(old_wire[:-1])


# ---------------------------------------------------------------------------
# check_stale_reads (the read-mix soak's targeted assertion)
# ---------------------------------------------------------------------------


def _h(ops_spec):
    """Build a History from (client, kind, args, invoke, ret, result)."""
    from tpuraft.util.linearizability import History

    h = History()
    for client, kind, args, invoke, ret, result in ops_spec:
        tok = h.invoke(client, kind, args, now=invoke)
        if ret is not None:
            h.complete(tok, result, now=ret)
    return h


def _seq(v):
    return int(v[1:]) if isinstance(v, bytes) and v[:1] == b"s" else -1


def test_stale_read_detected():
    from tpuraft.util.linearizability import check_stale_reads

    k = b"k"
    h = _h([
        (0, "w", (k, b"s1"), 1.0, 1.1, True),
        (0, "w", (k, b"s2"), 2.0, 2.1, True),     # acked at 2.1
        (1, "r", (k,), 3.0, 3.1, b"s1"),          # issued after: STALE
    ])
    v = check_stale_reads(h.ops(), _seq)
    assert len(v) == 1 and "stale read" in v[0]


def test_fresh_read_and_pending_write_explanation_pass():
    from tpuraft.util.linearizability import check_stale_reads

    k = b"k"
    h = _h([
        (0, "w", (k, b"s1"), 1.0, 1.1, True),
        (0, "w", (k, b"s2"), 2.0, None, None),    # timed out: maybe applied
        (0, "w", (k, b"s3"), 3.0, 3.1, True),     # acked
        (1, "r", (k,), 4.0, 4.1, b"s3"),          # fresh: ok
        # s2 landing in the log after s3 is linearizable (pending write
        # may take effect at any point after its invoke) — not stale
        (1, "r", (k,), 5.0, 5.1, b"s2"),
    ])
    assert check_stale_reads(h.ops(), _seq) == []


def test_read_concurrent_with_write_may_see_either():
    from tpuraft.util.linearizability import check_stale_reads

    k = b"k"
    h = _h([
        (0, "w", (k, b"s1"), 1.0, 1.1, True),
        (0, "w", (k, b"s2"), 2.0, 2.5, True),
        (1, "r", (k,), 2.2, 2.3, b"s1"),   # overlaps s2's window: ok
    ])
    assert check_stale_reads(h.ops(), _seq) == []
