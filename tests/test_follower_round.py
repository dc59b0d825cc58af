"""The follower's side of a store_append round (PR 35): begin every row in
the handler's turn, await the log's round once, finish every row.

One follower store: a ``NodeManager`` hosting G nodes on the multilog, no
leader process: the tests are the leader and hand it ``StoreAppendRequest``s.
"""

from __future__ import annotations

import asyncio
import threading

import pytest

from tests.cluster import MockStateMachine
from tpuraft.conf import Configuration
from tpuraft.core.node import Node, State
from tpuraft.core.node_manager import NodeManager
from tpuraft.entity import EntryType, LogEntry, LogId, PeerId
from tpuraft.errors import RaftError
from tpuraft.options import NodeOptions
from tpuraft.rpc.messages import (AppendEntriesRequest, AppendEntriesResponse,
                                  ErrorResponse, StoreAppendRequest)
from tpuraft.rpc.transport import (InProcNetwork, InProcTransport, RpcError,
                                   RpcServer)

LEADER = PeerId.parse("127.0.0.1:7100")
SELF = PeerId.parse("127.0.0.1:7101")
THIRD = PeerId.parse("127.0.0.1:7102")


class FollowerStore:
    """One endpoint, G follower nodes on one multilog engine."""

    def __init__(self, base, groups: int, election_timeout_ms: int = 60000):
        self.base = str(base)
        self.groups = [f"g{k}" for k in range(groups)]
        self.eto = election_timeout_ms
        self.net = InProcNetwork()
        self.server = RpcServer(SELF.endpoint)
        self.nm = NodeManager(self.server)
        self.net.bind(self.server)
        self.nodes: dict[str, Node] = {}

    async def start(self) -> "FollowerStore":
        transport = InProcTransport(self.net, SELF.endpoint)
        conf = Configuration([LEADER, SELF, THIRD])
        for gid in self.groups:
            opts = NodeOptions(
                election_timeout_ms=self.eto, initial_conf=conf.copy(),
                fsm=MockStateMachine(),
                log_uri=f"multilog://{self.base}/mlog#{gid}",
                raft_meta_uri="memory://")
            node = Node(gid, SELF, opts, transport)
            node.node_manager = self.nm
            self.nm.add(node)
            assert await node.init()
            self.nodes[gid] = node
        return self

    async def stop(self) -> None:
        for node in self.nodes.values():
            await node.shutdown()

    @property
    def engine(self):
        return next(iter(self.nodes.values())).log_manager._storage.engine

    def row(self, gid: str, term: int = 1, prev: int = 0, prev_term: int = 0,
            entries=(), committed: int = 0) -> AppendEntriesRequest:
        return AppendEntriesRequest(
            group_id=gid, server_id=str(LEADER), peer_id=str(SELF),
            term=term, prev_log_index=prev, prev_log_term=prev_term,
            committed_index=committed, entries=list(entries))

    async def serve(self, rows: list) -> list:
        resp = await self.nm._handle_store_append(StoreAppendRequest(rows=rows))
        return resp.acks

    async def follow(self, term: int = 1, groups=None) -> None:
        """Every node a follower of LEADER at ``term`` (a probe each: the
        first contact of a term steps the node down, on the waiting path)."""
        acks = await self.serve([self.row(g, term=term)
                                 for g in (groups or self.groups)])
        assert all(a.success for a in acks), acks

    def state(self, gid: str) -> tuple:
        n = self.nodes[gid]
        lm = n.log_manager
        return (n.state, n.current_term, str(n.leader_id),
                lm.last_log_index(), lm._stable_index,
                n.ballot_box.last_committed_index,
                [(lm.get_entry(i).id.term, lm.get_entry(i).data)
                 for i in range(1, lm.last_log_index() + 1)])

    def idle(self) -> bool:
        """No lock held or waited for, no lane claimed."""
        return (not self.nm._append_inflight
                and all(not n._lock.locked() and not n._lock._waiters
                        for n in self.nodes.values()))

    def hold_syncs(self) -> threading.Event:
        """The journal's fsync goes to the executor and waits there for
        the event this returns."""
        gate = threading.Event()
        eng = self.engine
        eng.group_commit._cost_ewma = 1.0       # measured slow: off the loop
        real = eng.sync

        def held_sync():
            assert gate.wait(20), "the test never released the fsync"
            real()

        eng.sync = held_sync
        return gate


def entries(first: int, n: int, term: int = 1, tag: bytes = b"e") -> list:
    return [LogEntry(type=EntryType.DATA, id=LogId(first + k, term),
                     data=tag + b"%d" % (first + k)) for k in range(n)]


def counts(nm: NodeManager) -> tuple:
    return nm.follower_rows.count, nm.follower_rows_inline.count


# -- (a) fast and slow path: equal responses, equal node state ---------------


async def _mixed_round(store: FollowerStore) -> tuple:
    """A round that mixes a success, a stale term, a prev-log mismatch, a
    probe without entries, an unknown node and a node with a claim
    outstanding; returns (acks, states)."""
    await store.follow(1, ["g0", "g2", "g3", "g4"])
    await store.follow(2, ["g1"])
    store.nm._append_inflight.add(("g4", str(SELF)))
    rows = [
        store.row("g0", entries=entries(1, 2), committed=1),
        store.row("g1", term=1, entries=entries(1, 1)),         # stale term
        store.row("g2", prev=5, prev_term=1, entries=entries(6, 1)),
        store.row("g3"),                                        # a probe
        store.row("nowhere", entries=entries(1, 1)),
        store.row("g4", entries=entries(1, 1)),
    ]
    acks = await store.serve(rows)
    store.nm._append_inflight.discard(("g4", str(SELF)))
    return acks, [store.state(g) for g in store.groups]


async def test_fast_and_slow_path_answer_and_leave_the_nodes_alike(tmp_path):
    fast = await FollowerStore(tmp_path / "fast", 5).start()
    slow = await FollowerStore(tmp_path / "slow", 5).start()
    for node in slow.nodes.values():
        node._try_lock = lambda: False      # every row has to wait its turn
    try:
        f0, s0 = counts(fast.nm), counts(slow.nm)
        fast_acks, fast_states = await _mixed_round(fast)
        slow_acks, slow_states = await _mixed_round(slow)
        assert fast_acks == slow_acks
        assert fast_states == slow_states
        ok, stale, gap, probe, unknown, busy = fast_acks
        assert ok == AppendEntriesResponse(
            term=1, success=True, last_log_index=2, multi_hb=True)
        assert (stale.success, stale.term) == (False, 2)
        assert (gap.success, gap.last_log_index) == (False, 0)
        assert probe.success and probe.last_log_index == 0
        assert isinstance(unknown, ErrorResponse) \
            and unknown.code == int(RaftError.ENOENT)
        assert isinstance(busy, ErrorResponse) \
            and busy.code == int(RaftError.EBUSY)
        assert fast_states[0][3:6] == (2, 2, 1)     # last, stable, committed
        # the mixed round: 6 rows, of them the four that reach a node with
        # no claim outstanding are served inline on the fast store (the two
        # first-contact rounds before it had to step down: none inline)
        rows, inline = counts(fast.nm)
        assert (rows - f0[0], inline - f0[1]) == (5 + 6, 4)
        rows, inline = counts(slow.nm)
        assert (rows - s0[0], inline - s0[1]) == (5 + 6, 0)
        assert fast.idle() and slow.idle()
    finally:
        await fast.stop()
        await slow.stop()


# -- (b) nothing a group: no task, no timer, no future -----------------------


class _LoopCounts:
    """What the running loop is asked to make while it is in force."""

    def __init__(self):
        self.loop = asyncio.get_running_loop()
        # every timer is a call_at (call_later is one, a moment later)
        self.made = {"create_task": 0, "create_future": 0, "call_at": 0}

    def __enter__(self):
        for name in self.made:
            real = getattr(self.loop, name)

            def counted(*a, _real=real, _name=name, **kw):
                self.made[_name] += 1
                return _real(*a, **kw)

            setattr(self.loop, name, counted)
        return self

    def __exit__(self, *exc):
        for name in self.made:
            delattr(self.loop, name)


async def _round_costs(tmp_path, groups: int) -> tuple:
    store = await FollowerStore(tmp_path / f"s{groups}", groups).start()
    try:
        await store.follow(1)
        rows0, inline0 = counts(store.nm)
        rounds0 = store.engine.group_commit.rounds.count
        tasks0 = len(asyncio.all_tasks())
        scheduled0 = len(asyncio.get_running_loop()._scheduled)
        with _LoopCounts() as c:
            job = asyncio.ensure_future(store.serve(
                [store.row(g, entries=entries(1, 1)) for g in store.groups]))
            await asyncio.sleep(0)          # begun; waiting for its round
            in_flight = len(asyncio.all_tasks()) - tasks0
            scheduled = len(c.loop._scheduled) - scheduled0
            acks = await job
        assert all(a.success and a.last_log_index == 1 for a in acks)
        rows, inline = counts(store.nm)
        assert (rows - rows0, inline - inline0) == (groups, groups)
        assert store.engine.group_commit.rounds.count - rounds0 == 1
        assert store.idle()
        return c.made, in_flight, scheduled
    finally:
        await store.stop()


async def test_a_64_group_round_makes_no_task_timer_or_future_a_group(tmp_path):
    small = await _round_costs(tmp_path, 8)
    large = await _round_costs(tmp_path, 64)
    assert large[0] == small[0], (small, large)     # a constant, not x8
    made, in_flight, _scheduled = large
    assert made["create_task"] == 1                 # the test's own job
    assert in_flight == 1
    assert made["create_future"] <= 1               # the one wait's
    assert made["call_at"] == 1                     # the RPC's deadline
    assert large[2] == small[2] == 1                # on the loop's heap


# -- (c) the deadline: EBUSY now, the finish when the round lands ------------


async def test_a_round_past_the_deadline_answers_ebusy_and_finishes_late(
        tmp_path):
    store = await FollowerStore(tmp_path, 3).start()
    try:
        await store.follow(1)
        for node in store.nodes.values():
            node.options.election_timeout_ms = 400      # deadline: 0.2 s
        gate = store.hold_syncs()
        rows = [store.row("g0", entries=entries(1, 2), committed=2),
                store.row("g1"),                        # a probe: no wait
                store.row("g2", entries=entries(1, 1))]
        t0 = asyncio.get_running_loop().time()
        acks = await store.serve(rows)
        waited = asyncio.get_running_loop().time() - t0
        assert 0.15 < waited < 2.0
        sent = list(acks)
        assert [isinstance(a, ErrorResponse) and a.code == int(RaftError.EBUSY)
                for a in acks] == [True, False, True]
        assert acks[1].success
        # staged, not stable, the lock and the lane still theirs
        assert store.nodes["g0"].log_manager._stable_index == 0
        assert store.nodes["g0"]._lock.locked()
        assert ("g0", str(SELF)) in store.nm._append_inflight
        # a retry meanwhile is refused at once
        again = await store.serve([store.row("g0", prev=2, prev_term=1,
                                             entries=entries(3, 1))])
        assert again[0].code == int(RaftError.EBUSY)
        gate.set()
        for _ in range(200):
            if store.idle():
                break
            await asyncio.sleep(0.01)
        assert store.idle()
        assert store.state("g0")[3:6] == (2, 2, 2)
        assert store.state("g2")[3:6] == (1, 1, 0)
        assert acks == sent and acks[0] is sent[0]      # the reply untouched
        rows_n, inline_n = counts(store.nm)
        assert rows_n - inline_n >= 2       # the two late rows are not inline
    finally:
        gate.set()
        await store.stop()


# -- (d) a row that has to wait; the others are still served inline -----------


async def test_step_down_and_truncation_wait_beside_inline_rows(tmp_path):
    store = await FollowerStore(tmp_path, 4).start()
    try:
        await store.follow(3)
        first = await store.serve(
            [store.row(g, term=3, entries=entries(1, 2, term=1))
             for g in store.groups])
        assert all(a.success for a in first)
        rows0, inline0 = counts(store.nm)
        rows = [
            # a higher term: the node steps down first
            store.row("g0", term=4, prev=2, prev_term=1,
                      entries=entries(3, 1, term=4)),
            # index 2 holds term 1, the leader's has term 2: truncate first
            store.row("g1", term=3, prev=1, prev_term=1,
                      entries=entries(2, 1, term=2, tag=b"n")
                      + entries(3, 1, term=3, tag=b"n")),
            store.row("g2", term=3, prev=2, prev_term=1,
                      entries=entries(3, 1, term=3)),
            store.row("g3", term=3, prev=2, prev_term=1),
        ]
        acks = await store.serve(rows)
        assert [a.success for a in acks] == [True] * 4
        assert [a.term for a in acks] == [4, 3, 3, 3]
        assert [a.last_log_index for a in acks] == [3, 3, 3, 2]
        rows_n, inline_n = counts(store.nm)
        assert (rows_n - rows0, inline_n - inline0) == (4, 2)
        assert store.nodes["g0"].current_term == 4
        assert store.state("g1")[6] == [(1, b"e1"), (2, b"n2"), (3, b"n3")]
        assert store.idle()
    finally:
        await store.stop()


async def test_rows_of_one_node_run_in_batch_order(tmp_path):
    store = await FollowerStore(tmp_path, 2).start()
    try:
        await store.follow(1)
        rows0, inline0 = counts(store.nm)
        acks = await store.serve([
            store.row("g0", entries=entries(1, 2)),
            store.row("g1", entries=entries(1, 1)),
            store.row("g0", prev=2, prev_term=1, entries=entries(3, 2)),
        ])
        assert [a.success for a in acks] == [True] * 3
        assert [a.last_log_index for a in acks] == [2, 1, 4]
        rows_n, inline_n = counts(store.nm)
        assert (rows_n - rows0, inline_n - inline0) == (3, 1)
        assert store.idle()
    finally:
        await store.stop()


async def test_a_held_lock_sends_its_row_to_wait_not_the_round(tmp_path):
    store = await FollowerStore(tmp_path, 3).start()
    try:
        await store.follow(1)
        rows0, inline0 = counts(store.nm)
        held = store.nodes["g1"]._lock
        await held.acquire()
        job = asyncio.ensure_future(store.serve(
            [store.row(g, entries=entries(1, 1)) for g in store.groups]))
        await asyncio.sleep(0.05)
        assert not job.done()
        assert store.state("g0")[3:5] == (1, 1)     # the others are through
        held.release()
        acks = await job
        assert all(a.success for a in acks)
        rows_n, inline_n = counts(store.nm)
        assert (rows_n - rows0, inline_n - inline0) == (3, 2)
        assert store.idle()
    finally:
        await store.stop()


# -- (e) no success before the fsync ------------------------------------------


async def test_no_success_is_built_before_the_rounds_fsync_returned(
        tmp_path, monkeypatch):
    import tpuraft.core.node as node_mod

    store = await FollowerStore(tmp_path, 16).start()
    try:
        await store.follow(1)
        order: list = []
        eng = store.engine
        real_sync = eng.sync

        def sync():
            order.append("sync>")
            real_sync()
            order.append("<sync")

        eng.sync = sync

        def recording_response(**kw):
            order.append(("ack", kw["success"]))
            return AppendEntriesResponse(**kw)

        monkeypatch.setattr(node_mod, "AppendEntriesResponse",
                            recording_response)
        acks = await store.serve(
            [store.row(g, entries=entries(1, 3)) for g in store.groups])
        assert all(a.success and a.last_log_index == 3 for a in acks)
        assert order[:2] == ["sync>", "<sync"]
        assert order[2:] == [("ack", True)] * 16
    finally:
        await store.stop()


# -- (f) a crash in the middle of a round -------------------------------------


async def test_a_crash_in_a_round_leaves_no_lock_and_delivers_no_reply(
        tmp_path):
    store = await FollowerStore(tmp_path, 4).start()
    try:
        await store.follow(1)
        gate = store.hold_syncs()
        caller = InProcTransport(store.net, LEADER.endpoint)
        call = asyncio.ensure_future(caller.call(
            SELF.endpoint, "store_append", StoreAppendRequest(rows=[
                store.row(g, entries=entries(1, 1)) for g in store.groups]),
            timeout_ms=10000))
        for _ in range(100):
            if len(store.nm._append_inflight) == 4:
                break
            await asyncio.sleep(0.01)
        assert all(n._lock.locked() for n in store.nodes.values())
        # the store goes as StoreEngine.crash() takes it: endpoint first
        store.net.stop_endpoint(SELF.endpoint)
        for node in store.nodes.values():
            node.crash()
            store.nm.remove(node)
        gate.set()
        with pytest.raises(RpcError) as err:
            await call
        assert err.value.status.code == int(RaftError.EHOSTDOWN)
        for _ in range(200):
            if store.idle():
                break
            await asyncio.sleep(0.01)
        assert store.idle()
        assert all(n.state == State.SHUTDOWN for n in store.nodes.values())
    finally:
        gate.set()
        store.nodes.clear()     # crashed: nothing to shut down


# -- the lock taken without a coroutine ---------------------------------------


async def test_try_lock_is_an_uncontended_acquire_and_nothing_else(tmp_path):
    store = await FollowerStore(tmp_path, 1).start()
    try:
        node = store.nodes["g0"]
        lock = node._lock
        assert node._try_lock() and lock.locked()
        assert not node._try_lock()             # held
        waiter = asyncio.ensure_future(lock.acquire())
        await asyncio.sleep(0)
        assert not waiter.done()
        lock.release()                          # wakes the waiter ...
        assert not lock.locked()
        assert not node._try_lock()             # ... and the lock is its
        assert await waiter and lock.locked()
        lock.release()
        assert node._try_lock()                 # free again
        async with asyncio.timeout(1):
            other = asyncio.ensure_future(lock.acquire())
            await asyncio.sleep(0)
            lock.release()                      # a try-locked lock releases
            assert await other                  # as any other and hands over
        lock.release()
        assert store.idle()
    finally:
        await store.stop()
