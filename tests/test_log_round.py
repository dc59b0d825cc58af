"""The log's flush round is the loop turn (ISSUE 32): every group of a
store that stages in one turn of the event loop shares ONE ``tlm_sync``
and ONE future; the round is synced on the loop thread while the disk is
measured fast and in an executor thread when it is not; between a staged
entry and its acknowledgement there is no task and no future of the
group's own.  The guarantee is what it was: nothing is acknowledged
before an fsync that began after its append returned."""

import asyncio
import threading
import time

import pytest

from tests.test_multilog import _available, mk_storage
from tests.test_storage import mk_entries
from tpuraft.errors import RaftError, RaftException
from tpuraft.storage.log_manager import LogManager

pytestmark = pytest.mark.skipif(not _available(),
                                reason="C++ multilog engine not buildable")

PATHS = ["inline", "executor"]


def pin_path(gc, path: str) -> None:
    """Hold a group commit to one side of its rule for a whole test: the
    EWMA moves with every round, the ceiling does not."""
    gc.INLINE_MAX_S = 1e9 if path == "inline" else 0.0


async def mk_managers(tmp_path, n: int, prefix: str = "g") -> list:
    lms = [LogManager(mk_storage(tmp_path, f"{prefix}{k}"))
           for k in range(n)]
    for lm in lms:
        await lm.init()
    return lms


def turn_counter(loop) -> list:
    """[turns of ``loop`` so far]; stop it by appending anything."""
    box = [0]

    def tick():
        if len(box) == 1:
            box[0] += 1
            loop.call_soon(tick)

    loop.call_soon(tick)
    return box


# -- one round a store a turn ------------------------------------------------


@pytest.mark.parametrize("path", PATHS)
async def test_groups_staged_in_one_turn_share_one_fsync(tmp_path, path):
    n = 32
    lms = await mk_managers(tmp_path, n)
    eng = lms[0]._storage.engine
    gc = eng.group_commit
    pin_path(gc, path)
    events = []
    real_sync = eng.sync
    eng.sync = lambda: (real_sync(), events.append("sync"))
    sync0 = eng.sync_count

    async def one(k):
        await lms[k].append_entries_leader(mk_entries(1, 2), 1)
        events.append(k)

    try:
        await asyncio.gather(*(one(k) for k in range(n)))
        assert eng.sync_count - sync0 == 1
        # the one fsync, then every group's acknowledgement
        assert events[0] == "sync" and sorted(events[1:]) == list(range(n))
        assert all(lm._stable_index == 2 for lm in lms)
        assert (gc.rounds.count, gc.round_groups.count,
                gc.round_inline.count) == (1, n, int(path == "inline"))
        assert not gc._lanes                 # nothing is left open
    finally:
        for lm in lms:
            await lm.shutdown()


async def test_a_lone_stager_is_synced_the_turn_after_it_staged(tmp_path):
    """No linger and no timer: the close is the next callback of the
    loop, the resumption the one after."""
    (lm,) = await mk_managers(tmp_path, 1)
    eng = lm._storage.engine
    loop = asyncio.get_running_loop()
    turns = turn_counter(loop)
    synced_at = []
    real_sync = eng.sync
    eng.sync = lambda: (synced_at.append(turns[0]), real_sync())
    try:
        await asyncio.sleep(0)
        staged_at = turns[0]
        await lm.append_entries_leader(mk_entries(1, 1), 1)
        resumed_at = turns[0]
        assert synced_at == [staged_at + 1]
        assert resumed_at == staged_at + 2
        assert lm._stable_index == 1
    finally:
        turns.append("stop")
        await lm.shutdown()


async def test_per_group_order_under_interleaved_stagers(tmp_path):
    """Groups interleave in one turn, and a group stages twice in it:
    each group's entries reach the journal in the caller's order, and the
    group's two stagings are one stake in the round (one continuation)."""
    n = 6
    lms = await mk_managers(tmp_path, n, "o")
    eng = lms[0]._storage.engine
    sync0 = eng.sync_count
    stable_calls = [[] for _ in range(n)]
    for k, lm in enumerate(lms):
        lm.on_stable = stable_calls[k].append

    def entries(k, first, count):
        out = mk_entries(first, count, term=1)
        for e in out:
            e.data = b"g%d-i%d" % (k, e.id.index)
        return out

    try:
        oks = await asyncio.gather(*(
            lms[k].append_entries_follower(first - 1, int(first > 1),
                                           entries(k, first, 2))
            for first in (1, 3, 5) for k in range(n)))
        assert all(oks)
        assert eng.sync_count - sync0 == 1
        for k, lm in enumerate(lms):
            assert lm._stable_index == 6 and stable_calls[k] == [6]
            assert lm._inflight_flushes == 0 and lm._ride is None
    finally:
        for lm in lms:
            await lm.shutdown()
    # what the journal holds, read back cold
    for k in range(n):
        s = mk_storage(tmp_path, f"o{k}")
        s.init()
        try:
            assert s.last_log_index() == 6
            assert [s.get_entry(i).data for i in range(1, 7)] == \
                [b"g%d-i%d" % (k, i) for i in range(1, 7)]
        finally:
            s.shutdown()


# -- the guarantee -------------------------------------------------------------


@pytest.mark.parametrize("path", PATHS)
async def test_no_acknowledgement_before_the_fsync_that_covers_it(
        tmp_path, path):
    """Leader's self-ballot and follower's AppendEntriesResponse, on a
    live three-node group: the index acknowledged is at most what the
    journal held when a COMPLETED sync of that store BEGAN."""
    from tests.cluster import TestCluster

    c = TestCluster(3, tmp_path, log_scheme="multilog")
    await c.start_all()
    loop_thread = threading.get_ident()
    covered = {}
    acks = {"leader": 0, "follower": 0}
    sync_threads = set()
    try:
        for peer, node in c.nodes.items():
            storage = node.log_manager._storage
            eng = storage.engine
            pin_path(eng.group_commit, path)
            covered[peer] = storage.last_log_index()

            def sync(peer=peer, storage=storage, real=eng.sync):
                sync_threads.add(threading.get_ident())
                staged = storage.last_log_index()   # appended so far
                real()
                covered[peer] = max(covered[peer], staged)

            eng.sync = sync

            def commit_at(who, index, *a, peer=peer,
                          real=node.ballot_box.commit_at, **kw):
                if who == peer:
                    assert index <= covered[peer], \
                        f"{peer} balloted {index} before its fsync"
                    acks["leader"] += 1
                return real(who, index, *a, **kw)

            node.ballot_box.commit_at = commit_at

            async def handle(req, peer=peer,
                             real=node.handle_append_entries):
                resp = await real(req)
                if req.entries and resp.success:
                    last = req.prev_log_index + len(req.entries)
                    assert last <= covered[peer], \
                        f"{peer} acked {last} before its fsync"
                    acks["follower"] += 1
                return resp

            node.handle_append_entries = handle
        leader = await c.wait_leader()
        sts = await asyncio.gather(*(c.apply_ok(leader, b"op%d" % i)
                                     for i in range(40)))
        assert all(st.is_ok() for st in sts)
        await c.wait_applied(40)
        assert acks["leader"] > 0 and acks["follower"] > 0
        if path == "inline":
            assert sync_threads == {loop_thread}
        else:
            assert loop_thread not in sync_threads
    finally:
        await c.stop_all()


# -- a failing round -------------------------------------------------------------


@pytest.mark.parametrize("path", PATHS)
async def test_a_failing_round_fails_every_stager_and_rolls_back_once(
        tmp_path, path):
    n = 5
    lms = await mk_managers(tmp_path, n, "f")
    eng = lms[0]._storage.engine
    pin_path(eng.group_commit, path)
    errors = [[] for _ in range(n)]
    for k, lm in enumerate(lms):
        lm.on_storage_error = errors[k].append
    real_sync = eng.sync
    eng.sync = lambda: (_ for _ in ()).throw(IOError("injected EIO"))
    try:
        # group 0 stages twice in the turn: still one roll-back
        got = await asyncio.gather(
            lms[0].append_entries_follower(0, 0, mk_entries(1, 2)),
            lms[0].append_entries_follower(2, 1, mk_entries(3, 1)),
            *(lm.append_entries_leader(mk_entries(1, 2), 1)
              for lm in lms[1:]),
            return_exceptions=True)
        assert len(got) == n + 1
        for g in got:
            assert isinstance(g, RaftException), g
            assert g.status.code == RaftError.EIO
            assert "injected EIO" in str(g.status)
        assert [len(e) for e in errors] == [1] * n       # once each
        assert all(lm._stable_index == 0 for lm in lms)  # never acked
        assert all(lm._inflight_flushes == 0 for lm in lms)
        # memory converged on what the journal holds; the next round
        # (the disk healed) carries on from there
        eng.sync = real_sync
        for lm in lms:
            assert lm.last_log_index() == lm._storage.last_log_index()
            base = lm.last_log_index()
            await lm.append_entries_follower(
                base, 1, mk_entries(base + 1, 1))
            assert lm._stable_index == base + 1
    finally:
        eng.sync = real_sync
        for lm in lms:
            await lm.shutdown()


async def test_a_group_whose_append_fails_fails_alone(tmp_path):
    """The round appends every rider's entries in one native call; a
    group whose own frames are refused (here: not contiguous with its
    journal) gets the retryable EIO and its roll-back, and the other
    riders of the same round are appended, synced and acknowledged."""
    a, b, c = await mk_managers(tmp_path, 3, "q")
    hooks = []
    a.on_storage_error = hooks.append
    try:
        await asyncio.gather(*(lm.append_entries_leader(mk_entries(1, 2), 1)
                               for lm in (a, b, c)))
        # a's journal moves on behind its LogManager's back
        a._storage.append_entries(mk_entries(3, 1), sync=True)
        got = await asyncio.gather(
            a.append_entries_leader(mk_entries(3, 1), 1),
            b.append_entries_leader(mk_entries(3, 1), 1),
            c.append_entries_leader(mk_entries(3, 1), 1),
            return_exceptions=True)
        assert isinstance(got[0], RaftException)
        assert got[0].status.code == RaftError.EIO
        assert "non-contiguous" in str(got[0].status)
        assert got[1].index == got[2].index == 3
        assert (b._stable_index, c._stable_index) == (3, 3)
        assert len(hooks) == 1
        # a's memory converged on its journal: the retry lands
        assert a.last_log_index() == a._storage.last_log_index() == 3
        await a.append_entries_follower(3, 1, mk_entries(4, 1))
        assert a._stable_index == 4
    finally:
        for lm in (a, b, c):
            await lm.shutdown()


async def test_a_full_disk_fails_the_round_and_the_next_one_lands(tmp_path):
    """The staging refused for want of space (the engine's fault gate,
    where the one write of the round would fail): every rider gets the
    same retryable EIO, nothing is appended, and once there is room the
    same entries go in."""
    a, b = await mk_managers(tmp_path, 2, "w")
    eng = a._storage.engine
    hooks = []
    a.on_storage_error = b.on_storage_error = hooks.append

    def gate(nbytes):
        raise OSError(28, "No space left on device")

    try:
        eng.fault_gate = gate
        got = await asyncio.gather(
            a.append_entries_leader(mk_entries(1, 2), 1),
            b.append_entries_leader(mk_entries(1, 2), 1),
            return_exceptions=True)
        assert all(isinstance(g, RaftException)
                   and g.status.code == RaftError.EIO for g in got)
        assert len(hooks) == 2
        assert a.last_log_index() == b.last_log_index() == 0
        assert a._storage.last_log_index() == 0
        eng.fault_gate = None
        await asyncio.gather(a.append_entries_leader(mk_entries(1, 2), 1),
                             b.append_entries_leader(mk_entries(1, 2), 1))
        assert (a._stable_index, b._stable_index) == (2, 2)
    finally:
        eng.fault_gate = None
        await a.shutdown()
        await b.shutdown()


async def test_a_failed_flush_withdraws_the_groups_open_stake(tmp_path):
    """"Fail everything in flight": when a flush of a group fails while
    the group has a stake in a round still open, that stake is withdrawn
    (never appended) and its callers get the same error, so memory and
    journal roll back to the same place."""
    from concurrent.futures import ThreadPoolExecutor

    a, b = await mk_managers(tmp_path, 2, "x")
    loop = asyncio.get_running_loop()
    pool = ThreadPoolExecutor(max_workers=1)
    loop.set_default_executor(pool)
    eng = a._storage.engine
    pin_path(eng.group_commit, "executor")
    gate = threading.Event()
    real_sync = eng.sync
    fail = [True]

    def sync():
        gate.wait(5)
        if fail and fail.pop():
            raise IOError("injected EIO")
        real_sync()

    eng.sync = sync
    try:
        first = asyncio.ensure_future(
            a.append_entries_follower(0, 0, mk_entries(1, 2)))
        await asyncio.sleep(0.01)            # appended, with the executor
        second = asyncio.ensure_future(
            a.append_entries_follower(2, 1, mk_entries(3, 2)))
        other = asyncio.ensure_future(
            b.append_entries_follower(0, 0, mk_entries(1, 2)))
        await asyncio.sleep(0.01)            # both staked in the open round
        gate.set()
        for t in (first, second):
            with pytest.raises(RaftException):
                await t
        assert await other is True and b._stable_index == 2
        # entries 3..4 never reached the journal; 1..2 did, unsynced
        assert a._storage.last_log_index() == 2 == a.last_log_index()
        assert a._stable_index == 0 and a._inflight_flushes == 0
    finally:
        gate.set()
        await a.shutdown()
        await b.shutdown()
        pool.shutdown(wait=False)


async def test_a_failing_round_steps_the_leader_down_unballoted(tmp_path):
    """The leader whose fsync failed never counts itself toward the
    entry's quorum (the followers may still commit it: their disks are
    fine), fails the proposal retryably and gives up the term."""
    from tests.cluster import TestCluster
    from tpuraft.core.node import State

    c = TestCluster(3, tmp_path, log_scheme="multilog")
    await c.start_all()
    try:
        leader = await c.wait_leader()
        assert (await c.apply_ok(leader, b"before")).is_ok()
        term = leader.current_term
        eng = leader.log_manager._storage.engine
        real_sync = eng.sync
        failed = []

        def failing_sync():
            failed.append(1)
            raise IOError("injected EIO")

        eng.sync = failing_sync
        own = []
        real_commit_at = leader.ballot_box.commit_at
        leader.ballot_box.commit_at = lambda who, index, *a, **kw: (
            own.append(index) if who == leader.server_id else None,
            real_commit_at(who, index, *a, **kw))[1]
        st = await c.apply_ok(leader, b"unsynced", retry=False)
        assert failed and not st.is_ok() and not own
        deadline = time.monotonic() + 5
        while leader.state == State.LEADER and leader.current_term == term:
            assert time.monotonic() < deadline, "leader kept its term"
            await asyncio.sleep(0.01)
        eng.sync = real_sync
        st = await c.apply_ok(await c.wait_leader(10), b"after",
                              timeout_s=10)
        assert st.is_ok()
    finally:
        await c.stop_all()


# -- where the fsync runs ----------------------------------------------------------


class _SpyProbe:
    """A disk probe that records the thread of each call."""

    def __init__(self):
        self.calls = []

    def begin(self):
        self.calls.append(("begin", threading.get_ident()))
        return len(self.calls)

    def end(self, tok):
        self.calls.append(("end", threading.get_ident()))

    def note(self, dur):
        self.calls.append(("note", threading.get_ident()))


async def test_a_slow_disk_never_syncs_on_the_loop_thread(tmp_path):
    """At or over ``INLINE_MAX_S`` the loop thread never calls ``sync``,
    the stall token is taken on the loop and given back in the I/O
    thread, and the EWMA is fed from there; the loop-thread close comes
    back only after executor rounds measured the disk fast again."""
    stores = [mk_storage(tmp_path, f"s{k}") for k in range(4)]
    for s in stores:
        s.init()
    eng = stores[0].engine
    gc = eng.group_commit
    gc.health_probe = probe = _SpyProbe()
    me = threading.get_ident()
    sync_threads = []
    slow = [True]

    def sync():
        sync_threads.append(threading.get_ident())
        if slow[0]:
            time.sleep(0.003)

    eng.sync = sync
    gc._cost_ewma = gc.INLINE_MAX_S          # AT the ceiling: banned
    try:
        for r in range(4):
            await asyncio.gather(*(
                s.append_entries_async(mk_entries(r + 1, 1), sync=True)
                for s in stores))
        assert len(sync_threads) == 4 and me not in sync_threads
        assert gc.rounds.count == 4 and gc.round_inline.count == 0
        assert [c for c in probe.calls if c[1] == me] == \
            [("begin", me)] * 4
        off = [c[0] for c in probe.calls if c[1] != me]
        assert off == ["end", "note"] * 4
        assert gc._cost_ewma >= gc.INLINE_MAX_S
        # the disk recovers: the executor rounds say so, then the loop
        slow[0] = False
        rounds = 0
        while gc.round_inline.count == 0:
            rounds += 1
            assert rounds < 40, "the ban never lifted"
            await stores[0].append_entries_async(
                mk_entries(4 + rounds, 1), sync=True)
        assert rounds > 1 and sync_threads[-1] == me
        assert me not in sync_threads[:-1]
    finally:
        for s in stores:
            s.shutdown()


async def test_turns_that_stage_behind_an_executor_round_share_the_next(
        tmp_path):
    """One fsync in flight per loop: what stages while it runs, over
    however many turns, is ONE round behind it (group commit)."""
    from concurrent.futures import ThreadPoolExecutor

    stores = [mk_storage(tmp_path, f"b{k}") for k in range(5)]
    for s in stores:
        s.init()
    loop = asyncio.get_running_loop()
    pool = ThreadPoolExecutor(max_workers=1)
    loop.set_default_executor(pool)
    eng = stores[0].engine
    gc = eng.group_commit
    pin_path(gc, "executor")
    gate = threading.Event()
    real_sync = eng.sync
    syncs = []
    eng.sync = lambda: (gate.wait(5), syncs.append(1), real_sync())
    try:
        first = stores[0].append_entries_async(mk_entries(1, 1), sync=True)
        await asyncio.sleep(0.01)            # appended, with the executor
        behind = []
        for s in stores[1:]:
            behind.append(s.append_entries_async(mk_entries(1, 1),
                                                 sync=True))
            await asyncio.sleep(0)           # a turn each
        assert len({id(st.future) for st in behind}) == 1
        assert behind[0].future is not first.future
        assert not first.future.done() and not behind[0].future.done()
        gate.set()
        iv1 = await first
        iv2 = await behind[0]
        assert iv1[1] <= iv2[0]              # the second began after
        # (the engine itself skips a sync that finds nothing unsynced)
        assert len(syncs) == 2
        assert (gc.rounds.count, gc.round_groups.count) == (2, 5)
        assert not gc._lanes
    finally:
        gate.set()
        for s in stores:
            s.shutdown()
        pool.shutdown(wait=False)


# -- loops, cancellation, shutdown ---------------------------------------------


def test_a_round_belongs_to_the_loop_that_opened_it(tmp_path):
    """Two event loops (two stores' threads) over one engine: each
    loop's rounds are closed, synced and resolved on its own thread."""
    from tpuraft.storage.multilog import get_engine, _release_engine

    keep = get_engine(str(tmp_path / "mlog"))    # outlives both loops
    sync_threads = []
    real_sync = keep.sync
    keep.sync = lambda: (sync_threads.append(threading.get_ident()),
                         real_sync())
    pin_path(keep.group_commit, "inline")
    rounds = 20
    seen = {}
    errors = []
    barrier = threading.Barrier(2)

    def worker(k):
        async def run():
            stores = [mk_storage(tmp_path, f"l{k}-{j}") for j in range(3)]
            for s in stores:
                s.init()
            try:
                for i in range(rounds):
                    got = await asyncio.gather(*(
                        s.append_entries_async(mk_entries(i + 1, 1),
                                               sync=True) for s in stores))
                    assert len(set(got)) == 1 and got[0][2] is False
                    await asyncio.sleep(0.0005 * k)
            finally:
                for s in stores:
                    s.shutdown()

        barrier.wait(timeout=30)
        seen[k] = threading.get_ident()
        try:
            asyncio.run(run())
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(k,)) for k in (1, 2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    try:
        assert not any(t.is_alive() for t in threads), "a loop hung"
        assert not errors, errors
        gc = keep.group_commit
        assert gc.rounds.count == gc.round_inline.count == 2 * rounds
        assert gc.round_groups.count == 6 * rounds
        assert sorted(set(sync_threads)) == sorted(seen.values())
        assert all(sync_threads.count(t) == rounds for t in seen.values())
    finally:
        _release_engine(keep)


async def test_a_cancelled_stager_does_not_cancel_the_round(tmp_path):
    """The round's future is shared: cancelling one rider leaves the
    others their fsync, and the cancelled group's entries are stable all
    the same (its continuation is the round's callback, not its task)."""
    a, b = await mk_managers(tmp_path, 2, "c")
    try:
        ta = asyncio.ensure_future(
            a.append_entries_leader(mk_entries(1, 2), 1))
        tb = asyncio.ensure_future(
            b.append_entries_leader(mk_entries(1, 2), 1))
        await asyncio.sleep(0)               # both staged, round open
        assert a._inflight_flushes == b._inflight_flushes == 1
        ta.cancel()
        assert (await tb).index == 2
        with pytest.raises(asyncio.CancelledError):
            await ta
        assert (a._stable_index, b._stable_index) == (2, 2)
        assert a._inflight_flushes == 0
    finally:
        await a.shutdown()
        await b.shutdown()


@pytest.mark.parametrize("path", PATHS)
async def test_shutdown_waits_for_the_open_round(tmp_path, path):
    """A LogManager that shuts down with a round open gives its storage
    back only after that round landed: the rider is acknowledged, the
    entries are in the journal."""
    a, b = await mk_managers(tmp_path, 2, "d")
    eng = a._storage.engine
    pin_path(eng.group_commit, path)
    order = []
    real_sync, real_down = eng.sync, a._storage.shutdown
    eng.sync = lambda: (order.append("sync"), real_sync())
    a._storage.shutdown = lambda: (order.append("storage down"),
                                   real_down())
    try:
        task = asyncio.ensure_future(
            a.append_entries_leader(mk_entries(1, 3), 1))
        await asyncio.sleep(0)               # staged, the round is open
        assert not task.done() and a._inflight_flushes == 1
        await a.shutdown()
        assert order == ["sync", "storage down"]
        assert (await task).index == 3 and a._stable_index == 3
    finally:
        await b.shutdown()
    s = mk_storage(tmp_path, "d0")
    s.init()
    try:
        assert s.last_log_index() == 3
    finally:
        s.shutdown()


async def test_an_engine_closed_under_an_open_round_fails_it_cleanly(
        tmp_path):
    s = mk_storage(tmp_path, "z")
    s.init()
    staged = s.append_entries_async(mk_entries(1, 1), sync=True)
    s.shutdown()                             # last reference: closed
    with pytest.raises(IOError, match="closed"):
        await staged


async def test_a_round_that_crosses_a_journal_boundary(tmp_path):
    """The round's one staging call rotates the journal in mid-round when
    it fills: every rider's entries are readable, in order, cold."""
    n = 8
    stores = [mk_storage(tmp_path, f"j{k}", seg_max=4096) for k in range(n)]
    for s in stores:
        s.init()
    eng = stores[0].engine
    files0 = eng.file_count
    try:
        for r in range(3):
            first = 3 * r + 1
            got = await asyncio.gather(*(
                s.append_entries_async(
                    mk_entries(first, 3, term=1, size=300 + k), sync=True)
                for k, s in enumerate(stores)))
            assert len(set(got)) == 1
        assert eng.file_count > files0 + 1          # rotated in mid-round
    finally:
        for s in stores:
            s.shutdown()
    for k in range(n):
        s = mk_storage(tmp_path, f"j{k}")
        s.init()
        try:
            assert (s.first_log_index(), s.last_log_index()) == (1, 9)
            assert [len(s.get_entry(i).data) for i in range(1, 10)] == \
                [300 + k] * 9
        finally:
            s.shutdown()


# -- a store's two roles in one round (ISSUE 36) -----------------------------


async def test_a_round_counts_as_mixed_only_when_both_roles_rode_it(
        tmp_path):
    """``rounds_mixed`` takes one sample for a round that carried a
    leader's staging AND a follower's: a store that only leads, or only
    follows, has none; the inline follower round (``begin_follower_append``)
    counts as the waiting path does."""
    lms = await mk_managers(tmp_path, 4)
    gc = lms[0]._storage.engine.group_commit
    try:
        # two leaders' stagings in one turn: one round, not mixed
        await asyncio.gather(*(lm.append_entries_leader(mk_entries(1, 1), 1)
                               for lm in lms[:2]))
        assert (gc.rounds.count, gc.rounds_mixed.count) == (1, 0)
        # two followers' appends in one turn: one round, not mixed
        assert await asyncio.gather(*(
            lm.append_entries_follower(0, 0, mk_entries(1, 1))
            for lm in lms[2:])) == [True, True]
        assert (gc.rounds.count, gc.rounds_mixed.count) == (2, 0)
        # a leader's and a follower's in one turn: one round, mixed
        await asyncio.gather(
            lms[0].append_entries_leader(mk_entries(2, 1), 1),
            lms[2].append_entries_follower(1, 1, mk_entries(2, 1)))
        assert (gc.rounds.count, gc.rounds_mixed.count) == (3, 1)
        # the same through the handler's inline path: staged without a wait
        lead = asyncio.ensure_future(
            lms[1].append_entries_leader(mk_entries(2, 1), 1))
        await asyncio.sleep(0)      # the leader has staged: its round is open
        ride = lms[3].begin_follower_append(1, 1, mk_entries(2, 1))
        await ride.future
        assert lms[3].end_follower_append(ride) is True
        await lead
        assert (gc.rounds.count, gc.rounds_mixed.count) == (4, 2)
        # and the next round starts clean: a lone leader is not mixed
        await lms[0].append_entries_leader(mk_entries(3, 1), 1)
        assert (gc.rounds.count, gc.rounds_mixed.count) == (5, 2)
        assert gc.round_groups.count == 9
    finally:
        for lm in lms:
            await lm.shutdown()
