"""The store-wide apply round (``ApplyRound``): every region of a store
that applies in one turn of the event loop reaches the raw store in ONE
``apply_write_batch`` call — one KV WAL record, one fsync — and nothing
a region reports (closures, applied index, read waiters) moves before
the call covering its rows has returned.  A region whose commit finds
its FSMCaller idle applies inside the round's own callback (the apply
pass) with no task; the rest go through the drain task, in order."""

from __future__ import annotations

import asyncio
import contextlib
import struct
import time
from types import SimpleNamespace

import pytest

from tpuraft.core.fsm_caller import FSMCaller
from tpuraft.core.state_machine import Iterator, WitnessStateMachine
from tpuraft.entity import EntryType, LogEntry, LogId
from tpuraft.errors import RaftError, RaftException, Status
from tpuraft.rheakv.kv_operation import KVOp, KVOperation
from tpuraft.rheakv.metadata import Region
from tpuraft.rheakv.native_store import NativeRawKVStore
from tpuraft.rheakv.raw_store import MemoryRawKVStore
from tpuraft.rheakv.state_machine import (
    ApplyRound,
    KVClosure,
    KVStoreStateMachine,
)

KINDS = ("memory", "native")


class _Spy:
    """A raw store that records every ``apply_write_batch`` call, runs a
    hook inside it and can refuse rows; everything else is the store's."""

    def __init__(self, inner):
        self.inner = inner
        self.calls: list[list] = []
        self.on_write = None      # called with the rows, inside the call
        self.refuse = None        # key: a call holding it raises

    def apply_write_batch(self, rows):
        self.calls.append(list(rows))
        if self.on_write is not None:
            self.on_write(rows)
        if self.refuse is not None and any(k == self.refuse for k, _ in rows):
            raise IOError(f"disk refuses {self.refuse!r}")
        self.inner.apply_write_batch(rows)

    def __getattr__(self, name):
        return getattr(self.inner, name)


@pytest.fixture(params=KINDS)
def spy(request, tmp_path):
    if request.param == "native":
        inner = NativeRawKVStore(str(tmp_path / "kv"))
    else:
        inner = MemoryRawKVStore()
    yield _Spy(inner)
    close = getattr(inner, "close", None)
    if close is not None:
        close()


class _Group:
    """One region's apply pipeline as a node wires it: an FSMCaller over
    a log of DATA entries, a KVStoreStateMachine over the shared store
    and the shared round, a KVClosure per entry."""

    def __init__(self, rid: int, store, apply_round: ApplyRound,
                 ops: list[KVOperation]):
        self.fsm = KVStoreStateMachine(
            Region(id=rid, start_key=b"", end_key=b""), store,
            apply_round=apply_round)
        self.entries = {
            i + 1: LogEntry(type=EntryType.DATA, id=LogId(i + 1, 1),
                            data=op.encode())
            for i, op in enumerate(ops)}
        self.applied_marks: list[int] = []
        lm = SimpleNamespace(get_entry=self.entries.get,
                             set_applied_index=self.applied_marks.append)
        self.caller = FSMCaller(self.fsm, lm)
        self.futs: list[asyncio.Future] = []

    async def init(self) -> "_Group":
        await self.caller.init(LogId(0, 0))
        loop = asyncio.get_running_loop()
        for index in self.entries:
            fut = loop.create_future()
            self.futs.append(fut)
            self.caller.append_pending_closure(index, KVClosure(fut))
        return self

    def commit(self) -> None:
        self.caller.on_committed(len(self.entries))

    async def results(self) -> list:
        return await asyncio.wait_for(asyncio.gather(*self.futs), 10)


def _put(rid: int, i: int) -> KVOperation:
    return KVOperation(KVOp.PUT, b"r%03d-k%d" % (rid, i), b"v%d-%d" % (rid, i))


async def _groups(store, n: int, ops_of) -> tuple[ApplyRound, list[_Group]]:
    apply_round = ApplyRound(store)
    return apply_round, [await _Group(rid, store, apply_round,
                                      ops_of(rid)).init()
                         for rid in range(1, n + 1)]


# -- (a) one store call for a turn's commits ---------------------------------


@pytest.mark.parametrize("n_regions", (1, 2, 16))
async def test_regions_committing_in_one_turn_share_one_store_call(
        spy, n_regions):
    apply_round, groups = await _groups(
        spy, n_regions, lambda rid: [_put(rid, 0), _put(rid, 1)])
    for g in groups:       # one turn: no await between the commits
        g.commit()
    for g in groups:
        for st, result in await g.results():
            assert st.is_ok() and result is True
    assert len(spy.calls) == 1
    assert len(spy.calls[0]) == 2 * n_regions
    assert apply_round.syncs.count == 1
    assert apply_round.sync_entries.count == 2 * n_regions
    for g in groups:
        rid = g.fsm.region.id
        assert spy.get(b"r%03d-k1" % rid) == b"v%d-1" % rid
        assert g.caller.last_applied_index == 2


async def test_lone_apply_is_written_in_the_turn_it_arrived(spy):
    """No timer and no linger: commit, the drain task's turn, the flush
    callback's turn, the drain's resumption — a handful of loop turns,
    never a wait."""
    _round, (g,) = await _groups(spy, 1, lambda rid: [_put(rid, 0)])
    g.commit()
    for _ in range(6):
        await asyncio.sleep(0)
    assert g.futs[0].done() and len(spy.calls) == 1
    assert g.caller.last_applied_index == 1


async def test_commits_of_later_turns_make_later_rounds(spy):
    apply_round, groups = await _groups(spy, 2,
                                        lambda rid: [_put(rid, 0)])
    groups[0].commit()
    await groups[0].results()
    groups[1].commit()
    await groups[1].results()
    assert [len(c) for c in spy.calls] == [1, 1]
    assert apply_round.syncs.count == 2
    assert apply_round.sync_entries.count == 2


# -- (b) order: nothing moves before the write returned ----------------------


async def test_nothing_advances_before_the_write_returns(spy):
    _round, groups = await _groups(
        spy, 4, lambda rid: [_put(rid, 0), _put(rid, 1), _put(rid, 2)])
    waiters = [g.caller.wait_applied(3) for g in groups]
    seen: list[tuple] = []

    def during_write(_rows):
        seen.append((
            [g.caller.last_applied_index for g in groups],
            [f.done() for g in groups for f in g.futs],
            [w.done() for w in waiters],
            [list(g.applied_marks) for g in groups]))

    spy.on_write = during_write
    for g in groups:
        g.commit()
    for g in groups:
        await g.results()
    assert len(seen) == 1
    applied, closures, read_waiters, marks = seen[0]
    assert applied == [0] * 4
    assert not any(closures)
    assert not any(read_waiters)
    assert marks == [[]] * 4
    assert [await asyncio.wait_for(w, 10) for w in waiters] == [3] * 4
    assert [g.applied_marks for g in groups] == [[3]] * 4


# -- (c) a failing merged write stays with its region ------------------------


async def test_failed_round_falls_back_to_one_write_a_region(spy):
    apply_round, groups = await _groups(
        spy, 3, lambda rid: [_put(rid, 0), _put(rid, 1)])
    spy.refuse = b"r002-k1"
    for g in groups:
        g.commit()
    res = [await g.results() for g in groups]
    # the merged call, then one per stager
    assert [len(c) for c in spy.calls] == [6, 2, 2, 2]
    for st, result in res[0] + res[2]:
        assert st.is_ok() and result is True
    for st, result in res[1]:
        assert st.code == RaftError.ESTATEMACHINE and result is None
    assert spy.get(b"r001-k1") == b"v1-1" and spy.get(b"r003-k0") == b"v3-0"
    assert spy.get(b"r002-k0") is None
    # a run-level failure is not fatal: the region's pipeline goes on
    assert [g.caller.last_applied_index for g in groups] == [2, 2, 2]
    assert apply_round.syncs.count == 1


# -- (d) what rides a round and what does not --------------------------------


def _record(spy, method: str) -> list:
    """Record, in the order of the store's calls, each call of
    ``method``: ``(method, apply_write_batch calls before it)``."""
    inner, seen = getattr(spy.inner, method), []

    def call(*args):
        seen.append((method, len(spy.calls)))
        return inner(*args)

    setattr(spy, method, call)
    return seen


# the non-run op that follows the run on one key: (op, store method, the
# result its closure carries, the key's value afterwards)
_AFTER_RUN = {
    "compare_put": (lambda k: KVOperation.cas(k, b"one", b"two"),
                    "compare_and_put", True, b"two"),
    "put_if_absent": (lambda k: KVOperation(KVOp.PUT_IF_ABSENT, k, b"two"),
                      "put_if_absent", b"one", b"one"),
    "get_and_put": (lambda k: KVOperation(KVOp.GET_AND_PUT, k, b"two"),
                    "get_and_put", b"one", b"two"),
    "delete_range": (lambda k: KVOperation.delete_range(k, k + b"\xff"),
                     "delete_range", True, None),
    "merge": (lambda k: KVOperation(KVOp.MERGE, k, b"two"),
              "merge", True, b"one,two"),
}


@pytest.mark.parametrize("after", sorted(_AFTER_RUN))
async def test_run_then_compare_put_keeps_log_order_and_results(spy, after):
    make_op, method, result, value = _AFTER_RUN[after]
    own_calls = _record(spy, method)
    _round, (g, other) = await _groups(spy, 2, lambda rid: [
        KVOperation(KVOp.PUT, b"r%03d-k" % rid, b"one"),
        make_op(b"r%03d-k" % rid),
        KVOperation(KVOp.PUT, b"r%03d-j" % rid, b"three"),
    ] if rid == 1 else [_put(rid, 0)])
    g.commit()
    other.commit()
    (s1, r1), (s2, r2), (s3, r3) = await g.results()
    assert s1.is_ok() and s2.is_ok() and s3.is_ok()
    # the op saw the PUT ahead of it: the run was written first
    assert (r1, r2, r3) == (True, result, True)
    assert spy.get(b"r001-k") == value and spy.get(b"r001-j") == b"three"
    await other.results()
    # round 1: both regions' leading runs; round 2: the PUT after the op,
    # which paid a store call of its own between the two
    assert [sorted(k for k, _ in c) for c in spy.calls] == [
        [b"r001-k", b"r002-k0"], [b"r001-j"]]
    assert own_calls == [(method, 1)]


async def test_mixed_batch_through_on_apply_rides_rounds_in_log_order(spy):
    """One ``on_apply`` over a put, an all-put MULTI, a CAS on the first
    put's value and a put: a round of three rows, the CAS's own call
    (which sees the staged put), a round of one row."""
    cas_calls = _record(spy, "compare_and_put")
    apply_round = ApplyRound(spy)
    fsm = KVStoreStateMachine(Region(id=1, start_key=b"", end_key=b""), spy,
                              apply_round=apply_round)
    ops = [_put(1, 0), KVOperation.multi([_put(1, 1), _put(1, 2)]),
           KVOperation.cas(b"r001-k0", b"v1-0", b"cas"), _put(1, 3)]
    loop = asyncio.get_running_loop()
    futs = [loop.create_future() for _ in ops]
    it = Iterator([LogEntry(type=EntryType.DATA, id=LogId(i + 1, 1),
                            data=op.encode()) for i, op in enumerate(ops)],
                  [KVClosure(f) for f in futs])
    await fsm.on_apply(it)
    assert [f.result()[0].is_ok() for f in futs] == [True] * 4
    assert [f.result()[1] for f in futs] == \
        [True, [(0, "", True)] * 2, True, True]
    assert [len(c) for c in spy.calls] == [3, 1]
    assert cas_calls == [("compare_and_put", 1)]
    assert apply_round.syncs.count == 2
    assert apply_round.sync_entries.count == 3
    assert spy.get(b"r001-k0") == b"cas" and spy.get(b"r001-k3") == b"v1-3"


async def test_all_put_multi_rides_the_round_with_per_op_outcomes(spy):
    apply_round, (g, other) = await _groups(spy, 2, lambda rid: [
        KVOperation.multi([_put(rid, 0), _put(rid, 1),
                           KVOperation(KVOp.DELETE, b"r%03d-k0" % rid)]),
        _put(rid, 2),
    ] if rid == 1 else [_put(rid, 0)])
    g.commit()
    other.commit()
    (s1, outs), (s2, r2) = await g.results()
    await other.results()
    assert s1.is_ok() and outs == [(0, "", True)] * 3
    assert s2.is_ok() and r2 is True
    assert len(spy.calls) == 1 and len(spy.calls[0]) == 5
    assert apply_round.sync_entries.count == 3
    assert spy.get(b"r001-k0") is None and spy.get(b"r001-k1") == b"v1-1"


async def test_all_put_multi_in_a_failed_round_reports_per_op(spy):
    _round, (g,) = await _groups(spy, 1, lambda rid: [
        KVOperation.multi([_put(rid, 0), _put(rid, 1)])])
    spy.refuse = b"r001-k1"
    g.commit()
    ((st, outs),) = await g.results()
    # as a mixed MULTI does: the entry applied, each sub-op failed
    assert st.is_ok()
    assert [(c, r) for c, _m, r in outs] == \
        [(int(RaftError.ESTATEMACHINE), None)] * 2


async def test_mixed_multi_does_not_ride_the_round(spy):
    apply_round, (g,) = await _groups(spy, 1, lambda rid: [
        KVOperation.multi([_put(rid, 0),
                           KVOperation.cas(b"r001-k0", b"v1-0", b"cas"),
                           _put(rid, 1)])])
    g.commit()
    ((st, outs),) = await g.results()
    assert st.is_ok() and [r for _c, _m, r in outs] == [True, True, True]
    assert apply_round.syncs.count == 0
    assert [len(c) for c in spy.calls] == [1, 1]   # its own store calls
    assert spy.get(b"r001-k0") == b"cas"


async def test_sealed_region_does_not_ride_the_round(spy):
    apply_round, (g,) = await _groups(spy, 1,
                                      lambda rid: [_put(rid, 0)])
    g.fsm.sealed_into = 7
    g.commit()
    ((st, result),) = await g.results()
    assert st.code == RaftError.ESTATEMACHINE and result is None
    assert apply_round.syncs.count == 0 and spy.calls == []
    assert spy.get(b"r001-k0") is None


async def test_flush_at_shutdown_writes_an_open_round(spy):
    apply_round = ApplyRound(spy)
    fut = apply_round.stage([(b"late", b"row")], 1)
    apply_round.flush()          # StoreEngine.shutdown's call
    assert fut.done() and fut.result() is None
    assert spy.get(b"late") == b"row"
    await asyncio.sleep(0)       # the scheduled flush finds nothing
    assert len(spy.calls) == 1 and apply_round.syncs.count == 1


async def test_traced_round_has_its_own_section_and_none_spans_an_await(spy):
    from tpuraft.rheakv.state_machine import SYNC_SECTION
    from tpuraft.util.trace import TRACER

    TRACER.configure(enabled=True)
    TRACER.reset()
    try:
        open_during_write: list = []
        # under the frame of the handle that runs the round (the tracer
        # frames the loop's dispatch while it is on)
        spy.on_write = lambda _rows: open_during_write.append(
            [frame[0] for frame in TRACER._sec_stack
             if not frame[0].startswith("turn.")])
        _round, groups = await _groups(
            spy, 3, lambda rid: [_put(rid, 0),
                                 KVOperation.cas(b"r%03d-k0" % rid,
                                                 b"v%d-0" % rid, b"c"),
                                 _put(rid, 1)])
        for g in groups:
            g.commit()
        for g in groups:
            await g.results()
        table = TRACER.section_table()
        # two rounds (the runs before and after each region's CAS)
        assert table[SYNC_SECTION][0] == 2
        assert open_during_write == [[SYNC_SECTION]] * 2
        # the pass opens the section twice (its staging and its finish
        # of the three leading puts); each region's drain task enters
        # the body anew after every await: to its run's stage, then to
        # the end
        assert table["fsm.apply"][0] == 2 + 3 * 2
        # nothing is left open but the frame of the handle this runs in
        assert [f[0].partition(".")[0] for f in TRACER._sec_stack] \
            in ([], ["turn"])
        assert SYNC_SECTION.startswith("fsm.")   # rolls up into loop.fsm
    finally:
        TRACER.configure(enabled=False)
        TRACER.reset()


# -- (e) the WAL record of a round replays -----------------------------------


@pytest.mark.parametrize("n_regions", (1, 8))
async def test_native_store_reopened_holds_every_row_of_merged_rounds(
        tmp_path, n_regions):
    store = NativeRawKVStore(str(tmp_path / "kv"))
    spy_store = _Spy(store)
    try:
        _round, groups = await _groups(
            spy_store, n_regions,
            lambda rid: [_put(rid, 0), KVOperation.multi(
                [_put(rid, 1), _put(rid, 2)]),
                KVOperation(KVOp.DELETE, b"r%03d-k0" % rid)])
        for g in groups:
            g.commit()
        for g in groups:
            await g.results()
        assert len(spy_store.calls) == 1
    finally:
        store.close()
    reopened = NativeRawKVStore(str(tmp_path / "kv"))
    try:
        for rid in range(1, n_regions + 1):
            assert reopened.get(b"r%03d-k0" % rid) is None
            assert reopened.get(b"r%03d-k1" % rid) == b"v%d-1" % rid
            assert reopened.get(b"r%03d-k2" % rid) == b"v%d-2" % rid
    finally:
        reopened.close()


# -- (f) the apply pass: no task for a region that applies plain writes -----


@contextlib.contextmanager
def _tasks_made():
    """Record every ``asyncio.ensure_future`` and every task the running
    loop creates while the block runs."""
    loop = asyncio.get_running_loop()
    ensure = asyncio.ensure_future
    made: list = []

    def create_task(coro, **kw):
        made.append(coro)
        return type(loop).create_task(loop, coro, **kw)

    def ensure_future(obj, **kw):
        made.append(obj)
        return ensure(obj, **kw)

    loop.create_task = create_task
    asyncio.ensure_future = ensure_future
    try:
        yield made
    finally:
        del loop.create_task
        asyncio.ensure_future = ensure


def _routes(apply_round: ApplyRound) -> tuple:
    """(region runs applied in a pass, ``on_apply`` calls on a task)."""
    return apply_round.pass_regions.count, apply_round.task_runs.count


@pytest.mark.parametrize("n_regions", (1, 16))
async def test_regions_committing_in_one_turn_apply_in_one_pass_and_no_task(
        spy, n_regions):
    apply_round, groups = await _groups(
        spy, n_regions, lambda rid: [_put(rid, 0), _put(rid, 1)])
    with _tasks_made() as made:
        for g in groups:       # one turn: no await between the commits
            g.commit()
        await asyncio.sleep(0)     # the pass: the round's one callback
    assert made == []
    assert len(spy.calls) == 1 and len(spy.calls[0]) == 2 * n_regions
    assert _routes(apply_round) == (n_regions, 0)
    assert (apply_round.syncs.count, apply_round.sync_entries.count) == \
        (1, 2 * n_regions)
    for g in groups:
        assert [f.result()[1] for f in g.futs] == [True, True]
        assert g.caller.last_applied_index == 2 and g.applied_marks == [2]
        assert g.caller._task is None and g.caller.apply_batches == 1


async def test_the_pass_moves_nothing_before_its_write_returned(spy):
    _round, groups = await _groups(
        spy, 3, lambda rid: [_put(rid, i) for i in range(3)])
    waiters = [g.caller.wait_applied(3) for g in groups]
    seen: list = []

    def blocking_write(_rows):
        seen.append(([g.caller.last_applied_index for g in groups],
                     [list(g.applied_marks) for g in groups],
                     [w.done() for w in waiters],
                     [f.done() for g in groups for f in g.futs]))
        time.sleep(0.05)     # the fsync holds the thread

    spy.on_write = blocking_write
    for g in groups:
        g.commit()
    await asyncio.sleep(0)
    assert seen == [([0] * 3, [[]] * 3, [False] * 3, [False] * 9)]
    assert [g.caller.last_applied_index for g in groups] == [3] * 3
    assert [g.applied_marks for g in groups] == [[3]] * 3
    assert [w.result() for w in waiters] == [3] * 3


async def test_a_run_then_compare_put_applies_the_run_in_the_pass(spy):
    apply_round, (g,) = await _groups(spy, 1, lambda rid: [
        _put(rid, 0), _put(rid, 1),
        KVOperation.cas(b"r001-k1", b"v1-1", b"cas"), _put(rid, 3)])
    fired: list = []
    for i, fut in enumerate(g.futs):
        fut.add_done_callback(lambda _f, i=i: fired.append(i + 1))
    g.commit()
    await asyncio.sleep(0)           # the pass: the two leading puts
    assert g.caller.last_applied_index == 2 and g.applied_marks == [2]
    assert [f.done() for f in g.futs] == [True, True, False, False]
    assert g.caller._task is not None     # the CAS and after: the task's
    assert _routes(apply_round) == (1, 0)
    res = await g.results()
    assert [st.is_ok() for st, _r in res] == [True] * 4
    assert [r for _st, r in res] == [True, True, True, True]
    await asyncio.sleep(0)
    assert fired == [1, 2, 3, 4]          # results in log order
    assert spy.get(b"r001-k1") == b"cas"  # the CAS saw the pass's rows
    assert [len(c) for c in spy.calls] == [2, 1]
    assert _routes(apply_round) == (1, 1)
    assert g.caller.last_applied_index == 4


@pytest.mark.parametrize("case", ("sealed", "mixed_multi"))
async def test_a_sealed_region_or_a_mixed_multi_takes_the_drain_task(
        spy, case):
    ops = [_put(1, 0)] if case == "sealed" else [KVOperation.multi(
        [_put(1, 0), KVOperation.cas(b"r001-k0", b"v1-0", b"cas")])]
    apply_round, (g,) = await _groups(spy, 1, lambda rid: ops)
    if case == "sealed":
        g.fsm.sealed_into = 7
    g.commit()
    await asyncio.sleep(0)
    assert g.caller._task is not None and g.caller.last_applied_index == 0
    ((st, result),) = await g.results()
    if case == "sealed":
        assert st.code == RaftError.ESTATEMACHINE and result is None
    else:
        assert st.is_ok() and [r for _c, _m, r in result] == [True, True]
    assert _routes(apply_round) == (0, 1)
    assert apply_round.syncs.count == 0
    assert g.caller.last_applied_index == 1


async def test_a_failed_merged_write_in_the_pass_fails_only_its_region(spy):
    apply_round, groups = await _groups(
        spy, 3, lambda rid: [_put(rid, 0), _put(rid, 1)])
    spy.refuse = b"r002-k1"
    for g in groups:
        g.commit()
    await asyncio.sleep(0)            # all of it inside the pass
    res = [[f.result() for f in g.futs] for g in groups]
    for st, result in res[0] + res[2]:
        assert st.is_ok() and result is True
    for st, result in res[1]:
        assert st.code == RaftError.ESTATEMACHINE and result is None
    assert [len(c) for c in spy.calls] == [6, 2, 2, 2]
    assert _routes(apply_round) == (3, 0)
    assert [g.caller._task for g in groups] == [None] * 3
    # a run-level failure is not fatal: the region's pipeline goes on
    assert [g.caller.last_applied_index for g in groups] == [2, 2, 2]


class _Writer:
    def __init__(self):
        self.files: dict = {}

    def write_file(self, name: str, data: bytes) -> None:
        self.files[name] = data


@pytest.mark.parametrize("event", ("leader_start", "snapshot_save"))
async def test_an_event_queued_in_a_pending_pass_runs_after_its_entries(
        spy, event):
    apply_round, (g,) = await _groups(
        spy, 1, lambda rid: [_put(rid, i) for i in range(3)])
    seen = asyncio.get_running_loop().create_future()
    g.commit()
    assert g.caller._in_pass
    if event == "leader_start":
        async def on_leader_start(term):
            seen.set_result((g.caller.last_applied_index,
                             [f.done() for f in g.futs]))

        g.fsm.on_leader_start = on_leader_start
        g.caller.on_leader_start(7)
    else:
        writer = _Writer()

        async def save_wrapper(w, done):   # as SnapshotExecutor's
            applied = g.caller.last_applied_index
            await g.fsm.on_snapshot_save(w, done)
            seen.set_result((applied, [f.done() for f in g.futs]))

        g.caller._enqueue(("snapshot_save_custom",
                           (writer, lambda _st: None, save_wrapper)))
    assert not g.caller._in_pass     # the event took it out of the pass
    assert await asyncio.wait_for(seen, 10) == (3, [True] * 3)
    assert _routes(apply_round) == (0, 1)
    if event == "snapshot_save":
        # the data the snapshot holds is what its applied index says
        restored = MemoryRawKVStore()
        restored.load_serialized(writer.files["kv_data"])
        assert [restored.get(b"r001-k%d" % i) for i in range(3)] == \
            [b"v1-%d" % i for i in range(3)]


async def test_abandon_with_a_pass_pending_applies_nothing(spy):
    apply_round, (g,) = await _groups(spy, 1, lambda rid: [_put(rid, 0)])
    waiter = g.caller.wait_applied(1)
    g.commit()
    g.caller.abandon()                # a crash between commit and pass
    for _ in range(3):
        await asyncio.sleep(0)
    assert spy.calls == [] and g.caller.last_applied_index == 0
    assert g.applied_marks == []
    ((st, _result),) = await g.results()
    assert st.code == RaftError.ENODESHUTTING
    with pytest.raises(RaftException):
        await waiter
    assert _routes(apply_round) == (0, 0)


@pytest.mark.parametrize("who", ("node", "store"))
async def test_shutdown_with_a_pass_pending_writes_it_first(spy, who):
    apply_round, groups = await _groups(
        spy, 2, lambda rid: [_put(rid, 0), _put(rid, 1)])
    for g in groups:
        g.commit()
    if who == "node":
        await asyncio.wait_for(groups[0].caller.shutdown(), 10)
    else:
        apply_round.flush()          # StoreEngine.shutdown's last call
        assert [g.caller.last_applied_index for g in groups] == [2, 2]
    assert all(st.is_ok() for st, _r in await groups[0].results())
    assert groups[0].caller.last_applied_index == 2
    assert spy.get(b"r001-k1") == b"v1-1"
    await groups[1].results()
    assert spy.get(b"r002-k1") == b"v2-1"


async def test_a_backlog_applies_apply_batch_a_pass_over_later_turns(spy):
    apply_round, (g,) = await _groups(
        spy, 1, lambda rid: [_put(rid, i) for i in range(1000)])
    g.commit()
    applied: list = []
    while g.caller.last_applied_index < 1000:
        await asyncio.sleep(0)
        applied.append(g.caller.last_applied_index)
    assert applied == list(range(32, 1000, 32)) + [1000]
    assert [len(c) for c in spy.calls] == [32] * 31 + [8]
    assert g.applied_marks == applied
    assert _routes(apply_round) == (32, 0)
    assert g.caller._task is None
    assert spy.get(b"r001-k999") == b"v1-999"


async def test_a_plain_state_machine_applies_on_its_drain_task_and_counts_it():
    from examples.counter import CounterStateMachine

    fsm = CounterStateMachine()
    entries = {i: LogEntry(type=EntryType.DATA, id=LogId(i, 1),
                           data=struct.pack("<q", i)) for i in (1, 2, 3)}
    marks: list = []
    caller = FSMCaller(fsm, SimpleNamespace(get_entry=entries.get,
                                            set_applied_index=marks.append))
    await caller.init(LogId(0, 0))
    with _tasks_made() as made:
        caller.on_committed(2)
    assert made and not caller._in_pass
    await asyncio.wait_for(caller._task, 10)
    caller.on_committed(3)
    await asyncio.wait_for(caller._task, 10)
    assert fsm.value == 6 and caller.last_applied_index == 3
    assert marks == [2, 3]
    assert caller.task_runs.count == 2


async def test_a_commit_behind_a_live_drain_task_keeps_the_queue(spy):
    apply_round, (g,) = await _groups(
        spy, 1, lambda rid: [_put(rid, i) for i in range(4)])
    g.caller.on_leader_start(7)       # spawns the drain task
    g.caller.on_committed(1)          # queued behind the event
    await asyncio.sleep(0)            # the task stages entry 1 and waits
    assert g.caller._task is not None and not g.caller._task.done()
    g.caller.on_committed(3)          # the task is alive: queued too
    assert not g.caller._in_pass
    await asyncio.gather(*g.futs[:3])
    await asyncio.wait_for(g.caller._task, 10)
    assert _routes(apply_round) == (0, 2)
    g.caller.on_committed(4)          # idle again: the pass
    assert g.caller._in_pass
    await asyncio.sleep(0)
    assert g.caller.last_applied_index == 4
    assert _routes(apply_round) == (1, 2)


async def test_a_poisoned_caller_leaves_the_pending_pass(spy):
    apply_round, (g,) = await _groups(spy, 1, lambda rid: [_put(rid, 0)])
    errors: list = []

    async def on_error(st):
        errors.append(st)

    g.fsm.on_error = on_error
    g.commit()
    g.caller.poison(Status.error(RaftError.ESTATEMACHINE, "diverged"))
    for _ in range(4):
        await asyncio.sleep(0)
    assert spy.calls == [] and g.caller.last_applied_index == 0
    assert [st.code for st in errors] == [RaftError.ESTATEMACHINE]
    assert _routes(apply_round) == (0, 0)


@pytest.mark.parametrize("half", ("stage", "finish"))
async def test_a_crash_in_a_regions_half_of_the_pass_stays_with_it(
        spy, half):
    """The log read or the state machine raising in the pass poisons that
    region through its drain task; the other regions of the pass finish."""
    apply_round, groups = await _groups(spy, 2, lambda rid: [_put(rid, 0)])
    node_errors: list = []

    async def on_node_error(st):
        node_errors.append(st)

    def boom(*_args):
        raise OSError(f"{half} failed")

    if half == "stage":
        groups[0].caller._lm = SimpleNamespace(get_entry=boom)
    else:
        groups[0].fsm.finish_staged = boom
    groups[0].caller._node_on_error = on_node_error
    for g in groups:
        g.commit()
    await asyncio.sleep(0)
    assert groups[1].caller.last_applied_index == 1
    assert groups[1].futs[0].result()[0].is_ok()
    await asyncio.wait_for(groups[0].caller._task, 10)
    assert groups[0].caller._error is not None
    assert [st.code for st in node_errors] == [RaftError.ESTATEMACHINE]
    assert groups[0].caller.last_applied_index == 0


async def test_a_witness_swap_in_a_pending_pass_hands_it_to_the_task(spy):
    apply_round, (g,) = await _groups(spy, 1, lambda rid: [_put(rid, 0)])
    g.commit()
    g.caller.replace_fsm(WitnessStateMachine())
    assert not g.caller._in_pass
    await asyncio.wait_for(g.caller._task, 10)
    assert g.caller.last_applied_index == 1 and spy.calls == []
    # the region's applies still count on its store's histogram
    assert _routes(apply_round) == (0, 1)
    g.entries[2] = LogEntry(type=EntryType.DATA, id=LogId(2, 1),
                            data=_put(1, 1).encode())
    g.caller.on_committed(2)          # a witness never takes the pass
    assert not g.caller._in_pass
    await asyncio.wait_for(g.caller._task, 10)
    assert _routes(apply_round) == (0, 2)


@pytest.mark.parametrize("kind", (EntryType.NO_OP, EntryType.CONFIGURATION))
async def test_a_no_op_or_a_configuration_goes_to_the_drain_task(spy, kind):
    apply_round, (g,) = await _groups(
        spy, 1, lambda rid: [_put(rid, 0), _put(rid, 1), _put(rid, 2)])
    g.entries[2] = LogEntry(type=kind, id=LogId(2, 1), peers=[])
    g.caller._closures.pop(2)
    g.futs[1].set_result(None)
    g.commit()
    await asyncio.sleep(0)
    assert g.caller.last_applied_index == 1    # the put ahead of it
    await asyncio.wait_for(g.caller._task, 10)
    assert g.caller.last_applied_index == 3
    assert _routes(apply_round) == (1, 1)
    assert spy.get(b"r001-k2") == b"v1-2"


async def test_traced_pass_runs_under_the_fsm_sections_within_one_turn(spy):
    from tpuraft.rheakv.state_machine import SYNC_SECTION
    from tpuraft.util.trace import TRACER

    TRACER.configure(enabled=True, sample_rate=1.0)
    TRACER.reset()
    try:
        def open_sections():
            return [frame[0] for frame in TRACER._sec_stack
                    if not frame[0].startswith("turn.")]

        seen: dict = {"write": [], "stage": [], "finish": []}
        spy.on_write = lambda _rows: seen["write"].append(open_sections())
        _round, groups = await _groups(
            spy, 3, lambda rid: [_put(rid, 0), _put(rid, 1)])
        for g in groups:
            stage, finish = g.fsm.stage_entries, g.fsm.finish_staged

            def staged(entries, closures, stage=stage):
                seen["stage"].append(open_sections())
                return stage(entries, closures)

            def finished(run, err, finish=finish):
                seen["finish"].append(open_sections())
                finish(run, err)

            g.fsm.stage_entries, g.fsm.finish_staged = staged, finished
        tid = TRACER.begin_op("put")
        groups[0].entries[1].trace_id = tid   # one span a traced entry
        for g in groups:
            g.commit()
        await asyncio.sleep(0)            # the pass, one callback
        assert all(f.done() for g in groups for f in g.futs)
        assert seen == {"write": [[SYNC_SECTION]],
                        "stage": [["fsm.apply"]] * 3,
                        "finish": [["fsm.apply"]] * 3}
        table = TRACER.section_table()
        # the staging and the finish of the three regions, and the write
        assert table["fsm.apply"][0] == 2 and table[SYNC_SECTION][0] == 1
        # nothing is left open past the callback that ran the pass
        assert [f[0].partition(".")[0] for f in TRACER._sec_stack] \
            in ([], ["turn"])
        TRACER.end_op(tid)
        (span,) = [s for s in TRACER.spans(tid) if s["name"] == "fsm_apply"]
        assert span["args"] == {"entries": 2}
    finally:
        TRACER.configure(enabled=False)
        TRACER.reset()


# -- the store engine's wiring -----------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
async def test_store_engine_shares_one_round_and_counts_it(tmp_path, kind):
    from tests.kv_cluster import KVTestCluster
    from tpuraft.core.engine import MultiRaftEngine
    from tpuraft.options import TickOptions

    def engine():
        return MultiRaftEngine(TickOptions(
            max_groups=8, max_peers=4, tick_interval_ms=2, backend="numpy"))

    def raw(ep):
        if kind == "memory":
            return MemoryRawKVStore()
        return NativeRawKVStore(str(tmp_path / ep.replace(":", "_")))

    regions = [Region(id=1, start_key=b"", end_key=b"m"),
               Region(id=2, start_key=b"m", end_key=b"")]
    c = KVTestCluster(3, regions=regions, multi_raft_engine_factory=engine,
                      raw_store_factory=raw)
    await c.start_all()
    try:
        leaders = [await c.wait_region_leader(rid) for rid in (1, 2)]
        oks = await asyncio.gather(*[
            leader.raft_store.put(b"%s%02d" % (prefix, i), b"v")
            for leader, prefix in zip(leaders, (b"a", b"z"))
            for i in range(10)])
        assert all(oks)
        await asyncio.sleep(0.3)     # followers apply behind
        for s in c.stores.values():
            r = s.apply_round
            assert all(re.fsm.apply_round is r
                       for re in s._regions.values())
            hists = s.multi_raft_engine.tick_hists
            assert hists["kv_wal_syncs"] is r.syncs
            assert hists["kv_wal_sync_entries"] is r.sync_entries
            assert hists["apply_pass_regions"] is r.pass_regions
            assert hists["apply_task_runs"] is r.task_runs
            # the puts applied in passes; drain tasks took the role
            # events and what a commit found queued behind them
            assert r.pass_regions.count > 0
            assert 0 < r.syncs.count <= r.sync_entries.count
            assert r.sync_entries.count >= 20
            assert s.raw_store.get(b"a09") == b"v"
            assert s.raw_store.get(b"z09") == b"v"
    finally:
        await c.stop_all()


@pytest.mark.parametrize("health", [True, False])
async def test_store_engine_counts_the_logs_flush_rounds(tmp_path, health):
    """The log's round (ISSUE 32) beside the KV WAL's: the store's shared
    multilog engine counts its flush rounds in three event histograms that
    sit with the engine's, so the benchmark's ``summary:`` line carries
    ``engine<i>.log_rounds.count``, ``.log_round_groups.count`` and
    ``.log_round_inline.count`` (groups per log fsync is the quotient of
    the first two), whether or not the store scores its disk's health."""
    from tests.kv_cluster import KVTestCluster
    from tpuraft.core.engine import MultiRaftEngine
    from tpuraft.options import TickOptions
    from tpuraft.storage.multilog import peek_engine

    def engine():
        return MultiRaftEngine(TickOptions(
            max_groups=8, max_peers=4, tick_interval_ms=2, backend="numpy"))

    regions = [Region(id=1, start_key=b"", end_key=b"m"),
               Region(id=2, start_key=b"m", end_key=b"")]
    c = KVTestCluster(3, tmp_path=tmp_path, regions=regions,
                      multi_raft_engine_factory=engine, log_scheme="multilog",
                      store_opts={"health_scoring": health})
    await c.start_all()
    try:
        leaders = [await c.wait_region_leader(rid) for rid in (1, 2)]
        oks = await asyncio.gather(*[
            leader.raft_store.put(b"%s%02d" % (prefix, i), b"v")
            for leader, prefix in zip(leaders, (b"a", b"z"))
            for i in range(10)])
        assert all(oks)
        for s in c.stores.values():
            sid = s.server_id
            mlog = peek_engine(f"{tmp_path}/{sid.ip}_{sid.port}/mlog")
            rounds = mlog.group_commit
            assert (rounds.health_probe is not None) is health
            hists = s.multi_raft_engine.tick_hists
            assert hists["log_rounds"] is rounds.rounds
            assert hists["log_round_groups"] is rounds.round_groups
            assert hists["log_round_inline"] is rounds.round_inline
            # both regions' logs of this store ride the same rounds
            assert 0 < rounds.rounds.count <= rounds.round_groups.count
            assert rounds.round_inline.count <= rounds.rounds.count
            assert mlog.sync_count <= rounds.rounds.count
    finally:
        await c.stop_all()


# -- a store's two roles in one round (ISSUE 36) -----------------------------


async def test_a_kv_round_counts_as_mixed_only_when_both_roles_applied(spy):
    """``syncs_mixed`` takes one sample for a round in which a region this
    store leads and one it follows applied together; a round of one role
    has none."""
    apply_round, groups = await _groups(
        spy, 4, lambda rid: [_put(rid, 0), _put(rid, 1)])
    lead, follow = groups[:2], groups[2:]
    for g in lead:
        await g.fsm.on_leader_start(1)

    async def turn(commits) -> None:
        for g, upto in commits:     # one turn: no await between the commits
            g.caller.on_committed(upto)
        for g, upto in commits:
            await asyncio.wait_for(g.futs[upto - 1], 10)

    await turn([(g, 1) for g in lead])
    assert (apply_round.syncs.count, apply_round.syncs_mixed.count) == (1, 0)
    await turn([(g, 1) for g in follow])
    assert (apply_round.syncs.count, apply_round.syncs_mixed.count) == (2, 0)
    await turn([(lead[0], 2), (follow[0], 2)])
    assert (apply_round.syncs.count, apply_round.syncs_mixed.count) == (3, 1)
    # a leader that stepped down applies as a follower
    await lead[1].fsm.on_leader_stop(None)
    await turn([(lead[1], 2), (follow[1], 2)])
    assert (apply_round.syncs.count, apply_round.syncs_mixed.count) == (4, 1)
    assert apply_round.sync_entries.count == 8
