"""Shared multi-group log engine (native/multilog.cc + storage.multilog):
one engine instance per process, group-keyed records in shared journals,
ONE fsync per flush round across all groups (VERDICT r1 #3; reference:
RocksDB WriteBatch under RocksDBLogStorage, SURVEY §3.1/§8.3)."""

import asyncio
import os
import signal
import struct
import subprocess
import sys
import time

import pytest

from tests.test_storage import _BaseLogStorageSuite, mk_entries
from tpuraft.entity import LogId


def _available():
    try:
        from tpuraft.storage.multilog import ensure_built

        ensure_built()
        return True
    except Exception:
        return False


pytestmark = pytest.mark.skipif(not _available(),
                                reason="C++ multilog engine not buildable")


def mk_storage(tmp_path, group="g1", seg_max=0):
    from tpuraft.storage.multilog import MultiLogStorage

    s = MultiLogStorage(str(tmp_path / "mlog"), group)
    if seg_max:
        # engine seg_max is fixed at first open per process+dir
        from tpuraft.storage import multilog

        key = os.path.realpath(str(tmp_path / "mlog"))
        if key not in multilog._engines:
            multilog._engines[key] = multilog.MultiLogEngine(
                str(tmp_path / "mlog"), seg_max)
    return s


class TestMultiLogStorage(_BaseLogStorageSuite):
    """The generic LogStorage battery over one group of the shared
    engine (same contract as file/native single-group engines)."""

    def mk(self, tmp_path):
        return mk_storage(tmp_path)


def test_groups_are_independent(tmp_path):
    a = mk_storage(tmp_path, "ga")
    b = mk_storage(tmp_path, "gb")
    a.init()
    b.init()
    try:
        # interleaved appends share journals but not index spaces
        a.append_entries(mk_entries(1, 5, term=1))
        b.append_entries(mk_entries(1, 3, term=7))
        a.append_entries(mk_entries(6, 5, term=2))
        assert a.last_log_index() == 10
        assert b.last_log_index() == 3
        assert a.get_term(7) == 2 and b.get_term(2) == 7
        # truncation in one group leaves the other intact
        a.truncate_suffix(4)
        b.truncate_prefix(2)
        assert a.last_log_index() == 4
        assert b.first_log_index() == 2 and b.last_log_index() == 3
        assert a.engine is b.engine  # ONE engine instance
    finally:
        a.shutdown()
        b.shutdown()


def test_multi_group_restart_recovery(tmp_path):
    groups = [f"g{i}" for i in range(16)]
    stores = [mk_storage(tmp_path, g) for g in groups]
    for i, s in enumerate(stores):
        s.init()
        s.append_entries(mk_entries(1, 4 + i, term=i + 1))
    stores[3].truncate_suffix(2)
    stores[5].truncate_prefix(3)
    stores[7].reset(50)
    stores[7].append_entries(mk_entries(50, 2, term=9))
    for s in stores:
        s.shutdown()

    stores = [mk_storage(tmp_path, g) for g in groups]
    for s in stores:
        s.init()
    try:
        for i, s in enumerate(stores):
            if i == 3:
                assert s.last_log_index() == 2
            elif i == 5:
                assert (s.first_log_index(), s.last_log_index()) == (3, 9)
            elif i == 7:
                assert (s.first_log_index(), s.last_log_index()) == (50, 51)
                assert s.get_term(51) == 9
            else:
                assert s.last_log_index() == 4 + i, groups[i]
                assert s.get_entry(2).id == LogId(2, i + 1)
    finally:
        for s in stores:
            s.shutdown()


def test_thousand_groups_one_engine(tmp_path):
    """1K groups on ONE engine instance: fd count stays O(journal
    files), not O(groups) (round 1: thousands of open segment files)."""
    G = 1000
    stores = [mk_storage(tmp_path, f"r{k}") for k in range(G)]
    for s in stores:
        s.init()
    try:
        for k, s in enumerate(stores):
            s.append_entries(mk_entries(1, 2, term=k % 7 + 1), sync=False)
        eng = stores[0].engine
        eng.sync()
        assert eng.file_count <= 4, "journal files should be shared"
        # spot-check reads across the space
        for k in (0, 1, 499, 998, 999):
            assert stores[k].last_log_index() == 2
            assert stores[k].get_term(2) == k % 7 + 1
    finally:
        for s in stores:
            s.shutdown()
    # reopen: all 1000 groups recover
    stores = [mk_storage(tmp_path, f"r{k}") for k in range(G)]
    for s in stores:
        s.init()
    try:
        assert all(s.last_log_index() == 2 for s in stores)
    finally:
        for s in stores:
            s.shutdown()


def test_every_append_is_covered_by_the_next_sync(tmp_path):
    """``tlm_sync`` decides by epoch whether the journal holds unsynced
    bytes.  Until PR 32 ``tlm_append`` never bumped it, so a sync after
    entry appends alone fsynced nothing and returned at once (it only
    ran when a registration or a control record rode along): entries
    were acknowledged on ``write()``.  A sync after an append is a round
    (``sync_count`` steps); a sync after nothing is not."""
    a, b = mk_storage(tmp_path, "ea"), mk_storage(tmp_path, "eb")
    a.init()
    b.init()
    try:
        eng = a.engine
        a.append_entries(mk_entries(1, 1), sync=True)   # registry too
        b.append_entries(mk_entries(1, 1), sync=True)
        for k in range(2, 6):
            n0 = eng.sync_count
            (a if k % 2 else b).append_entries(mk_entries(k, 1),
                                               sync=False)
            (b if k % 2 else a).append_entries(mk_entries(k, 1),
                                               sync=False)
            assert eng.sync_count == n0
            eng.sync()
            assert eng.sync_count == n0 + 1, "append left unsynced"
            eng.sync()
            assert eng.sync_count == n0 + 1     # nothing new: no round
    finally:
        a.shutdown()
        b.shutdown()


async def test_group_fsync_coalescing(tmp_path):
    """The headline property: N groups flushing concurrently cost ~1
    fsync round, not N (RocksDB group commit)."""
    G = 64
    stores = [mk_storage(tmp_path, f"c{k}") for k in range(G)]
    for s in stores:
        s.init()
    try:
        eng = stores[0].engine
        sync0 = eng.sync_count

        async def flush_one(k):
            await stores[k].append_entries_async(
                mk_entries(1, 3, term=1), sync=True)

        await asyncio.gather(*(flush_one(k) for k in range(G)))
        rounds = eng.sync_count - sync0
        # every group's flush is durable, but the 64 concurrent flushes
        # coalesced into a handful of fsync rounds
        assert rounds <= G // 4, f"{rounds} fsync rounds for {G} groups"
        assert all(s.last_log_index() == 3 for s in stores)
        print(f"{G} group flushes -> {rounds} fsync rounds")
    finally:
        for s in stores:
            s.shutdown()


def test_group_commit_across_event_loops(tmp_path):
    """The engine is shared process-wide by directory, so stores on
    DIFFERENT event loops (threads) may join the same group-commit; each
    waiter must resolve on its own loop (ADVICE r2: futures were set
    from whichever loop ran the round — not thread-safe)."""
    import threading

    from tests.test_storage import mk_entries

    T, ROUNDS = 4, 25
    errors: list[BaseException] = []
    barrier = threading.Barrier(T)

    def worker(k: int) -> None:
        async def run():
            s = mk_storage(tmp_path, f"loop{k}")
            s.init()
            try:
                for i in range(ROUNDS):
                    await s.append_entries_async(
                        mk_entries(3 * i + 1, 3, term=1), sync=True)
                    # stagger so rounds interleave across loops
                    await asyncio.sleep(0.001 * (k % 3))
                assert s.last_log_index() == 3 * ROUNDS
            finally:
                s.shutdown()

        barrier.wait(timeout=30)
        try:
            asyncio.run(run())
        except BaseException as e:  # noqa: BLE001 — surfaced to the test
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    # a stranded waiter hangs its worker inside asyncio.run — join()
    # returning on timeout must fail the test, not pass it silently
    assert not any(t.is_alive() for t in threads), "worker deadlocked"
    assert not errors, errors


def test_journal_gc_after_prefix_truncation(tmp_path):
    s = mk_storage(tmp_path, "g1", seg_max=4096)
    s.init()
    try:
        s.append_entries(mk_entries(1, 200, term=1, size=64))
        eng = s.engine
        files_before = eng.file_count
        assert files_before > 2  # rotated
        s.truncate_prefix(190)  # storage gc()s opportunistically
        assert eng.file_count < files_before
        # data still intact post-GC
        assert s.first_log_index() == 190
        assert s.last_log_index() == 200
        assert s.get_entry(195) is not None
    finally:
        s.shutdown()
    # and recovery after GC (markers re-asserted state)
    s = mk_storage(tmp_path, "g1")
    s.init()
    try:
        assert (s.first_log_index(), s.last_log_index()) == (190, 200)
    finally:
        s.shutdown()


def test_torn_tail_recovery(tmp_path):
    s = mk_storage(tmp_path, "g1")
    s.init()
    s.append_entries(mk_entries(1, 3, size=40))
    s.shutdown()
    j = sorted((tmp_path / "mlog").glob("journal_*.log"))[0]
    j.write_bytes(j.read_bytes()[:-10])
    s = mk_storage(tmp_path, "g1")
    s.init()
    try:
        assert s.last_log_index() == 2
        assert s.get_entry(2) is not None
    finally:
        s.shutdown()


def test_torn_registry_tail_recovery(tmp_path):
    """The group registry is append-only (r5: per-registration rewrites
    made 16K boot O(G^2)); a torn registration append must be dropped
    at reopen while every completed registration survives, and new
    registrations must extend the cleaned stream."""
    stores = [mk_storage(tmp_path, f"g{i}") for i in range(8)]
    for s in stores:
        s.init()
        s.append_entries(mk_entries(1, 2, size=40))
    for s in stores:
        s.shutdown()
    reg = tmp_path / "mlog" / "groups"
    reg.write_bytes(reg.read_bytes() + b"\x05\x00\x00\x00")  # torn append
    back = [mk_storage(tmp_path, f"g{i}") for i in range(8)]
    for s in back:
        s.init()
    try:
        for s in back:
            assert s.last_log_index() == 2
            assert s.get_entry(1) is not None
        extra = mk_storage(tmp_path, "g-new")
        extra.init()
        extra.append_entries(mk_entries(1, 1, size=40))
        assert extra.last_log_index() == 1
        extra.shutdown()
    finally:
        for s in back:
            s.shutdown()
    # and the new registration is durable across another reopen
    again = mk_storage(tmp_path, "g-new")
    again.init()
    assert again.last_log_index() == 1
    again.shutdown()


def test_corrupt_record_drops_tail(tmp_path):
    """A flipped byte mid-journal: recovery keeps the clean prefix, the
    engine reopens (no exception, no half-read groups)."""
    s = mk_storage(tmp_path, "g1")
    s.init()
    s.append_entries(mk_entries(1, 10, size=40))
    s.shutdown()
    j = sorted((tmp_path / "mlog").glob("journal_*.log"))[0]
    data = bytearray(j.read_bytes())
    data[len(data) // 2] ^= 0xFF
    j.write_bytes(bytes(data))
    s = mk_storage(tmp_path, "g1")
    s.init()
    try:
        last = s.last_log_index()
        assert 0 < last < 10
        for i in range(s.first_log_index(), last + 1):
            assert s.get_entry(i) is not None
    finally:
        s.shutdown()


_KILL_WRITER = r"""
import sys, time
sys.path.insert(0, {repo!r})
from tests.test_storage import mk_entries
from tpuraft.storage.multilog import MultiLogStorage

d = {dir!r}
stores = [MultiLogStorage(d, "k%d" % k) for k in range(8)]
for s in stores:
    s.init()
print("READY", flush=True)
i = 1
while True:
    for k, s in enumerate(stores):
        s.append_entries(mk_entries(i, 1, term=1, size=32), sync=(k == 7))
    i += 1
"""


def test_kill9_recovery_per_group(tmp_path):
    """kill -9 a process writing 8 groups through one engine; reopen:
    every group's log is contiguous with no exception."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = _KILL_WRITER.format(repo=repo, dir=str(tmp_path / "mlog"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    p = subprocess.Popen([sys.executable, "-c", script],
                         stdout=subprocess.PIPE, env=env)
    try:
        assert p.stdout.readline().strip() == b"READY"
        time.sleep(1.0)  # let it write under fire
    finally:
        p.send_signal(signal.SIGKILL)
        p.wait()

    stores = [mk_storage(tmp_path, f"k{k}") for k in range(8)]
    for s in stores:
        s.init()
    try:
        lasts = [s.last_log_index() for s in stores]
        assert min(lasts) > 5, lasts  # it was really writing
        for s, last in zip(stores, lasts):
            # contiguity: every index up to last reads back
            for i in range(1, last + 1):
                e = s.get_entry(i)
                assert e is not None and e.id.index == i
        # all groups within one sync round of each other
        assert max(lasts) - min(lasts) <= 2, lasts
    finally:
        for s in stores:
            s.shutdown()


async def test_kv_store_regions_share_one_log_engine(tmp_path):
    """RheaKV production integration: StoreEngineOptions(log_scheme=
    "multilog") puts every region of a store on ONE shared journal
    engine — writes across regions coalesce into shared fsync rounds
    and survive a store restart."""
    from tests.kv_cluster import KVTestCluster
    from tpuraft.rheakv.metadata import Region
    from tpuraft.storage import multilog

    regions = [Region(id=1, start_key=b"", end_key=b"m"),
               Region(id=2, start_key=b"m", end_key=b"")]
    c = KVTestCluster(3, tmp_path=tmp_path, regions=regions,
                      log_scheme="multilog")
    await c.start_all()
    try:
        l1 = await c.wait_region_leader(1)
        l2 = await c.wait_region_leader(2)
        for i in range(10):
            assert await l1.raft_store.put(b"a%03d" % i, b"v%d" % i)
            assert await l2.raft_store.put(b"z%03d" % i, b"v%d" % i)
        # both regions' logs live in each store's ONE engine
        engines = list(multilog._engines.values())
        assert engines, "no shared engines registered"
        assert len(engines) == 3  # one per store, not one per region
        # restart a store: both its region logs recover from the engine
        victim = c.endpoints[0]
        await c.stop_store(victim)
        await c.start_store(victim)
        l1 = await c.wait_region_leader(1)
        assert await l1.raft_store.get(b"a005") == b"v5"
        l2 = await c.wait_region_leader(2)
        assert await l2.raft_store.get(b"z007") == b"v7"
    finally:
        await c.stop_all()


async def test_cluster_on_shared_log_engine(tmp_path):
    """End-to-end: 3 endpoints x 8 groups, every endpoint's groups on
    ONE shared log engine, electing and committing through the device
    plane with group-commit fsync."""
    from tests.test_engine import MultiRaftCluster
    from tpuraft.entity import Task

    class MLCluster(MultiRaftCluster):
        def __init__(self, *a, **kw):
            self.tmp = kw.pop("tmp")
            super().__init__(*a, **kw)

    c = MLCluster(3, 8, election_timeout_ms=500, tmp=tmp_path)
    # monkey-wire log uris: one shared dir per endpoint
    orig_start = c.start_all

    async def start_all():
        from tests.cluster import MockStateMachine
        from tpuraft.core.node import Node
        from tpuraft.core.node_manager import NodeManager
        from tpuraft.core.engine import MultiRaftEngine
        from tpuraft.options import NodeOptions, TickOptions
        from tpuraft.rpc.transport import InProcTransport, RpcServer

        for ep in c.endpoints:
            server = RpcServer(ep.endpoint)
            manager = NodeManager(server)
            c.net.bind(server)
            transport = InProcTransport(c.net, ep.endpoint)
            engine = MultiRaftEngine(TickOptions(
                max_groups=len(c.groups) + 4, max_peers=8,
                tick_interval_ms=c.tick_ms))
            await engine.start()
            c.engines[ep.endpoint] = engine
            factory = engine.ballot_box_factory()
            mdir = f"{c.tmp}/{ep.port}/mlog"
            for gid in c.groups:
                fsm = MockStateMachine()
                c.fsms[(gid, ep)] = fsm
                opts = NodeOptions(
                    election_timeout_ms=c.election_timeout_ms,
                    initial_conf=c.conf.copy(), fsm=fsm,
                    log_uri=f"multilog://{mdir}#{gid}",
                    raft_meta_uri=f"file://{c.tmp}/{ep.port}/meta_{gid}")
                node = Node(gid, ep, opts, transport,
                            ballot_box_factory=factory)
                node.node_manager = manager
                manager.add(node)
                assert await node.init()
                c.nodes[(gid, ep)] = node

    c.start_all = start_all
    await c.start_all()
    try:
        async def put(gid, i):
            leader = await c.wait_leader(gid)
            fut = asyncio.get_running_loop().create_future()
            await leader.apply(Task(data=b"%s-%d" % (gid.encode(), i),
                                    done=fut.set_result))
            st = await asyncio.wait_for(fut, 15)
            assert st.is_ok(), f"{gid}: {st}"

        await asyncio.gather(*(put(g, i) for g in c.groups for i in range(3)))
        # one engine dir per endpoint; fsyncs coalesced across groups
        from tpuraft.storage import multilog

        engines = [e for e in multilog._engines.values()]
        assert engines, "shared engines should be registered"
        for eng in engines:
            assert eng.sync_count <= eng.append_count
    finally:
        await c.stop_all()


@pytest.mark.parametrize("path", ["inline", "executor"])
async def test_flush_returns_the_fsync_interval_read_in_its_thread(
        tmp_path, path):
    """(t0, t1, off_loop) around the fsync itself, the same for every
    rider of a round.  Where the round is synced follows the measured
    fsync cost and nothing else: under ``INLINE_MAX_S`` the close runs it
    on the loop thread, at or over it in an executor thread; either way
    it ends before any rider resumes."""
    import threading
    import time

    stores = [mk_storage(tmp_path, f"iv{k}") for k in range(8)]
    for s in stores:
        s.init()
    try:
        eng = stores[0].engine
        gc = eng.group_commit
        sync_threads = []
        real_sync = eng.sync

        def spying_sync():
            sync_threads.append(threading.get_ident())
            time.sleep(0.002)
            real_sync()

        eng.sync = spying_sync
        # the inline close is open, or banned by a slow disk's EWMA
        gc._cost_ewma = 0.0 if path == "inline" else gc.INLINE_MAX_S
        before = time.perf_counter()
        resumed = []

        async def one(k):
            iv = await stores[k].append_entries_async(
                mk_entries(1, 2, term=1), sync=True)
            resumed.append(time.perf_counter())
            return iv

        got = await asyncio.gather(*(one(k) for k in range(8)))
        # the eight staged in one turn: one round, one fsync, one interval
        assert len(sync_threads) == 1 and len(set(got)) == 1
        assert (sync_threads[0] == threading.get_ident()) \
            is (path == "inline")
        t0, t1, off_loop = got[0]
        assert off_loop is (path == "executor")
        assert before <= t0 and t1 - t0 >= 0.002
        assert t1 <= min(resumed)
        assert (gc.rounds.count, gc.round_groups.count,
                gc.round_inline.count) == (1, 8, int(path == "inline"))
        # nothing to sync, nothing to wait for
        assert stores[0].append_entries_async([], sync=True) is None
        assert stores[0].append_entries_async(
            mk_entries(3, 1, term=1), sync=False) is None
        assert len(sync_threads) == 1
    finally:
        for s in stores:
            s.shutdown()


def test_the_shared_handles_of_a_directory_resolve_its_path_once(
        tmp_path, monkeypatch):
    """Every group of a store opens the same directory string, and a
    ``realpath`` is one ``lstat`` a path component (20,000 lstats of a
    4,096-region store's boot): the registries of the shared log engine and
    the shared meta journal resolve a string once while its handle is
    live, still find the one handle through another spelling of the
    directory, and resolve afresh after the handle is closed."""
    import os

    from tpuraft.storage import meta_multilog, multilog
    from tpuraft.util import dirkeys

    resolved = []
    real = os.path.realpath
    monkeypatch.setattr(dirkeys.os.path, "realpath",
                        lambda p: resolved.append(p) or real(p))
    d = str(tmp_path / "mlog")
    other = os.path.join(str(tmp_path), ".", "mlog")
    for get, release in ((multilog.get_engine, multilog._release_engine),
                         (meta_multilog.get_journal,
                          meta_multilog._release_journal)):
        resolved.clear()
        handles = [get(d) for _ in range(64)]
        assert resolved == [d]
        assert all(h is handles[0] for h in handles)
        assert get(other) is handles[0]
        assert resolved == [d, other]
        for h in handles + [handles[0]]:
            release(h)
        resolved.clear()
        again = get(d)                  # closed in between: resolved anew
        assert resolved == [d] and again is not handles[0]
        release(again)


async def test_a_rounds_stall_token_ages_from_the_hand_off_to_the_fsyncs_end(
        tmp_path):
    """The gray-failure disk probe's stall token of a flush round whose
    disk is too slow for the loop thread: taken when the round is handed
    to the executor, given back in the thread at the fsync's end.  A round queued behind a blocked executor ages it (a
    saturated executor IS a gray signal, and a hung fsync never returns
    it); a loop that resumes the waiter late does not (ISSUE 29: held to
    the resumption, a 0.06 ms disk read as stalled whenever the loop ran
    half a second late)."""
    import threading
    import time
    from concurrent.futures import ThreadPoolExecutor

    from tpuraft.util.health import DiskLatencyProbe

    store = mk_storage(tmp_path, "tok")
    store.init()
    loop = asyncio.get_running_loop()
    pool = ThreadPoolExecutor(max_workers=1)
    loop.set_default_executor(pool)
    try:
        gc = store.engine.group_commit
        gc.health_probe = probe = DiskLatencyProbe()
        gc._cost_ewma = 1.0              # the inline close is banned
        gate = threading.Event()
        pool.submit(gate.wait)           # the one thread is taken
        flush = asyncio.ensure_future(store.append_entries_async(
            mk_entries(1, 2, term=1), sync=True))
        await asyncio.sleep(0.05)
        assert not flush.done()
        _, age_ms, samples = probe.snapshot()
        assert age_ms >= 40 and samples == 0     # queued, never started
        # the executor frees up; the fsync ends in its thread while this
        # loop is busy elsewhere: no token is left to age with the loop
        synced = threading.Event()
        real_sync = store.engine.sync
        store.engine.sync = lambda: (real_sync(), synced.set())
        gate.set()
        assert synced.wait(5)
        time.sleep(0.05)                 # the loop, late for its waiter
        assert not flush.done()
        assert probe.snapshot()[1] == 0.0
        await flush
        _, age_ms, samples = probe.snapshot()
        assert age_ms == 0.0 and samples == 1
    finally:
        store.shutdown()
        pool.shutdown(wait=False)
