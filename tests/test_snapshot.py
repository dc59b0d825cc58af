"""Snapshot subsystem tests (reference: SnapshotExecutorTest,
LocalSnapshotStorageTest, NodeTest snapshot+install cases — SURVEY.md §5).
"""

import asyncio
import time

import pytest

from tests.cluster import MockStateMachine, TestCluster
from tpuraft.core.node import State
from tpuraft.entity import PeerId
from tpuraft.rpc.messages import SnapshotMeta
from tpuraft.storage.snapshot import LocalSnapshotStorage


class TestLocalSnapshotStorage:
    def test_roundtrip(self, tmp_path):
        s = LocalSnapshotStorage(str(tmp_path))
        s.init()
        assert s.open() is None
        w = s.create()
        w.write_file("a", b"alpha")
        w.write_file("b", b"beta" * 100)
        s.commit(w, SnapshotMeta(last_included_index=7, last_included_term=2,
                                 peers=["1.1.1.1:1"]))
        r = s.open()
        assert r is not None
        assert r.load_meta().last_included_index == 7
        assert r.read_file("a") == b"alpha"
        assert r.read_file("b") == b"beta" * 100
        assert r.read_file("missing") is None

    def test_only_newest_kept(self, tmp_path):
        s = LocalSnapshotStorage(str(tmp_path))
        s.init()
        for idx in (5, 9):
            w = s.create()
            w.write_file("d", b"x%d" % idx)
            s.commit(w, SnapshotMeta(last_included_index=idx))
        assert len(s._snapshot_dirs()) == 1
        assert s.open().load_meta().last_included_index == 9

    def test_corrupt_file_detected(self, tmp_path):
        s = LocalSnapshotStorage(str(tmp_path))
        s.init()
        w = s.create()
        w.write_file("d", b"payload")
        path = s.commit(w, SnapshotMeta(last_included_index=3))
        (tmp_path / "snapshot_3" / "d").write_bytes(b"tampered")
        r = s.open()
        with pytest.raises(IOError):
            r.read_file("d")

    def test_chunked_read(self, tmp_path):
        s = LocalSnapshotStorage(str(tmp_path))
        s.init()
        w = s.create()
        w.write_file("big", bytes(range(256)) * 10)
        s.commit(w, SnapshotMeta(last_included_index=1))
        r = s.open()
        out = bytearray()
        off = 0
        while True:
            data, eof = r.read_chunk("big", off, 100)
            out += data
            off += len(data)
            if eof:
                break
        assert bytes(out) == bytes(range(256)) * 10


async def test_snapshot_save_and_restart_recovery(tmp_path):
    c = TestCluster(3, tmp_path=tmp_path, snapshot=True)
    await c.start_all()
    leader = await c.wait_leader()
    for i in range(20):
        await c.apply_ok(leader, b"e%d" % i)
    await c.wait_applied(20)
    st = await leader.snapshot()
    assert st.is_ok(), str(st)
    assert c.fsms[leader.server_id].snapshots_saved == 1
    # log compacted behind the snapshot
    assert leader.log_manager.first_log_index() > 1
    # more entries after the snapshot
    for i in range(20, 25):
        await c.apply_ok(leader, b"e%d" % i)
    await c.wait_applied(25)
    await c.stop_all()
    # restart: leader-side node must restore from snapshot + log tail
    c2 = TestCluster(3, tmp_path=tmp_path, snapshot=True)
    c2.net = c.net
    await c2.start_all()
    leader2 = await c2.wait_leader()
    await c2.apply_ok(leader2, b"e25")
    await c2.wait_applied(26)
    for p in c2.peers:
        assert c2.fsms[p].logs == [b"e%d" % i for i in range(26)], str(p)
    # at least one node loaded from snapshot rather than replaying all
    assert any(c2.fsms[p].snapshots_loaded > 0 for p in c2.peers)
    await c2.stop_all()


async def test_install_snapshot_to_lagging_follower(tmp_path):
    c = TestCluster(3, tmp_path=tmp_path, snapshot=True)
    await c.start_all()
    leader = await c.wait_leader()
    victim = next(p for p in c.peers if p != leader.server_id)
    await c.apply_ok(leader, b"s0")
    await c.wait_applied(1)
    # crash one follower, write + snapshot + compact so the log is gone
    await c.stop(victim)
    for i in range(1, 15):
        await c.apply_ok(leader, b"s%d" % i)
    st = await leader.snapshot()
    assert st.is_ok(), str(st)
    assert (leader.log_manager.first_log_index()
            == leader.fsm_caller.last_applied_index + 1)
    # follower comes back: too far behind the compacted log -> InstallSnapshot
    # (drain first: a pre-compaction entry frame still in flight would
    # legally catch the victim up via the log path — the r4 flake)
    await c.drain_sends_to(leader, victim.endpoint)
    await c.start(victim)
    await c.wait_applied(15, timeout_s=10)
    assert c.fsms[victim].logs == [b"s%d" % i for i in range(15)]
    assert c.fsms[victim].snapshots_loaded >= 1
    await c.stop_all()


async def test_snapshot_nothing_new_rejected(tmp_path):
    c = TestCluster(1, tmp_path=tmp_path, snapshot=True)
    await c.start_all()
    leader = await c.wait_leader()
    await c.apply_ok(leader, b"x")
    await c.wait_applied(1)
    st = await leader.snapshot()
    assert st.is_ok()
    st2 = await leader.snapshot()
    assert not st2.is_ok()  # nothing new
    await c.stop_all()


async def test_periodic_snapshot_timer_compacts(tmp_path):
    """The snapshot timer (reference: snapshotIntervalSecs, default 3600)
    must fire on its own, save a snapshot, and compact the log — no
    explicit Node#snapshot call."""
    c = TestCluster(3, tmp_path=tmp_path, snapshot=True,
                    snapshot_interval_secs=1)
    await c.start_all()
    leader = await c.wait_leader()
    for i in range(10):
        await c.apply_ok(leader, b"p%d" % i)
    await c.wait_applied(10)
    deadline = asyncio.get_running_loop().time() + 6
    while asyncio.get_running_loop().time() < deadline:
        if c.fsms[leader.server_id].snapshots_saved >= 1:
            break
        await asyncio.sleep(0.1)
    assert c.fsms[leader.server_id].snapshots_saved >= 1
    # compaction follows the periodic save
    deadline = asyncio.get_running_loop().time() + 3
    while asyncio.get_running_loop().time() < deadline:
        if leader.log_manager.first_log_index() > 1:
            break
        await asyncio.sleep(0.1)
    assert leader.log_manager.first_log_index() > 1
    # the cluster still serves writes afterwards
    st = await c.apply_ok(leader, b"post-snap")
    assert st.is_ok()
    await c.stop_all()


async def test_install_snapshot_filter_before_copy(tmp_path):
    """Files the follower's latest LOCAL snapshot already holds with
    identical name+size+crc are copied locally during InstallSnapshot,
    not re-downloaded (reference: LocalSnapshotCopier#filterBeforeCopy).
    An FSM with a large constant blob + small changing state ships only
    the changed file."""
    from tests.cluster import MockStateMachine
    from tpuraft.errors import Status

    BLOB = bytes(range(256)) * 256          # 64KB, never changes

    class TwoFileFSM(MockStateMachine):
        async def on_snapshot_save(self, writer, done) -> None:
            import struct
            blob = struct.pack("<I", len(self.logs)) + b"".join(
                struct.pack("<I", len(x)) + x for x in self.logs)
            writer.write_file("data", blob)
            writer.write_file("constant-blob", BLOB)
            self.snapshots_saved += 1
            done(Status.OK())

        async def on_snapshot_load(self, reader) -> bool:
            assert reader.read_file("constant-blob") == BLOB
            return await super().on_snapshot_load(reader)

    c = TestCluster(3, tmp_path=tmp_path, snapshot=True)
    for p in c.peers:
        c.fsms[p] = TwoFileFSM()
    await c.start_all()
    leader = await c.wait_leader()
    victim = next(p for p in c.peers if p != leader.server_id)
    for i in range(3):
        await c.apply_ok(leader, b"f%d" % i)
    await c.wait_applied(3)
    # the victim takes its OWN local snapshot (so it holds the blob)
    st = await c.nodes[victim].snapshot()
    assert st.is_ok(), str(st)
    # victim crashes; leader moves on and compacts past its log
    await c.stop(victim)
    for i in range(3, 16):
        await c.apply_ok(leader, b"f%d" % i)
    st = await leader.snapshot()
    assert st.is_ok(), str(st)
    # back up: too far behind -> InstallSnapshot; blob must be reused
    await c.start(victim, fsm=TwoFileFSM())
    await c.wait_applied(16, timeout_s=10)
    node = c.nodes[victim]
    reused = node.metrics.snapshot().get("counters", {}).get(
        "install-snapshot-files-reused")
    assert reused == 1, node.metrics.snapshot()
    assert c.fsms[victim].logs == [b"f%d" % i for i in range(16)]
    await c.stop_all()


async def test_filter_before_copy_rejects_rotted_local_file(tmp_path):
    """A local snapshot file whose on-disk bytes rotted after its
    manifest crc was recorded must NOT be reused: the install detects
    the rot on its crc-verified local read and falls back to the
    network copy.  The rot lives in a file the FSM does not touch at
    load time, so startup recovery stays healthy and the install path
    is what meets it."""
    import glob
    import struct

    from tests.cluster import MockStateMachine
    from tpuraft.errors import Status

    BLOB = bytes(range(256)) * 256          # reusable, stays intact
    AUX = b"\x5a" * 4096                    # reusable, gets rotted

    class ThreeFileFSM(MockStateMachine):
        async def on_snapshot_save(self, writer, done) -> None:
            blob = struct.pack("<I", len(self.logs)) + b"".join(
                struct.pack("<I", len(x)) + x for x in self.logs)
            writer.write_file("data", blob)
            writer.write_file("constant-blob", BLOB)
            writer.write_file("aux-blob", AUX)
            self.snapshots_saved += 1
            done(Status.OK())
        # on_snapshot_load: MockStateMachine reads only "data" — the
        # rotted aux-blob is never read at startup

    c = TestCluster(3, tmp_path=tmp_path, snapshot=True)
    for p in c.peers:
        c.fsms[p] = ThreeFileFSM()
    await c.start_all()
    leader = await c.wait_leader()
    victim = next(p for p in c.peers if p != leader.server_id)
    for i in range(3):
        await c.apply_ok(leader, b"r%d" % i)
    await c.wait_applied(3)
    st = await c.nodes[victim].snapshot()
    assert st.is_ok(), str(st)
    await c.stop(victim)
    # rot the victim's local aux-blob on disk (crc recorded at save time)
    pat = f"{tmp_path}/{victim.ip}_{victim.port}/snapshot/snapshot_*/aux-blob"
    paths = glob.glob(pat)
    assert paths, pat
    with open(paths[0], "r+b") as f:
        f.seek(100)
        f.write(b"\xff\xff\xff")
    for i in range(3, 16):
        await c.apply_ok(leader, b"r%d" % i)
    st = await leader.snapshot()
    assert st.is_ok(), str(st)
    await c.start(victim, fsm=ThreeFileFSM())
    await c.wait_applied(16, timeout_s=10)
    node = c.nodes[victim]
    # only constant-blob reused; the rotted aux-blob fell back to the
    # network, and the installed snapshot's aux bytes are the leader's
    reused = node.metrics.snapshot().get("counters", {}).get(
        "install-snapshot-files-reused")
    assert reused == 1, node.metrics.snapshot()
    from tpuraft.storage.snapshot import SnapshotReader
    snaps = sorted(glob.glob(
        f"{tmp_path}/{victim.ip}_{victim.port}/snapshot/snapshot_*"))
    reader = SnapshotReader(snaps[-1])
    assert reader.read_file("aux-blob") == AUX
    assert reader.read_file("constant-blob") == BLOB
    assert c.fsms[victim].logs == [b"r%d" % i for i in range(16)]
    await c.stop_all()


async def test_install_recovers_from_stale_partial_temp(tmp_path):
    """A crash mid-InstallSnapshot leaves a partial temp dir; on
    restart the temp is ignored by snapshot discovery and the next
    install clears it and succeeds (reference: LocalSnapshotStorage
    temp handling)."""
    import os

    c = TestCluster(3, tmp_path=tmp_path, snapshot=True)
    await c.start_all()
    leader = await c.wait_leader()
    victim = next(p for p in c.peers if p != leader.server_id)
    await c.apply_ok(leader, b"t0")
    await c.wait_applied(1)
    await c.stop(victim)
    # simulate a crash mid-install: partial temp with junk files
    snap_root = f"{tmp_path}/{victim.ip}_{victim.port}/snapshot"
    temp = os.path.join(snap_root, "temp")
    os.makedirs(temp, exist_ok=True)
    with open(os.path.join(temp, "data"), "wb") as f:
        f.write(b"half-written garbage")
    with open(os.path.join(temp, "unrelated-file"), "wb") as f:
        f.write(b"x" * 100)
    # leader moves on and compacts so the victim needs an install
    for i in range(1, 15):
        await c.apply_ok(leader, b"t%d" % i)
    st = await leader.snapshot()
    assert st.is_ok(), str(st)
    await c.drain_sends_to(leader, victim.endpoint)  # r4 flake guard
    await c.start(victim)
    await c.wait_applied(15, timeout_s=10)
    assert c.fsms[victim].logs == [b"t%d" % i for i in range(15)]
    assert c.fsms[victim].snapshots_loaded >= 1
    # the stale junk did not leak into the installed snapshot
    snaps = [d for d in os.listdir(snap_root) if d.startswith("snapshot_")]
    assert snaps, os.listdir(snap_root)
    newest = os.path.join(snap_root, sorted(
        snaps, key=lambda d: int(d.split("_")[1]))[-1])
    assert "unrelated-file" not in os.listdir(newest)
    await c.stop_all()


async def test_install_under_write_load(tmp_path):
    """InstallSnapshot races the hot replication pipeline: periodic
    snapshots compact the log while a crashed follower misses several
    intervals of writes, then recovers by install DURING sustained
    load — converging to identical logs with every acked entry exactly
    once."""
    from collections import Counter

    c = TestCluster(3, tmp_path=tmp_path, snapshot=True,
                    snapshot_interval_secs=1, election_timeout_ms=400)
    await c.start_all()
    await c.wait_leader()
    acked = []
    stop = False

    async def writer(wid):
        i = 0
        while not stop:
            data = b"iw%d-%05d" % (wid, i)
            try:
                leader = await c.wait_leader(3.0)
                st = await c.apply_ok(leader, data, timeout_s=3.0)
                if st.is_ok():
                    acked.append(data)
            except Exception:
                pass
            i += 1
            await asyncio.sleep(0.004)

    ws = [asyncio.ensure_future(writer(w)) for w in range(2)]
    try:
        for _round in range(2):
            await asyncio.sleep(1.0)
            leader = await c.wait_leader(5.0)
            victim = next(p for p in c.peers
                          if p != leader.server_id and p in c.nodes)
            await c.stop(victim)
            await asyncio.sleep(2.5)   # 2+ snapshot intervals of writes
            await c.start(victim)
    finally:
        stop = True
        await asyncio.gather(*ws)
    deadline = time.monotonic() + 30
    ok = False
    while time.monotonic() < deadline:
        logs = [c.fsms[p].logs for p in c.peers if p in c.nodes]
        if len(logs) == 3 and logs[0] == logs[1] == logs[2] \
                and set(acked) <= set(logs[0]):
            ok = True
            break
        await asyncio.sleep(0.2)
    assert ok, "no convergence after install-under-load"
    counts = Counter(logs[0])
    assert all(counts[k] == 1 for k in acked)
    assert len(acked) > 100, len(acked)
    # the recovery path under test actually ran: at least one victim
    # came back via a REMOTE InstallSnapshot (the node-side counter —
    # fsm.snapshots_loaded would also count plain boot-time loads of a
    # node's own local snapshot)
    installs = sum(
        n.metrics.snapshot().get("counters", {}).get(
            "install-snapshot-received", 0)
        for n in c.nodes.values())
    assert installs >= 1, "no InstallSnapshot occurred — vacuous run"
    await c.stop_all()


async def test_add_peer_behind_compacted_log_installs_snapshot(tmp_path):
    """Adding a FRESH voter after the leader compacted its log: the
    joint-consensus catch-up phase must bootstrap the joiner via
    InstallSnapshot (its next_index is below the leader's first log
    index), then the change commits and the joiner serves as a voter."""
    c = TestCluster(3, tmp_path=tmp_path, snapshot=True)
    await c.start_all()
    leader = await c.wait_leader()
    for i in range(12):
        await c.apply_ok(leader, b"a%d" % i)
    await c.wait_applied(12)
    st = await leader.snapshot()
    assert st.is_ok(), str(st)
    assert leader.log_manager.first_log_index() > 1  # compacted
    # boot an empty 4th node, then add it as a voter
    new_peer = PeerId.parse("127.0.0.1:5003")
    c.peers.append(new_peer)
    from tpuraft.conf import Configuration
    save_conf = c.conf
    c.conf = Configuration()
    await c.start(new_peer)
    c.conf = save_conf
    st = await asyncio.wait_for(leader.add_peer(new_peer), 15)
    assert st.is_ok(), str(st)
    assert new_peer in leader.list_peers()
    await c.wait_applied(12, nodes=[c.nodes[new_peer]], timeout_s=10)
    # it arrived via a REMOTE install, not log replay
    got = c.nodes[new_peer].metrics.snapshot().get("counters", {}).get(
        "install-snapshot-received", 0)
    assert got >= 1, c.nodes[new_peer].metrics.snapshot()
    # and it votes: kill one ORIGINAL voter, quorum (3 of 4) holds
    victim = next(p for p in c.peers
                  if p not in (leader.server_id, new_peer))
    await c.stop(victim)
    st = await c.apply_ok(await c.wait_leader(), b"post-join")
    assert st.is_ok(), str(st)
    await c.stop_all()


async def _wait_install_received(node, timeout_s: float = 10.0) -> None:
    """Until ``node`` has finished a remote InstallSnapshot.  One install
    loads the FSM first and resets the log after it
    (``_load_committed_install``), so the applied count says nothing
    about the log; ``install-snapshot-received`` is counted after both."""
    deadline = time.monotonic() + timeout_s
    while not node.metrics.counters_snapshot().get(
            "install-snapshot-received", 0):
        if time.monotonic() >= deadline:
            raise TimeoutError(
                f"{node} finished no InstallSnapshot in {timeout_s}s "
                f"(installing={node.snapshot_executor.installing})")
        await asyncio.sleep(0.02)


async def test_install_snapshot_on_multilog_scheme(tmp_path):
    """InstallSnapshot + log reset over the SHARED journal engine: a
    follower crashed past the compaction horizon pulls the snapshot and
    its multilog-backed log resets (tlm_reset) to the snapshot index —
    the LogManager#setSnapshot divergent-log path on the shared engine."""
    try:
        from tpuraft.storage.multilog import ensure_built

        ensure_built()
    except Exception:
        pytest.skip("C++ multilog engine not buildable")
    c = TestCluster(3, tmp_path=tmp_path, snapshot=True,
                    log_scheme="multilog")
    await c.start_all()
    try:
        leader = await c.wait_leader()
        victim = next(p for p in c.peers if p != leader.server_id)
        for i in range(10):
            st = await c.apply_ok(leader, b"m%d" % i)
            assert st.is_ok(), st
        await c.wait_applied(10)
        await c.stop(victim)
        leader = await c.wait_leader()
        for i in range(10, 25):
            st = await c.apply_ok(leader, b"m%d" % i)
            assert st.is_ok(), st
        # snapshot + compact: the victim's catch-up point is gone
        st = await leader.snapshot()
        assert st.is_ok(), st
        await c.drain_sends_to(leader, victim.endpoint)  # r4 flake guard
        node = await c.start(victim)
        # generous: re-init + snapshot transfer + FSM load on a loaded host
        await c.wait_applied(25, timeout_s=10)
        assert c.fsms[victim].logs == c.fsms[leader.server_id].logs
        assert c.fsms[victim].snapshots_loaded >= 1  # installed, not replayed
        # and the recovered node's log lives on the shared engine, reset
        # to the snapshot index once the install has ended
        await _wait_install_received(node)
        assert node.log_manager.first_log_index() > 1
    finally:
        await c.stop_all()


def test_a_first_boots_snapshot_root_is_known_empty_without_a_listing(
        tmp_path, monkeypatch):
    """A store's first boot makes every replica's snapshot root itself, so
    there is nothing to sweep and nothing to open: no ``listdir`` and no
    ``stat`` of ``temp`` (0.1 to 0.2 ms each on the chip host, times 12,288
    replicas).  A root that was there is swept and opened as before."""
    import os

    from tpuraft.rpc.messages import SnapshotMeta
    from tpuraft.storage import snapshot as snapmod

    root = str(tmp_path / "r1" / "snapshot")
    st = snapmod.LocalSnapshotStorage(root)
    listed = []
    real_listdir = os.listdir
    monkeypatch.setattr(snapmod.os, "listdir",
                        lambda p: listed.append(p) or real_listdir(p))
    st.init()
    assert os.path.isdir(root)
    assert st.open() is None
    assert listed == []
    # a commit ends that: the next open lists and finds it
    w = st.create()
    w.write_file("data", b"x")
    st.commit(w, SnapshotMeta(last_included_index=7, last_included_term=1))
    reader = st.open()
    assert reader is not None and reader.meta.last_included_index == 7
    assert listed
    # a second boot finds the root there: sweeps (temp dropped), opens
    os.makedirs(os.path.join(root, "temp"))
    again = snapmod.LocalSnapshotStorage(root)
    again.init()
    assert not os.path.exists(os.path.join(root, "temp"))
    assert again.open().meta.last_included_index == 7
