"""Native C++ KV storage engine tests.

Mirrors the reference's RocksRawKVStoreTest tier (SURVEY.md §5 "Storage
unit"): real engine on a temp dir, torn down per test, plus the
crash-recovery drives the reference gets from RocksDB's own WAL tests.
"""

import os
import signal
import struct
import subprocess
import sys
import textwrap
import time

import pytest

from tpuraft.rheakv.native_store import (
    NativeRawKVStore,
    create_raw_kv_store,
    ensure_built,
)
from tpuraft.rheakv.raw_store import MemoryRawKVStore


@pytest.fixture(scope="module", autouse=True)
def built():
    ensure_built()


@pytest.fixture
def store(tmp_path):
    s = NativeRawKVStore(str(tmp_path / "kv"))
    yield s
    s.close()


def test_basic_point_ops(store):
    assert store.get(b"a") is None
    store.put(b"a", b"1")
    store.put(b"b", b"2")
    assert store.get(b"a") == b"1"
    assert store.contains_key(b"b")
    assert store.put_if_absent(b"a", b"x") == b"1"
    assert store.get(b"a") == b"1"
    assert store.get_and_put(b"a", b"3") == b"1"
    assert store.compare_and_put(b"a", b"3", b"4")
    assert not store.compare_and_put(b"a", b"nope", b"5")
    store.merge(b"m", b"x")
    store.merge(b"m", b"y")
    assert store.get(b"m") == b"x,y"
    store.delete(b"a")
    assert store.get(b"a") is None
    assert store.multi_get([b"b", b"zz"]) == {b"b": b"2", b"zz": None}


def test_scan_and_ranges(store):
    store.put_list([(bytes([i]), bytes([i]) * 2) for i in range(10)])
    rows = store.scan(bytes([2]), bytes([5]))
    assert [k for k, _ in rows] == [bytes([2]), bytes([3]), bytes([4])]
    assert rows[0][1] == bytes([2, 2])
    # open-ended + limit + keys-only
    rows = store.scan(b"", b"", limit=3, return_value=False)
    assert [k for k, _ in rows] == [bytes([0]), bytes([1]), bytes([2])]
    assert rows[0][1] is None
    rev = store.reverse_scan(bytes([2]), bytes([5]))
    assert [k for k, _ in rev] == [bytes([4]), bytes([3]), bytes([2])]
    assert store.approximate_keys_in_range(bytes([1]), bytes([4])) == 3
    assert store.jump_over(b"", b"", 4) == bytes([4])
    store.delete_range(bytes([3]), bytes([8]))
    assert [k for k, _ in store.scan(b"", b"")] == [
        bytes([0]), bytes([1]), bytes([2]), bytes([8]), bytes([9])]


def test_binary_safe_keys_values(store):
    k = b"\x00\xff\x00 embedded"
    v = bytes(range(256))
    store.put(k, v)
    assert store.get(k) == v
    assert store.scan(b"\x00", b"\x01")[0] == (k, v)


def test_sequences_and_locks_persist(tmp_path):
    s = NativeRawKVStore(str(tmp_path / "kv"))
    seq = s.get_sequence(b"ids", 10)
    assert (seq.start, seq.end) == (0, 10)
    assert s.get_sequence(b"ids", 5).start == 10
    ok, token, owner = s.try_lock_with(b"L", b"me", 60_000, False)
    assert ok and owner == b"me"
    ok2, token2, owner2 = s.try_lock_with(b"L", b"other", 60_000, False)
    assert not ok2 and owner2 == b"me" and token2 == token
    s.close()

    s = NativeRawKVStore(str(tmp_path / "kv"))  # reopen: WAL replay
    assert s.get_sequence(b"ids", 0).start == 15
    ok3, token3, owner3 = s.try_lock_with(b"L", b"other", 1000, False)
    assert not ok3 and owner3 == b"me"  # lease survives restart
    assert s.release_lock(b"L", b"me")
    ok4, token4, _ = s.try_lock_with(b"L", b"other", 1000, False)
    assert ok4 and token4 > token  # fencing token monotonic across restart
    s.close()


def test_reentrant_lock(store):
    ok, t1, _ = store.try_lock_with(b"L", b"me", 60_000, False)
    ok, t2, _ = store.try_lock_with(b"L", b"me", 60_000, False)
    assert ok and t1 == t2
    assert store.release_lock(b"L", b"me")
    ok, _, owner = store.try_lock_with(b"L", b"other", 1000, False)
    assert not ok and owner == b"me"  # still held: acquired twice
    assert store.release_lock(b"L", b"me")
    ok, _, _ = store.try_lock_with(b"L", b"other", 1000, False)
    assert ok


def test_checkpoint_and_reopen(tmp_path):
    s = NativeRawKVStore(str(tmp_path / "kv"))
    s.put_list([(f"k{i}".encode(), f"v{i}".encode()) for i in range(100)])
    assert s.wal_bytes() > 0
    s.checkpoint()
    assert s.wal_bytes() == 0
    s.put(b"after", b"ckpt")
    s.close()
    s = NativeRawKVStore(str(tmp_path / "kv"))  # checkpoint + WAL replay
    assert s.get(b"k42") == b"v42"
    assert s.get(b"after") == b"ckpt"
    assert len(s.scan(b"", b"")) == 101
    s.close()


def test_auto_checkpoint_threshold(tmp_path):
    s = NativeRawKVStore(str(tmp_path / "kv"), checkpoint_wal_bytes=4096)
    for i in range(200):
        s.put(f"k{i:04}".encode(), b"x" * 64)
    assert s.wal_bytes() < 4096 + 2048  # truncated at least once
    s.close()
    s = NativeRawKVStore(str(tmp_path / "kv"))
    assert len(s.scan(b"", b"")) == 200
    s.close()


def test_torn_wal_tail_dropped(tmp_path):
    path = str(tmp_path / "kv")
    s = NativeRawKVStore(path)
    s.put(b"good", b"1")
    s.put(b"torn", b"2")
    s.close()
    # corrupt the last record's payload byte
    wal = os.path.join(path, "wal.log")
    blob = bytearray(open(wal, "rb").read())
    blob[-1] ^= 0xFF
    open(wal, "wb").write(bytes(blob))
    s = NativeRawKVStore(path)
    assert s.get(b"good") == b"1"
    assert s.get(b"torn") is None  # torn tail dropped cleanly
    s.put(b"new", b"3")  # and appending after recovery works
    s.close()
    s = NativeRawKVStore(path)
    assert s.get(b"new") == b"3"
    s.close()


def test_oversized_wal_length_field_dropped(tmp_path):
    """A corrupted header whose length field reads huge must be treated
    as a torn tail (the header is not self-checksummed) — not trigger a
    multi-GB allocation that aborts the reopening process."""
    path = str(tmp_path / "kv")
    s = NativeRawKVStore(path)
    s.put(b"good", b"1")
    s.close()
    wal = os.path.join(path, "wal.log")
    blob = open(wal, "rb").read()
    # append a frame claiming 0xFFFFFFF0 payload bytes
    open(wal, "ab").write(struct.pack("=II", 0xFFFFFFF0, 0xDEADBEEF))
    s = NativeRawKVStore(path)
    assert s.get(b"good") == b"1"
    s.close()
    assert os.path.getsize(wal) == len(blob)  # bogus frame truncated away
    s = NativeRawKVStore(path)
    s.put(b"new", b"2")
    s.close()
    s = NativeRawKVStore(path)
    assert s.get(b"new") == b"2"
    s.close()


def test_kill9_mid_write_recovers(tmp_path):
    """The reference's durability contract: kill -9 a writer mid-stream,
    reopen, and the surviving prefix is contiguous and uncorrupted."""
    path = str(tmp_path / "kv")
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))!r})
        from tpuraft.rheakv.native_store import NativeRawKVStore
        s = NativeRawKVStore({path!r})
        i = 0
        while True:
            s.put(b"k%08d" % i, b"v%08d" % i)
            i += 1
    """)
    proc = subprocess.Popen([sys.executable, "-c", code])
    # wait for REAL bytes on disk, not a fixed sleep: interpreter boot
    # stretches arbitrarily under full-suite CPU contention
    deadline = time.time() + 60
    while time.time() < deadline:
        total = 0
        if os.path.isdir(path):
            total = sum(
                os.path.getsize(os.path.join(path, f))
                for f in os.listdir(path))
        if total > 200_000:
            break
        time.sleep(0.2)
    proc.send_signal(signal.SIGKILL)
    proc.wait()
    s = NativeRawKVStore(path)
    rows = s.scan(b"", b"")
    assert len(rows) > 0, "writer never wrote"
    for n, (k, v) in enumerate(rows):
        assert k == b"k%08d" % n and v == b"v%08d" % n
    s.close()


def test_snapshot_blob_interchange(tmp_path):
    """serialize_range blobs round-trip between the native and memory
    engines (snapshot install may land on either)."""
    nat = NativeRawKVStore(str(tmp_path / "kv"))
    nat.put_list([(f"k{i}".encode(), f"v{i}".encode()) for i in range(20)])
    nat.get_sequence(b"k5seq", 7)
    nat.try_lock_with(b"k7lock", b"me", 60_000, False)
    blob = nat.serialize_range(b"", b"")

    mem = MemoryRawKVStore()
    mem.load_serialized(blob)
    assert mem.get(b"k9") == b"v9"
    assert mem.get_sequence(b"k5seq", 0).start == 7
    ok, _, owner = mem.try_lock_with(b"k7lock", b"other", 1000, False)
    assert not ok and owner == b"me"

    # and back: memory -> native
    blob2 = mem.serialize_range(b"", b"")
    nat2 = NativeRawKVStore(str(tmp_path / "kv2"))
    nat2.load_serialized(blob2)
    assert nat2.get(b"k9") == b"v9"
    assert nat2.get_sequence(b"k5seq", 0).start == 7
    nat.close()
    nat2.close()


def test_reset_range_clears_all_namespaces(tmp_path):
    """Snapshot load = exact state reset: sequences/locks created after
    the snapshot must not survive a reset_range (replay determinism)."""
    for make in (lambda: NativeRawKVStore(str(tmp_path / "kv")),
                 MemoryRawKVStore):
        s = make()
        s.put(b"ka", b"1")
        s.get_sequence(b"kseq", 10)
        s.try_lock_with(b"klock", b"me", 60_000, False)
        s.put(b"za", b"outside")  # different range: must survive
        s.get_sequence(b"zseq", 5)
        s.reset_range(b"k", b"l")
        assert s.get(b"ka") is None
        assert s.get_sequence(b"kseq", 0).start == 0
        ok, _, _ = s.try_lock_with(b"klock", b"other", 1000, False)
        assert ok  # lock gone
        assert s.get(b"za") == b"outside"
        assert s.get_sequence(b"zseq", 0).start == 5
        if hasattr(s, "close"):
            s.close()


def test_use_after_close_raises(tmp_path):
    s = NativeRawKVStore(str(tmp_path / "kv"))
    s.put(b"a", b"1")
    s.close()
    with pytest.raises(IOError):
        s.get(b"a")
    with pytest.raises(IOError):
        s.put(b"b", b"2")
    s.close()  # idempotent


def test_factory_uri(tmp_path):
    s = create_raw_kv_store(f"native://{tmp_path}/kv")
    assert isinstance(s, NativeRawKVStore)
    s.put(b"a", b"b")
    assert s.get(b"a") == b"b"
    s.close()
    assert isinstance(create_raw_kv_store("memory://"), MemoryRawKVStore)
    with pytest.raises(ValueError):
        create_raw_kv_store("bogus://x")


@pytest.mark.asyncio
async def test_kv_cluster_on_native_engine(tmp_path):
    """Full RheaKV region cluster with the native engine under every
    store: put/get/scan/sequence/lock through raft."""
    from tests.kv_cluster import KVTestCluster
    from tpuraft.rheakv.client import RheaKVStore
    from tpuraft.rheakv.pd_client import FakePlacementDriverClient

    c = KVTestCluster(
        3, raw_store_factory=lambda ep: NativeRawKVStore(
            str(tmp_path / ep.replace(":", "_"))))
    await c.start_all()
    try:
        await c.wait_region_leader(1)
        pd = FakePlacementDriverClient(
            [r.copy() for s in [next(iter(c.stores.values()))]
             for r in s.list_regions()])
        client = RheaKVStore(pd, c.client_transport())
        await client.start()
        try:
            assert await client.put(b"alpha", b"1")
            assert await client.put(b"beta", b"2")
            assert await client.get(b"alpha") == b"1"
            rows = await client.scan(b"", b"")
            assert [k for k, _ in rows] == [b"alpha", b"beta"]
            seq = await client.get_sequence(b"s", 100)
            assert seq.end == 100
        finally:
            await client.shutdown()
        # the data actually lives in the native engines
        leader = await c.wait_region_leader(1)
        raw = leader.store_engine.raw_store
        assert isinstance(raw, NativeRawKVStore)
    finally:
        await c.stop_all()
