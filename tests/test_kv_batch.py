"""Store-grouped KV serving plane: kv_command_batch wire compat, the
cross-region propose fan-out, MULTI log entries, and coalesced FSM apply.

Covers ISSUE 6's tentpole + test satellites: old-client/new-server and
new-client/old-server interop (the ENOMETHOD fallback sticks and the
counters say so), per-item epoch/range errors (a batch racing a split
re-shards only the escaping items), apply_multi's one-entry
amortization with per-op results, and the apply coalescer's semantics.
"""

import asyncio
import contextlib
import threading

from tests.kv_cluster import KVTestCluster
from tpuraft.entity import LogEntry, LogId
from tpuraft.errors import RaftError, Status
from tpuraft.rheakv.client import BatchingOptions, RheaKVStore
from tpuraft.rheakv.kv_operation import KVOp, KVOperation
from tpuraft.rheakv.kv_service import (
    ERR_INVALID_EPOCH,
    ERR_KEY_OUT_OF_RANGE,
    KVCommandBatchRequest,
    decode_batch_reply,
    decode_result,
    encode_batch_item,
)
from tpuraft.rheakv.metadata import Region
from tpuraft.rheakv.pd_client import FakePlacementDriverClient
from tpuraft.rheakv.raw_store import MemoryRawKVStore
from tpuraft.rheakv.state_machine import KVClosure, KVStoreStateMachine
from tpuraft.core.state_machine import Iterator


@contextlib.asynccontextmanager
async def batch_cluster(regions=None, batching=True, **kw):
    c = KVTestCluster(3, regions=regions, **kw)
    await c.start_all()
    pd = FakePlacementDriverClient(c.region_template)
    pd._regions = {r.id: r.copy() for s in [next(iter(c.stores.values()))]
                   for r in s.list_regions()}
    transport = c.client_transport()
    calls = []
    orig_call = transport.call

    async def counting_call(dst, method, req, timeout_ms=None):
        calls.append(method)
        return await orig_call(dst, method, req, timeout_ms)

    transport.call = counting_call
    kv = RheaKVStore(pd, transport,
                     batching=BatchingOptions(enabled=True)
                     if batching else None)
    await kv.start()
    try:
        yield c, kv, calls
    finally:
        await kv.shutdown()
        await c.stop_all()


REGIONS2 = lambda: [Region(id=1, start_key=b"", end_key=b"m"),  # noqa: E731
                    Region(id=2, start_key=b"m", end_key=b"")]


async def test_batched_puts_ride_store_grouped_rpcs():
    """Concurrent puts spanning regions coalesce into kv_command_batch
    RPCs — one per leader STORE, not one per region or per op."""
    async with batch_cluster(regions=REGIONS2()) as (c, kv, calls):
        for rid in (1, 2):
            await c.wait_region_leader(rid)
        # prime leader hints so the measured burst groups by known stores
        assert await kv.put(b"a-prime", b"p")
        assert await kv.put(b"z-prime", b"p")
        n0 = len(calls)
        b0 = kv.batch_rpcs
        oks = await asyncio.gather(
            *[kv.put(b"a%03d" % i, b"v%d" % i) for i in range(20)],
            *[kv.put(b"z%03d" % i, b"w%d" % i) for i in range(20)])
        assert all(oks)
        burst = [m for m in calls[n0:] if m.startswith("kv_command")]
        # 40 puts over 2 regions whose leaders sit on <= 2 stores: a
        # handful of store-grouped RPCs, NOT one per region/op
        assert len(burst) <= 4, burst
        assert kv.batch_rpcs > b0
        assert kv.batch_items >= kv.batch_rpcs  # many items per RPC
        # server counted them too
        assert sum(s.kv_processor.batch_rpcs
                   for s in c.stores.values()) >= kv.batch_rpcs - b0
        # data landed
        got = await kv.multi_get([b"a%03d" % i for i in range(20)])
        assert got == {b"a%03d" % i: b"v%d" % i for i in range(20)}


async def test_batched_gets_and_mixed_rounds():
    async with batch_cluster(regions=REGIONS2()) as (c, kv, calls):
        for rid in (1, 2):
            await c.wait_region_leader(rid)
        oks = await asyncio.gather(
            *[kv.put(b"g%03d" % i, b"v%d" % i) for i in range(16)])
        assert all(oks)
        n0 = len(calls)
        got = await asyncio.gather(
            *[kv.get(b"g%03d" % i) for i in range(16)],
            kv.get(b"zz-missing"))
        assert got[:16] == [b"v%d" % i for i in range(16)]
        assert got[16] is None
        reads = [m for m in calls[n0:] if m.startswith("kv_command")]
        assert len(reads) <= 4, reads


async def test_new_client_old_server_enomethod_fallback_sticks():
    """A fleet without kv_command_batch: the first batch RPC comes back
    ENOMETHOD, the client downgrades PERMANENTLY to per-op kv_command,
    serves the round through it, and never probes again."""
    async with batch_cluster(regions=REGIONS2()) as (c, kv, calls):
        for rid in (1, 2):
            await c.wait_region_leader(rid)
        assert await kv.put(b"a-prime", b"p")
        assert await kv.put(b"z-prime", b"p")
        # simulate an old fleet: drop the handler from every store
        for s in c.stores.values():
            s.rpc_server._handlers.pop("kv_command_batch", None)
        oks = await asyncio.gather(
            *[kv.put(b"a%03d" % i, b"v%d" % i) for i in range(8)],
            *[kv.put(b"z%03d" % i, b"w%d" % i) for i in range(8)])
        assert all(oks)
        assert kv._batch_ok is False
        assert kv.batch_fallbacks >= 1
        fallbacks_after_first = kv.batch_fallbacks
        n0 = len(calls)
        assert await asyncio.gather(
            *[kv.put(b"a-again%d" % i, b"x") for i in range(6)])
        # no further kv_command_batch attempts — the downgrade stuck
        assert "kv_command_batch" not in calls[n0:]
        assert kv.batch_fallbacks == fallbacks_after_first
        assert await kv.get(b"a003") == b"v3"


async def test_old_client_new_server_per_op_path_serves():
    """Old clients keep speaking per-op kv_command against a batch-aware
    store (the handler stays registered and counted)."""
    async with batch_cluster(regions=REGIONS2(), batching=False) \
            as (c, kv, calls):
        await c.wait_region_leader(1)
        assert await kv.put(b"legacy", b"v")
        assert await kv.get(b"legacy") == b"v"
        assert "kv_command" in calls
        assert "kv_command_batch" not in calls
        assert sum(s.kv_processor.single_rpcs
                   for s in c.stores.values()) >= 2
        assert sum(s.kv_processor.batch_rpcs
                   for s in c.stores.values()) == 0


async def test_batch_per_item_errors_epoch_and_range():
    """One RPC, three items: a valid op, a stale-epoch item and an
    out-of-range item — each answers its OWN code (+ fresh region meta),
    the valid neighbour commits."""
    async with batch_cluster(regions=REGIONS2()) as (c, kv, calls):
        leader2 = await c.wait_region_leader(2)
        se = leader2.store_engine
        region2 = leader2.region
        ep = se.server_id.endpoint
        items = [
            # valid: a region-2 key at the current epoch
            encode_batch_item(2, region2.epoch.conf_ver,
                              region2.epoch.version,
                              KVOperation(KVOp.PUT, b"zz-ok", b"1").encode()),
            # stale epoch
            encode_batch_item(2, region2.epoch.conf_ver,
                              region2.epoch.version + 7,
                              KVOperation(KVOp.PUT, b"zz-x", b"1").encode()),
            # right epoch, key belongs to region 1
            encode_batch_item(2, region2.epoch.conf_ver,
                              region2.epoch.version,
                              KVOperation(KVOp.PUT, b"aa-x", b"1").encode()),
        ]
        resp = await c.client_transport("probe:0").call(
            ep, "kv_command_batch", KVCommandBatchRequest(items=items), 2000)
        codes = [decode_batch_reply(b)[0] for b in resp.items]
        assert codes == [0, ERR_INVALID_EPOCH, ERR_KEY_OUT_OF_RANGE], codes
        # rejected items carry the current region meta for re-sharding
        for blob in resp.items[1:]:
            meta = decode_batch_reply(blob)[3]
            assert Region.decode(meta).id == 2
        assert decode_result(decode_batch_reply(resp.items[0])[2]) is True
        assert await kv.get(b"zz-ok") == b"1"
        assert await kv.get(b"aa-x") is None


async def test_batch_races_split_reshards_only_escaping_items():
    """A split lands under a batching client's stale route view: the
    stale region's items bounce per item, get re-sharded against the
    refreshed routes and commit; items for other regions in the same
    store batch are untouched."""
    async with batch_cluster() as (c, kv, calls):
        leader = await c.wait_region_leader(1)
        for i in range(32):
            assert await kv.put(b"key%02d" % i, b"v%d" % i)
        # split behind the client's back
        st = await leader.store_engine.apply_split(1, 2)
        assert st.is_ok(), str(st)
        await c.wait_region_on_all(2)
        await c.wait_region_leader(2)
        # burst across the WHOLE old range: every item initially groups
        # into stale region 1
        oks = await asyncio.gather(
            *[kv.put(b"key%02d" % i, b"u%d" % i) for i in range(32)])
        assert all(oks)
        assert len(kv.route_table.list_regions()) == 2
        for i in range(32):
            assert await kv.get(b"key%02d" % i) == b"u%d" % i


async def test_apply_multi_one_entry_per_region_with_per_op_results():
    """apply_multi rides ONE log entry (one quorum round) and returns
    per-op (status, result); a failing sub-op fails only its slot."""
    async with batch_cluster() as (c, kv, calls):
        leader = await c.wait_region_leader(1)
        rs = leader.raft_store
        node = leader.node
        entries = []
        orig_ab = node.apply_batch

        async def counting_ab(tasks):
            entries.append(len(tasks))
            return await orig_ab(tasks)

        node.apply_batch = counting_ab
        try:
            await rs.apply(KVOperation(KVOp.PUT, b"m0", b"base"))
            entries.clear()
            outs = await rs.apply_multi([
                KVOperation(KVOp.PUT, b"m1", b"v1"),
                KVOperation.cas(b"m0", b"WRONG", b"nope"),   # CAS miss
                KVOperation(KVOp.PUT_IF_ABSENT, b"m0", b"x"),
                KVOperation(KVOp.DELETE, b"m1"),
                KVOperation(KVOp.GET_SEQUENCE, b"mseq",
                            aux=__import__("struct").pack("<q", 5)),
            ])
        finally:
            node.apply_batch = orig_ab
        # the whole sub-batch rode one Task in one apply_batch call
        assert sum(entries) == 1, entries
        sts = [st for st, _ in outs]
        assert all(st.is_ok() for st in sts), sts
        results = [r for _, r in outs]
        assert results[0] is True
        assert results[1] is False          # CAS miss is a result, not error
        assert results[2] == b"base"        # put_if_absent saw existing
        assert results[3] is True
        assert results[4] == (0, 5)
        assert await kv.get(b"m1") is None
        assert await kv.get(b"m0") == b"base"


async def test_raft_store_public_apply_api():
    """kv_service drives proposals through the public apply() now; the
    legacy _apply name stays as an alias for straggler callers."""
    async with batch_cluster() as (c, kv, calls):
        leader = await c.wait_region_leader(1)
        rs = leader.raft_store
        assert await rs.apply(KVOperation(KVOp.PUT, b"pub", b"1")) is True
        assert await rs._apply(KVOperation(KVOp.PUT, b"pri", b"2")) is True
        # blind writes ack at COMMIT (ISSUE 15 pipelined apply): the
        # fenced read path — not a raw store peek — observes the value
        assert await rs.get(b"pub") == b"1"
        assert await rs.get(b"pri") == b"2"


# ---- FSM apply coalescing (unit tier) --------------------------------------


def _entry(op: KVOperation, index: int) -> LogEntry:
    return LogEntry(id=LogId(index=index, term=1), data=op.encode())


class _BatchSpyStore(MemoryRawKVStore):
    def __init__(self):
        super().__init__()
        self.batch_calls: list[int] = []

    def apply_write_batch(self, ops):
        self.batch_calls.append(len(ops))
        super().apply_write_batch(ops)


async def test_fsm_merges_consecutive_put_delete_runs():
    store = _BatchSpyStore()
    region = Region(id=1, start_key=b"", end_key=b"")
    fsm = KVStoreStateMachine(region, store)
    futs = [asyncio.get_running_loop().create_future() for _ in range(6)]
    ops = [
        KVOperation(KVOp.PUT, b"a", b"1"),
        KVOperation(KVOp.PUT, b"b", b"2"),
        KVOperation.put_list([(b"c", b"3"), (b"d", b"4")]),
        KVOperation(KVOp.DELETE, b"a"),
        KVOperation(KVOp.MERGE, b"e", b"x"),   # breaks the run
        KVOperation(KVOp.PUT, b"f", b"6"),
    ]
    it = Iterator([_entry(op, i + 1) for i, op in enumerate(ops)],
                  [KVClosure(f) for f in futs])
    await fsm.on_apply(it)
    # one coalesced flush for ops 0-3 (5 rows), merge dispatched singly,
    # trailing put flushed as its own run
    assert store.batch_calls[0] == 5, store.batch_calls
    assert fsm.coalesced_flushes == 1
    assert fsm.coalesced_ops == 5
    assert store.get(b"a") is None
    assert store.get(b"b") == b"2"
    assert store.get(b"c") == b"3"
    assert store.get(b"e") == b"x"
    assert store.get(b"f") == b"6"
    for f in futs:
        st, result = f.result()
        assert st.is_ok()
        assert result is True or result is None  # merge returns True too
    # every closure that rode the run reports True
    assert futs[0].result()[1] is True
    assert futs[3].result()[1] is True


async def test_fsm_multi_entry_per_op_outcomes_and_inner_coalescing():
    store = _BatchSpyStore()
    region = Region(id=1, start_key=b"", end_key=b"")
    fsm = KVStoreStateMachine(region, store)
    store.put(b"seed", b"s")
    store.batch_calls.clear()
    multi = KVOperation.multi([
        KVOperation(KVOp.PUT, b"p1", b"1"),
        KVOperation(KVOp.DELETE, b"seed"),
        KVOperation(KVOp.PUT, b"p2", b"2"),
        KVOperation.cas(b"p9", b"nope", b"x"),     # CAS miss mid-batch
        KVOperation(KVOp.PUT, b"p3", b"3"),
    ])
    fut = asyncio.get_running_loop().create_future()
    it = Iterator([_entry(multi, 1)], [KVClosure(fut)])
    await fsm.on_apply(it)
    st, outs = fut.result()
    assert st.is_ok()
    codes = [c for c, _m, _r in outs]
    results = [r for _c, _m, r in outs]
    assert codes == [0, 0, 0, 0, 0]
    assert results == [True, True, True, False, True]
    # the three leading write ops coalesced into one batch write
    assert store.batch_calls[0] == 3, store.batch_calls
    assert store.get(b"p1") == b"1" and store.get(b"seed") is None


async def test_batching_client_history_stays_linearizable():
    """Writers + readers through the batching client: the recorded
    history checks out linearizable — batched ops ack and apply
    atomically per item."""
    from tpuraft.util.linearizability import History, check_history

    async with batch_cluster(regions=REGIONS2()) as (c, kv, calls):
        for rid in (1, 2):
            await c.wait_region_leader(rid)
        h = History()
        stop = asyncio.Event()
        keys = [b"ba-%d" % i for i in range(2)] + [b"zb-%d" % i
                                                  for i in range(2)]
        # one guaranteed-concurrent burst across both regions so the
        # store-grouped path is exercised even if the mixed load below
        # happens to drain one op per round
        seeds = [h.invoke(9, "w", (k, b"seed")) for k in keys]
        assert all(await asyncio.gather(*(kv.put(k, b"seed")
                                          for k in keys)))
        for tok in seeds:
            h.complete(tok, True)
        assert kv.batch_rpcs > 0
        n_ok = [0]

        async def writer(cid):
            n = 0
            while not stop.is_set():
                n += 1
                key = keys[n % len(keys)]
                val = b"c%d-%d" % (cid, n)
                tok = h.invoke(cid, "w", (key, val))
                try:
                    await asyncio.wait_for(kv.put(key, val), 4.0)
                    h.complete(tok, True)
                    n_ok[0] += 1
                except Exception:
                    pass
                await asyncio.sleep(0.003)

        async def reader(cid):
            n = 0
            while not stop.is_set():
                n += 1
                key = keys[n % len(keys)]
                tok = h.invoke(cid, "r", (key,))
                try:
                    v = await asyncio.wait_for(kv.get(key), 4.0)
                    h.complete(tok, v)
                    n_ok[0] += 1
                except Exception:
                    pass
                await asyncio.sleep(0.002)

        tasks = [asyncio.ensure_future(writer(0)),
                 asyncio.ensure_future(writer(1)),
                 asyncio.ensure_future(reader(2)),
                 asyncio.ensure_future(reader(3))]
        await asyncio.sleep(2.0)
        stop.set()
        await asyncio.gather(*tasks)
        assert n_ok[0] > 100, f"only {n_ok[0]} ops completed"
        assert kv.batch_rpcs > 0   # the load actually rode the batch path
        rep = check_history(h)
        assert rep.ok, str(rep)


# -- the loop thread is the only owner of the raw store -----------------------


class _ThreadSpyStore:
    """A raw store that notes, for every call into it, the method and
    the thread that made the call."""

    def __init__(self, inner, calls: list):
        self._inner = inner
        self._calls = calls

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if not callable(attr):
            return attr

        def call(*args, **kw):
            self._calls.append((name, threading.get_ident()))
            return attr(*args, **kw)

        return call


async def test_every_raw_store_call_is_made_on_the_loop_thread(tmp_path):
    """The ownership rule of ``RawKVStore``: writes, fenced reads, a
    scan, a CAS, a snapshot save and a split's probes all reach the
    store from the store engine's loop thread."""
    calls: list = []
    async with batch_cluster(
            tmp_path=tmp_path,
            raw_store_factory=lambda _ep: _ThreadSpyStore(
                MemoryRawKVStore(), calls)) as (c, kv, _rpcs):
        leader = await c.wait_region_leader(1)
        assert all(await asyncio.gather(
            *[kv.put(b"key%02d" % i, b"v%d" % i) for i in range(32)]))
        assert await asyncio.gather(
            *[kv.get(b"key%02d" % i) for i in range(32)]) == \
            [b"v%d" % i for i in range(32)]
        assert len(await kv.scan(b"key00", b"key10")) == 10
        assert await kv.compare_and_put(b"key00", b"v0", b"cas")
        st = await leader.node.snapshot()
        assert st.is_ok(), str(st)
        st = await leader.store_engine.apply_split(1, 2)
        assert st.is_ok(), str(st)
        await c.wait_region_on_all(2)
        await c.wait_region_leader(2)
        assert await kv.put(b"key31", b"after")
        assert await kv.get(b"key31") == b"after"
    assert {"apply_write_batch", "multi_get", "scan", "compare_and_put",
            "serialize_range", "approximate_keys_in_range",
            "jump_over"} <= {name for name, _tid in calls}
    off_loop = sorted({name for name, tid in calls
                       if tid != threading.get_ident()})
    assert off_loop == [], f"raw-store calls off the loop thread: {off_loop}"


# -- a round cut into chunks is one round (ISSUE 36) -------------------------


async def test_a_chunked_round_answers_all_its_callers_together():
    """Three chunks of one loop turn's items, finishing at three times:
    no caller is answered before the last chunk has its answers, then all
    are, in one turn, each with its own result or its own error."""
    from tpuraft.rheakv.client import _Batcher

    gates = [asyncio.Event() for _ in range(3)]
    started: list = []

    async def flush(chunk):
        k = len(started)
        started.append([item for item, _ in chunk])
        await gates[k].wait()
        for item, fut in chunk:
            if item == 7:
                fut.set_exception(ValueError("seven"))
            else:
                fut.set_result(item * 10)

    b = _Batcher(4, flush)
    futs = [b.add(i) for i in range(10)]            # one turn: one round
    for _ in range(4):
        await asyncio.sleep(0)
    assert started == [[0, 1, 2, 3], [4, 5, 6, 7], [8, 9]]
    gates[2].set()                                  # the small last chunk
    gates[0].set()
    for _ in range(4):
        await asyncio.sleep(0)
    assert not any(f.done() for f in futs)          # chunk 1 is still out
    futs[5].cancel()                                # a caller goes away
    gates[1].set()
    for _ in range(4):
        await asyncio.sleep(0)
    assert all(f.done() for f in futs)
    assert [f.result() for i, f in enumerate(futs) if i not in (5, 7)] == \
        [0, 10, 20, 30, 40, 60, 80, 90]
    assert futs[5].cancelled()
    assert isinstance(futs[7].exception(), ValueError)


async def test_a_round_of_one_chunk_and_rounds_of_later_turns_stay_apart():
    """What fits one chunk is flushed as before, with the callers' own
    futures, and a later turn's items make a later round that waits for
    nobody."""
    from tpuraft.rheakv.client import _Batcher

    seen: list = []
    hold = asyncio.Event()

    async def flush(chunk):
        seen.append([item for item, _ in chunk])
        if len(seen) == 1:
            await hold.wait()
        for item, fut in chunk:
            fut.set_result(item)

    b = _Batcher(4, flush)
    first = [b.add(i) for i in range(3)]
    for _ in range(3):
        await asyncio.sleep(0)
    second = [b.add(i) for i in (10, 11)]
    for _ in range(3):
        await asyncio.sleep(0)
    assert seen == [[0, 1, 2], [10, 11]]
    assert [f.result() for f in second] == [10, 11]     # not held up
    assert not any(f.done() for f in first)
    hold.set()
    assert await asyncio.gather(*first) == [0, 1, 2]
