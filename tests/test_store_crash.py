"""A store that crashes and comes back (``StoreEngine.crash``), and the
client that waits for the election it leaves behind (``op_deadline_ms``):
what the failover deployment (``benchmark/clusters/failover.py``) asks of the
program."""

import asyncio
import inspect
import time

import pytest

from tests.kv_cluster import KVTestCluster
from tpuraft.core.engine import MultiRaftEngine
from tpuraft.options import TickOptions
from tpuraft.rheakv.client import BatchingOptions, RheaKVError, RheaKVStore
from tpuraft.rheakv.metadata import Region
from tpuraft.rheakv.native_store import NativeRawKVStore
from tpuraft.rheakv.pd_client import FakePlacementDriverClient
from tpuraft.rheakv.store_engine import StoreEngine
from tpuraft.storage.multilog import peek_engine

REGIONS = 6


def _regions() -> list:
    cut = [b""] + [b"k%02d" % (10 * i) for i in range(1, REGIONS)] + [b""]
    return [Region(id=i + 1, start_key=cut[i], end_key=cut[i + 1])
            for i in range(REGIONS)]


def _cluster(tmp_path, eto_ms: int) -> KVTestCluster:
    return KVTestCluster(
        3, tmp_path=tmp_path, regions=_regions(), election_timeout_ms=eto_ms,
        log_scheme="multilog",
        multi_raft_engine_factory=lambda: MultiRaftEngine(TickOptions(
            max_groups=16, max_peers=4, tick_interval_ms=10)),
        raw_store_factory=lambda ep: NativeRawKVStore(
            f"{tmp_path}/kv-{ep.replace(':', '_')}"))


async def _leaders(c: KVTestCluster) -> dict:
    return {rid: (await c.wait_region_leader(rid, 20.0)).store_engine
            for rid in range(1, REGIONS + 1)}


def _crash(c: KVTestCluster, ep: str) -> StoreEngine:
    """The endpoint off the network first, then the store."""
    c.net.stop_endpoint(ep)
    c.net.unbind(ep)
    store = c.stores.pop(ep)
    out = store.crash()
    assert out is None and not inspect.iscoroutinefunction(StoreEngine.crash)
    return store


def _client(c: KVTestCluster, **kw) -> RheaKVStore:
    return RheaKVStore(FakePlacementDriverClient(c.region_template),
                       c.client_transport(),
                       batching=BatchingOptions(enabled=True), **kw)


def _most_leading(by_region: dict) -> StoreEngine:
    stores = list(by_region.values())
    return max(set(stores), key=stores.count)


def test_a_client_with_a_deadline_rides_out_an_outage_longer_than_eight_bounces(
        tmp_path):
    """The election timeout is 3 s, the old retry budget (eight bounces,
    1.8 to 2.7 s of backoff) shorter: a client without a deadline fails
    what it is asked during the outage, one with a deadline fails nothing."""
    async def go():
        c = _cluster(tmp_path, 3000)
        await c.start_all()
        try:
            patient = _client(c, op_deadline_ms=20000)
            counted = _client(c)
            await patient.start()
            await counted.start()
            keys = [b"k%02d" % i for i in range(60)]
            assert all(await asyncio.gather(
                *(patient.put(k, b"v0-" + k) for k in keys)))
            victim = _most_leading(await _leaders(c))
            led = [rid for rid, re in victim._regions.items()
                   if re.is_leader()]
            t0 = time.perf_counter()
            _crash(c, str(victim.server_id.endpoint))
            assert time.perf_counter() - t0 < 1.0

            async def ask(kv, k):
                try:
                    return await kv.put(k, b"v1-" + k) is True \
                        and await kv.get(k) == b"v1-" + k
                except RheaKVError:
                    return False

            first = asyncio.gather(*(ask(counted, k) for k in keys))
            second = asyncio.gather(*(ask(patient, k) for k in keys))
            gave_up = (await first).count(False)
            took = time.perf_counter() - t0
            assert all(await second)
            outage = time.perf_counter() - t0
            return led, gave_up, took, outage, patient, counted
        finally:
            await c.stop_all()

    led, gave_up, took, outage, patient, counted = asyncio.run(go())
    assert led
    # by the count: spent before any leader could exist
    assert gave_up >= 1 and took < outage
    assert 2.5 < outage < 15.0
    # by the clock: more than eight cycles, each region asked by one batch
    assert patient.retry_cycles > 8
    assert sum(patient.batch_retries.values()) > 0
    assert not patient._probes and not counted._probes


def test_a_crashed_stores_files_reopen_to_the_state_it_acknowledged(tmp_path):
    async def go():
        c = _cluster(tmp_path, 1000)
        await c.start_all()
        try:
            kv = _client(c, op_deadline_ms=20000)
            await kv.start()
            keys = [b"k%02d" % i for i in range(60)]
            assert all(await asyncio.gather(
                *(kv.put(k, b"a-" + k) for k in keys)))
            victim = _most_leading(await _leaders(c))
            ep = str(victim.server_id.endpoint)
            mlog = (f"{victim.opts.data_path}/{victim.server_id.ip}_"
                    f"{victim.server_id.port}/mlog")
            assert peek_engine(mlog) is not None
            tasks_before = len(asyncio.all_tasks())
            # what each of its logs had made durable (and so acknowledged)
            durable = {rid: re.node.log_manager._stable_index
                       for rid, re in victim._regions.items()}
            assert min(durable.values()) >= 1
            _crash(c, ep)
            # nothing of it is left open, so a successor can open the files
            assert peek_engine(mlog) is None
            assert victim._regions == {} and not victim._started
            with pytest.raises(IOError):
                victim.raw_store.get(keys[0])
            # the survivors elect and serve; the dead store misses this
            assert all(await asyncio.gather(
                *(kv.put(k, b"b-" + k) for k in keys[::2])))
            await asyncio.sleep(0)
            assert len(asyncio.all_tasks()) <= tasks_before + 8
            again = await c.start_store(ep)
            # what it had acknowledged it still has, from its own files
            for rid, index in durable.items():
                log = again._regions[rid].node.log_manager
                assert log.last_log_index() >= index, rid
                assert log.get_term(index) >= 1
            deadline = time.perf_counter() + 20.0
            want = {k: (b"b-" if i % 2 == 0 else b"a-") + k
                    for i, k in enumerate(keys)}
            while time.perf_counter() < deadline:
                if all(s.raw_store.get(k) == v for s in c.stores.values()
                       for k, v in want.items()):
                    break
                await asyncio.sleep(0.05)
            for s in c.stores.values():
                assert {k: s.raw_store.get(k) for k in keys} == want
            assert sum(1 for s in c.stores.values()
                       for re in s._regions.values() if re.is_leader()) \
                == REGIONS
        finally:
            await c.stop_all()

    asyncio.run(go())
