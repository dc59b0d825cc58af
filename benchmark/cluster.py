"""The system under test, built from a configuration file.

The only module of the benchmark that imports ``tpuraft``: it builds the
deployment the configuration states (the topology ``chip_smoke.py`` proved on
the chip in PR 21), loads the records, and hands the harness the client, the
program's counters and the engines' live tick inputs.  ``IMPLEMENTS`` says
which values of the guarded fields this class builds; ``benchmark/plugins.py``
refuses any other by name.  A deployment built otherwise is a module under
``benchmark/clusters/`` with a ``Cluster`` of its own (as a rule a subclass
that overrides one of ``start``'s steps) and its own ``IMPLEMENTS``; its
configuration file names it under ``cluster`` and may hand it an ``options``
object, which nothing else reads.
"""

from __future__ import annotations

import asyncio
import inspect
import os
import time

import numpy as np

from benchmark.reference import TICK_OUTPUTS
from benchmark.traffic import LOADER, Values


class NotImplementedConfig(NotImplementedError):
    pass


IMPLEMENTS = {"stores": [3], "replicas": [3], "read_mode": ["safe"],
              "transport": ["inproc"], "log_scheme": ["multilog"],
              "kv_store": ["native"], "engine.backend": ["jax"],
              # served traffic has never run over the mesh
              "engine.mesh_devices": [0, 1]}


def region_start(k: int) -> bytes:
    return b"%06x" % k


class Cluster:
    """Three ``StoreEngine``s over ``InProcNetwork`` in this process, each
    with its own ``MultiRaftEngine``, and one batching ``RheaKVStore``."""

    def __init__(self, cfg: dict, workdir: str):
        if cfg["record_count"] % cfg["regions"]:
            raise NotImplementedConfig(
                f"config {cfg['name']}: record_count must be a multiple of "
                f"regions")
        self.cfg = cfg
        self.options = cfg.get("options", {})   # the subclass's own
        self.workdir = workdir
        self.regions = cfg["regions"]
        self.engines: list = []
        self.stores: list = []
        self.client = None
        self.timings: dict = {}
        # record i lives in region i % R: the scrambled zipfian's hot
        # records land on regions the seed picks
        self.keys = [region_start(i % self.regions)
                     + b"/user%08d" % (i // self.regions)
                     for i in range(cfg["record_count"])]

    # -- lifecycle ----------------------------------------------------------

    # The steps of ``start`` that a deployment built otherwise overrides.

    def endpoints(self) -> list:
        return [f"127.0.0.1:{6600 + i}" for i in range(self.cfg["stores"])]

    def tick_options(self, i: int):
        """Store ``i``'s engine."""
        from tpuraft.options import TickOptions

        eng = self.cfg["engine"]
        return TickOptions(
            max_groups=eng["max_groups"], max_peers=eng["max_peers"],
            tick_interval_ms=eng["tick_interval_ms"],
            mesh_devices=eng["mesh_devices"], backend=eng["backend"])

    def store_options(self, i: int, endpoint: str, region_list: list):
        """Store ``i``: its regions, where it keeps them, what it promises."""
        from tpuraft.options import ReadOnlyOption
        from tpuraft.rheakv.native_store import NativeRawKVStore
        from tpuraft.rheakv.store_engine import StoreEngineOptions

        return StoreEngineOptions(
            server_id=endpoint,
            initial_regions=[r.copy() for r in region_list],
            data_path=f"{self.workdir}/store{i}",
            election_timeout_ms=self.cfg["election_timeout_ms"],
            log_scheme=self.cfg["log_scheme"],
            read_only_option=ReadOnlyOption.SAFE,
            raw_store_factory=lambda i=i: NativeRawKVStore(
                f"{self.workdir}/store{i}/kv"))

    def pd_client(self, region_list: list):
        from tpuraft.rheakv.pd_client import FakePlacementDriverClient

        return FakePlacementDriverClient([r.copy() for r in region_list])

    def make_client(self, net, region_list: list):
        from tpuraft.rheakv.client import BatchingOptions, RheaKVStore
        from tpuraft.rpc.transport import InProcTransport

        return RheaKVStore(
            self.pd_client(region_list), InProcTransport(net, "kvclient:0"),
            batching=BatchingOptions(enabled=True), timeout_ms=20000)

    async def start(self, elect_deadline_s: float = 300.0) -> None:
        from tpuraft.core.engine import MultiRaftEngine
        from tpuraft.options import RaftOptions
        from tpuraft.rheakv.metadata import Region
        from tpuraft.rheakv.native_store import NativeRawKVStore
        from tpuraft.rheakv.store_engine import StoreEngine
        from tpuraft.rpc.transport import (InProcNetwork, InProcTransport,
                                           RpcServer)

        R = self.regions
        # the guarantee is fsync at the program's defaults: see that they
        # still are what the configuration states
        if not (RaftOptions().sync and RaftOptions().sync_meta
                and inspect.signature(NativeRawKVStore)
                .parameters["sync"].default is True):
            raise RuntimeError("a durability default is no longer fsync-on")
        net = InProcNetwork()
        endpoints = self.endpoints()
        region_list = [Region(id=k + 1,
                              start_key=region_start(k) if k else b"",
                              end_key=region_start(k + 1) if k + 1 < R
                              else b"", peers=list(endpoints))
                       for k in range(R)]
        t0 = time.perf_counter()
        for i, ep in enumerate(endpoints):
            os.makedirs(f"{self.workdir}/store{i}", exist_ok=True)
            server = RpcServer(ep)
            net.bind(server)
            engine = MultiRaftEngine(self.tick_options(i))
            self.engines.append(engine)
            store = StoreEngine(
                self.store_options(i, ep, region_list),
                server, InProcTransport(net, ep), multi_raft_engine=engine)
            self.stores.append(store)
            await store.start()
        self.timings["boot_s"] = time.perf_counter() - t0
        node = next(iter(self.stores[0]._regions.values())).node
        if not (node.options.raft_options.sync
                and node.options.raft_options.sync_meta):
            raise RuntimeError("region nodes run with fsync off")

        t1 = time.perf_counter()
        while self.leaders() < R:
            if time.perf_counter() - t1 > elect_deadline_s:
                raise RuntimeError(f"only {self.leaders()} of {R} regions "
                                   f"elected within {elect_deadline_s}s")
            await asyncio.sleep(0.1)
        self.timings["elect_s"] = time.perf_counter() - t1

        self.client = self.make_client(net, region_list)
        await self.client.start()

    async def shutdown(self) -> None:
        if self.client is not None:
            await self.client.shutdown()
            self.client = None
        for s in self.stores:
            await s.shutdown()
        self.stores = []

    # -- data ---------------------------------------------------------------

    async def load(self, values: Values, chunk: int = 256,
                   inflight: int = 8) -> None:
        """Every record written once by the LOADER, in ``put_list`` chunks."""
        t0 = time.perf_counter()
        # by key, so a chunk of 256 is whole regions and each region's
        # records go into its log as one entry
        kvs = sorted((key, values.make(LOADER, i, i))
                     for i, key in enumerate(self.keys))
        sem = asyncio.Semaphore(inflight)

        async def put(part):
            async with sem:
                if await self.client.put_list(part) is not True:
                    raise RuntimeError("a put_list of the load was refused")

        await asyncio.gather(*(put(kvs[i:i + chunk])
                               for i in range(0, len(kvs), chunk)))
        self.timings["load_s"] = time.perf_counter() - t0

    async def read_all(self, deadline_s: float, inflight: int = 512) -> list:
        """A linearizable read of every record, through the client.  A read
        that fails is asked again until ``deadline_s`` has passed (a late
        answer is late, not wrong); one that never comes reads as None."""
        sem = asyncio.Semaphore(inflight)
        give_up = time.perf_counter() + deadline_s

        async def get(key):
            while True:
                try:
                    async with sem:
                        return await self.client.get(key)
                except asyncio.CancelledError:
                    raise
                except Exception:  # noqa: BLE001 — asked again, then counted
                    if time.perf_counter() > give_up:
                        return None
                    await asyncio.sleep(0.5)

        return await asyncio.gather(*(get(k) for k in self.keys))

    def replica_values(self, store: int, records) -> list:
        """What store ``store``'s own state machine holds for ``records``."""
        raw = self.stores[store].raw_store
        return [raw.get(self.keys[i]) for i in records]

    # -- what the program counts --------------------------------------------

    def leaders(self) -> int:
        return sum(1 for s in self.stores for re in s._regions.values()
                   if re.is_leader())

    def leaders_per_store(self) -> list:
        return [sum(1 for re in s._regions.values() if re.is_leader())
                for s in self.stores]

    def counters(self) -> dict:
        """A flat snapshot of the program's counters; the harness takes the
        difference over the window."""
        c = self.client
        out = {"client.batch_rpcs": c.batch_rpcs,
               "client.batch_items": c.batch_items,
               "client.batch_retries": sum(c.batch_retries.values())}
        for name in ("read_confirms", "read_rounds", "read_beat_rpcs",
                     "read_beats", "read_classic_beats", "read_failed",
                     "read_device_fences"):
            out[f"kv.{name}"] = sum(s.read_batcher.counters()[name]
                                    for s in self.stores)
        for i, e in enumerate(self.engines):
            st = e.lane_stats()
            out[f"engine{i}.ticks"] = e.ticks
            out[f"engine{i}.tick_failures"] = st["tick_failures"]
            out[f"engine{i}.fence_lane_resolves"] = st["fence_lane_resolves"]
            out[f"engine{i}.commit_advances"] = e.commit_advances
            out[f"engine{i}.eager_commits"] = e.eager_commits
            out[f"engine{i}.leaders"] = st["leaders"]
            for hname, h in e.tick_hists.items():
                out[f"engine{i}.{hname}.count"] = h.count
                out[f"engine{i}.{hname}.total"] = h.total
        for name in ("ticks", "tick_failures", "fence_lane_resolves",
                     "commit_advances", "eager_commits"):
            out[f"engine.{name}"] = sum(out[f"engine{i}.{name}"]
                                        for i in range(len(self.engines)))
        # the two fsync rounds, over the stores: events counted as
        # histograms that take one sample an event
        for name in ("kv_wal_syncs", "kv_wal_sync_entries", "log_rounds",
                     "log_round_groups"):
            out[f"engine.{name}.count"] = sum(
                out.get(f"engine{i}.{name}.count", 0)
                for i in range(len(self.engines)))
        return out

    def device_tick_runs(self) -> bool:
        return all(e._tick_fn is not None for e in self.engines)

    # -- the compiled tick on the engines' live state -----------------------

    def tick_probe(self, engine: int) -> tuple:
        """(inputs, now, params, outputs): the rows engine ``engine`` would
        hand its compiled tick right now, and what that tick returns for
        them, through the engine's own call (no await in between, so the
        rows cannot move)."""
        e = self.engines[engine]
        now = e.now_ms()
        rel, commit_rel = e._rel_views()
        state = e._group_state(rel, commit_rel)
        inputs = {name: np.array(getattr(state, name))
                  for name in state.__dataclass_fields__}
        out = e._device_tick(rel, commit_rel, now)
        params = {"election_timeout_ms": e.eto_ms.astype(np.int64),
                  "heartbeat_ms": e.hb_ms.astype(np.int64),
                  "lease_ms": e.lease_ms.astype(np.int64),
                  "snapshot_ms": e.snap_ms.astype(np.int64)}
        outputs = {name: np.asarray(getattr(out, name))
                   for name in TICK_OUTPUTS}
        return inputs, int(now), params, outputs
