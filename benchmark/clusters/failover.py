"""``kv3x1024-failover``: the cluster of ``benchmark/cluster.py`` with a store
that can be lost and brought back inside a window.

The loop that runs the fault schedule (``benchmark/loops/open_faults.py``)
reaches this class through the client it is handed (``client.cluster``, the
attribute the control's ``faults._Faulty`` carries too).  ``kill`` is a crash
of an in-process store: its endpoint goes down first, then the program drops
the store with nothing flushed and nobody told; its files stay, and
``restart`` boots a new ``StoreEngine`` and a new ``MultiRaftEngine`` from
them on the loop that is serving.  ``counters`` keeps what a dead engine
counted, so a window's end minus its start is never negative.
"""

from __future__ import annotations

import inspect
import time

from benchmark import cluster as base
from benchmark.cluster import NotImplementedConfig

IMPLEMENTS = dict(base.IMPLEMENTS)

# counters of one engine that are states, not counts: never carried over
_GAUGES = ("leaders",)
_ENGINE_SUMS = ("ticks", "tick_failures", "fence_lane_resolves",
                "commit_advances", "eager_commits")
_ENGINE_EVENTS = ("kv_wal_syncs", "kv_wal_sync_entries", "log_rounds",
                  "log_round_groups", "elections_started",
                  "leader_stepdowns", "vote_rounds_lost",
                  "elections_yielded")
_KV = ("read_confirms", "read_rounds", "read_beat_rpcs", "read_beats",
       "read_classic_beats", "read_failed", "read_device_fences")


class Cluster(base.Cluster):
    def __init__(self, cfg: dict, workdir: str):
        super().__init__(cfg, workdir)
        self.net = None
        self.region_list: list = []
        self.dead: set = set()          # stores killed and not yet restarted
        self.retired: dict = {}         # what dead engines and stores counted

    # -- what the program has to have ---------------------------------------

    def require_program(self) -> None:
        """The deployment needs a store that can crash and a client that
        rides out an election timeout; a tree without them cannot run it."""
        from tpuraft.rheakv.client import RheaKVStore
        from tpuraft.rheakv.store_engine import StoreEngine

        missing = []
        if not callable(getattr(StoreEngine, "crash", None)):
            missing.append("StoreEngine.crash() (lose a store with no flush "
                           "and no farewell)")
        if "op_deadline_ms" not in inspect.signature(RheaKVStore).parameters:
            missing.append("RheaKVStore(op_deadline_ms=...) (retry until the "
                           "operation's deadline, not for a count of bounces)")
        if missing:
            raise NotImplementedConfig(
                f"config {self.cfg['name']}: the program lacks "
                + "; ".join(missing))

    async def start(self, elect_deadline_s: float = 300.0) -> None:
        self.require_program()
        await super().start(elect_deadline_s)

    def make_client(self, net, region_list: list):
        from tpuraft.rheakv.client import BatchingOptions, RheaKVStore
        from tpuraft.rpc.transport import InProcTransport

        self.net, self.region_list = net, region_list
        client = RheaKVStore(
            self.pd_client(region_list), InProcTransport(net, "kvclient:0"),
            batching=BatchingOptions(enabled=True), timeout_ms=20000,
            op_deadline_ms=self.options["client_deadline_ms"])
        client.cluster = self       # the loop's way to the faults
        return client

    # -- the faults ----------------------------------------------------------

    def most_leaders(self) -> int:
        per = self.leaders_per_store()
        return per.index(max(per))

    async def kill(self, i: int) -> set:
        """Crash store ``i``; the regions (0-based) it led."""
        store, ep = self.stores[i], self.endpoints()[i]
        led = {rid - 1 for rid, re in store._regions.items()
               if re.is_leader()}
        self.net.stop_endpoint(ep)
        self.net.unbind(ep)
        self.dead.add(i)
        store.crash()
        return led

    async def restart(self, i: int) -> None:
        """A new store ``i`` on a new engine, from the dead one's files."""
        from tpuraft.core.engine import MultiRaftEngine
        from tpuraft.rheakv.store_engine import StoreEngine
        from tpuraft.rpc.transport import InProcTransport, RpcServer

        self._retire(i)
        ep = self.endpoints()[i]
        server = RpcServer(ep)
        self.net.bind(server)
        self.net.start_endpoint(ep)
        engine = MultiRaftEngine(self.tick_options(i))
        store = StoreEngine(
            self.store_options(i, ep, self.region_list), server,
            InProcTransport(self.net, ep), multi_raft_engine=engine)
        self.engines[i], self.stores[i] = engine, store
        t0 = time.perf_counter()
        await store.start()
        self.timings["restart_boot_s"] = time.perf_counter() - t0
        self.dead.discard(i)

    def lagging(self, i: int) -> set:
        """The regions whose replica on store ``i`` is behind its leader's
        commit index right now (a region with no leader counts)."""
        live = [s for j, s in enumerate(self.stores) if j not in self.dead]
        mine = self.stores[i]._regions
        behind = {r.id for r in self.region_list} - set(mine)
        for rid, re in mine.items():
            lead = next((s._regions[rid].node for s in live
                         if rid in s._regions
                         and s._regions[rid].is_leader()), None)
            if lead is None or re.node.ballot_box.last_committed_index \
                    < lead.ballot_box.last_committed_index:
                behind.add(rid)
        return behind

    # -- the harness's reads, over the stores as they are now ---------------

    def leaders_per_store(self) -> list:
        return [0 if i in self.dead else
                sum(1 for re in s._regions.values() if re.is_leader())
                for i, s in enumerate(self.stores)]

    def leaders(self) -> int:
        return sum(self.leaders_per_store())

    def replica_values(self, store: int, records) -> list:
        if store in self.dead:      # never restarted: it holds nothing
            return [None for _ in records]
        return super().replica_values(store, records)

    def _retire(self, i: int) -> None:
        """Carry what engine ``i`` and store ``i`` have counted."""
        now = base.Cluster.counters(self)
        own = f"engine{i}."
        for key, value in now.items():
            if key.startswith(own) and key[len(own):] not in _GAUGES:
                self.retired[key] = self.retired.get(key, 0) + value
        kv = self.stores[i].read_batcher.counters()
        for name in _KV:
            key = f"kv.{name}"
            self.retired[key] = self.retired.get(key, 0) + kv[name]

    def counters(self) -> dict:
        out = super().counters()
        for key, value in self.retired.items():
            out[key] = out.get(key, 0) + value
        n = range(len(self.engines))
        for name in _ENGINE_SUMS:
            out[f"engine.{name}"] = sum(out[f"engine{i}.{name}"] for i in n)
        for name in _ENGINE_EVENTS:
            out[f"engine.{name}.count"] = sum(
                out.get(f"engine{i}.{name}.count", 0) for i in n)
        for part in ("count", "total"):     # how late the engines' ticks ran
            out[f"engine.tick_late_ms.{part}"] = sum(
                out.get(f"engine{i}.tick_late_ms.{part}", 0) for i in n)
        out["cluster.regions"] = self.regions
        return out

    def section_seconds(self) -> list:
        """(perf_counter at the second's start, section, self seconds) for
        every ``loop.*`` roll-up the program's tracer holds."""
        from tpuraft.util.trace import TRACER

        return [(s["ts_s"] + TRACER._pc0, s["name"], s["dur_s"])
                for s in TRACER.spans() if s["name"].startswith("loop.")]
