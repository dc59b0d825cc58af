"""``kv3x1024-balanced``: the cluster of ``benchmark/cluster.py`` once its
region leaders are spread over the stores.

``benchmark/cluster.py`` boots the stores one after another, so the first
wins every election and leads every region.  A cluster in service does not
stay so: its placement driver, or its operator with ``CliService#rebalance``,
evens the leaders.  ``start`` boots and elects as the parent class does and
then spreads the leaders through the library's public path: a
``CliService.rebalance`` over the stores' own CLI processors, which moves a
leadership by ``transfer_leadership_to`` (``TimeoutNow`` once the transferee
has caught up).  A transfer can be lost (its transferee refuses, its old
leader's watchdog resumes), so the layout is waited for and ``rebalance`` is
called again for what is left; a layout not reached inside
``rebalance_deadline_s`` raises, and no window opens on a skewed cluster.
The client has sent nothing by then, so the load already runs over three
leading stores.  Nothing here touches a timeout, an fsync, the read mode, the
batching options or an engine's options.
"""

from __future__ import annotations

import asyncio
import time

from benchmark import cluster as base

IMPLEMENTS = dict(base.IMPLEMENTS)

# summed over the engines where an engine has them (a program without the
# histogram reports nothing under its name)
_ENGINE_EVENTS = ("elections_started", "leader_stepdowns", "leader_transfers",
                  "log_rounds_mixed", "kv_wal_syncs_mixed")


class _CountingTransport:
    """The CLI's transport, counting its calls by method."""

    def __init__(self, transport):
        self._transport = transport
        self.calls: dict = {}

    async def call(self, endpoint, method, request, timeout_ms=None):
        self.calls[method] = self.calls.get(method, 0) + 1
        return await self._transport.call(endpoint, method, request,
                                          timeout_ms)


class Cluster(base.Cluster):
    # the elections of 683 transferees and, for a lost transfer, its old
    # leader's watchdog (one election timeout) before the next call
    rebalance_deadline_s = 120.0

    def __init__(self, cfg: dict, workdir: str):
        super().__init__(cfg, workdir)
        self.net = None
        self.ceiling = self.options["max_leaders_per_store"]
        if self.ceiling * cfg["stores"] < cfg["regions"]:
            raise base.NotImplementedConfig(
                f"config {cfg['name']}: {cfg['stores']} stores of at most "
                f"{self.ceiling} leaders cannot lead {cfg['regions']} regions")

    def make_client(self, net, region_list: list):
        self.net = net
        return super().make_client(net, region_list)

    def cli_transport(self):
        """What the CLI calls the stores through."""
        from tpuraft.rpc.transport import InProcTransport

        return InProcTransport(self.net, "cli:0")

    def transfers_gained(self):
        """Leaderships the engines' nodes have gained through TimeoutNow;
        None on a program that does not count them."""
        hists = [e.tick_hists.get("leader_transfers") for e in self.engines]
        return None if None in hists else sum(h.count for h in hists)

    def balanced(self) -> bool:
        per = self.leaders_per_store()
        return sum(per) == self.regions and max(per) <= self.ceiling

    async def start(self, elect_deadline_s: float = 300.0) -> None:
        await super().start(elect_deadline_s)
        await self.spread_leaders()

    async def spread_leaders(self) -> None:
        from tpuraft.conf import Configuration
        from tpuraft.core.cli_service import CliService
        from tpuraft.entity import PeerId
        from tpuraft.rheakv.metadata import region_group_id

        t0 = time.perf_counter()
        gained0 = self.transfers_gained()
        transport = _CountingTransport(self.cli_transport())
        cli = CliService(transport)
        conf = Configuration([PeerId.parse(ep) for ep in self.endpoints()])
        name = self.stores[0].cluster_name
        groups = [region_group_id(name, k + 1) for k in range(self.regions)]
        # a lost transfer is known to be lost when its old leader's watchdog
        # has resumed, one election timeout after it was asked for
        per_call_s = self.cfg["election_timeout_ms"] / 1e3 + 2.0
        give_up = t0 + self.rebalance_deadline_s
        calls, status = 0, None
        while not self.balanced() and time.perf_counter() < give_up:
            calls += 1
            status = await cli.rebalance(groups, conf)
            wait_until = min(give_up, time.perf_counter() + per_call_s)
            while not self.balanced() and time.perf_counter() < wait_until:
                await asyncio.sleep(0.05)
        self.timings["rebalance_s"] = time.perf_counter() - t0
        self.timings["rebalance_calls"] = calls
        self.timings["transfers_asked"] = transport.calls.get(
            "cli_transfer_leader", 0)
        if gained0 is not None:
            self.timings["transfers_gained"] = \
                self.transfers_gained() - gained0
        if not self.balanced():
            raise RuntimeError(
                f"leaders {self.leaders_per_store()} of {self.regions} "
                f"regions, at most {self.ceiling} a store wanted, after "
                f"{calls} rebalance calls and "
                f"{self.timings['transfers_asked']} transfers asked for "
                f"within {self.rebalance_deadline_s}s (last status: "
                f"{status})")
        # whatever the client learned before the leaders moved
        self.client._leaders.clear()

    def counters(self) -> dict:
        out = super().counters()
        n = range(len(self.engines))
        for name in _ENGINE_EVENTS:
            have = [out[f"engine{i}.{name}.count"] for i in n
                    if f"engine{i}.{name}.count" in out]
            if have:
                out[f"engine.{name}.count"] = sum(have)
        for part in ("count", "total"):     # how late the engines' ticks ran
            out[f"engine.tick_late_ms.{part}"] = sum(
                out.get(f"engine{i}.tick_late_ms.{part}", 0) for i in n)
        per = self.leaders_per_store()
        out["cluster.regions"] = self.regions
        out["cluster.leaders_max"] = max(per)   # gauges: the stores right now
        out["cluster.leaders_min"] = min(per)
        return out
