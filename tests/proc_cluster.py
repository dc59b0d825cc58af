"""ProcCluster: the multi-process test harness over examples.proc_supervisor.

The promoted form of the in-file ``NativeKVCluster`` from
test_kv_over_native_tcp.py: each store is a REAL OS process running the
``examples.rheakv_server`` main (own interpreter, own GIL, own loop),
reached over TCP, with readiness probes, SIGTERM drain, and SIGKILL +
supervised restart — so lifecycle tests exercise the exact serving
topology the committed cross-process bench rows use.

Usage::

    async with ProcCluster(tmp_path, stores=3, regions=2) as c:
        kv = await c.client()
        ...
        await c.sigkill(0); await c.restart(0)
"""

from __future__ import annotations

import contextlib

from examples.proc_supervisor import (
    ProcSupervisor,
    StoreProcess,
    free_endpoints,
    server_argv,
)
from examples.rheakv_server import client_for
from tpuraft.rheakv.client import RheaKVStore


class ProcCluster:
    def __init__(self, tmp_path, stores: int = 3, regions: int = 2,
                 transport: str = "tcp", store_kind: str = "memory",
                 eto_ms: int = 500,
                 drain_timeout_s: float = 10.0,
                 boot_delay_s: dict[int, float] | None = None,
                 metrics: bool = False):
        self._tmp = tmp_path
        self.n_regions = regions
        self.transport_kind = transport
        self.endpoints = free_endpoints(stores)
        delays = boot_delay_s or {}
        self.sup = ProcSupervisor([
            StoreProcess(ep, server_argv(
                ep, self.endpoints, regions, str(tmp_path),
                transport=transport, store=store_kind, eto_ms=eto_ms,
                drain_timeout_s=drain_timeout_s,
                boot_delay_s=delays.get(i, 0.0),
                metrics_port=0 if metrics else None))
            for i, ep in enumerate(self.endpoints)])
        self._clients: list[RheaKVStore] = []
        self._transports: list = []

    @property
    def procs(self) -> list[StoreProcess]:
        return self.sup.procs

    async def __aenter__(self) -> "ProcCluster":
        await self.sup.start()
        return self

    async def __aexit__(self, *exc) -> None:
        for kv in self._clients:
            with contextlib.suppress(Exception):
                await kv.shutdown()
        for t in self._transports:
            with contextlib.suppress(Exception):
                await t.close()
        await self.sup.stop()

    def _make_transport(self):
        if self.transport_kind == "native":
            from tpuraft.rpc.native_tcp import NativeTcpTransport
            t = NativeTcpTransport()
        else:
            from tpuraft.rpc.tcp import TcpTransport
            t = TcpTransport()
        self._transports.append(t)
        return t

    async def client(self, **kw) -> RheaKVStore:
        kv = client_for(self.endpoints, self.n_regions,
                        transport=self._make_transport(), **kw)
        await kv.start()
        self._clients.append(kv)
        return kv

    # -- lifecycle controls ---------------------------------------------

    async def sigterm(self, i: int, timeout_s: float = 20.0) -> int:
        """Drain-stop store ``i``; returns its exit code."""
        p = self.procs[i]
        p.terminate()
        return await p.wait_exit(timeout_s)

    async def sigkill(self, i: int, timeout_s: float = 10.0) -> int:
        """Crash-stop store ``i`` (no drain); returns its exit code."""
        p = self.procs[i]
        p.kill()
        return await p.wait_exit(timeout_s)

    async def restart(self, i: int, ready_timeout_s: float = 30.0) -> dict:
        """Respawn a stopped store and await its READY probe."""
        p = self.procs[i]
        p.spawn()
        return await p.wait_ready(ready_timeout_s)
