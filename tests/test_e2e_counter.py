"""True multi-process end-to-end: 3 CounterServer OS processes over real
TCP, a client in this process, and a kill -9 of the LEADER process.

The strongest tier above the in-process TestCluster pattern: separate
interpreters, real sockets, real crash (SIGKILL, no graceful shutdown),
durable on-disk state. Reference analog: running CounterServer mains on
three machines (example:counter — SURVEY.md §3.3).
"""

import asyncio
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_ports(n: int) -> list[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


@pytest.mark.asyncio
async def test_three_process_cluster_kill9_leader(tmp_path):
    ports = _free_ports(3)
    peers = ",".join(f"127.0.0.1:{p}" for p in ports)
    procs: dict[int, subprocess.Popen] = {}
    env = dict(os.environ, PYTHONPATH=REPO)
    try:
        for p in ports:
            procs[p] = subprocess.Popen(
                [sys.executable, "-m", "examples.counter",
                 "--serve", f"127.0.0.1:{p}", "--peers", peers,
                 "--data", str(tmp_path / str(p))],
                cwd=REPO, env=env,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

        from examples.counter import CounterClient
        from tpuraft.conf import Configuration

        conf = Configuration.parse(peers)
        client = CounterClient(conf)
        try:
            # interpreter start takes a while (the package imports jax);
            # the client retry loop rides out boot + first election.
            # The client's retry on a timed-out (but applied) increment
            # is NOT idempotent, so assert monotonicity + linearizable
            # read agreement rather than exact values.
            deadline = time.monotonic() + 60
            value = None
            while time.monotonic() < deadline:
                try:
                    value = await client.increment_and_get()
                    break
                except Exception:
                    await asyncio.sleep(0.5)
            assert value is not None and value >= 1, value
            for _ in range(4):
                nxt = await client.increment_and_get()
                assert nxt > value, (nxt, value)
                value = nxt
            assert await client.get() == value

            # find the leader process and SIGKILL it — no graceful path
            leader = await client._find_leader()
            procs[leader.port].send_signal(signal.SIGKILL)
            procs[leader.port].wait()
            client._leader = None

            # survivors re-elect; acked state survives the hard crash
            deadline = time.monotonic() + 30
            v = None
            while time.monotonic() < deadline:
                try:
                    v = await client.increment_and_get(10)
                    break
                except Exception:
                    await asyncio.sleep(0.5)
            assert v is not None and v >= value + 10, (v, value)
            assert await client.get() == v
        finally:
            await client.transport.close()
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                proc.send_signal(signal.SIGKILL)
        for proc in procs.values():
            proc.wait()
