"""Arrival loops: how a mix's operations reach the client.

A mix names its loop (``loop.kind``), and the harness finds the module of that
name here (``benchmark/plugins.py``), so a new way of arriving is a new file.
A loop module has

- ``IMPLEMENTS``: which values of the mix's guarded fields it runs
  (``plugins.TRAFFIC_GUARDED``); any other is refused by name;
- ``async run_window(client, keys, stream, values, mix, seconds,
  on_window_start=None, on_window_end=None) -> Window``: warm up for
  ``mix["warm_seconds"]``, call ``on_window_start``, send for ``seconds``, call
  ``on_window_end``, then finish what is in flight (a late answer is late, not
  lost) for at most ``SETTLE_DEADLINE_S``.

What every loop shares is here: the rows it hands back and the lag monitor.
"""

from __future__ import annotations

import asyncio
import time

SETTLE_DEADLINE_S = 60.0    # how long a late answer or replica is waited for


class Window:
    """What a loop recorded: one row per operation sent."""

    def __init__(self) -> None:
        self.ops: list = []      # (kind, record, invoke, complete, ok, id)
        self.start = self.end = 0.0
        self.loop_lag_ms: list = []
        # an open loop: when each row of ``ops`` was due (same order); its
        # latency counts from there.  Empty: latency counts from ``invoke``
        self.due: list = []
        # what the loop itself counted over the window, for counter_ratio
        # readers, and what it has to say on the summary line
        self.counters: dict = {}
        self.notes: dict = {}


async def _lag_monitor(win: Window, stop: asyncio.Event,
                       period_s: float = 0.01) -> None:
    """How late the event loop that clients and stores share wakes a sleeper:
    the generator's lateness."""
    while not stop.is_set():
        t = time.perf_counter()
        await asyncio.sleep(period_s)
        win.loop_lag_ms.append((time.perf_counter() - t - period_s) * 1e3)
