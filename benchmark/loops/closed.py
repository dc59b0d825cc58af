"""The closed loop: YCSB's own.  ``loop: {"kind": "closed", "clients": n}``."""

from __future__ import annotations

import asyncio
import time

from benchmark.loops import SETTLE_DEADLINE_S, Window, _lag_monitor
from benchmark.traffic import READ, OpStream, Values

IMPLEMENTS = {"faults": [[]]}


async def run_window(client, keys: list, stream: OpStream, values: Values,
                     mix: dict, seconds: float,
                     on_window_start=None, on_window_end=None) -> Window:
    """``clients`` callers, each sending its next operation when the last
    returned (YCSB's own loop), for ``warm_s`` and then ``seconds``.  Ops are
    taken from one cursor over the seeded stream.  At the close every caller
    finishes the operation it has in flight: a late answer is late, not lost.
    """
    clients, warm_s = mix["loop"]["clients"], mix["warm_seconds"]
    win = Window()
    cursor = [0]
    stopping = [False]
    kinds, records, n = stream.kinds, stream.records, stream.n
    pc = time.perf_counter

    async def caller(cid: int) -> None:
        seq = 0
        while not stopping[0]:
            i = cursor[0]
            cursor[0] = i + 1
            kind, rec = int(kinds[i % n]), int(records[i % n])
            key = keys[rec]
            t0 = pc()
            try:
                if kind == READ:
                    got = values.parse(await client.get(key))
                    ok = True
                else:
                    seq += 1
                    got = (cid, seq, rec)
                    ok = await client.put(key, values.make(cid, seq, rec)) \
                        is True
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 — a failed operation is counted
                ok, got = False, (cid, seq, rec) if kind != READ else None
            win.ops.append((kind, rec, t0, pc(), ok, got))

    stop_lag = asyncio.Event()
    tasks = [asyncio.ensure_future(caller(c)) for c in range(clients)]
    lag_task = asyncio.ensure_future(_lag_monitor(win, stop_lag))
    try:
        await asyncio.sleep(warm_s)
        if on_window_start is not None:
            on_window_start()
        win.loop_lag_ms.clear()
        win.start = pc()
        await asyncio.sleep(seconds)
        win.end = pc()
        if on_window_end is not None:
            on_window_end()
        stopping[0] = True
        stop_lag.set()
        done, pending = await asyncio.wait(tasks, timeout=SETTLE_DEADLINE_S)
        for t in pending:
            t.cancel()
        for t in done:
            t.result()
    finally:
        stopping[0] = True
        stop_lag.set()
        for t in tasks + [lag_task]:
            if not t.done():
                t.cancel()
        await asyncio.gather(*tasks, lag_task, return_exceptions=True)
    return win
