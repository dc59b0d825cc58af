"""The open loop: independent users, whose requests do not wait for each
other's replies.  ``loop: {"kind": "open", "rate": <operations a second>}``.

One seeded schedule covers warm-up and window: exponential gaps at ``rate``
(Poisson arrivals), operation ``i`` of the ``OpStream`` due at ``due[i]``
seconds after the loop began, so one seed gives the same operations at the
same due times.  Each is sent at its due time or as soon after as the
generator runs, whatever is in flight: nothing caps what is in flight and
nothing is shed.  Latency counts from the DUE time (``Window.due``), so the
wait a stall imposes on the requests that came due during it is counted; a
row's ``invoke`` is when the call was really made, which is what the reference
needs (a write that had not been sent cannot have been seen).
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from benchmark.loops import SETTLE_DEADLINE_S, Window, _lag_monitor
from benchmark.stats import percentile
from benchmark.traffic import READ, OpStream, Values

IMPLEMENTS = {"faults": [[]]}
SCHEDULE_TAG = 0x09E2100B
WRITER = 0      # a value names its write (WRITER, the operation's index)


def schedule(seed: int, rate: float, n: int) -> np.ndarray:
    """When operations 0..n-1 are due, in seconds from the loop's start."""
    rng = np.random.default_rng([seed, SCHEDULE_TAG])
    return np.cumsum(rng.exponential(1.0 / rate, n))


async def run_window(client, keys: list, stream: OpStream, values: Values,
                     mix: dict, seconds: float,
                     on_window_start=None, on_window_end=None) -> Window:
    """Operations sent on the schedule for ``warm_seconds`` and then
    ``seconds``; every operation due before the close is sent, and at the
    close every one in flight is finished: a late answer is late, not lost.
    """
    rate, warm_s = mix["loop"]["rate"], mix["warm_seconds"]
    kinds, records, n = stream.kinds, stream.records, stream.n
    due = schedule(stream.seed, rate, n)
    if due[-1] <= 2 * (warm_s + seconds):      # room for a late close
        raise ValueError(f"{n} operations do not last twice "
                         f"{warm_s + seconds} s at {rate} a second")
    win = Window()
    pc = time.perf_counter
    inflight: set = set()
    close_at: list = []         # the window's end, once it has passed

    async def one(i: int, t_due: float) -> None:
        kind, rec = int(kinds[i]), int(records[i])
        key = keys[rec]
        got = None if kind == READ else (WRITER, i, rec)
        ok = False
        t0 = pc()
        try:
            if kind == READ:
                got = values.parse(await client.get(key))
                ok = True
            else:
                ok = await client.put(key, values.make(WRITER, i, rec)) \
                    is True
        except Exception:  # noqa: BLE001 — a failed operation is counted
            pass
        finally:
            # one cancelled too: never answered within the deadline past
            # the close, it failed
            win.ops.append((kind, rec, t0, pc(), ok, got))
            win.due.append(t_due)

    async def generator(t_begin: float) -> None:
        i = 0
        while True:
            closing = bool(close_at)
            upto = (close_at[0] if closing else pc()) - t_begin
            while due[i] < upto:
                task = asyncio.ensure_future(one(i, t_begin + due[i]))
                inflight.add(task)
                task.add_done_callback(inflight.discard)
                i += 1
            if closing:
                return
            await asyncio.sleep(max(0.0, t_begin + due[i] - pc()))

    stop_lag = asyncio.Event()
    gen_task = asyncio.ensure_future(generator(pc()))
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
        close_at.append(win.end)
        stop_lag.set()
        await gen_task          # its last pass: what was due before the close
        if inflight:
            _, pending = await asyncio.wait(set(inflight),
                                            timeout=SETTLE_DEADLINE_S)
            for t in pending:
                t.cancel()
            await asyncio.gather(*pending, return_exceptions=True)
    finally:
        stop_lag.set()
        for t in [gen_task, lag_task, *inflight]:
            if not t.done():
                t.cancel()
        await asyncio.gather(gen_task, lag_task, *inflight,
                             return_exceptions=True)
    describe(win, seconds)
    return win


def describe(win: Window, seconds: float) -> None:
    """From the rows: how late the generator sent, what was in flight, and
    the backlog (due and not yet answered) at each third of the window and at
    its close.  ``counters`` carry the mean lateness for ``arrival_late_ms``.
    """
    due = np.array(win.due)
    sent = np.array([o[2] for o in win.ops])
    done = np.array([o[3] for o in win.ops])
    mine = (due >= win.start) & (due < win.end)
    n, length = int(mine.sum()), win.end - win.start
    if not n:
        return
    late_ms = ((sent - due)[mine] * 1e3).tolist()
    win.counters = {"loop.arrivals": n, "loop.arrival_late_ms": sum(late_ms)}
    dues, answers = np.sort(due), np.sort(done)

    def backlog(t: float) -> int:
        return int(np.searchsorted(dues, t, side="right")
                   - np.searchsorted(answers, t, side="right"))

    # in flight: +1 at each call, -1 at each answer, of the window's own
    sent, done = sent[mine], done[mine]
    steps = np.concatenate([np.ones(n), -np.ones(n)])
    level = np.cumsum(steps[np.argsort(np.concatenate([sent, done]),
                                       kind="stable")])
    held = np.minimum(done, win.end) - np.minimum(sent, win.end)
    answered = int((done < win.end).sum())
    win.notes = {
        "rate": n / length, "due": n, "answered_in_window": answered,
        "answered_share": answered / n,
        "late_ms": {"p50": percentile(late_ms, 50),
                    "p95": percentile(late_ms, 95), "max": max(late_ms),
                    "mean": sum(late_ms) / n},
        "in_flight": {"mean": float(held.sum() / length),
                      "max": int(level.max())},
        "backlog": {**{f"{seconds * k / 3:g}s":
                       backlog(win.start + seconds * k / 3) for k in (1, 2)},
                    "close": backlog(win.end)},
    }
