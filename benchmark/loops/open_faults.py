"""The open loop with a fault schedule: ``open.py``'s seeded Poisson arrivals
while a store is lost and brought back inside the window.
``loop: {"kind": "open_faults", "rate": <operations a second>}`` and
``faults: [{"kind": "kill_store", "store": "most_leaders", "at": 0.2},
{"kind": "restart_store", "at": 0.65}]``.

Arrivals are ``open.run_window``'s own, imported and not copied: operation
``i`` due at ``t_i``, sent at its due time or as soon after as the generator
runs, nothing capped, nothing shed, latency from the DUE time.  So the
requests that come due while a region has no leader are sent, wait in the
client, and count.  Each fault fires at its share ``at`` of the window,
counted from the window's start; the cluster is reached through the client
the loop is handed (``client.cluster``: ``kill``, ``restart``, ``leaders``,
``lagging``).  What only this loop can know goes into ``Window.counters``:

- ``loop.kills``, ``loop.restarts``: faults that fired inside the window;
- ``loop.unavailable_ms``: from the kill to the first acknowledgement of an
  operation that was SENT after the kill to a region the dead store led: a
  leader elected after the kill answered it;
- ``loop.all_led_ms``: from the kill to the first poll (ten a second, during
  the outage only) that finds every region led again;
- ``loop.catch_up_ms``: from the restart call, the boot included, to the poll
  at which each replica of the restarted store has been seen at its leader's
  commit index at least once;
- ``cluster.regions``, for ratios a region.

``Window.notes`` holds the phases: when each began, what the program counted
in it, how long what came due in it waited and how late the generator sent
it; and the backlog's peak and ``backlog_drained_s``, when it was back under
2 % of that peak (the rate fits the schedule if that lies before the restart).
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from benchmark.loops import SETTLE_DEADLINE_S, Window
from benchmark.loops import open as open_loop
from benchmark.stats import percentile
from benchmark.traffic import OpStream, Values

IMPLEMENTS = {"faults": [[
    {"kind": "kill_store", "store": "most_leaders", "at": 0.2},
    {"kind": "restart_store", "at": 0.65}]]}
POLL_S = 0.1
FAILED_S = 60.0     # what the harness counts a failed operation as
# what a phase's line shows of the cluster's counters
_PHASE_COUNTS = ("engine.elections_started.count",
                 "engine.leader_stepdowns.count",
                 "engine.vote_rounds_lost.count",
                 "engine.elections_yielded.count", "client.batch_retries",
                 "engine.ticks")


class _Faults:
    """The schedule's one task, and what it saw."""

    def __init__(self, cluster, faults: list, seconds: float):
        self.cluster, self.seconds = cluster, seconds
        self.faults = sorted(faults, key=lambda f: f["at"])
        self.t0 = 0.0
        self.closed_at = None
        self.tasks: list = []
        self.victim = None
        self.led: set = set()
        self.marks: list = []       # (phase, perf_counter, counters)
        self.t_kill = self.t_kill_done = self.t_all_led = None
        self.t_restart = self.t_booted = self.t_caught_up = None
        self.kills = self.restarts = 0
        self.lag_seen: list = []    # (seconds after the restart, replicas behind)

    def mark(self, phase: str) -> float:
        now = time.perf_counter()
        self.marks.append((phase, now, self.cluster.counters()))
        return now

    def begin(self) -> None:
        self.t0 = self.mark("healthy")
        self.tasks.append(asyncio.ensure_future(self._run()))

    def close(self) -> None:
        self.closed_at = self.mark("close")

    async def _run(self) -> None:
        for f in self.faults:
            await asyncio.sleep(
                max(0.0, self.t0 + f["at"] * self.seconds
                    - time.perf_counter()))
            if f["kind"] == "kill_store":
                await self._kill(f)
            else:
                await self._restart()

    async def _kill(self, f: dict) -> None:
        c = self.cluster
        assert f["store"] == "most_leaders"
        self.victim = c.most_leaders()
        inside = self.closed_at is None
        self.t_kill = self.mark("outage")
        self.led = await c.kill(self.victim)
        self.t_kill_done = time.perf_counter()
        self.kills += inside
        # beside the schedule: a supervisor restarts on its own clock
        self.tasks.append(asyncio.ensure_future(self._watch_leaders()))

    async def _watch_leaders(self) -> None:
        c = self.cluster
        while c.leaders() < c.regions:      # the outage only
            await asyncio.sleep(POLL_S)
        self.t_all_led = self.mark("degraded")

    async def _restart(self) -> None:
        c = self.cluster
        inside = self.closed_at is None
        self.t_restart = self.mark("restart")
        await c.restart(self.victim)
        self.t_booted = self.mark("catch_up")
        self.restarts += inside
        # a replica counts once it has been seen at its leader's commit
        # index: under load some region always has an entry in flight
        behind = None
        while True:
            now_behind = c.lagging(self.victim)
            behind = now_behind if behind is None else behind & now_behind
            self.lag_seen.append((time.perf_counter() - self.t_restart,
                                  len(behind)))
            if not behind:
                break
            await asyncio.sleep(POLL_S)
        self.t_caught_up = self.mark("recovered")

    async def finish(self) -> None:
        """After the close: a catch-up still under way is waited for as a
        late answer is, then the task goes."""
        if self.tasks:
            await asyncio.wait(self.tasks, timeout=SETTLE_DEADLINE_S)

    async def stop(self) -> None:
        """Whatever is left goes; a fault that raised is raised here."""
        for t in self.tasks:
            if not t.done():
                t.cancel()
        for r in await asyncio.gather(*self.tasks, return_exceptions=True):
            if isinstance(r, Exception):
                raise r

    # -- from the rows -------------------------------------------------------

    def describe(self, win: Window) -> None:
        c = self.cluster
        counters = {"loop.kills": self.kills, "loop.restarts": self.restarts,
                    "cluster.regions": c.regions}
        notes: dict = {"victim": self.victim, "regions_led": len(self.led)}
        due = np.array(win.due)
        sent = np.array([o[2] for o in win.ops])
        done = np.array([o[3] for o in win.ops])
        ok = np.array([bool(o[4]) for o in win.ops])
        region = np.array([o[1] for o in win.ops]) % c.regions
        if self.t_kill is not None:
            # answered by a leader elected after the kill: sent after it, to
            # a region the dead store led
            mine = ok & (sent >= self.t_kill_done) \
                & np.isin(region, list(self.led))
            if mine.any():
                counters["loop.unavailable_ms"] = \
                    (done[mine].min() - self.t_kill) * 1e3
            if self.t_all_led is not None:
                counters["loop.all_led_ms"] = \
                    (self.t_all_led - self.t_kill) * 1e3
        if self.t_caught_up is not None:
            counters["loop.catch_up_ms"] = \
                (self.t_caught_up - self.t_restart) * 1e3
        win.counters.update(counters)

        dues, answers = np.sort(due), np.sort(done)

        def backlog(t: float) -> int:
            return int(np.searchsorted(dues, t, side="right")
                       - np.searchsorted(answers, t, side="right"))

        def latency_ms(t_from: float, t_to: float) -> dict:
            m = (due >= t_from) & (due < t_to)
            if not m.any():
                return {}
            lat = (np.where(ok[m], done[m] - due[m], FAILED_S)
                   * 1e3).tolist()
            # how late the generator sent what came due here: the latency
            # counts it (the cell cannot report ``arrival_late_ms``: that
            # file lists its cells)
            return {"due": len(lat), "p50_ms": percentile(lat, 50),
                    "p95_ms": percentile(lat, 95), "max_ms": max(lat),
                    "sent_late_ms": float((sent[m] - due[m]).mean() * 1e3)}

        sections = c.section_seconds()
        phases = []
        for (name, t, counts), after in zip(self.marks,
                                            self.marks[1:] + [None]):
            row = {"phase": name, "at_s": t - self.t0, "backlog": backlog(t)}
            if after is not None:
                _, t_end, nxt = after
                row["seconds"] = t_end - t
                for key in _PHASE_COUNTS:
                    row[key] = nxt[key] - counts[key]
                late_n = nxt["engine.tick_late_ms.count"] \
                    - counts["engine.tick_late_ms.count"]
                if late_n > 0:
                    row["tick_late_ms"] = (
                        nxt["engine.tick_late_ms.total"]
                        - counts["engine.tick_late_ms.total"]) / late_n
                row["ops"] = latency_ms(t, t_end)
                row["loop_pct"] = _shares(sections, t, t_end)
            phases.append(row)
        notes["phases"] = phases
        for key, t in (("kill_s", self.t_kill), ("all_led_s", self.t_all_led),
                       ("restart_s", self.t_restart),
                       ("booted_s", self.t_booted),
                       ("caught_up_s", self.t_caught_up)):
            notes[key] = None if t is None else t - self.t0
        if self.t_kill is not None:
            notes["kill_call_ms"] = (self.t_kill_done - self.t_kill) * 1e3
            notes["backlog_at_kill"] = backlog(self.t_kill)
        if self.t_restart is not None:
            notes["backlog_at_restart"] = backlog(self.t_restart)
            notes["replicas_behind"] = self.lag_seen[::5][:40]
        grid = np.arange(win.start, win.end, 0.25)
        level = [backlog(t) for t in grid]
        if level:
            top = int(np.argmax(level))
            notes["backlog_peak"] = {"ops": int(level[top]),
                                     "at_s": float(grid[top] - win.start)}
            # the drain's end: the first quarter second after the peak at
            # which the backlog is back under 2 % of it (the rate fits the
            # schedule if this lies before the restart)
            low = [t for t, n in zip(grid[top:], level[top:])
                   if n <= 0.02 * level[top]]
            notes["backlog_drained_s"] = \
                float(low[0] - win.start) if low else None
        notes["leaders_per_store"] = c.leaders_per_store()
        win.notes["faults"] = notes


def _shares(sections: list, t0: float, t1: float) -> dict:
    """% of the loop thread by layer over the whole seconds of [t0, t1)."""
    total: dict = {}
    seconds = set()
    for t, name, self_s in sections:
        if t0 <= t and t + 1.0 <= t1 and name.count(".") == 1:
            total[name[5:]] = total.get(name[5:], 0.0) + self_s
            seconds.add(round(t, 3))
    n = len(seconds)
    return {k: round(100.0 * v / n, 2) for k, v in sorted(total.items())} \
        if n else {}


async def run_window(client, keys: list, stream: OpStream, values: Values,
                     mix: dict, seconds: float,
                     on_window_start=None, on_window_end=None) -> Window:
    """``open.run_window`` with the mix's faults fired beside it."""
    run = _Faults(client.cluster, mix["faults"], seconds)

    def started() -> None:
        if on_window_start is not None:
            on_window_start()
        run.begin()

    def ended() -> None:
        run.close()
        if on_window_end is not None:
            on_window_end()

    try:
        win = await open_loop.run_window(client, keys, stream, values, mix,
                                         seconds, started, ended)
        await run.finish()
    finally:
        await run.stop()
    run.describe(win)
    return win
