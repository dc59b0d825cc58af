"""The tick's two halves (PR 38): ``MultiRaftEngine.tick()`` enqueues
the program in one loop turn and collects it in the next.

Off the chip, on a numpy-backed fake of the jitted call: its packed
``[3, G]`` output is the engine's own numpy twin's, and it is "computed"
when the test says so (``ready``) or when the fetch asks for it, so a
test decides what happens between the halves.
"""

from __future__ import annotations

import asyncio
import time
from types import SimpleNamespace

import numpy as np
import pytest

from tpuraft.conf import Configuration
from tpuraft.core.engine import _NEG_I32, MultiRaftEngine
from tpuraft.entity import PeerId
from tpuraft.ops.tick import (PACKED_OUTPUT_MASKS, ROLE_FOLLOWER, ROLE_LEADER)
from tpuraft.options import TickOptions

from tests.test_ops_tick import randomize_mirrors


class ManualClock:
    """The store clock, moved by hand (milliseconds)."""

    def __init__(self, ms: int = 0):
        self.ms = ms

    def monotonic(self) -> float:
        return (self.ms + 0.25) / 1000.0    # clear of the ms boundary

    wall = monotonic


class FakeOut:
    """What the jitted call hands back: a device array that may not be
    computed yet."""

    def __init__(self, rows: np.ndarray, ready: bool, log: list):
        self._rows, self.ready, self._log = rows, ready, log
        self.nbytes = rows.nbytes
        self.host_copies = 0
        self.waited = False      # the fetch had to wait for the program

    def is_ready(self) -> bool:
        return self.ready

    def copy_to_host_async(self) -> None:
        self.host_copies += 1

    def __array__(self, dtype=None, copy=None):
        self._log.append("fetch")
        self.waited = not self.ready
        self.ready = True
        return self._rows


def overlap_engine(g: int = 8, p: int = 3, clock=None,
                   ready: bool = True) -> MultiRaftEngine:
    """An engine on the packed single-device path whose program is the
    numpy twin.  ``eng.outs`` are the calls' outputs, ``eng.log`` the
    order of calls and fetches, ``eng.born_ready`` whether an output is
    computed by the time anybody looks."""
    eng = MultiRaftEngine(TickOptions(max_groups=g, max_peers=p,
                                      backend="numpy", clock=clock))
    eng.outs, eng.log, eng.born_ready = [], [], ready
    if clock is not None:
        eng._t0 = 0.0           # now_ms() is the clock's ms

    def call(buf, params):
        eng.log.append("call")
        o = eng._np_tick(*eng._rel_views(), int(buf[-1, 0]))
        rows = np.zeros((3, eng.G), np.int32)
        rows[0], rows[1] = o.commit_rel, o.q_ack
        for i, name in enumerate(PACKED_OUTPUT_MASKS):
            rows[2] |= getattr(o, name).astype(np.int32) << i
        eng.outs.append(FakeOut(rows, eng.born_ready, eng.log))
        return eng.outs[-1]

    eng._tick_fn, eng._packed = call, True
    return eng


def split_tick(eng: MultiRaftEngine, between=None) -> int:
    """Both halves by hand, ``between`` in the loop turn they leave."""
    flight = eng._tick_begin()
    if between is not None:
        between()
    return eng._tick_end(flight, time.perf_counter(), yielded=True)


class Ctrl:
    """An EngineControl as far as ``_apply_protocol`` goes: it records
    what the tick scheduled."""

    def __init__(self, eng, slot: int, events: list):
        self.engine, self.slot, self.events = eng, slot, events
        self.node = SimpleNamespace(
            is_leader=lambda: False, _on_election_due=None,
            _on_engine_elected=None, _on_engine_quorum_dead=None,
            _check_dead_nodes=None, _on_snapshot_due=None)

    def push_election_deadline(self, now):
        self.engine.elect_deadline[self.slot] = now + 1000

    def note_election_due(self):
        pass

    def note_leader_contact(self):
        self.engine.elect_deadline[self.slot] = self.engine.now_ms() + 1000

    def priority_rounds_accrue(self) -> bool:
        return True

    def maybe_quiesce(self, now):
        pass

    def schedule(self, name, handler):
        self.events.append((self.slot, name))


class Fence:
    done = False

    def __init__(self, name: str, events: list):
        self.name, self.events = name, events

    def note_quorum(self):
        self.done = True
        self.events.append(("fence", self.name))


def controlled(eng, events: list) -> None:
    """A control, a ballot box and, where the row has one armed, a read
    fence on every slot, all recording into ``events``."""
    for s in range(eng.G):
        eng._ctrls[s] = Ctrl(eng, s, events)
        eng.has_ctrl[s] = True
        eng._boxes[s] = SimpleNamespace(
            _advance=lambda c, s=s: events.append((s, "commit", c)))
        if eng.fence_start[s] > _NEG_I32:
            eng._fence_waiters[s] = [
                (int(eng.fence_start[s]), Fence(f"f{s}", events))]


MIRRORS = ("match_abs", "base", "pending_rel", "commit_abs", "role",
           "elect_deadline", "hb_deadline", "last_ack", "tick_q_ack",
           "stepdown_deadline", "snap_deadline", "fence_start", "quiescent")


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_tick_once_is_begin_then_end_bit_for_bit(seed):
    """The engine oracle's randomized rows, every event lane firing into
    recording controls: the synchronous tick and the two halves leave
    the same mirrors and schedule the same events in the same order."""
    runs = []
    for halves in (False, True):
        eng = overlap_engine(256, 5, clock=ManualClock(1200))
        randomize_mirrors(eng, np.random.default_rng(seed))
        events: list = []
        controlled(eng, events)
        advanced = [split_tick(eng) if halves else eng.tick_once()
                    for _ in range(2)]
        runs.append((advanced, events,
                     {m: getattr(eng, m).copy() for m in MIRRORS}, eng))
    (adv_a, ev_a, rows_a, sync), (adv_b, ev_b, rows_b, split) = runs
    assert adv_a == adv_b and ev_a == ev_b and ev_a
    assert {name for _s, name, *_ in ev_a} >= {
        "commit", "election_due", "elected", "quorum_dead", "stepdown_tick",
        "snapshot_due"}
    assert any(e[0] == "fence" for e in ev_a)
    for m in MIRRORS:
        np.testing.assert_array_equal(rows_a[m], rows_b[m], err_msg=m)
    assert sync.ticks == split.ticks == 2
    assert sync.log == split.log == ["call", "fetch"] * 2
    assert all(o.host_copies == 1 for o in sync.outs + split.outs)
    # only the halves taken apart count as overlapped
    assert sync.tick_hists["tick_overlapped"].count == 0
    assert split.tick_hists["tick_overlapped"].count == 2
    assert split.tick_hists["tick_ready"].count == 2
    for eng in (sync, split):
        h = eng.tick_hists
        assert h["tick_device_ms"].total == pytest.approx(
            h["tick_state_ms"].total + h["tick_call_ms"].total
            + h["tick_fetch_ms"].total)


def led_slot(eng, events: list):
    """Slot 0 leads a three-voter group (itself in column 0)."""
    s = eng.alloc_slot()
    peers = [PeerId.parse(f"127.0.0.1:{7900 + i}") for i in range(3)]
    eng.set_conf(s, Configuration(peers), Configuration())
    eng._ctrls[s], eng.has_ctrl[s] = Ctrl(eng, s, events), True
    eng.self_col[s], eng.role[s] = 0, ROLE_LEADER
    eng.hb_deadline[s] = eng.stepdown_deadline[s] = 1 << 29
    return s


def test_a_fence_armed_between_the_halves_waits_for_the_next_tick():
    clock, events = ManualClock(100), []
    eng = overlap_engine(clock=clock)
    s = led_slot(eng, events)
    eng.last_ack[s, 1:] = 100
    clock.ms = 90
    eng.arm_read_fence(s, Fence("before", events))     # start 90 <= 100
    clock.ms = 200

    def between():
        clock.ms = 205
        eng.arm_read_fence(s, Fence("between", events))    # start 205
        eng.last_ack[s, 1:] = 206                   # and its acks, after

    split_tick(eng, between)
    # the output is begin's: q_ack 100 covers the fence that was there,
    # and is a lower bound for the one that was not
    assert events == [("fence", "before")]
    assert int(eng.tick_q_ack[s]) == 100
    assert int(eng.fence_start[s]) == 205           # kept, still armed
    clock.ms = 210
    split_tick(eng)
    assert events == [("fence", "before"), ("fence", "between")]
    assert int(eng.fence_start[s]) == _NEG_I32


@pytest.mark.parametrize("contact", [False, True])
def test_a_leader_contact_in_the_yielded_turn_keeps_its_timer(contact):
    clock, events = ManualClock(200), []
    eng = overlap_engine(clock=clock)
    s = led_slot(eng, events)
    eng.role[s], eng.elect_deadline[s] = ROLE_FOLLOWER, 150

    def between():
        clock.ms = 203
        if contact:
            eng._ctrls[s].note_leader_contact()

    split_tick(eng, between)
    assert events == ([] if contact else [(s, "election_due")])
    # pushed by the contact (from its own time), or by the fire (begin's)
    assert int(eng.elect_deadline[s]) == (1203 if contact else 1200)


@pytest.mark.parametrize("lane", ["hb_due", "stepdown_due"])
def test_a_beat_or_a_stepdown_in_the_yielded_turn_keeps_the_leaders_timer(
        lane):
    clock, events = ManualClock(500), []
    eng = overlap_engine(clock=clock)
    s = led_slot(eng, events)
    row = eng.hb_deadline if lane == "hb_due" else eng.stepdown_deadline
    row[s] = 400
    flushed = []
    eng._flush_heartbeats = lambda slots, now: flushed.append(list(slots))
    before = int(eng.stepdown_ticks)

    def between():
        if lane == "hb_due":
            row[s] = 900                    # the beat went out meanwhile
        else:
            eng.role[s] = ROLE_FOLLOWER     # it stepped down meanwhile

    split_tick(eng, between)
    assert flushed == [] and eng.stepdown_ticks == before and not events
    row[s], eng.role[s] = 400, ROLE_LEADER
    split_tick(eng)
    assert (flushed == [[s]]) if lane == "hb_due" \
        else (eng.stepdown_ticks == before + 1)


def test_a_slot_that_changed_hands_between_the_halves_drops_the_output():
    events: list = []
    eng = overlap_engine(clock=ManualClock(300))
    make = eng.ballot_box_factory()
    box = make(lambda c: events.append(("old", c)))
    peers = [PeerId.parse(f"127.0.0.1:{7950 + i}") for i in range(3)]
    box.update_conf(Configuration(peers), Configuration())
    box.reset_pending_index(1)
    s = box.slot
    eng.match_abs[s, :2] = 5                        # a quorum matched 5
    eng.last_ack[s, :] = 250
    new = []

    def between():
        box.close()
        new.append(make(lambda c: events.append(("new", c))))
        new[0].update_conf(Configuration(peers), Configuration())
        new[0].reset_pending_index(1)

    eng._dirty = False
    assert split_tick(eng, between) == 0
    assert new[0].slot == s                         # the same row
    assert events == [] and eng.commit_abs[s] == 0
    assert int(eng.tick_q_ack[s]) == _NEG_I32       # not the old group's
    assert eng.ticks == 1 and eng.ticks_dropped == 1
    assert eng._dirty                               # asked for again
    assert eng.lane_stats()["ticks_dropped"] == 1
    # the same rows under one generation: applied
    eng.match_abs[s, :2] = 7
    assert split_tick(eng) == 1 and events == [("new", 7)]
    assert eng.ticks_dropped == 1


@pytest.mark.parametrize("meanwhile", ["stepped_down", "leads_anew"])
def test_a_commit_of_a_leadership_that_ended_between_the_halves_is_void(
        meanwhile):
    events: list = []
    eng = overlap_engine(clock=ManualClock(300))
    box = eng.ballot_box_factory()(lambda c: events.append(c))
    peers = [PeerId.parse(f"127.0.0.1:{7960 + i}") for i in range(3)]
    box.update_conf(Configuration(peers), Configuration())
    box.reset_pending_index(1)
    s = box.slot
    eng.match_abs[s, :2] = 5                        # a quorum matched 5

    def between():
        box.clear_pending()
        if meanwhile == "leads_anew":
            box.reset_pending_index(50)             # another base

    assert split_tick(eng, between) == 0 and events == []
    assert eng.ticks_dropped == 0                   # one row's matter
    if meanwhile == "leads_anew":
        assert eng.commit_abs[s] == 49
        eng.match_abs[s, :2] = 52
        assert split_tick(eng) == 1 and events == [52]


@pytest.mark.parametrize("mutate", ["alloc_slot", "set_conf", "grow",
                                    "unregister_ctrl", "rebase"])
def test_every_layout_mutation_bumps_the_generation(mutate):
    eng = overlap_engine(g=2)
    s = eng.alloc_slot()
    gen = eng._layout_gen
    if mutate == "alloc_slot":
        eng.alloc_slot()
    elif mutate == "set_conf":
        eng.set_conf(s, Configuration([PeerId.parse("127.0.0.1:1")]),
                     Configuration())
    elif mutate == "grow":
        eng._grow()
    elif mutate == "unregister_ctrl":
        eng.unregister_ctrl(s)
    else:
        eng.match_abs[s, 0] = eng.commit_abs[s] = (1 << 28) + 5
        eng._rebase()
        assert eng.base[s] == (1 << 28) + 5
    assert eng._layout_gen > gen
    # and a plain tick does not
    gen = eng._layout_gen
    eng.tick_once()
    assert eng._layout_gen == gen


async def asks_after_an_ack(eng) -> int:
    eng.mark_dirty()            # something the flight's snapshot lacks
    return await eng.tick()


async def test_callers_during_a_flight_share_the_tick_after_it():
    eng = overlap_engine(ready=False)
    a = asyncio.ensure_future(eng.tick())
    b, c = (asyncio.ensure_future(asks_after_an_ack(eng)) for _ in range(2))
    await asyncio.wait_for(asyncio.gather(a, b, c), 5)
    # a's tick, and ONE for b and c, begun after a's was collected
    assert eng.ticks == 2
    assert eng.log == ["call", "fetch", "call", "fetch"]
    assert eng._flight is None and eng._next_tick is None
    h = eng.tick_hists
    assert h["tick_overlapped"].count == 2
    assert h["tick_ready"].count == 0 and all(o.waited for o in eng.outs)
    assert h["tick_inflight_ms"].count == 2
    # an output that is there when the turn is over: counted ready
    eng.born_ready = True
    await eng.tick()
    assert h["tick_overlapped"].count == 3 and h["tick_ready"].count == 1
    assert not eng.outs[-1].waited


async def test_callers_the_flight_has_seen_everything_for_share_the_flight():
    """Nothing was recorded since its snapshot (no dirty mark, no ack):
    the flight is the tick they ask for, one device call for all."""
    eng = overlap_engine()
    a, b, c = (asyncio.ensure_future(eng.tick()) for _ in range(3))
    assert await asyncio.wait_for(asyncio.gather(a, b, c), 5) == [0, 0, 0]
    assert eng.ticks == 1 and eng.log == ["call", "fetch"]
    # and so it is for the tick the loop collects (begun for waiters)
    a = asyncio.ensure_future(eng.tick())
    b = asyncio.ensure_future(asks_after_an_ack(eng))
    await asyncio.sleep(0)
    await asyncio.sleep(0)      # a landed; b's tick flies
    assert eng.ticks == 2 and eng._flight is not None
    assert await asyncio.wait_for(eng.tick(), 5) == 0       # rides b's
    assert eng.ticks == 3 and b.done() and a.done()


@pytest.mark.parametrize("ack", ["record_ack", "store_lease_ack"])
async def test_an_ack_since_the_snapshot_asks_for_the_next_tick(ack):
    from tpuraft.core.engine import EngineControl

    events: list = []
    eng = overlap_engine()
    s = led_slot(eng, events)
    peer = next(p for p, col in eng._peer_cols[s].items() if col == 1)
    a = asyncio.ensure_future(eng.tick())
    await asyncio.sleep(0)
    if ack == "record_ack":
        ctrl = SimpleNamespace(engine=eng, slot=s)
        EngineControl.record_ack(ctrl, peer, eng._clock.monotonic() + 1.0)
    else:
        eng.note_quiesce_leader(s)
        eng.note_store_ack(peer.endpoint)
    await asyncio.wait_for(eng.tick(), 5)
    assert a.done() and eng.ticks == 2


async def test_a_cancelled_caller_leaves_the_shared_tick_to_the_others():
    eng = overlap_engine()
    a = asyncio.ensure_future(eng.tick())
    b, c = (asyncio.ensure_future(asks_after_an_ack(eng)) for _ in range(2))
    await asyncio.sleep(0)              # a flies, b and c wait for next
    assert eng._flight is not None and eng._next_tick is not None
    b.cancel()
    assert await asyncio.wait_for(c, 5) == 0
    assert b.cancelled() and a.done() and eng.ticks == 2
    # and a cancelled FLYER's tick is collected all the same, and the
    # tick the others wait for follows it
    a = asyncio.ensure_future(eng.tick())
    b = asyncio.ensure_future(asks_after_an_ack(eng))
    c = asyncio.ensure_future(eng.tick())   # after b's mark: next, too
    await asyncio.sleep(0)
    a.cancel()
    assert await asyncio.wait_for(asyncio.gather(b, c), 5) == [0, 0]
    assert a.cancelled() and eng.ticks == 4 and eng._flight is None


async def test_tick_soon_begins_now_and_the_loop_collects():
    eng = overlap_engine()
    eng.tick_soon()
    assert eng.log == ["call"] and eng._flight is not None
    eng.tick_soon()                     # one in flight: nothing more
    assert eng.log == ["call"]
    await asyncio.sleep(0)
    assert eng.log == ["call", "fetch"] and eng.ticks == 1
    assert eng.tick_hists["tick_overlapped"].count == 1
    # nothing to overlap, or stopped: the caller's dirty mark stands
    twin = MultiRaftEngine(TickOptions(max_groups=4, max_peers=3,
                                       backend="numpy"))
    twin.tick_soon()
    eng.crash()
    eng.tick_soon()
    await asyncio.sleep(0)
    assert twin.ticks == 0 and eng.ticks == 1


async def test_the_loop_does_not_tick_again_for_a_mark_a_tick_has_served():
    eng = overlap_engine()
    eng.mark_dirty()                    # an ack with a fence waiting
    eng.tick_soon()                     # and the tick it asked for
    task = asyncio.ensure_future(eng._loop())
    try:
        await asyncio.sleep(0.02)
        assert eng.ticks == 1 and not eng._dirty
        eng.mark_dirty()                # a later one is the loop's
        await asyncio.sleep(0.02)
        assert eng.ticks == 2
    finally:
        task.cancel()


@pytest.mark.parametrize("confirmed", [True, False])
def test_an_ack_during_a_flight_is_weighed_when_the_flight_lands(confirmed):
    """It marks nothing dirty; the landing does, if a fence still waits
    and something was recorded that its snapshot lacks."""
    from tpuraft.core.engine import EngineControl

    clock, events = ManualClock(100), []
    eng = overlap_engine(clock=clock)
    s = led_slot(eng, events)
    ctrl = SimpleNamespace(engine=eng, slot=s)
    p1, p2 = (p for p, col in sorted(eng._peer_cols[s].items(),
                                     key=lambda kv: kv[1]) if col)
    eng.arm_read_fence(s, Fence("f", events))           # start 100
    if confirmed:
        EngineControl.record_ack(ctrl, p1, 0.1003)      # quorum: 100 ms
    eng._dirty = False

    def between():
        clock.ms = 101
        EngineControl.record_ack(ctrl, p2, 0.1013)
        assert not eng._dirty           # the flight may do without it

    split_tick(eng, between)
    assert events == ([("fence", "f")] if confirmed else [])
    assert eng._dirty is (not confirmed)
    if not confirmed:
        clock.ms = 102
        split_tick(eng)                 # self and p2: a quorum at 101
        assert events == [("fence", "f")]


async def test_tick_once_during_a_flight_collects_it_first():
    eng = overlap_engine()
    a = asyncio.ensure_future(eng.tick())
    await asyncio.sleep(0)
    assert eng._flight is not None
    eng.tick_once()                     # e.g. a round that was cancelled
    assert eng.log == ["call", "fetch", "call", "fetch"]
    assert eng.ticks == 2 and eng._flight is None
    await asyncio.wait_for(a, 5)        # finds its tick collected
    assert eng.ticks == 2
    assert eng.tick_hists["tick_overlapped"].count == 0


async def test_waiters_of_a_flight_collected_early_ride_after_the_one_in_the_air():
    eng = overlap_engine()
    a = asyncio.ensure_future(eng.tick())
    b = asyncio.ensure_future(asks_after_an_ack(eng))
    await asyncio.sleep(0)              # a flies, b waits for the next
    eng.tick_once()                     # collects a's, ticks itself
    eng.tick_soon()                     # and another one is in the air
    in_the_air = eng._flight
    assert eng.ticks == 2 and in_the_air is not None
    assert await asyncio.wait_for(asyncio.gather(a, b), 5) == [0, 0]
    # a's landing left b's tick to the flight's: never two at once
    assert eng.ticks == 4 and in_the_air.done and eng._flight is None
    assert eng.log == ["call", "fetch"] * 4


async def test_a_probe_during_a_flight_leaves_its_buffer_alone():
    eng = overlap_engine()
    eng.tick_once()
    buf = eng._tick_buf
    flight = eng._tick_begin()
    sent = buf.copy()
    eng.role[:] = ROLE_FOLLOWER         # the probe packs other rows
    eng._device_tick(*eng._rel_views(), eng.now_ms())
    np.testing.assert_array_equal(buf, sent)
    assert eng._tick_buf is buf and eng._flight is flight
    eng._tick_end(flight, time.perf_counter())


async def test_the_numpy_twin_and_the_mesh_path_tick_synchronously():
    eng = MultiRaftEngine(TickOptions(max_groups=4, max_peers=3,
                                      backend="numpy"))
    assert await eng.tick() == 0 and eng.ticks == 1
    eng = overlap_engine()
    eng._packed = False                 # as over a mesh: rows, not packed
    called = []
    eng.tick_once = lambda: called.append(1) or 0
    assert await eng.tick() == 0 and called == [1]
    # a spy around tick_once sees every tick of the packed path too
    # (tests/benchmark/test_bench_failover.py probes before each)
    eng = overlap_engine()
    real = eng.tick_once
    eng.tick_once = lambda: called.append(2) or real()
    assert await eng.tick() == 0 and called == [1, 2]
    assert eng.ticks == 1 and eng.tick_hists["tick_overlapped"].count == 0


async def test_the_loops_tick_cost_leaves_the_yielded_turn_out():
    eng = overlap_engine()
    eng.mark_dirty()
    loop = asyncio.get_running_loop()
    task = asyncio.ensure_future(eng._loop())
    loop.call_soon(time.sleep, 0.05)    # another task's 50 ms, between
    try:
        for _ in range(200):
            if eng.ticks:
                break
            await asyncio.sleep(0.001)
    finally:
        task.cancel()
    assert eng.ticks >= 1
    assert eng.tick_hists["tick_inflight_ms"].percentile(100) >= 45.0
    assert 0.0 < eng._tick_cost_ema_s < 0.02
    assert eng.tick_hists["tick_total_ms"].percentile(100) < 20.0


async def test_a_flight_that_lands_after_a_stop_is_dropped():
    events: list = []
    eng = overlap_engine(clock=ManualClock(300))
    s = led_slot(eng, events)
    eng.role[s], eng.elect_deadline[s] = ROLE_FOLLOWER, 100
    a = asyncio.ensure_future(eng.tick())
    b = asyncio.ensure_future(asks_after_an_ack(eng))
    await asyncio.sleep(0)
    eng.crash()
    assert await asyncio.wait_for(a, 5) == 0
    assert await asyncio.wait_for(b, 5) == 0
    assert events == [] and eng.ticks_dropped == 1
    assert eng.log == ["call", "fetch"]             # and none follows it


# -- read-confirm rounds on the device fence lane ------------------------------


def device_fence_node(eng, gid: str, transport, voters: list):
    """tests/test_read_only.py's batcher node, leading a slot of ``eng``
    whose read fences the tick tallies (EngineControl's part, as far as
    ReadConfirmBatcher goes)."""
    from tests.test_read_only import _batcher_node

    node = _batcher_node(gid, transport, voters)
    s = eng.alloc_slot()
    eng.set_conf(s, Configuration(voters), Configuration())
    eng.has_ctrl[s], eng.self_col[s], eng.role[s] = True, 0, ROLE_LEADER
    eng.hb_deadline[s] = eng.stepdown_deadline[s] = 1 << 29

    def on_peer_ack(peer, when):
        eng.last_ack[s, eng.peer_col(s, peer)] = eng.to_ms(when)

    node.on_peer_ack = on_peer_ack
    node._ctrl = SimpleNamespace(
        drives_read_fences=True, engine=eng, slot=s,
        arm_read_fence=lambda fence: eng.arm_read_fence(s, fence))
    return node


@pytest.mark.parametrize("cancelled_in", ["rpc", "tick"])
async def test_a_cancelled_confirm_round_still_disarms_every_fence(
        cancelled_in):
    from tests.test_read_only import _StallTransport, _voters
    from tpuraft.rheakv.store_engine import ReadConfirmBatcher

    eng = overlap_engine()
    voters = _voters(8100)
    stalled = {p.endpoint for p in voters[1:]} if cancelled_in == "rpc" \
        else set()
    transport = _StallTransport(stalled)
    nodes = [device_fence_node(eng, f"g{i}", transport, voters)
             for i in range(3)]
    in_tick = asyncio.Event()

    async def never_collected():
        in_tick.set()
        await asyncio.Event().wait()

    if cancelled_in == "tick":
        eng.tick = never_collected
        eng.tick_soon = lambda: None    # the round's close has to ask
    b = ReadConfirmBatcher()
    futs = [asyncio.ensure_future(b.confirm(n)) for n in nodes]
    if cancelled_in == "rpc":
        await asyncio.sleep(0.02)
    else:
        await asyncio.wait_for(in_tick.wait(), 5)
    slots = [n._ctrl.slot for n in nodes]
    assert all(eng.fence_start[s] > _NEG_I32 for s in slots)
    assert not any(f.done() for f in futs)
    b.close()
    outs = await asyncio.wait_for(asyncio.gather(*futs), 5)
    # the synchronous close ran: one tick, every fence off the lane; a
    # round whose acks had all landed is confirmed by that tick
    assert outs == [cancelled_in == "tick"] * 3
    assert eng.ticks == 1 and eng.log == ["call", "fetch"]
    assert all(eng.fence_start[s] == _NEG_I32 for s in slots)
    assert eng._fence_waiters == {} and not b._rounds_inflight
