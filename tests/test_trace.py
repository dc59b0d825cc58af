"""Trace plane: span recorder semantics, wire-context compatibility,
perfetto export, the flight recorder, and the metrics exposition layer.

Covers ISSUE 12's test satellites: ring bounds under churn, seeded
sampling determinism, slow-op force-retention, trace-context wire
compat BOTH directions (an old decoder sees a plain request), perfetto
JSON schema validity, recorder dump-on-anomaly on a forced SICK
transition — plus the end-to-end acceptance shape (one traced KV put =
client + leader + follower spans joined by the trailing wire context)
and the Prometheus exposition surfaces (metrics_text, the
describe_metrics admin RPC, the HTTP listener).
"""

from __future__ import annotations

import asyncio
import functools
import gc
import json
import threading

import pytest

from tpuraft.rpc.messages import (
    AppendEntriesRequest,
    decode_message,
    encode_message,
)
from tpuraft.util.trace import (
    ANCHOR_EVENT,
    RECORDER,
    TRACER,
    FlightRecorder,
    Tracer,
    adopt_entry_ctx,
    entry_ctx,
    pack_ctx,
    unpack_ctx,
)


@pytest.fixture(autouse=True)
def _isolate_tracer():
    """The tracer is a module singleton: every test starts disabled and
    empty, and leaves it that way."""
    TRACER.configure(enabled=False)
    TRACER.reset()
    yield
    TRACER.configure(enabled=False)
    TRACER.reset()


# ---------------------------------------------------------------------------
# span recorder semantics
# ---------------------------------------------------------------------------


def _run_op(t: Tracer, dur_s: float = 0.0, spans: int = 0) -> int:
    tid = t.begin_op("op")
    if tid:
        import time

        base = time.perf_counter()
        for i in range(spans):
            t.span(tid, f"stage{i}", base, base + 1e-6)
        if dur_s:
            # synthesize the duration by back-dating the staged start
            t._staged[tid].t0 -= dur_s
        t.end_op(tid)
    return tid


def test_disabled_tracer_is_inert():
    t = Tracer()
    assert t.begin_op() == 0
    t.span(0, "x", 0.0, 1.0)
    assert t.end_op(0) == 0.0
    assert t.spans() == []
    assert t.counters()["trace_ops_seen"] == 0


def test_ring_bounds_under_churn():
    t = Tracer().configure(enabled=True, sample_rate=1.0, seed=1, ring=64)
    for _ in range(500):
        _run_op(t, spans=3)
    assert len(t.spans()) <= 64
    c = t.stats()
    assert c["trace_ring_spans"] <= 64
    assert c["trace_ops_seen"] == 500
    # the ring keeps the NEWEST spans
    assert t.spans()[-1]["name"] in ("op", "stage2")


def test_staging_bounded_and_abandoned_ops_evicted():
    t = Tracer().configure(enabled=True, sample_rate=1.0, seed=1)
    t._max_staged = 8
    for _ in range(100):
        t.begin_op()  # never ended
    assert len(t._staged) <= 8


def test_seeded_sampling_determinism():
    a = Tracer().configure(enabled=True, sample_rate=0.3, seed=42,
                           slow_trigger=False)
    b = Tracer().configure(enabled=True, sample_rate=0.3, seed=42,
                           slow_trigger=False)
    sampled_a = [bool(_run_op(a)) for _ in range(200)]
    sampled_b = [bool(_run_op(b)) for _ in range(200)]
    assert sampled_a == sampled_b
    assert 20 < sum(sampled_a) < 120  # ~30%
    c = Tracer().configure(enabled=True, sample_rate=0.3, seed=7,
                           slow_trigger=False)
    assert [bool(_run_op(c)) for _ in range(200)] != sampled_a


def test_slow_op_force_retention(monkeypatch):
    """Unsampled ops drop — unless slower than the rolling p99 EMA.
    A slow-retained op keeps its ROOT span (duration + slow flag);
    child attribution exists only for sampled ops (the overhead gate's
    budget: unsampled candidacy must cost a clock read, not a span
    pipeline).  Durations come from a fake clock: back-dating t0 over
    the real perf_counter adds the loop's wall time to every synthetic
    duration, and one host stall past warmup reads as a real slow op."""
    import tpuraft.util.trace as trace_mod

    clock = [0.0]
    monkeypatch.setattr(trace_mod, "_pc", lambda: clock[0])
    t = Tracer().configure(enabled=True, sample_rate=0.0, seed=1)
    t._warmup = 50
    for i in range(100):                   # ~1ms steady state; the mild
        tid = t.begin_op("op")             # decay keeps each dur strictly
        clock[0] += 0.001 - i * 1e-7       # below the EMA, as a real
        t.end_op(tid)                      # stream sits below its p99
    assert t.spans() == []                 # nothing sampled => dropped
    assert t.counters()["trace_ops_dropped"] == 100
    tid = t.begin_op("op")                 # 500x the EMA
    t.span(tid, "stage0", clock[0], clock[0])
    t.span(tid, "stage1", clock[0], clock[0])
    clock[0] += 0.5
    t.end_op(tid)
    spans = t.spans()
    assert spans, "slow op must be force-retained"
    assert {s["name"] for s in spans} == {"op"}   # root-only
    root = spans[-1]
    assert root["args"].get("slow") is True
    assert root["dur_s"] >= 0.4
    assert t.counters()["trace_ops_slow_retained"] == 1


def test_sampled_ops_keep_child_spans():
    t = Tracer().configure(enabled=True, sample_rate=1.0, seed=1)
    _run_op(t, spans=2)
    names = [s["name"] for s in t.spans()]
    assert names.count("op") == 1
    assert "stage0" in names and "stage1" in names


def test_wire_ctx_masks_unsampled():
    from tpuraft.util.trace import wire_ctx

    assert wire_ctx(0) == 0
    assert wire_ctx(0b101) == 0b101   # sampled rides the wire
    assert wire_ctx(0b100) == 0       # slow-candidate stays local


def test_remote_context_records_only_sampled():
    """A remote process records a wire-borne context iff the sampled
    bit is set (the slow-op trigger is client-local)."""
    t = Tracer().configure(enabled=True, sample_rate=1.0, seed=1)
    sampled_tid = 0b101   # seq 2, sampled
    unsampled_tid = 0b100  # seq 2, not sampled
    t.span(sampled_tid, "remote_stage", 0.0, 0.001, proc="storeX")
    t.span(unsampled_tid, "remote_stage", 0.0, 0.001, proc="storeX")
    spans = t.spans()
    assert len(spans) == 1
    assert spans[0]["trace_id"] == sampled_tid
    assert spans[0]["proc"] == "storeX"


# ---------------------------------------------------------------------------
# trace-context wire helpers + compat both directions
# ---------------------------------------------------------------------------


def test_pack_unpack_ctx_roundtrip_and_zero_cost():
    assert pack_ctx([0, 0, 0]) == b""          # untraced = no wire bytes
    blob = pack_ctx([0, 7, 0, 9])
    assert unpack_ctx(blob, 4) == [0, 7, 0, 9]
    assert unpack_ctx(b"", 3) == [0, 0, 0]     # old sender
    assert unpack_ctx(blob[:8], 4) == [0, 0, 0, 0]  # short blob = zeros


def test_entry_ctx_adoption():
    from tpuraft.entity import EntryType, LogEntry

    entries = [LogEntry(type=EntryType.DATA, data=b"a"),
               LogEntry(type=EntryType.DATA, data=b"b", trace_id=11)]
    blob = entry_ctx(entries)
    fresh = [LogEntry(type=EntryType.DATA, data=b"a"),
             LogEntry(type=EntryType.DATA, data=b"b")]
    adopt_entry_ctx(fresh, blob)
    assert [e.trace_id for e in fresh] == [0, 11]
    adopt_entry_ctx(fresh, b"")   # old sender: no-op
    assert [e.trace_id for e in fresh] == [0, 11]


def test_append_entries_trace_ctx_wire_compat_both_directions():
    """AppendEntriesRequest gained a trailing trace_ctx.  Old frames
    decode on new receivers with the default; a new frame is a strict
    extension whose prefix an old decoder reads identically."""
    from tpuraft.entity import EntryType, LogEntry

    e = LogEntry(type=EntryType.DATA, data=b"payload")
    e.id = e.id.__class__(3, 2)
    new = AppendEntriesRequest(
        group_id="g", server_id="a:1", peer_id="b:2", term=2,
        prev_log_index=2, prev_log_term=2, committed_index=1,
        entries=[e], trace_ctx=pack_ctx([5]))
    wire = encode_message(new)
    got = decode_message(wire)
    assert got.trace_ctx == pack_ctx([5])
    assert got.entries[0].data == b"payload"
    # old sender -> new receiver: strip the trailing bytes field
    # (4-byte length prefix + ctx payload); trace_ctx defaults
    old_wire = wire[:-(4 + len(new.trace_ctx))]
    old_got = decode_message(old_wire)
    assert old_got.trace_ctx == b""
    assert old_got.entries[0].data == b"payload"
    # new -> old receiver: the old-format prefix is byte-identical, so
    # an old decoder (which stops after entries) reads the same values
    old_fmt = encode_message(AppendEntriesRequest(
        group_id="g", server_id="a:1", peer_id="b:2", term=2,
        prev_log_index=2, prev_log_term=2, committed_index=1,
        entries=[e]))
    assert wire[:len(old_wire)] == old_fmt[:len(old_wire)]


def test_kv_batch_trace_ctx_wire_compat_both_directions():
    from tpuraft.rheakv.kv_service import KVCommandBatchRequest

    new = KVCommandBatchRequest(items=[b"item0", b"item1"],
                                trace_ctx=pack_ctx([0, 9]))
    wire = encode_message(new)
    assert decode_message(wire) == new
    old_wire = wire[:-(4 + len(new.trace_ctx))]
    got = decode_message(old_wire)      # old sender -> new receiver
    assert got.items == [b"item0", b"item1"]
    assert got.trace_ctx == b""
    # an untraced new frame differs from the old format only by the
    # empty trailing field an old decoder never reads
    untraced = encode_message(KVCommandBatchRequest(
        items=[b"item0", b"item1"]))
    assert untraced[:len(old_wire)] == old_wire


# ---------------------------------------------------------------------------
# perfetto export
# ---------------------------------------------------------------------------


def test_chrome_export_schema(tmp_path):
    t = Tracer().configure(enabled=True, sample_rate=1.0, seed=1)
    tid = t.begin_op("op", proc="client")
    import time

    base = time.perf_counter()
    t.span(tid, "stage", base, base + 0.001, proc="store:x")
    t.end_op(tid)
    path = str(tmp_path / "trace.json")
    n = t.export_chrome(path)
    assert n == 2
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert isinstance(evs, list)
    x = [e for e in evs if e["ph"] == "X"]
    metas = [e for e in evs if e["ph"] == "M"]
    # the clock anchor leads, then the two procs are named
    assert len(x) == 2 and len(metas) == 3
    assert metas[0]["name"] == ANCHOR_EVENT
    for e in x:
        for key in ("name", "ph", "ts", "dur", "pid", "tid", "args"):
            assert key in e
        assert e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    # the two spans of one op share a tid row, on different pid rows
    assert x[0]["tid"] == x[1]["tid"]
    assert x[0]["pid"] != x[1]["pid"]


# ---------------------------------------------------------------------------
# loop sections: what the loop thread runs
# ---------------------------------------------------------------------------


class _Clock:
    """A scripted perf_counter / thread_time pair for the tracer."""

    def __init__(self, t: float = 100.0) -> None:
        self.t = t
        self.cpu = 0.0

    def run(self, seconds: float, cpu_share: float = 1.0) -> None:
        self.t += seconds
        self.cpu += seconds * cpu_share


@pytest.fixture
def clock(monkeypatch):
    from tpuraft.util import trace

    c = _Clock()
    monkeypatch.setattr(trace, "_pc", lambda: c.t)
    monkeypatch.setattr(trace, "_thread_time", lambda: c.cpu)
    return c


def _section(t: Tracer, name: str):
    """The call sites' pattern: one attribute test while tracing is off."""
    return t.enter(name) if t.enabled else None


@pytest.mark.parametrize("children", [
    [],                                        # a leaf: self == inclusive
    [("raft.heartbeat", 0.25)],                # one child
    [("raft.heartbeat", 0.25), ("raft.ack", 0.125)],   # two siblings
    [("tick.apply", 0.5)],                     # the same name nested
])
def test_nested_sections_self_is_inclusive_minus_children(clock, children):
    t = Tracer().configure(enabled=True)
    outer = _section(t, "tick.apply")
    clock.run(0.1)
    for name, dur in children:
        sec = _section(t, name)
        clock.run(dur)
        t.leave(sec)
    clock.run(0.2)
    t.leave(outer)
    table = t.section_table()
    in_children = sum(d for _n, d in children)
    own = [d for n, d in children if n == "tick.apply"]
    calls, busy, self_s = table["tick.apply"]
    assert calls == 1 + len(own)
    # inclusive seconds count a nested entry of the same name twice;
    # self seconds never do
    assert busy == pytest.approx(0.3 + in_children + sum(own))
    assert self_s == pytest.approx(0.3 + sum(own))
    for name, dur in children:
        if name != "tick.apply":
            assert table[name] == (1, pytest.approx(dur),
                                   pytest.approx(dur))
    assert sum(v[2] for v in table.values()) \
        == pytest.approx(0.3 + in_children)


def test_disabled_tracer_opens_no_section_and_records_nothing():
    t = Tracer()
    assert not t.enabled
    for _ in range(3):
        assert _section(t, "kv.batch") is None
    assert t.section_table() == {}
    assert t._sec_stack == []
    assert t.spans() == []
    assert not any(k.startswith("trace_section") for k in t.counters())


def test_rollups_sum_to_self_seconds_within_one_bucket(clock):
    t = Tracer().configure(enabled=True, ring=4096)
    u = 1 / 256                 # binary fractions: the sums are exact
    want = {"kv.batch": 0.0, "raft.propose": 0.0, "raft.ack": 0.0}
    # 10.3 s of work in steps of 9 u: 4 u of kv.batch holding 1 u of
    # raft.propose, 2 u of raft.ack, 3 u outside any section of which
    # 2 u are spent in the selector (no CPU)
    for _ in range(293):
        a = _section(t, "kv.batch")
        clock.run(2 * u)
        b = _section(t, "raft.propose")
        clock.run(u)
        t.leave(b)
        clock.run(u)
        t.leave(a)
        c = _section(t, "raft.ack")
        clock.run(2 * u)
        t.leave(c)
        clock.run(u)
        clock.run(2 * u, cpu_share=0.0)
        want["kv.batch"] += 3 * u
        want["raft.propose"] += u
        want["raft.ack"] += 2 * u
    spans = t.spans()
    assert {s["proc"] for s in spans} == {"loop"}
    by_name: dict = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    assert set(by_name) == {"loop.kv.batch", "loop.raft.propose",
                            "loop.raft.ack", "loop.kv", "loop.raft",
                            "loop.cpu"}
    # ten whole buckets, a second apart; the last partial one is dropped
    assert {len(rows) for rows in by_name.values()} == {10}
    starts = [s["ts_s"] for s in by_name["loop.cpu"]]
    assert [b - a for a, b in zip(starts, starts[1:])] == [1.0] * 9
    for name, total in want.items():
        rolled = sum(s["dur_s"] for s in by_name["loop." + name])
        in_one_bucket = total / 10.3
        assert total - in_one_bucket <= rolled <= total
    for k in range(10):
        # a layer's share is the sum of its sections' self seconds
        assert by_name["loop.raft"][k]["dur_s"] \
            == by_name["loop.raft.propose"][k]["dur_s"] \
            + by_name["loop.raft.ack"][k]["dur_s"]
        assert by_name["loop.kv"][k]["dur_s"] \
            == by_name["loop.kv.batch"][k]["dur_s"]
        # 7 u of 9 burn CPU; the sections cover 6 u of 9 (a bucket
        # closes at the first exit past its second: a step's slack)
        cpu = by_name["loop.cpu"][k]
        assert cpu["dur_s"] == pytest.approx(7 / 9, abs=9 * u)
        assert cpu["args"]["busy_s"] == pytest.approx(6 / 9, abs=9 * u)
        assert cpu["args"]["busy_s"] == sum(
            by_name[n][k]["dur_s"] for n in ("loop.kv", "loop.raft"))
    # in the perfetto export every roll-up name has a row of its own
    rows = {e["args"]["name"] for e in t.chrome_events()
            if e["ph"] == "M" and e["name"] == "thread_name"}
    assert rows == set(by_name)


def test_a_second_with_no_section_exit_shares_the_delta(clock):
    t = Tracer().configure(enabled=True)
    sec = _section(t, "fsm.apply")
    clock.run(3.5)                       # one stretch over three seconds
    t.leave(sec)
    rows = [s for s in t.spans() if s["name"] == "loop.fsm.apply"]
    assert len(rows) == 3
    # a bucket holds the self seconds of its own wall second, no more:
    # the half second past the third waits for the bucket it fell in
    assert [s["dur_s"] for s in rows] == [pytest.approx(1.0)] * 3
    assert sum(s["args"]["n"] for s in rows) == pytest.approx(1)
    sec = _section(t, "raft.ack")
    clock.run(0.75)
    t.leave(sec)
    fourth = {s["name"]: s["dur_s"] for s in t.spans()
              if s["ts_s"] == pytest.approx(3.0)}
    assert fourth["loop.fsm.apply"] == pytest.approx(0.5)
    assert fourth["loop.raft.ack"] == pytest.approx(0.5)
    assert t.section_table()["fsm.apply"][2] == pytest.approx(3.5)


def test_anchor_pair_is_in_chrome_events():
    import time

    before = time.perf_counter_ns(), time.time_ns()
    t = Tracer().configure(enabled=True)
    t.leave(_section(t, "kv.batch"))     # the annotation re-reads it
    after = time.perf_counter_ns(), time.time_ns()
    first = t.chrome_events()[0]
    assert first["ph"] == "M" and first["name"] == ANCHOR_EVENT
    assert first["args"] == {"perf_counter_ns": t.anchor[0],
                             "time_ns": t.anchor[1]}
    assert before[0] <= t.anchor[0] <= after[0]
    assert before[1] <= t.anchor[1] <= after[1]


def test_sections_accumulate_with_jax_unimportable(monkeypatch, clock):
    import sys

    # None in sys.modules makes ``import jax...`` raise ImportError
    for mod in ("jax", "jax.profiler"):
        monkeypatch.setitem(sys.modules, mod, None)
    t = Tracer().configure(enabled=True)
    for _ in range(3):
        sec = _section(t, "fsm.apply")
        assert sec is not None and sec[1] is None    # no annotation
        clock.run(0.4)
        t.leave(sec)
    assert t._annotation is False
    assert t.section_table()["fsm.apply"] == (3, pytest.approx(1.2),
                                              pytest.approx(1.2))
    assert [s["name"] for s in t.spans()] == ["loop.fsm.apply", "loop.fsm",
                                              "loop.cpu"]


@pytest.mark.parametrize("how", ["flag_cleared", "reset", "rearmed"])
def test_a_section_left_after_tracing_went_off_leaves_cleanly(how):
    t = Tracer().configure(enabled=True)
    outer = _section(t, "kv.batch")
    inner = _section(t, "raft.propose")
    if how == "flag_cleared":        # how the benchmark disarms
        t.enabled = False
    elif how == "reset":
        t.reset()
    else:
        t.configure(enabled=True)
    t.leave(inner)
    t.leave(outer)
    assert t._sec_stack == []
    assert inner[1] is None and outer[1] is None     # annotations closed
    if how == "flag_cleared":
        assert set(t.section_table()) == {"kv.batch", "raft.propose"}
    else:                            # the accumulators started over
        assert t.section_table() == {}
    assert _section(t, "kv.batch") is None or how != "flag_cleared"


def test_a_skipped_leave_is_unwound_by_the_enclosing_one(clock):
    t = Tracer().configure(enabled=True)
    outer = _section(t, "tick.apply")
    _section(t, "raft.heartbeat")        # an exception skipped its leave
    clock.run(0.5)
    t.leave(outer)
    assert t._sec_stack == []
    assert t.section_table()["raft.heartbeat"][0] == 1
    assert t.section_table()["tick.apply"][2] == pytest.approx(0.0)


def test_sections_are_confined_to_the_loop_thread():
    t = Tracer().configure(enabled=True)
    t.leave(_section(t, "fsm.apply"))
    got = []
    th = threading.Thread(target=lambda: got.append(_section(t, "fsm.apply")))
    th.start()
    th.join()
    assert got == [None]
    assert t.section_table()["fsm.apply"][0] == 1


def test_section_table_rides_the_counters():
    t = Tracer().configure(enabled=True)
    t.leave(_section(t, "kv.read_round"))
    c = t.counters()
    assert c["trace_section_calls_kv.read_round"] == 1
    assert c["trace_section_busy_seconds_kv.read_round"] >= 0.0
    assert c["trace_section_self_seconds_kv.read_round"] >= 0.0


# ---------------------------------------------------------------------------
# the loop's dispatch, framed: handles by owner, the selector, the collector
# ---------------------------------------------------------------------------

# the interpreter's own, as this module found it: nothing traces at import
_RUN = asyncio.events.Handle._run


def _patched(loop, t: Tracer) -> tuple:
    """(the dispatch, the selector, the collector) as the tracer left them:
    True where it has a hook of its own in place."""
    return (asyncio.events.Handle._run is not _RUN,
            "select" in vars(loop._selector),
            t._on_gc in gc.callbacks)


@pytest.fixture
def tracer():
    """A tracer of the test's own; whatever it patched goes with it."""
    t = Tracer()
    yield t
    t.configure(enabled=False)
    assert asyncio.events.Handle._run is _RUN


class _Callable:
    def __call__(self, *_a):
        pass

    def method(self, *_a):
        pass


def _plain(*_a):
    pass


async def test_a_tasks_steps_file_under_its_coroutine(tracer, clock):
    t = tracer.configure(enabled=True)

    async def worker():
        clock.run(0.25)
        sec = _section(t, "kv.batch")       # opens inside the step's frame
        clock.run(0.5)
        t.leave(sec)
        await asyncio.sleep(0)              # a second step
        clock.run(0.125)

    await asyncio.ensure_future(worker())
    table = t.section_table()
    step = "turn.step." + worker.__qualname__.replace(".", ":")
    assert step.count(".") == 2             # three dotted parts, always
    # the frame's self seconds are what ran outside any section
    assert table[step] == (2, pytest.approx(0.875), pytest.approx(0.375))
    # and the section's numbers are what they are without the frames
    assert table["kv.batch"] == (1, pytest.approx(0.5), pytest.approx(0.5))
    # the step that armed was running already: it has no frame, its later
    # steps (the wake-up from the await above) do
    (mine,) = [f[0] for f in t._sec_stack]
    assert mine.startswith("turn.step.") and table[mine][0] == 0


@pytest.mark.parametrize("how, name", [
    ("call_soon", "turn.callback._plain"),
    ("done_callback", "turn.callback._plain"),
    ("partial", "turn.callback._plain"),
    ("bound_method", "turn.callback._Callable:method"),
    ("builtin", "turn.callback.Future:cancelled"),
    ("call_later", "turn.timer._plain"),
    ("unnameable", "turn.callback.?"),
])
async def test_a_handle_files_under_its_kind_and_owner(tracer, how, name):
    t = tracer.configure(enabled=True)
    loop = asyncio.get_running_loop()
    fut = loop.create_future()
    if how == "call_soon":
        loop.call_soon(_plain)
    elif how == "done_callback":
        fut.add_done_callback(_plain)
        fut.set_result(None)
    elif how == "partial":
        loop.call_soon(functools.partial(functools.partial(_plain, 1), 2))
    elif how == "bound_method":
        loop.call_soon_threadsafe(_Callable().method)
    elif how == "builtin":
        loop.call_soon(fut.cancelled)
    elif how == "call_later":
        loop.call_later(0.0, _plain)
    else:
        loop.call_soon(_Callable())         # no __qualname__: never raises
    for _ in range(3):
        await asyncio.sleep(0.001)
    assert t.section_table()[name][0] == 1
    assert t.turn_handles >= 1
    # looked up once: the second handle of an owner builds no string
    kind = name.split(".")[1]
    assert name in [acc[3] for acc in t._turn_accs[kind].values()]


async def test_idle_select_opens_once_an_iteration(tracer):
    t = tracer.configure(enabled=True)
    for _ in range(5):
        await asyncio.sleep(0)
    # this step runs in the turn after the fifth select since arming
    selects = t.section_table()["idle.select"][0]
    assert selects == t.turns + 1 == 5
    assert t._sec_stack[-1][0].startswith("turn.step.")   # not in select
    assert t.counters()["trace_turns"] == t.turns
    assert t.counters()["trace_turn_handles"] == t.turn_handles >= 4


async def test_a_collection_is_a_child_section_and_a_ring_record(tracer,
                                                                  clock):
    t = tracer.configure(enabled=True)

    def pass_takes(phase, _info):           # after the tracer's own hook
        if phase == "start":
            clock.run(0.25)

    gc.callbacks.append(pass_takes)
    gc.disable()                            # no pass but the one asked for
    try:
        sec = _section(t, "fsm.apply")
        clock.run(0.5)
        gc.collect(1)
        clock.run(0.125)
        t.leave(sec)
        table = t.section_table()
        # a pass on another thread is not the loop's
        th = threading.Thread(target=gc.collect, args=(1,))
        th.start()
        th.join()
        assert t.section_table()["gc.gen1"][0] == 1
    finally:
        gc.enable()
        gc.callbacks.remove(pass_takes)
    assert table["gc.gen1"] == (1, pytest.approx(0.25), pytest.approx(0.25))
    # the pass began inside the section and is not in its self seconds
    assert table["fsm.apply"] == (1, pytest.approx(0.875),
                                  pytest.approx(0.625))
    (rec,) = [s for s in t.spans() if s["name"] == "gc.gen1"]
    assert rec["proc"] == "loop" and rec["dur_s"] == pytest.approx(0.25)
    assert set(rec["args"]) == {"collected", "uncollectable"}
    assert table["gc.gen0"][0] == table["gc.gen2"][0] == 0


async def test_a_pass_that_straddles_the_seconds_end_is_split(tracer, clock):
    t = tracer.configure(enabled=True)
    await asyncio.sleep(0)                  # from here on, in a frame

    def pass_takes(phase, _info):
        if phase == "start":
            clock.run(0.25)

    gc.callbacks.append(pass_takes)
    gc.disable()
    try:
        clock.run(0.9)
        sec = _section(t, "fsm.apply")
        clock.run(0.05)
        gc.collect(1)                       # 0.95 to 1.20 of the tracer's time
        clock.run(0.02)
        t.leave(sec)
        clock.run(0.9)
        t.leave(_section(t, "raft.ack"))    # an exit past the second second
    finally:
        gc.enable()
        gc.callbacks.remove(pass_takes)
    by_name: dict = {}
    for s in t.spans():
        if s["name"].startswith("loop."):
            by_name.setdefault(s["name"], []).append(s["dur_s"])
    # the pass's exit brought the roll-up and was split at the second's end;
    # the section that held it has what it ran before and after, never less
    # than nothing
    assert by_name["loop.gc"] == [pytest.approx(0.05), pytest.approx(0.2)]
    assert by_name["loop.fsm.apply"] == [pytest.approx(0.05),
                                         pytest.approx(0.02)]
    assert by_name["loop.turn"] == [pytest.approx(0.9), pytest.approx(0.78)]
    for k in range(2):
        assert sum(by_name[n][k] for n in (
            "loop.fsm", "loop.raft", "loop.turn", "loop.idle.select",
            "loop.gc", "loop.rest") if len(by_name[n]) > k) \
            == pytest.approx(1.0)
    assert all(d >= 0.0 for rows in by_name.values() for d in rows)


async def test_a_turn_of_a_millisecond_leaves_a_record(tracer, clock):
    t = tracer.configure(enabled=True)

    async def worker(section_s, rest_s):
        sec = _section(t, "fsm.apply")
        clock.run(section_s)
        t.leave(sec)
        clock.run(rest_s)

    await asyncio.ensure_future(worker(0.003, 0.001))       # 4 ms
    await asyncio.ensure_future(worker(0.0005, 0.00025))    # under 1 ms
    await asyncio.ensure_future(worker(0.004, 0.021))       # a tick period
    for _ in range(2):
        await asyncio.sleep(0)
    turns = [s for s in t.spans() if s["name"] == "turn"]
    assert [s["dur_s"] for s in turns] == [pytest.approx(0.004),
                                           pytest.approx(0.025)]
    assert all(s["proc"] == "loop" for s in turns)
    first, second = (s["args"] for s in turns)
    assert first["top"] == "fsm.apply" and first["handles"] >= 1
    assert first["top_s"] == pytest.approx(0.003)
    # the frame of the step itself, when no section took more
    assert second["top"] == "turn.step." + worker.__qualname__.replace(
        ".", ":")
    assert second["top_s"] == pytest.approx(0.021)
    # the short one only counts
    assert t.turns >= 5 and t.turns_long == 1
    assert t.counters()["trace_turns_long"] == 1


async def test_every_second_of_the_thread_has_a_name(tracer, clock):
    """Sections, handle frames, the selector, the collector and the rest
    are the whole of each wall second."""
    loop = asyncio.get_running_loop()
    u = 1 / 64
    # the clock moves inside the selector (the thread asleep) and in the
    # loop's own bookkeeping between the selector and the handles
    loop._selector.select = lambda timeout, real=loop._selector.select: (
        clock.run(2 * u, cpu_share=0.0), real(timeout))[1]
    loop._process_events = lambda events, real=loop._process_events: (
        clock.run(u), real(events))[1]
    t = tracer.configure(enabled=True, ring=1 << 14)

    async def worker():
        for _ in range(30):                 # 8 u an iteration: 3.75 s
            clock.run(3 * u)
            sec = _section(t, "kv.batch")
            clock.run(u)
            t.leave(sec)
            loop.call_soon(clock.run, u)
            await asyncio.sleep(0)

    try:
        await asyncio.ensure_future(worker())
    finally:
        del loop._process_events
    by_name: dict = {}
    for s in t.spans():
        by_name.setdefault(s["name"], []).append(s)
    mine = "loop.turn.step." + worker.__qualname__.replace(".", ":")
    want = {"loop.turn.step": 24 * u, "loop.turn.callback": 8 * u,
            "loop.turn.timer": 0.0, "loop.turn": 32 * u, mine: 24 * u,
            "loop.turn.callback._Clock:run": 8 * u,
            "loop.kv.batch": 8 * u, "loop.kv": 8 * u,
            "loop.idle.select": 16 * u, "loop.idle": 16 * u,
            "loop.gc": 0.0, "loop.rest": 8 * u}
    for name, seconds in want.items():
        rows = by_name[name]
        assert len(rows) == 3, name         # every second, 0.0 or not
        for row in rows[1:]:    # the first second begins in mid-iteration
            assert row["dur_s"] == pytest.approx(seconds, abs=1e-9), name
    for k in range(3):
        whole = sum(by_name[n][k]["dur_s"] for n in (
            "loop.kv", "loop.turn", "loop.idle.select", "loop.gc",
            "loop.rest"))
        assert whole == pytest.approx(1.0, rel=0.02)
        cpu = by_name["loop.cpu"][k]
        # busy_s stays the seconds under the sections authors wrote
        assert cpu["args"]["busy_s"] == by_name["loop.kv"][k]["dur_s"]
        assert cpu["dur_s"] == pytest.approx(48 * u, abs=8 * u)
    # a two-part name rolls up once: no duplicate record
    assert not [n for n in by_name if n.count(".") == 1
                and len(by_name[n]) != 3]


async def test_off_means_absent(tracer):
    loop = asyncio.get_running_loop()
    assert _patched(loop, tracer) == (False, False, False)
    tracer.configure(enabled=False)         # never on: nothing installed
    assert _patched(loop, tracer) == (False, False, False)
    t = tracer.configure(enabled=True)
    assert _patched(loop, t) == (True, True, True)
    framed = asyncio.events.Handle._run
    t.configure(enabled=True)               # twice: one wrapper
    t.reset()
    assert asyncio.events.Handle._run is framed and t._turn_run is _RUN
    assert gc.callbacks.count(t._on_gc) == 1
    for _ in range(2):                      # a turn is counted at its end
        await asyncio.sleep(0)
    assert t.turn_handles >= 1
    t.enabled = False                       # how the benchmark ends it
    assert _patched(loop, t) == (True, True, True)
    await asyncio.sleep(0)                  # one more handle
    assert _patched(loop, t) == (False, False, False)
    assert loop._selector.select.__func__ is type(loop._selector).select
    before = t.turn_handles
    await asyncio.sleep(0)
    assert t.turn_handles == before and t.enabled is False


def test_enabled_with_no_running_loop_patches_nothing():
    t = Tracer().configure(enabled=True)
    assert asyncio.events.Handle._run is _RUN
    assert t._on_gc not in gc.callbacks and t._turn_loop is None
    t.leave(_section(t, "kv.batch"))
    # no frames, so none of their rows: the roll-up is what it was
    assert set(t.section_table()) == {"kv.batch"}


async def test_a_second_tracer_takes_the_dispatch_over(tracer):
    loop = asyncio.get_running_loop()
    first = Tracer().configure(enabled=True)
    second = tracer.configure(enabled=True)
    assert first._turn_loop is None and second._turn_loop is loop
    assert second._turn_run is _RUN and first._on_gc not in gc.callbacks
    for _ in range(2):
        await asyncio.sleep(0)
    assert second.turn_handles >= 1 and first.turn_handles == 0


async def test_a_callback_that_raises_leaves_the_stack_balanced(tracer):
    t = tracer.configure(enabled=True)
    loop = asyncio.get_running_loop()
    seen = []
    loop.set_exception_handler(lambda _loop, ctx: seen.append(ctx))

    def raises():
        _section(t, "raft.ack")             # its leave is skipped
        raise RuntimeError("boom")

    loop.call_soon(raises)
    for _ in range(2):
        await asyncio.sleep(0)
    loop.set_exception_handler(None)
    assert len(seen) == 1 and "boom" in str(seen[0]["exception"])
    table = t.section_table()
    name = "turn.callback." + raises.__qualname__.replace(".", ":")
    assert table[name][0] == 1 and table["raft.ack"][0] == 1
    # only the frame of the step this runs in is open
    assert [f[0].split(".")[1] for f in t._sec_stack] == ["step"]
    t.reset()                               # under a running handle's frame
    assert t._sec_stack == []
    await asyncio.sleep(0)                  # its exit finds nothing to pop
    assert [f[0].split(".")[1] for f in t._sec_stack] == ["step"]


@pytest.mark.parametrize("ending", ["returns", "raises", "cancelled"])
async def test_drive_opens_the_section_around_each_stretch(tracer, clock,
                                                           ending):
    t = tracer.configure(enabled=True)
    gate = asyncio.get_running_loop().create_future()

    async def boot():
        clock.run(0.25)
        await asyncio.sleep(0)              # a bare yield
        clock.run(0.5)
        assert [f[0] for f in t._sec_stack][-1] == "store.boot"
        got = await gate                    # a future
        clock.run(0.125)
        if ending == "raises":
            raise ValueError(got)
        return got

    task = asyncio.ensure_future(t.drive("store.boot", boot()))
    for _ in range(3):
        await asyncio.sleep(0)
    # suspended: the section is closed, other handles are not its children
    assert "store.boot" not in [f[0] for f in t._sec_stack]
    if ending == "cancelled":
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task
        assert gate.cancelled()
        stretches, seconds = 3, 0.75        # the throw is a stretch too
    else:
        gate.set_result("up")
        if ending == "raises":
            with pytest.raises(ValueError, match="up"):
                await task
        else:
            assert await task == "up"
        stretches, seconds = 3, 0.875
    assert t.section_table()["store.boot"] == (
        stretches, pytest.approx(seconds), pytest.approx(seconds))


async def test_a_replicas_boot_runs_under_store_boot():
    from tests.kv_cluster import KVTestCluster

    TRACER.configure(enabled=True, sample_rate=0.0)
    c = KVTestCluster(3)
    try:
        await c.start_all()
        await c.wait_region_leader(1)
        table = TRACER.section_table()
        TRACER.enabled = False
        regions = sum(len(s._regions) for s in c.stores.values())
        # a stretch before each suspension of a boot, and one to its end
        assert regions >= 3 and table["store.boot"][0] >= regions
        assert table["store.boot"][2] > 0.0
        # what the boot's task steps ran outside the section is theirs
        assert "turn.step.StoreEngine:_start_region" in table
    finally:
        TRACER.configure(enabled=False)
        TRACER.reset()
        await c.stop_all()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_recorder_ring_bounds_and_dump():
    r = FlightRecorder(capacity=16)
    for i in range(100):
        r.record("step_down", f"g{i}", term=i)
    assert len(r.events()) == 16
    assert r.events_recorded == 100
    text = r.dump()
    assert "step_down" in text and "g99" in text
    assert "flight recorder" in text


def test_recorder_election_storm_anomaly():
    r = FlightRecorder()
    for _ in range(r.storm_threshold):
        r.record("election_start", "cluster--1", term=1)
    assert len(r.anomalies) == 1
    snap = r.anomaly_report()[0]
    assert snap["reason"] == "election_storm"
    assert "cluster--1" in snap["detail"]
    assert any("election_start" in line for line in snap["events"])
    # a storm keeps raging within the window: ONE snapshot, not N
    for _ in range(10):
        r.record("election_start", "cluster--1", term=2)
    assert len(r.anomalies) == 1


def test_recorder_dump_on_forced_sick_transition():
    """A SICK transition must record the health event AND snapshot the
    ring (the lead-up survives churn)."""
    from tpuraft.util.health import HealthOptions, HealthTracker, SICK

    RECORDER.record("election_start", "lead-up-group", term=9)
    opts = HealthOptions(worsen_after=2, recover_after=2)
    h = HealthTracker(opts, label="store-under-test")
    for _ in range(5):
        h.disk.note(10.0)   # 10s fsyncs: raw SICK
        assert h.evaluate() in ("healthy", "degraded", "sick")
    assert h.score() == SICK
    # the recorder is a process singleton and its anomaly buffer is
    # BOUNDED — earlier chaos tests may have filled it with real
    # election storms, so assert on the newest snapshot, not the count
    dumps = RECORDER.anomaly_report()
    assert dumps, "SICK transition must snapshot the ring"
    snap = dumps[-1]
    assert snap["reason"] == "sick_transition"
    assert "store-under-test" in snap["detail"]
    # the ring snapshot carries the lead-up event
    assert any("lead-up-group" in line for line in snap["events"])
    # the transition itself is an event too
    kinds = [k for _ts, k, _g, _d in RECORDER.events()]
    assert "health" in kinds


def test_recorder_coalesces_flood_kinds():
    """Request-rate kinds (shed, mass quiesce sweeps) must not evict
    the ring: one leading-edge event per window, the rest counted."""
    r = FlightRecorder(capacity=64)
    for _ in range(500):
        r.record_coalesced("shed", "s1", items=1)
    evs = [e for e in r.events() if e[1] == "shed"]
    assert len(evs) == 1
    # windows are per (kind, group): another store's first shed must
    # record immediately, not be swallowed by s1's window (its
    # suppressed count would otherwise surface attributed to s1)
    r.record_coalesced("shed", "s2", items=1)
    assert len([e for e in r.events()
                if e[1] == "shed" and e[2] == "s2"]) == 1
    r._coalesce[("shed", "s1")][0] -= 2.0   # expire the window
    r.record_coalesced("shed", "s1", items=1)
    evs = [e for e in r.events() if e[1] == "shed" and e[2] == "s1"]
    assert len(evs) == 2
    assert evs[-1][3].get("suppressed") == 499
    # sweep-shaped kinds (per_group=False): a hibernation sweep is
    # thousands of DISTINCT groups each quiescing once — per-group
    # windows would make every one a leading edge and flood the ring,
    # so they share one window per kind
    for i in range(500):
        r.record_coalesced("quiesce", f"g{i}", per_group=False, role="x")
    assert len([e for e in r.events() if e[1] == "quiesce"]) == 1


def test_recorder_thread_safety():
    r = FlightRecorder(capacity=256)
    errs = []

    def hammer(tag):
        try:
            for i in range(500):
                r.record("evt", f"g{tag}", i=i)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=hammer, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert r.events_recorded == 2000


# ---------------------------------------------------------------------------
# metrics: histogram fixes + registry thread safety + prometheus text
# ---------------------------------------------------------------------------


def test_histogram_ring_replaces_oldest_first():
    from tpuraft.util.metrics import Histogram

    h = Histogram(max_samples=4)
    for v in (1, 2, 3, 4):
        h.update(v)
    h.update(5)   # must replace slot 0 (oldest), not skew to slot 1
    assert sorted(h._samples) == [2, 3, 4, 5]
    h.update(6)
    assert sorted(h._samples) == [3, 4, 5, 6]


def test_histogram_percentile_rounding():
    from tpuraft.util.metrics import Histogram

    h = Histogram()
    for v in range(1, 101):   # 1..100
        h.update(v)
    assert h.percentile(99) == 99
    assert h.percentile(50) == 50
    assert h.percentile(100) == 100
    small = Histogram()
    for v in (10, 20, 30, 40):
        small.update(v)
    assert small.percentile(50) == 20     # 2nd of 4, not 3rd
    assert small.percentile(99) == 40
    one = Histogram()
    one.update(7)
    assert one.percentile(99) == 7


def test_histogram_cached_sort_invalidation():
    from tpuraft.util.metrics import Histogram

    h = Histogram()
    h.update(5)
    assert h.percentile(50) == 5
    h.update(1)   # must invalidate the cached sort
    assert h.percentile(50) == 1
    assert h.snapshot()["max"] == 5


def test_histogram_update_counts_n_events_with_one_sample():
    from tpuraft.util.metrics import Histogram

    h = Histogram(max_samples=4)
    h.update(1, 8192)       # a beat round: 8,192 rows, one ring slot
    h.update(3)
    assert (h.count, h.total, h._samples) == (8193, 8195, [1, 3])
    assert h.snapshot()["count"] == 8193


def test_metric_registry_thread_safety():
    from tpuraft.util.metrics import MetricRegistry

    reg = MetricRegistry()
    errs = []

    def hammer():
        try:
            for i in range(2000):
                reg.counter("c")
                reg.update("h", float(i % 50))
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert reg.counters["c"] == 8000
    assert reg.histograms["h"].count == 8000
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 8000


def test_prometheus_text_rendering():
    from tpuraft.util.metrics import Histogram, prometheus_text

    h = Histogram()
    for v in (1.0, 2.0, 3.0):
        h.update(v)
    text = prometheus_text({"kv.batch-rpcs": 7}, {"regions": 3},
                           {"flush_ms": h.snapshot()},
                           labels={"store": "127.0.0.1:6000"})
    assert 'tpuraft_kv_batch_rpcs{store="127.0.0.1:6000"} 7' in text
    assert 'tpuraft_regions{store="127.0.0.1:6000"} 3' in text
    assert '# TYPE tpuraft_kv_batch_rpcs counter' in text
    assert 'quantile="0.99"' in text
    assert 'tpuraft_flush_ms_count{store="127.0.0.1:6000"} 3' in text
    # every sample line parses as name{labels} value
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        name, value = line.rsplit(" ", 1)
        float(value)
        assert name.startswith("tpuraft_")


# ---------------------------------------------------------------------------
# end-to-end: one traced KV put spans client + leader + follower
# ---------------------------------------------------------------------------


async def _kv_cluster():
    from tests.kv_cluster import KVTestCluster
    from tpuraft.rheakv.client import BatchingOptions, RheaKVStore
    from tpuraft.rheakv.pd_client import FakePlacementDriverClient

    c = KVTestCluster(3)
    await c.start_all()
    pd = FakePlacementDriverClient(c.region_template)
    kv = RheaKVStore(pd, c.client_transport(),
                     batching=BatchingOptions(enabled=True))
    await kv.start()
    await c.wait_region_leader(1)
    return c, kv


async def test_traced_put_end_to_end(tmp_path):
    """The acceptance shape: ONE traced put produces >= 7 stage spans
    spanning the client, the leader store and at least one follower —
    joined across 'processes' by the trailing wire context — and the
    export is perfetto-loadable."""
    c, kv = await _kv_cluster()
    try:
        assert await kv.put(b"warm", b"w")        # untraced warm-up
        TRACER.configure(enabled=True, sample_rate=1.0, seed=0)
        assert await kv.put(b"k1", b"v1")
        # follower appends resolve off the ack path: give stragglers a
        # beat to land their spans before asserting
        for _ in range(50):
            spans = TRACER.spans()
            if sum(1 for s in spans
                   if s["name"] == "follower_append") >= 1:
                break
            await asyncio.sleep(0.02)
        TRACER.enabled = False
        spans = TRACER.spans()
        roots = [s for s in spans if s["name"] == "kv_op"]
        assert roots, "root op span missing"
        tid = roots[-1]["trace_id"]
        mine = [s for s in spans if s["trace_id"] == tid]
        assert len(mine) >= 7, [s["name"] for s in mine]
        procs = {s["proc"] for s in mine}
        names = {s["name"] for s in mine}
        assert "client" in procs
        store_procs = {p for p in procs if p.startswith("store:")}
        assert len(store_procs) >= 2, procs  # leader + >=1 follower
        for stage in ("client_queue", "kv_batch_rpc", "srv_validate",
                      "srv_propose", "quorum_commit", "log_flush",
                      "fsm_apply", "follower_append"):
            assert stage in names, (stage, names)
        # every log_flush envelope comes with its two parts: the fsync
        # in the thread that ran it (here an executor's) and from its
        # end to the waiter's resumption on the loop
        for proc in store_procs:
            flush, fsync, wake = (
                [s for s in mine if s["proc"] == proc and s["name"] == n]
                for n in ("log_flush", "log_fsync", "log_wake"))
            assert len(flush) == len(fsync) == len(wake) >= 1, proc
            for f, w in zip(fsync, wake):
                assert w["ts_s"] == pytest.approx(f["ts_s"] + f["dur_s"])
        # and the loop's sections saw the layers the put went through
        layers = {name.split(".")[0] for name in TRACER.section_table()}
        assert {"client", "kv", "raft", "log", "fsm", "rpc"} <= layers
        path = str(tmp_path / "put.json")
        TRACER.export_chrome(path)
        with open(path) as f:
            doc = json.load(f)
        assert any(e["ph"] == "X" and e["name"] == "follower_append"
                   for e in doc["traceEvents"])
    finally:
        TRACER.configure(enabled=False)
        TRACER.reset()
        await kv.shutdown()
        await c.stop_all()


async def test_traced_get_has_fence_and_serve_stages():
    c, kv = await _kv_cluster()
    try:
        assert await kv.put(b"k1", b"v1")
        TRACER.configure(enabled=True, sample_rate=1.0, seed=0)
        TRACER.reset()
        assert await kv.get(b"k1") == b"v1"
        TRACER.enabled = False
        names = {s["name"] for s in TRACER.spans()}
        for stage in ("kv_op", "srv_read_fence", "srv_read_serve"):
            assert stage in names, names
    finally:
        TRACER.configure(enabled=False)
        TRACER.reset()
        await kv.shutdown()
        await c.stop_all()


async def test_untraced_put_records_nothing():
    """Zero-cost sanity: with the tracer disabled, a full serving-path
    op leaves no spans, no staging, no wire context."""
    c, kv = await _kv_cluster()
    try:
        assert await kv.put(b"k", b"v")
        assert TRACER.spans() == []
        assert TRACER._staged == {}
        assert TRACER.counters()["trace_ops_seen"] == 0
    finally:
        await kv.shutdown()
        await c.stop_all()


# ---------------------------------------------------------------------------
# live metrics exposition (metrics_text / admin RPC / HTTP listener)
# ---------------------------------------------------------------------------


async def test_metrics_text_and_describe_metrics_rpc():
    from tpuraft.core.cli_service import CliService

    c, kv = await _kv_cluster()
    try:
        assert await kv.put(b"k", b"v")
        store = next(iter(c.stores.values()))
        text = store.metrics_text()
        assert "tpuraft_kv_batch_rpcs" in text
        assert "tpuraft_regions" in text
        assert f'store="{store.server_id}"' in text
        # counter/gauge semantics: monotonic series are counters,
        # ring occupancy / toggles / EMAs are gauges (a decrease on a
        # Prometheus counter reads as a reset)
        assert "# TYPE tpuraft_recorder_events counter" in text
        assert "# TYPE tpuraft_trace_ring_spans gauge" in text
        assert "# TYPE tpuraft_trace_slow_ema_ms gauge" in text
        # the collector: passes by generation, the young threshold
        assert "# TYPE tpuraft_gc_collections_gen2 counter" in text
        assert "# TYPE tpuraft_gc_threshold_young gauge" in text
        # over the wire: the admin scrape returns the same rendering
        cli = CliService(c.client_transport("admin:0"))
        remote = await cli.describe_metrics(str(store.server_id))
        assert "tpuraft_kv_batch_rpcs" in remote
        assert f'store="{store.server_id}"' in remote
    finally:
        await kv.shutdown()
        await c.stop_all()


def test_the_collectors_passes_and_young_threshold_are_exported():
    """A scrape and a ``describe()`` show how often each generation runs
    and the young threshold in force (a serving store raises it), with
    tracing off: the counts are CPython's own, monotonic across a forced
    full collection."""
    assert not TRACER.enabled
    first = TRACER.counters()
    names = [f"gc_collections_gen{g}" for g in range(3)]
    assert all(isinstance(first[n], int) for n in names)
    gc.collect()
    then = TRACER.counters()
    assert all(then[n] >= first[n] for n in names)
    assert then["gc_collections_gen2"] >= first["gc_collections_gen2"] + 1
    own = gc.get_threshold()
    try:
        gc.set_threshold(12_345, *own[1:])
        assert TRACER.gauges()["gc_threshold_young"] == 12_345
        assert "gc_young=12345" in TRACER.describe()
    finally:
        gc.set_threshold(*own)
    assert TRACER.gauges()["gc_threshold_young"] == own[0]


async def test_metrics_text_renders_the_section_table():
    """Loop share by layer for an operator's Prometheus, no profiler:
    calls, busy and self seconds per section, as counters."""
    c, kv = await _kv_cluster()
    try:
        TRACER.configure(enabled=True, sample_rate=0.0, seed=0)
        assert await kv.put(b"k", b"v")
        assert await kv.get(b"k") == b"v"
        TRACER.enabled = False
        store = next(iter(c.stores.values()))
        store.opts.metrics_cache_ttl_ms = 0
        text = store.metrics_text()
        for section in ("kv_batch", "raft_propose", "log_stage",
                        "fsm_apply", "rpc_inproc", "client_send"):
            for what in ("calls", "busy_seconds", "self_seconds"):
                name = f"tpuraft_trace_section_{what}_{section}"
                assert f"# TYPE {name} counter" in text, name
        calls = [ln for ln in text.splitlines()
                 if ln.startswith("tpuraft_trace_section_calls_kv_batch{")]
        assert len(calls) == 1 and float(calls[0].rsplit(" ", 1)[1]) >= 2
        # switched on from the running loop, so the dispatch was framed:
        # the turns' counters and the handles' sections, by owner
        for name in ("trace_turns", "trace_turn_handles", "trace_turns_long",
                     "trace_section_self_seconds_idle_select",
                     "trace_section_calls_turn_step__StoreSender:_send_safe"):
            assert f"# TYPE tpuraft_{name} counter" in text, name
        turns = [ln for ln in text.splitlines()
                 if ln.startswith("tpuraft_trace_turn_handles{")]
        assert len(turns) == 1 and float(turns[0].rsplit(" ", 1)[1]) >= 10
    finally:
        TRACER.configure(enabled=False)
        TRACER.reset()
        await kv.shutdown()
        await c.stop_all()


async def test_metrics_http_listener(tmp_path):
    """The optional stdlib HTTP listener serves Prometheus text on
    GET /metrics (port 0 = ephemeral bind)."""
    import urllib.error
    import urllib.request

    from tpuraft.rheakv.metadata import Region
    from tpuraft.rheakv.store_engine import StoreEngine, StoreEngineOptions
    from tpuraft.rpc.transport import InProcNetwork, InProcTransport, RpcServer

    net = InProcNetwork()
    ep = "127.0.0.1:6900"
    server = RpcServer(ep)
    net.bind(server)
    opts = StoreEngineOptions(
        server_id=ep,
        initial_regions=[Region(id=1, peers=[ep])],
        election_timeout_ms=200,
        metrics_port=0)
    store = StoreEngine(opts, server, InProcTransport(net, ep))
    await store.start()
    try:
        assert store.metrics_http_port
        url = f"http://127.0.0.1:{store.metrics_http_port}/metrics"
        loop = asyncio.get_running_loop()
        body = await loop.run_in_executor(
            None, lambda: urllib.request.urlopen(url, timeout=5).read())
        text = body.decode()
        assert "tpuraft_regions" in text
        assert "# TYPE" in text
        # non-metrics paths 404
        with pytest.raises(urllib.error.HTTPError):
            await loop.run_in_executor(
                None,
                lambda: urllib.request.urlopen(
                    f"http://127.0.0.1:{store.metrics_http_port}/nope",
                    timeout=5).read())
    finally:
        await store.shutdown()
