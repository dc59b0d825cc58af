"""Trace plane: per-op spans + the protocol flight recorder.

Two observability primitives the rest of the system feeds:

**Tracer** — a cheap per-op span recorder for the serving hot path.
Every stage the benches used to probe externally (client queue →
``_StoreSender`` batch → ``kv_command_batch`` RPC → server validate →
propose → log flush → quorum ack → FSM apply → client ack) emits a span
when tracing is enabled; disabled, every call site costs ONE attribute
branch (``if _TRACE.enabled``).  The contract is the benchmark's:
``BENCHMARK.json`` bounds the end-to-end metrics with tracing off, and
``PERF.md`` states what a traced run costs against an untraced one.
Retention is two-tier: a seeded probabilistic sample keeps a
deterministic fraction of ops end to end (full stage spans, context on
the wire), and an adaptive slow-op trigger force-retains any op slower
than a rolling p99 EMA even when the sampler skipped it — root span
with duration and a ``slow`` flag, because the tail is exactly what
you want attributed but universal candidacy must cost one clock read
per op, not a span pipeline.
Spans live in a bounded ring and export as Chrome trace-event JSON
(``chrome://tracing`` / perfetto-loadable) via soak ``--trace``.

Op spans are WAITING intervals on ``perf_counter``: they overlap and
say nothing of what the loop thread runs meanwhile.  **Sections** do:
a section is a synchronous stretch of the loop thread (``enter`` /
``leave`` on that thread, never across an ``await``), named
``<layer>.<what>``.  Each is a ``jax.profiler.TraceAnnotation`` — so it
lands on the profiler's host line, on the device trace's clock — and
adds to its name's calls, inclusive seconds and SELF seconds (inclusive
minus what child sections cover).  Once a second the tracer rolls the
self seconds of each section and of each layer prefix into the ring as
``loop.<name>`` records, with ``loop.cpu`` (the thread's CPU seconds
over the same second) beside them.  A ``tpuraft.trace_anchor``
annotation carries one simultaneous (``perf_counter_ns``, ``time_ns``)
pair, so the op spans can be laid over the profiler's trace offline.

Most of what the loop thread runs opens no section: asyncio's task
steps, ``call_soon`` callbacks, future resolutions, timers.  So while
tracing is on, and was switched on from a running loop, the tracer
frames the loop's own dispatch (``_arm_turns``): every handle the loop
runs is a frame ``turn.<kind>.<owner>`` on the section stack
(accumulators only), the selector's wait is the section
``idle.select``, a collector pass on the loop thread is ``gc.gen<n>``,
and one iteration's run phase is a *turn* (counted; a ``turn`` record
from 1 ms up).  The sections authors wrote open inside a handle's
frame as its children, so a frame's self seconds are what ran
"outside any section", by owner, and with ``loop.rest`` every second
of the thread has a name.  Nothing is patched in a process that never
enables tracing, and the hooks restore themselves at the first handle
that finds ``enabled`` false.

A trace context (one i64: ``seq << 1 | sampled``) rides the KV batch
item and the ``AppendEntriesRequest`` as TRAILING defaulted wire fields
— old decoders stop before them — so follower-side append/flush spans
join the same trace across processes.  A remote process records a
context-carrying span only when the sampled bit is set (the slow-op
trigger is a client-local decision; its staging buffer cannot span
processes).

**FlightRecorder** — a per-process bounded ring of protocol events
(elections, term changes, conf-change stage transitions, quiesce/wake,
leadership evacuations, health transitions, fence-round failures, shed
bounces) that is ALWAYS on: appends are O(1) into a deque and the rare
events it records are exactly the ones you need after an incident.
``describe()`` renders the tail for SIGUSR2 dumps (util/describer);
``note_anomaly`` snapshots the ring on a detected anomaly (SICK
transition, election storm, soak oracle failure) so the state *leading
up to* the incident survives ring churn.
"""

from __future__ import annotations

import asyncio.events as _events
import asyncio.tasks as _tasks
import contextlib
import functools
import gc
import json
import random
import threading
import time
import types
from collections import deque
from typing import Optional, Union

from tpuraft.util import describer

# perf_counter is the span clock (monotonic, ns resolution); one wall
# anchor taken at configure() maps it to absolute µs for the export
_pc = time.perf_counter
_thread_time = time.thread_time
_get_ident = threading.get_ident

# every roll-up record rides the one unconditional (odd) context
_LOOP_TID = 1
_LOOP_PROC = "loop"
ANCHOR_EVENT = "tpuraft.trace_anchor"
# a roll-up that finds this many whole seconds gone by was idle, not
# busy: one record takes the lot and the buckets realign
_MAX_SPREAD_BUCKETS = 8
# what frames the loop's own dispatch: none is a section an author wrote,
# so loop.cpu's busy_s and the loop_pct.* layers leave them out
_FRAME_LAYERS = ("turn", "idle", "gc")
_SELECT = "idle.select"
_GC_NAMES = ("gc.gen0", "gc.gen1", "gc.gen2")
# written every second while the dispatch is framed, 0.0 where nothing ran
_EVERY_SECOND = ("turn.step", "turn.callback", "turn.timer", "turn",
                 _SELECT, "gc")
_TURN_RECORD_S = 0.001      # a turn this long leaves a record
_TURN_LONG_S = 0.020        # one tick period: counted as long
# the tracer whose frames are on the interpreter's dispatch (Handle._run
# is the process's, so this is too), or None: nothing is patched
_framing: Optional["Tracer"] = None


# graftcheck: loop-confined — created and consumed only by the Tracer
# (itself loop-confined below); executor threads never hold one
class _Staged:
    """One locally-originated op, staged until end_op decides retention
    (sampled => always; slow => force-retained).  Only SAMPLED ops
    buffer child spans — an unsampled op is duration-only (``spans``
    stays None), so the universal slow-op candidacy costs one clock
    read and two dict ops per op, not a span pipeline (the overhead
    gate's 5% budget is the contract)."""

    __slots__ = ("name", "proc", "t0", "sampled", "spans")

    def __init__(self, name: str, proc: str, t0: float, sampled: bool):
        self.name = name
        self.proc = proc
        self.t0 = t0
        self.sampled = sampled
        self.spans: Optional[list] = [] if sampled else None


# graftcheck: loop-confined — begin_op/end_op/span all run on the
# owning process's event loop (executor threads measure t0/t1 but the
# record call happens after the await returns); the ring deque is
# additionally safe for the exposition thread's len()/iteration
class Tracer:
    """Bounded-ring span recorder with seeded sampling + slow-op
    force-retention.  One module-level instance per process
    (:data:`TRACER`); components tag spans with their own ``proc``
    identity so an in-proc multi-store bench still attributes stages to
    client / leader store / follower store."""

    def __init__(self) -> None:
        self.enabled = False
        self.sample_rate = 0.01
        self._rng = random.Random(0)
        self._ring: deque = deque(maxlen=4096)
        self._staged: dict[int, _Staged] = {}
        self._max_staged = 1024
        self._next_seq = 1
        self._wall0 = time.time()
        self._pc0 = _pc()
        # adaptive slow-op trigger: asymmetric EMA tracking ~p99 of op
        # durations; an op above the estimate is retained even when the
        # sampler skipped it.  Warmup gate: the estimate means nothing
        # until it has seen a population.
        self.slow_trigger = True
        self._p99_ema = 0.0
        self._q_alpha = 0.05
        self._durs_seen = 0
        self._warmup = 100
        # counters (exposition / tests)
        self.ops_seen = 0
        self.ops_sampled = 0
        self.ops_slow_retained = 0
        self.ops_dropped = 0
        self.spans_recorded = 0
        # loop sections: open frames innermost last ([name, annotation,
        # child seconds, t0])
        self._sec_stack: list = []
        # None = not looked for yet, False = no JAX here, else the
        # TraceAnnotation class (imported when a section first enters)
        self._annotation: Union[None, bool, type] = None
        # the loop's dispatch, framed while tracing is on (_arm_turns):
        # the loop whose handles, selector and collector passes are
        # frames on the stack above (None = nothing is patched)
        self._turn_loop = None
        self._turn_run = None            # Handle._run as it was found
        self._turn_t0 = 0.0              # the open turn's start (0 = none)
        self._turn_handles = 0           # handles run in the open turn
        # the frame or section with most self seconds in the open turn;
        # inf while no turn is framed, so that no exit ever claims it
        self._turn_top = ""
        self._turn_top_s = float("inf")
        # kind -> {qualname: the accumulator of turn.<kind>.<qualname>}
        self._turn_accs: dict = {"step": {}, "timer": {}, "callback": {}}
        self._gc_frame: Optional[list] = None
        self._rolling = False
        self.turns = 0
        self.turn_handles = 0
        self.turns_long = 0
        self._arm_sections()

    # -- lifecycle -----------------------------------------------------------

    def configure(self, enabled: bool = True, sample_rate: float = 0.01,
                  seed: int = 0, ring: int = 4096,
                  slow_trigger: bool = True) -> "Tracer":
        """(Re)arm the tracer.  Seeded: two tracers configured alike
        sample the same op sequence — bench A/B runs compare like for
        like."""
        self.enabled = enabled
        self.sample_rate = sample_rate
        self._rng = random.Random(seed)
        if ring != self._ring.maxlen:
            self._ring = deque(self._ring, maxlen=ring)
        self.slow_trigger = slow_trigger
        # NOTE: the wall/perf anchor is NOT re-taken here — spans store
        # offsets relative to the anchor, so re-anchoring mid-process
        # would shift every already-recorded span in the export
        if enabled:
            self._arm_sections()
        else:
            self._disarm_turns()
        return self

    def _arm_sections(self) -> None:
        """Accumulators and buckets start here.  The thread that arms is
        taken for the loop thread until a section says otherwise."""
        self._close_open_sections()
        # per name [calls, inclusive s, self s, the name]
        self._sec_acc: dict[str, list] = {}
        self._sec_tid = _get_ident()
        # the once-a-second roll-up: the open bucket's start on both
        # clocks, the accumulators as the last roll-up left them, and
        # the self seconds by name that the last roll-up took ahead of
        # (open frames) or left behind (past the bucket's end) them
        self._bucket_t0 = _pc()
        self._bucket_cpu0 = _thread_time()
        self._bucket_snap: dict[str, tuple] = {}
        self._bucket_carry: dict[str, float] = {}
        self.anchor = (time.perf_counter_ns(), time.time_ns())
        self._anchor_noted = False
        self._arm_turns()

    def _close_open_sections(self) -> None:
        while self._sec_stack:
            frame = self._sec_stack.pop()
            if frame[1] is not None:
                frame[1].__exit__(None, None, None)
                frame[1] = None

    # -- the loop's dispatch, framed (what runs outside any section) ----------

    def _arm_turns(self) -> None:
        """Part of "loop sections on": armed from a running loop, every
        handle that loop runs, every wait in its selector and every
        collector pass on its thread becomes a frame on the section
        stack.  ONE wrapper at ``asyncio.events.Handle._run`` (every
        ready callback goes through it, the C ``Task``'s steps and
        wake-ups too; the loop is whoever created it, so a subclass is
        no option), ``select`` shadowed on the loop's selector, one
        ``gc.callbacks`` hook.  Arming again starts the counts over and
        stacks nothing."""
        global _framing
        self._turn_t0 = 0.0
        self._turn_handles = 0
        for accs in self._turn_accs.values():
            accs.clear()            # they were the old accumulators'
        self._gc_frame = None
        self.turns = self.turn_handles = self.turns_long = 0
        loop = _events._get_running_loop() if self.enabled else None
        if loop is not self._turn_loop:
            self._disarm_turns()
        if loop is None:
            return
        if self._turn_loop is None:
            if _framing is not None:
                _framing._disarm_turns()
            _framing = self
            self._turn_loop = loop
            self._turn_run = _events.Handle._run
            _events.Handle._run = self._framed_run(self._turn_run)
            selector = getattr(loop, "_selector", None)
            if selector is not None:
                selector.select = self._framed_select(selector.select)
            gc.callbacks.append(self._on_gc)
            if self._annotation is None:
                self._find_annotation()     # not inside the first frame
        # a pass must never be the first of its name while a roll-up
        # walks the accumulators: its rows are there from the start
        for name in _GC_NAMES:
            self._acc_of(name)
        self._turn_top, self._turn_top_s = "", 0.0

    def _disarm_turns(self) -> None:
        """Give the interpreter its dispatch back; what was counted
        stays."""
        global _framing
        if self._turn_loop is None:
            return
        _events.Handle._run = self._turn_run
        selector = getattr(self._turn_loop, "_selector", None)
        if selector is not None:
            vars(selector).pop("select", None)
        with contextlib.suppress(ValueError):
            gc.callbacks.remove(self._on_gc)
        self._turn_loop = self._turn_run = None
        self._turn_t0 = 0.0
        self._turn_top_s = float("inf")
        if _framing is self:
            _framing = None

    def _framed_run(self, run):
        """``Handle._run`` with a frame around it: the handle's self
        seconds (its run less the sections opened in it) go to
        ``turn.<kind>.<owner>``.  No annotation: tens of thousands a
        second would swamp the xplane.  Every handle of the process
        pays this while tracing is on (PERF.md section 6, PR 39, has
        the price), so the two common kinds (a C task's step, a plain
        ``Handle``) find their accumulator in line and the exit does
        :meth:`_pop`'s sums in line."""
        stack = self._sec_stack
        loop = self._turn_loop
        step_accs = self._turn_accs["step"]
        plain_accs = self._turn_accs["callback"]
        # handles never nest, so one frame serves them all; and a new
        # list between the clock read and the push could start a
        # collector pass that is in the handle's seconds AND its own
        frame = ["", None, 0.0, 0.0]

        def framed(handle):
            if not self.enabled:
                # the harness just clears the flag: the first handle
                # after that puts everything back
                self._disarm_turns()
                return run(handle)
            if handle._loop is not loop:
                return run(handle)          # another thread's loop
            t0 = _pc()      # the look-up below is the handle's cost
            cb = handle._callback
            task = getattr(cb, "__self__", None)
            acc = None
            if task.__class__ is _tasks.Task:
                coro = task.get_coro()
                if coro.__class__ is types.CoroutineType:
                    acc = step_accs.get(coro.__qualname__)
            elif handle.__class__ is _events.Handle:
                acc = plain_accs.get(getattr(cb, "__qualname__", None))
            if acc is None:
                acc = self._turn_acc(handle)
            frame[0] = acc[3]
            frame[2] = 0.0
            frame[3] = t0
            stack.append(frame)
            self._turn_handles += 1
            try:
                return run(handle)
            finally:
                t1 = _pc()
                if stack and stack[-1] is frame:
                    del stack[-1]
                    dur = t1 - t0
                    own = dur - frame[2]
                    acc[0] += 1
                    acc[1] += dur
                    acc[2] += own
                    if own > self._turn_top_s:
                        self._turn_top, self._turn_top_s = acc[3], own
                    if stack:
                        stack[-1][2] += dur
                    if t1 - self._bucket_t0 >= 1.0 and self.enabled:
                        self._roll(t1, frame)
                else:       # a leave was skipped, or reset() ran under it
                    self.leave(frame, t1)

        return framed

    def _turn_acc(self, handle) -> list:
        """The accumulator of ``turn.step.<coroutine>`` for a task's
        step or wake-up, else of ``turn.timer.`` / ``turn.callback.
        <callable>``: qualnames with ``:`` for ``.`` (the name keeps
        three dotted parts), built once a qualname (a code object would
        hash its whole content on every look-up; a string does not);
        ``?`` for what has no name."""
        cb = handle._callback
        task = getattr(cb, "__self__", None)
        if isinstance(task, _tasks.Task):
            kind = "step"
            owner = getattr(task.get_coro(), "__qualname__", None)
        else:
            kind = "timer" if type(handle) is _events.TimerHandle \
                else "callback"
            while isinstance(cb, functools.partial):
                cb = cb.func
            owner = getattr(getattr(cb, "__func__", cb), "__qualname__",
                            None)
        accs = self._turn_accs[kind]
        acc = accs.get(owner)
        if acc is None:
            shown = owner if isinstance(owner, str) else "?"
            acc = accs[owner] = self._acc_of(
                f"turn.{kind}.{shown.replace('.', ':')}")
        return acc

    def _framed_select(self, select):
        """The selector's ``select`` inside the section ``idle.select``
        (an annotation: on the profiler's host line it parts "the host
        slept" from "the host was busy" inside a device gap).  Its call
        ends a turn and its return begins the next."""

        def framed(timeout=None):
            if not self.enabled:
                self._disarm_turns()
                return select(timeout)
            self._end_turn(_pc())
            frame = self.enter(_SELECT)     # its own clock read: a pass
            # that the turn's record began is not the selector's too
            try:
                return select(timeout)
            finally:
                t1 = _pc()
                if frame is not None:
                    self.leave(frame, t1)
                self._turn_top, self._turn_top_s = "", 0.0
                self._turn_handles = 0
                self._turn_t0 = t1

        return framed

    def _end_turn(self, now: float) -> None:
        """Count the turn that ends here; from 1 ms up it leaves a
        record with the frame or section that took most of it, so a
        slow round can be looked up: what ran in the turn a fence, a
        tick and an arrival all waited behind."""
        t0 = self._turn_t0
        if not t0:
            return
        self.turns += 1
        self.turn_handles += self._turn_handles
        dur = now - t0
        if dur < _TURN_RECORD_S:
            return
        if dur >= _TURN_LONG_S:
            self.turns_long += 1
        self._emit(_LOOP_TID, "turn", _LOOP_PROC, t0, now,
                   {"handles": self._turn_handles, "top": self._turn_top,
                    "top_s": self._turn_top_s})

    def _on_gc(self, phase: str, info: dict) -> None:
        """``gc.callbacks``: a pass on the loop thread is a child
        section of whatever allocated, so that section's self seconds no
        longer hold it.  gen0 is accumulators only; gen1 and gen2, rare
        and long, are annotations and one ring record a pass."""
        if _get_ident() != self._sec_tid:
            return
        stack = self._sec_stack
        if phase == "start":
            if not self.enabled:
                return
            gen, ann = info["generation"], None
            if gen and self._annotation:    # never imported from a pass
                ann = self._annotation(_GC_NAMES[gen])
                ann.__enter__()
            self._gc_frame = [_GC_NAMES[gen], ann, 0.0, _pc()]
            stack.append(self._gc_frame)
            return
        frame, self._gc_frame = self._gc_frame, None
        if frame is None:
            return
        t1 = _pc()
        # an exit like any other: a pass that ends past the second's
        # end brings the roll-up, which splits it there
        self.leave(frame, t1)
        if info["generation"]:
            self._emit(_LOOP_TID, frame[0], _LOOP_PROC, frame[3], t1,
                       {"collected": info["collected"],
                        "uncollectable": info["uncollectable"]})

    @types.coroutine
    def drive(self, name: str, coro):
        """``await TRACER.drive(name, coro)``: await ``coro`` with
        section ``name`` open around each of its synchronous stretches
        and closed across every suspension, for a path whose awaits lie
        in other modules (a replica's boot).  The caller tests
        ``enabled`` first, as for :meth:`enter`."""
        value = exc = None
        while True:
            frame = self.enter(name) if self.enabled else None
            try:
                if exc is None:
                    waits_for = coro.send(value)
                else:
                    waits_for = coro.throw(exc)
            except StopIteration as done:
                return done.value
            finally:
                if frame is not None:
                    self.leave(frame)
            try:
                value, exc = (yield waits_for), None
            except BaseException as e:  # noqa: BLE001 — handed to coro
                value, exc = None, e

    def reset(self) -> None:
        """Drop all recorded/staged spans and counters (test isolation)."""
        self._ring.clear()
        self._staged.clear()
        self._wall0 = time.time()
        self._pc0 = _pc()
        self._p99_ema = 0.0
        self._durs_seen = 0
        self.ops_seen = self.ops_sampled = 0
        self.ops_slow_retained = self.ops_dropped = 0
        self.spans_recorded = 0
        self._arm_sections()

    # -- loop sections (what the loop thread runs) ---------------------------

    def enter(self, name: str, now: float = 0.0) -> Optional[list]:
        """Open section ``name`` on the loop thread; the caller tests
        ``enabled`` first (``sec = T.enter(n) if T.enabled else None``)
        and hands the frame back to :meth:`leave` before any ``await``.
        ``now`` is a ``perf_counter`` reading the caller already took.
        None from a thread other than the loop's (an executor):
        sections are loop-confined."""
        if _get_ident() != self._sec_tid:
            if self._sec_stack or self._sec_acc:
                return None
            # armed from another thread than the one that runs sections
            self._sec_tid = _get_ident()
            self._bucket_t0 = _pc()
            self._bucket_cpu0 = _thread_time()
        cls = self._annotation
        if cls is None:
            cls = self._find_annotation()
        ann = None
        if cls:
            if not self._anchor_noted:
                self._note_anchor(cls)
            ann = cls(name)
            ann.__enter__()
        # on the stack before its clock is read: a collector pass that
        # the new list starts is the enclosing frame's child, not in
        # this section's seconds and its own
        frame = [name, ann, 0.0, 0.0]
        self._sec_stack.append(frame)
        frame[3] = now or _pc()
        return frame

    def leave(self, frame: list, now: float = 0.0) -> None:
        """Close a section :meth:`enter` opened, also after ``enabled``
        was cleared under it."""
        t1 = now or _pc()
        stack = self._sec_stack
        if not stack or stack[-1] is not frame:
            if frame not in stack:
                # reset() or configure() ran under it: already closed
                return
            while stack[-1] is not frame:      # a leave was skipped above
                self._pop(stack.pop(), t1)
        self._pop(stack.pop(), t1)
        if t1 - self._bucket_t0 >= 1.0 and self.enabled:
            self._roll(t1, frame)

    @contextlib.contextmanager
    def section(self, name: str):
        """``with TRACER.section(name):`` around a stretch with no
        ``await`` in it, for paths too cold to spell enter and leave
        out (elections); nothing happens while tracing is off."""
        frame = self.enter(name) if self.enabled else None
        try:
            yield
        finally:
            if frame is not None:
                self.leave(frame)

    def switch(self, frame: list, name: str, now: float = 0.0) -> list:
        """Leave ``frame`` and enter ``name`` at one instant."""
        now = now or _pc()
        self.leave(frame, now)
        return self.enter(name, now)

    def _pop(self, frame: list, t1: float) -> None:
        name, ann, child, t0 = frame
        if ann is not None:
            ann.__exit__(None, None, None)
            frame[1] = None
        dur = t1 - t0
        own = dur - child
        acc = self._sec_acc.get(name) or self._acc_of(name)
        acc[0] += 1
        acc[1] += dur
        acc[2] += own
        if own > self._turn_top_s:
            self._turn_top, self._turn_top_s = name, own
        if self._sec_stack:
            self._sec_stack[-1][2] += dur

    def _acc_of(self, name: str) -> list:
        """``name``'s accumulator, made on first use."""
        acc = self._sec_acc.get(name)
        if acc is None:
            acc = self._sec_acc[name] = [0, 0.0, 0.0, name]
        return acc

    def _find_annotation(self):
        """Import JAX's annotation when a section first enters.  A
        process with no JAX (a store on the numpy twin) still traces:
        accumulators and roll-ups, no annotation."""
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            self._annotation = False
        else:
            self._annotation = TraceAnnotation
        return self._annotation

    def _note_anchor(self, cls) -> None:
        """One instant on three clocks: the annotation's own timestamp
        is the profiler's, its metadata the other two."""
        self._anchor_noted = True
        self.anchor = pair = (time.perf_counter_ns(), time.time_ns())
        with cls(ANCHOR_EVENT, perf_counter_ns=pair[0], time_ns=pair[1]):
            pass

    def _roll(self, now: float, popped: Optional[list] = None) -> None:
        """:meth:`_roll_buckets`, but never from inside itself: a
        collector pass that ends inside a roll-up has added to its
        accumulator and waits for the next exit."""
        if self._rolling:
            return
        self._rolling = True
        try:
            self._roll_buckets(now, popped)
        finally:
            self._rolling = False

    def _roll_buckets(self, now: float, popped: Optional[list]) -> None:
        """Close the bucket(s) that ended before ``now``: one record per
        section, per layer (the name's first part) and, for a name of
        three parts, per first two, with the self seconds spent there,
        and ``loop.cpu`` with the thread's CPU seconds.  Whole seconds
        with no section exit in them share the delta evenly.  ``popped``
        is the frame whose exit brought the roll-up: no other frame has
        closed since the buckets' end, so what each open frame and that
        one ran before it and after it is known, and a bucket holds the
        self seconds of its own wall seconds, no more.  While the loop's
        dispatch is framed the frames' rows are written every second,
        and ``loop.rest`` takes what no frame covered: the loop's own
        bookkeeping between handles."""
        cpu = _thread_time()
        whole = int(now - self._bucket_t0)
        n_buckets = whole if whole <= _MAX_SPREAD_BUCKETS else 1
        end = self._bucket_t0 + whole
        leaf: dict[str, list] = {}
        snap = self._bucket_snap
        for name, acc in self._sec_acc.items():
            n0, busy0, self0 = snap.get(name, (0, 0.0, 0.0))
            if acc[0] != n0:
                snap[name] = (acc[0], acc[1], acc[2])
                leaf[name] = [acc[0] - n0, acc[1] - busy0, acc[2] - self0]
        # the accumulators know a frame once it has closed: take the
        # open frames' seconds up to ``end`` ahead of them, leave the
        # popped frame's seconds past ``end`` to the next bucket, and
        # settle what the last roll-up took or left
        for name, part in self._bucket_carry.items():
            leaf.setdefault(name, [0, 0.0, 0.0])[2] -= part
        carry: dict[str, float] = {}
        above, in_parent = now, 0.0
        for frame in ([popped] if popped is not None else []) \
                + self._sec_stack[::-1]:
            name, t0 = frame[0], frame[3]
            part = max(end, t0) - max(end, above)   # on top past ``end``
            if frame is popped:
                in_parent = now - t0
            else:
                part += (above - t0) - (frame[2] - in_parent)
                in_parent = 0.0
            if part:
                carry[name] = carry.get(name, 0.0) + part
                leaf.setdefault(name, [0, 0.0, 0.0])[2] += part
            above = t0
        self._bucket_carry = carry
        rows: dict[str, list] = {}
        total_n, named_self, all_self = 0, 0.0, 0.0
        for name, row in leaf.items():
            rows[name] = row
            parts = name.split(".")
            for k in range(1, min(len(parts), 3)):
                prefix = rows.setdefault(".".join(parts[:k]), [0, 0.0, 0.0])
                for f in range(3):
                    prefix[f] += row[f]
            all_self += row[2]
            if parts[0] not in _FRAME_LAYERS:
                total_n += row[0]
                named_self += row[2]
        if self._turn_loop is not None:
            for name in _EVERY_SECOND:
                rows.setdefault(name, [0, 0.0, 0.0])
            rows["rest"] = [0, 0.0, whole - all_self]
        # busy_s of loop.cpu: the wall seconds the authors' sections
        # account for
        rows["cpu"] = [total_n, named_self, cpu - self._bucket_cpu0]
        for k in range(n_buckets):
            rel0 = self._bucket_t0 + k - self._pc0
            for name, (n, busy, self_s) in rows.items():
                self._ring.append(
                    (_LOOP_TID, "loop." + name, _LOOP_PROC, rel0,
                     max(0.0, self_s / n_buckets),
                     {"n": n / n_buckets if n_buckets > 1 else n,
                      "busy_s": busy / n_buckets}))
            self.spans_recorded += len(rows)
        self._bucket_t0 += whole
        self._bucket_cpu0 = cpu

    def section_table(self) -> dict:
        """name -> (calls, inclusive seconds, self seconds) since the
        tracer was armed."""
        return {name: tuple(acc[:3])
                for name, acc in list(self._sec_acc.items())}

    # -- op lifecycle (locally-originated traces) ----------------------------

    def begin_op(self, name: str = "op", proc: str = "client") -> int:
        """Open one op's trace; returns its context (0 = not traced —
        tracing disabled, or the staging buffer is full and the sampler
        skipped it).  The context's low bit is the sampled flag remote
        processes key retention on."""
        if not self.enabled:
            return 0
        self.ops_seen += 1
        sampled = self._rng.random() < self.sample_rate
        if not sampled and (not self.slow_trigger
                            or len(self._staged) >= self._max_staged):
            return 0
        tid = (self._next_seq << 1) | (1 if sampled else 0)
        self._next_seq += 1
        if sampled:
            self.ops_sampled += 1
        self._staged[tid] = _Staged(name, proc, _pc(), sampled)
        while len(self._staged) > self._max_staged:
            # evict the oldest abandoned op (an end_op that never came)
            self._staged.pop(next(iter(self._staged)))
        return tid

    def end_op(self, tid: int, **args) -> float:
        """Close an op: emit its root span and decide retention.
        Returns the op duration in seconds (0.0 if untraced)."""
        if not tid:
            return 0.0
        st = self._staged.pop(tid, None)
        if st is None:
            return 0.0
        t1 = _pc()
        dur = t1 - st.t0
        slow = self._note_dur(dur)
        if st.sampled or slow:
            if slow and not st.sampled:
                # force-retained by the slow trigger: the root span
                # (with duration + slow flag) is what survives — child
                # attribution exists only for sampled ops
                self.ops_slow_retained += 1
                args = dict(args, slow=True)
            self._emit(tid, st.name, st.proc, st.t0, t1, args)
            for span in st.spans or ():
                self._ring.append(span)
                self.spans_recorded += 1
        else:
            self.ops_dropped += 1
        return dur

    def abandon_op(self, tid: int) -> None:
        """An op that will never end (an election somebody else won):
        its staging goes, nothing is recorded."""
        self._staged.pop(tid, None)

    def span(self, tid: int, name: str, t0: float, t1: float,
             proc: str = "", **args) -> None:
        """Record one stage span of trace ``tid`` covering perf_counter
        interval [t0, t1].  Locally-staged traces buffer (retention
        decided at end_op); a remote context records iff sampled."""
        if not tid:
            return
        st = self._staged.get(tid)
        if st is not None:
            if st.spans is not None:
                st.spans.append(self._event(tid, name, proc or st.proc,
                                            t0, t1, args))
        elif tid & 1:
            self._emit(tid, name, proc or "remote", t0, t1, args)

    # -- internals -----------------------------------------------------------

    def _note_dur(self, dur: float) -> bool:
        """Feed the rolling p99 estimate; True = this op is slow (above
        the warmed estimate)."""
        self._durs_seen += 1
        if self._p99_ema == 0.0:
            self._p99_ema = dur
            return False
        slow = (self.slow_trigger and self._durs_seen > self._warmup
                and dur > self._p99_ema)
        # asymmetric quantile EMA: rise on the 1% above, fall 99x slower
        # on the mass below — settles near the p99 of the stream
        if dur > self._p99_ema:
            self._p99_ema += self._q_alpha * (dur - self._p99_ema)
        else:
            self._p99_ema -= (self._q_alpha / 99.0) * (self._p99_ema - dur)
        return slow

    def _event(self, tid: int, name: str, proc: str, t0: float, t1: float,
               args: dict) -> tuple:
        return (tid, name, proc, t0 - self._pc0, max(0.0, t1 - t0),
                args or None)

    def _emit(self, tid: int, name: str, proc: str, t0: float, t1: float,
              args: dict) -> None:
        self._ring.append(self._event(tid, name, proc, t0, t1, args))
        self.spans_recorded += 1

    # -- export / introspection ---------------------------------------------

    def spans(self, tid: Optional[int] = None) -> list[dict]:
        """Retained spans as dicts (newest last); optionally one trace's."""
        out = []
        for ev_tid, name, proc, rel0, dur, args in list(self._ring):
            if tid is not None and ev_tid != tid:
                continue
            out.append({"trace_id": ev_tid, "seq": ev_tid >> 1,
                        "name": name, "proc": proc,
                        "ts_s": rel0, "dur_s": dur,
                        "args": dict(args) if args else {}})
        return out

    def chrome_events(self) -> list[dict]:
        """Chrome trace-event ("X" complete events + process_name
        metadata) — the format chrome://tracing and perfetto load.  The
        first event states the clock anchor; the ``loop`` process holds
        one row per roll-up name, a bar a second."""
        pids: dict[str, int] = {}
        loop_rows: dict[str, int] = {}
        events: list[dict] = [{
            "ph": "M", "name": ANCHOR_EVENT, "pid": 0, "tid": 0,
            "args": {"perf_counter_ns": self.anchor[0],
                     "time_ns": self.anchor[1]}}]
        for ev_tid, name, proc, rel0, dur, args in list(self._ring):
            pid = pids.get(proc)
            if pid is None:
                pid = pids[proc] = len(pids) + 1
                events.append({"ph": "M", "name": "process_name",
                               "pid": pid, "tid": 0,
                               "args": {"name": proc}})
            row = ev_tid >> 1
            if proc == _LOOP_PROC:
                row = loop_rows.get(name)
                if row is None:
                    row = loop_rows[name] = len(loop_rows) + 1
                    events.append({"ph": "M", "name": "thread_name",
                                   "pid": pid, "tid": row,
                                   "args": {"name": name}})
            ev = {"ph": "X", "name": name, "pid": pid,
                  "tid": row,
                  "ts": round((self._wall0 + rel0) * 1e6, 3),
                  "dur": round(dur * 1e6, 3),
                  "args": {"trace_id": ev_tid, **(args or {})}}
            events.append(ev)
        return events

    def export_chrome(self, path: str) -> int:
        """Write the ring as a perfetto-loadable JSON file; returns the
        number of span events written."""
        events = self.chrome_events()
        with open(path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
        return sum(1 for e in events if e["ph"] == "X")

    def counters(self) -> dict:
        """Monotonic series only (Prometheus 'counter' semantics —
        rate()/increase() must never see a decrease; re-arming the
        tracer reads as a counter reset).  The section table rides
        along, so a scrape sees the loop's share by layer with no
        profiler attached: ``rate(trace_section_self_seconds_<name>)``
        is that section's share of the loop thread."""
        out = {
            "trace_ops_seen": self.ops_seen,
            "trace_ops_sampled": self.ops_sampled,
            "trace_ops_slow_retained": self.ops_slow_retained,
            "trace_ops_dropped": self.ops_dropped,
            "trace_spans_recorded": self.spans_recorded,
            # loop iterations framed, the handles they ran, and the
            # turns of one tick period (20 ms) or more
            "trace_turns": self.turns,
            "trace_turn_handles": self.turn_handles,
            "trace_turns_long": self.turns_long,
        }
        # the collector's passes by generation, counted by CPython
        # whether tracing is on or not
        for gen, st in enumerate(gc.get_stats()):
            out[f"gc_collections_gen{gen}"] = st["collections"]
        for name, (n, busy, self_s) in self.section_table().items():
            out[f"trace_section_calls_{name}"] = n
            out[f"trace_section_busy_seconds_{name}"] = round(busy, 6)
            out[f"trace_section_self_seconds_{name}"] = round(self_s, 6)
        return out

    def gauges(self) -> dict:
        """Point-in-time series (toggles, ring occupancy, EMAs)."""
        return {
            "trace_enabled": int(self.enabled),
            "trace_ring_spans": len(self._ring),
            "trace_slow_ema_ms": round(self._p99_ema * 1000.0, 3),
            # the young generation's threshold in force: a serving
            # store raises it (rheakv/store_engine.py:_gc_store_up)
            "gc_threshold_young": gc.get_threshold()[0],
        }

    def stats(self) -> dict:
        """Everything, merged — the bench/soak report blob."""
        return {**self.counters(), **self.gauges()}

    def describe(self) -> str:
        c = self.stats()
        return (f"Tracer<enabled={self.enabled} rate={self.sample_rate} "
                f"ops={c['trace_ops_seen']} sampled={c['trace_ops_sampled']} "
                f"slow_retained={c['trace_ops_slow_retained']} "
                f"ring={c['trace_ring_spans']} "
                f"p99_ema={c['trace_slow_ema_ms']}ms "
                f"gc_young={c['gc_threshold_young']} gc_passes="
                f"{c['gc_collections_gen0']}/{c['gc_collections_gen1']}/"
                f"{c['gc_collections_gen2']}>")


# -- trace-context wire helpers ----------------------------------------------
# One i64 per item/entry, little-endian, concatenated; b"" = untraced.
# Riding TRAILING defaulted wire fields keeps old decoders compatible
# (they stop before the field) and costs zero bytes when tracing is off.

import struct as _struct

_CTX = _struct.Struct("<q")


def store_proc(server_id) -> str:
    """The canonical span 'proc' identity for a store-side component.
    ONE derivation: cross-stage correlation (and the bench's
    leader-proc matching) requires every stage of one store to render
    the identical string — four call sites re-deriving it from
    slightly different server_id sources would silently split a
    store's spans across two 'processes' in the export."""
    return f"store:{server_id}"


def wire_ctx(tid: int) -> int:
    """The context an op PROPAGATES downstream: sampled ops carry their
    tid (full stage attribution), unsampled slow-candidates carry 0 —
    their only artifact is the client-side root span, so the serving
    path stays untouched for the 1-sample_rate majority."""
    return tid if tid & 1 else 0


def pack_ctx(tids: list[int]) -> bytes:
    """Pack per-item trace contexts; all-zero packs to b"" (no wire
    cost on the untraced path)."""
    if not any(tids):
        return b""
    return b"".join(_CTX.pack(t) for t in tids)


def unpack_ctx(blob: bytes, n: int) -> list[int]:
    """Unpack ``n`` per-item contexts; a missing/short blob (old sender,
    tracing off) yields zeros for every item."""
    if not blob or len(blob) < n * _CTX.size:
        return [0] * n
    return [_CTX.unpack_from(blob, i * _CTX.size)[0] for i in range(n)]


def entry_ctx(entries) -> bytes:
    """Pack the trace contexts of a log-entry batch for the
    AppendEntriesRequest trailing field."""
    return pack_ctx([e.trace_id for e in entries])


def adopt_entry_ctx(entries, blob: bytes) -> None:
    """Follower side: stamp wire-borne contexts onto decoded entries so
    their append/flush spans join the originating trace."""
    if not blob:
        return
    tids = unpack_ctx(blob, len(entries))
    for e, tid in zip(entries, tids):
        if tid:
            e.trace_id = tid


# -- flight recorder ---------------------------------------------------------


class FlightRecorder:
    """Per-process bounded ring of protocol events + anomaly snapshots.

    Always on: the events it records (elections, conf-change stages,
    health transitions, evacuations, quiesce/wake, fence failures, shed
    bounces) happen at incident rate, not op rate, and a deque append
    is cheap enough to never gate.  Thread-safe: health transitions can
    arrive from the store's health task while node events arrive from
    RPC handlers on the same loop, and SIGUSR2 dumps from the signal
    frame.
    """

    def __init__(self, capacity: int = 2048) -> None:
        self._lock = threading.Lock()
        # writes serialized under _lock; reads are DELIBERATELY lock-free
        # (GIL-atomic deque snapshots via _snapshot) so dump()/describe()
        # stay safe from a SIGUSR2 frame that interrupted a record() call
        # holding the lock on this very thread
        self._ring: deque = deque(maxlen=capacity)  # guarded-by: _lock (writes)
        # anomaly snapshots: the ring tail AT the moment the anomaly
        # fired (ring churn after the incident must not erase the lead-up)
        self.anomalies: deque = deque(maxlen=8)     # guarded-by: _lock (writes)
        # election-storm detection: recent election_start timestamps per
        # group, pruned to the window
        self._elections: dict[str, deque] = {}      # guarded-by: _lock
        self._storm_last: dict[str, float] = {}     # guarded-by: _lock
        # coalescing windows for flood-prone event kinds (shed bounces
        # at request rate, mass hibernation sweeps), keyed per
        # (kind, group) so one store's flood can't swallow another's
        # first event or claim its suppressed count in the dump:
        # (kind, group) -> [window_start_monotonic, suppressed_count]
        self._coalesce: dict[tuple, list] = {}      # guarded-by: _lock
        self.storm_threshold = 5      # elections ...
        self.storm_window_s = 10.0    # ... within this window = a storm
        self.events_recorded = 0

    def record(self, kind: str, group: str = "", **detail) -> None:
        now = time.time()
        with self._lock:
            self._ring.append((now, kind, group, detail))
            self.events_recorded += 1
            if kind == "election_start" and group:
                self._note_election_locked(group, now)

    def _note_election_locked(self, group: str, now: float) -> None:
        dq = self._elections.get(group)
        if dq is None:
            dq = self._elections[group] = deque(maxlen=32)
            # bound the per-group map itself (region churn)
            if len(self._elections) > 512:
                self._elections.pop(next(iter(self._elections)))
        dq.append(now)
        while dq and now - dq[0] > self.storm_window_s:
            dq.popleft()
        if len(dq) >= self.storm_threshold:
            # once per window per group — a storm must not flood the
            # anomaly buffer with one snapshot per extra election
            if now - self._storm_last.get(group, 0.0) > self.storm_window_s:
                self._storm_last[group] = now
                self._anomaly_locked(
                    "election_storm",
                    f"group {group}: {len(dq)} elections in "
                    f"{self.storm_window_s:.0f}s")

    def record_coalesced(self, kind: str, group: str = "",
                         window_s: float = 1.0, per_group: bool = True,
                         **detail) -> None:
        """Leading-edge rate-bounded record for event kinds that can
        arrive in floods (a SICK store shedding at request rate, a
        hibernation sweep quiescing thousands of groups): the first
        occurrence in a window records immediately with its detail,
        the rest just count — the next recorded event of the kind
        carries ``suppressed=N`` plus ``suppressed_prior_s`` (how far
        back that suppressed window started), so a long-past flood's
        count reads as history, not as part of the new event.  Without
        coalescing, one incident's identical rows would evict the
        ring's entire lead-up (the exact history the recorder exists
        to keep).

        Windows are per (kind, group) by default — one source's flood
        must not swallow another's first event or claim its suppressed
        count in the dump.  Kinds whose flood IS many distinct groups
        at once (a hibernation sweep: every group quiesces exactly
        once, so each per-group window would be a leading edge and the
        sweep floods anyway) pass ``per_group=False`` to share one
        window per kind; the suppressed count then aggregates across
        groups and the recorded row's group is just the window's first
        trigger."""
        now = time.monotonic()
        key = (kind, group if per_group else "")
        with self._lock:
            ent = self._coalesce.get(key)
            if ent is not None and now - ent[0] < window_s:
                ent[1] += 1
                return
            if ent is not None and ent[1]:
                # time-stamp the carried count against ITS window — an
                # unrelated event hours later must not read as a flood
                detail = dict(detail, suppressed=ent[1],
                              suppressed_prior_s=round(now - ent[0], 1))
            if len(self._coalesce) > 1024:
                # bound the (kind, group) map itself (region churn)
                self._coalesce.pop(next(iter(self._coalesce)))
            self._coalesce[key] = [now, 0]
            self._ring.append((time.time(), kind, group, detail))
            self.events_recorded += 1

    def note_anomaly(self, reason: str, detail: str = "") -> None:
        """Snapshot the ring: something is wrong (SICK transition, soak
        oracle failure) and the lead-up events must survive churn."""
        with self._lock:
            self._anomaly_locked(reason, detail)

    def _anomaly_locked(self, reason: str, detail: str) -> None:
        # snapshot RAW tuples only — rendering 128 formatted lines here
        # would stall the event loop under the lock at the exact moment
        # (an election storm) the recorder is busiest; strings are built
        # lazily at dump/anomaly_report time
        self._ring.append((time.time(), "anomaly", "",
                           {"reason": reason, "detail": detail}))
        self.anomalies.append({
            "ts": time.time(),
            "reason": reason,
            "detail": detail,
            "raw_events": list(self._ring)[-128:],
        })

    def _snapshot(self, src) -> list:
        """LOCK-FREE read of a deque: dump()/describe() must be safe
        from a SIGNAL frame that may have interrupted a record() call
        holding ``_lock`` on this very thread — taking the lock there
        self-deadlocks the process.  ``list(deque)`` is GIL-safe except
        for a concurrent-mutation RuntimeError; retry, degrade to
        empty (a best-effort dump beats a hung node)."""
        for _ in range(4):
            try:
                return list(src)
            except RuntimeError:
                continue
        return []

    def events(self, last: int = 0) -> list[tuple]:
        evs = self._snapshot(self._ring)
        return evs[-last:] if last else evs

    @staticmethod
    def _render(evs: list) -> list[str]:
        out = []
        for ts, kind, group, detail in evs:
            stamp = time.strftime("%H:%M:%S", time.localtime(ts))
            extra = " ".join(f"{k}={v}" for k, v in detail.items())
            out.append(f"{stamp}.{int(ts % 1 * 1000):03d} {kind:<16} "
                       f"{group or '-':<24} {extra}".rstrip())
        return out

    def dump(self, last: int = 256) -> str:
        """Structured text dump of the event tail (SIGUSR2 / soak
        failure attachment).  Lock-free: callable from a signal frame."""
        lines = self._render(self.events(last))
        hdr = (f"--- flight recorder: {len(lines)} recent events, "
               f"{self.events_recorded} total, "
               f"{len(self.anomalies)} anomalies ---")
        return "\n".join([hdr] + lines)

    def anomaly_report(self) -> list[dict]:
        """Anomaly snapshots for machine-readable attachment (the
        soak's failure report); raw tuples render here, off the
        recording path."""
        return [{"ts": a["ts"], "reason": a["reason"],
                 "detail": a["detail"],
                 "events": self._render(a["raw_events"])}
                for a in self._snapshot(self.anomalies)]

    def counters(self) -> dict:
        """Monotonic series (Prometheus counter semantics); lock-free
        int/len reads (the exposition thread must never contend the
        recording path)."""
        return {"recorder_events": self.events_recorded}

    def gauges(self) -> dict:
        return {
            "recorder_ring": len(self._ring),
            "recorder_anomalies": len(self.anomalies),
        }

    def stats(self) -> dict:
        return {**self.counters(), **self.gauges()}

    def describe(self) -> str:
        return self.dump(last=64)


# Module-level singletons: one tracer + one recorder per process.  All
# components record into these; the describer renders them on SIGUSR2.
TRACER = Tracer()
RECORDER = FlightRecorder()
describer.register(TRACER)
describer.register(RECORDER)
