"""Shared multi-group log engine bindings (native/multilog.cc).

Reference parity: RocksDB as ONE log engine per process — all raft
groups share a write stream and a flush round covers every group with a
single fsync (``core:storage/impl/RocksDBLogStorage`` + RocksDB
WriteBatch; SURVEY.md §3.1 log-storage row, §8.3 "group-sharded column
spaces; batched group-fsync").  Round-1 gap (VERDICT #3): every group
opened its own segment directory, so a process hosting 1K regions held
thousands of fds and issued uncoalesced fsyncs.

Wiring:
  log_uri = "multilog://<dir>#<group_id>"
One :class:`MultiLogEngine` per directory per process (registry below);
each node's :class:`MultiLogStorage` is a per-group view.  Durability:
the engine's :class:`_GroupCommit` gives every group that stages in one
turn of the event loop ONE ``tlm_append_round`` (one ``write()``) and
ONE ``tlm_sync`` (one fsync) between them (observable via
``sync_count``/``append_count`` and the ``rounds`` / ``round_groups``
histograms).  The LogManager uses the ``append_entries_async`` hook when
present: it encodes in the caller's turn and awaits the round's one
future, with no task and no executor thread per group.
"""

from __future__ import annotations

import asyncio
import ctypes
import os
import struct
import threading
import time
from typing import Optional

from tpuraft.entity import LogEntry
from tpuraft.storage.log_storage import CorruptLogError, LogStorage
from tpuraft.util.dirkeys import RealPathKeys
from tpuraft.util.metrics import Histogram
from tpuraft.util.trace import TRACER as _TRACE

_FRAME = struct.Struct("<I")
_LIB_NAME = "libtpuraft_multilog.so"


def _native_dir() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "native")


def ensure_built(timeout: float = 120.0) -> str:
    from tpuraft.util.native_build import ensure_built as _eb
    return _eb(_native_dir(), os.path.join(_native_dir(), _LIB_NAME),
               target=_LIB_NAME, timeout=timeout)


_lib_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None


def _load() -> ctypes.CDLL:
    global _lib
    with _lib_lock:
        if _lib is None:
            lib = ctypes.CDLL(ensure_built())
            u8p = ctypes.POINTER(ctypes.c_uint8)
            lib.tlm_open.restype = ctypes.c_void_p
            lib.tlm_open.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                     ctypes.c_char_p, ctypes.c_int]
            lib.tlm_close.argtypes = [ctypes.c_void_p]
            lib.tlm_register_group.restype = ctypes.c_uint32
            lib.tlm_register_group.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
                ctypes.c_int]
            for name in ("tlm_first", "tlm_last"):
                fn = getattr(lib, name)
                fn.restype = ctypes.c_int64
                fn.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
            lib.tlm_append.restype = ctypes.c_int64
            lib.tlm_append.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                                       ctypes.c_char_p, ctypes.c_int64,
                                       ctypes.c_char_p, ctypes.c_int]
            lib.tlm_append_round.restype = ctypes.c_int64
            lib.tlm_append_round.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_uint32),
                ctypes.POINTER(ctypes.c_char_p),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64), ctypes.c_char_p,
                ctypes.c_int]
            lib.tlm_sync.restype = ctypes.c_int
            lib.tlm_sync.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_int]
            for name in ("tlm_sync_count", "tlm_append_count",
                         "tlm_file_count", "tlm_gc"):
                fn = getattr(lib, name)
                fn.restype = ctypes.c_int64
                fn.argtypes = [ctypes.c_void_p]
            lib.tlm_get.restype = ctypes.c_int64
            lib.tlm_get.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                                    ctypes.c_int64, ctypes.POINTER(u8p)]
            lib.tlm_free.argtypes = [u8p]
            for name in ("tlm_truncate_prefix", "tlm_truncate_suffix",
                         "tlm_reset"):
                fn = getattr(lib, name)
                fn.restype = ctypes.c_int
                fn.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                               ctypes.c_int64]
            lib.tlm_conf_count.restype = ctypes.c_int64
            lib.tlm_conf_count.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
            lib.tlm_conf_indexes.restype = ctypes.c_int64
            lib.tlm_conf_indexes.argtypes = [
                ctypes.c_void_p, ctypes.c_uint32,
                ctypes.POINTER(ctypes.c_int64), ctypes.c_int64]
            _lib = lib
        return _lib


class _RoundFuture(asyncio.Future):
    """The one future of a flush round, awaited by every stager of it:
    a stager that is cancelled must not cancel the round under the
    others, so ``cancel`` declines and the cancelled task is cancelled
    when the round resolves (``Task.cancel`` falls back to that)."""

    def cancel(self, msg=None) -> bool:
        return False


class _Staged:
    """One group's encoded entries riding a flush round: appended to the
    journal with the round's other riders in ONE call when the round
    closes, then synced with them.  Awaiting it gives the round's fsync
    interval, or raises what failed: this group's own append (``error``)
    or the round's sync."""

    __slots__ = ("gid", "frames", "follower", "future", "error")

    def __init__(self, gid: int, frames: bytes,
                 follower: bool = False) -> None:
        self.gid = gid
        self.frames: Optional[bytes] = frames
        self.follower = follower    # staged by a follower's append
        self.future: Optional[_RoundFuture] = None   # the round's
        self.error: Optional[BaseException] = None

    def withdraw(self) -> None:
        """Leave the round unappended (it has not closed yet): what a
        LogManager does with its stake when an earlier flush of its
        group failed."""
        self.frames = None

    def __await__(self):
        interval = yield from self.future.__await__()
        if self.error is not None:
            raise self.error
        return interval


_LEADER, _FOLLOWER = 1, 2    # a staging's role, as a bit of _Lane.roles


class _Lane:
    """One event loop's rounds on a group commit: the open one, which
    the loop's stagers are joining, and whether an earlier one is with
    the executor (the open one then waits for it to land)."""

    __slots__ = ("loop", "open", "groups", "roles", "items", "in_flight")

    def __init__(self, loop) -> None:
        self.loop = loop
        self.open: Optional[_RoundFuture] = None
        self.groups = 0          # stagings that joined the open round
        self.roles = 0           # _LEADER | _FOLLOWER: who staged them
        self.items: list = []    # their _Staged, where the engine has any
        self.in_flight = False


class _GroupCommit:
    """The flush round is the loop turn (the shape of the KV WAL's
    ``ApplyRound``).  The first stager of a turn opens a round and
    schedules its close with ``call_soon``; every group that stages
    before that callback runs rides it; the close makes ONE
    ``engine.append_round()`` for the riders' staged entries (the
    multilog: one native call, one ``write()``), ONE ``engine.sync()``,
    and resolves ONE future that all of the round's stagers await.
    Nothing decides where a round ends but the loop's own order: no
    linger, no timer.  A lone stager is synced in the turn after it
    staged.

    The round's sync begins after its append returned, and a rider that
    staged its bytes itself (``MetaJournal``) calls ``flush()`` after it
    did.

    Where the fsync runs is decided by what it is measured to cost:
    while the smoothed cost is under ``INLINE_MAX_S`` the close fsyncs
    on the loop thread; at or above it the close, having appended,
    hands the fsync to the executor and its completion resolves the
    round's future, and what stages in the turns in between waits for it
    as ONE round (classic group commit: one fsync in flight, the next
    one covers what arrived meanwhile).

    The engine is shared process-wide by directory, so stagers may live
    on DIFFERENT event loops (multi-store processes): a round belongs to
    the loop that opened it, is closed and resolved on that loop, and
    ``engine.sync()`` serializes concurrent rounds.

    The round's future resolves to the fsync's own interval
    ``(t0, t1, off_loop)``: ``perf_counter`` at its start and end, read
    in the thread that ran it, and whether that was an executor thread.
    From the interval's end to a stager's resumption is the loop's share
    of the awaited time, not the disk's."""

    # An inline fsync blocks the event loop, so the loop-thread close
    # self-bans the moment the smoothed sync cost reaches this (slow or
    # contended disk): stalling the loop stalls heartbeats for EVERY
    # group in the process.  One writeback spike doesn't ban it, a
    # genuinely slow disk does, and while banned there is no inline
    # re-probe (a probe blocks the loop for the full, unbounded fsync):
    # the executor rounds feed the same EWMA from their thread, so the
    # loop-thread close re-enables only after the DISK proves fast again,
    # off-loop.
    INLINE_MAX_S = 0.001

    def __init__(self, engine) -> None:
        self._engine = engine
        self._lock = threading.Lock()
        self._lanes: dict = {}                     # guarded-by: _lock
        # smoothed fsync cost (seconds), fed by every round
        self._cost_ewma = 0.0                      # guarded-by: _lock
        # gray-failure signal sink: a DiskLatencyProbe (util/health.py,
        # itself lock-guarded) fed every measured fsync duration — set
        # by the hosting StoreEngine; None = no health scoring
        self.health_probe = None
        # events, one sample each, so a window's ``count`` is the
        # number: a round closed, a staging that rode one (round_groups
        # / rounds = groups per fsync, 1.0 = nothing merges), a round
        # synced on the loop thread, a round that carried a leader's
        # staging and a follower's (the store's two roles rode one
        # fsync; a store that only leads or only follows has none)
        self.rounds = Histogram()
        self.round_groups = Histogram()
        self.round_inline = Histogram()
        self.rounds_mixed = Histogram()

    def flush(self, item: Optional[_Staged] = None) -> asyncio.Future:
        """Join the running loop's open round (opening it, and
        scheduling its close, if this is the turn's first stager), with
        ``item`` for the round to append at its close, or with bytes the
        caller staged already."""
        loop = asyncio.get_running_loop()
        with self._lock:
            lane = self._lanes.get(loop)
            if lane is None:
                lane = self._lanes[loop] = _Lane(loop)
            if lane.open is None:
                lane.open = _RoundFuture(loop=loop)
                if not lane.in_flight:
                    loop.call_soon(self._close, lane)
            lane.groups += 1
            if item is not None:
                item.future = lane.open
                lane.items.append(item)
                lane.roles |= _FOLLOWER if item.follower else _LEADER
            return lane.open

    def _close(self, lane: _Lane) -> None:
        """End the open round of the lane's loop (on its thread): append
        what its riders staged, then sync here or hand that to the
        executor."""
        with self._lock:
            fut, groups, items = lane.open, lane.groups, lane.items
            roles = lane.roles
            lane.open, lane.groups, lane.items, lane.roles = None, 0, [], 0
            lane.in_flight = True       # until this round resolves
            inline = self._cost_ewma < self.INLINE_MAX_S
        self.rounds.update(1)
        self.round_groups.update(1, groups)
        if roles == _LEADER | _FOLLOWER:
            self.rounds_mixed.update(1)
        probe = self.health_probe
        exc: Optional[BaseException] = None
        interval: Optional[tuple] = None
        # the loop thread appends (buffered) and, while the disk is
        # measured fast, blocks in the fsync: a stretch of the log layer
        sec = _TRACE.enter("log.stage") if _TRACE.enabled else None
        try:
            if items:
                try:
                    self._engine.append_round(items)
                except Exception as e:  # noqa: BLE001 — fails THIS round
                    exc = e
            if exc is None and inline:
                self.round_inline.update(1)
                exc, interval = self._timed_sync(probe, None, False)
        finally:
            if sec is not None:
                _TRACE.leave(sec)
        if exc is not None or inline:
            self._landed(lane, fut, exc, interval)
            return
        # the health probe's stall token spans the hand-off to the
        # executor, the wait for a free thread (a saturated executor IS
        # a gray signal) and the fsync, and ends in the thread with the
        # fsync: a hung or never-started fsync ages it.  It does not
        # span the stagers' resumption: held until then, a store whose
        # fsyncs take 0.06 ms read as a stalled disk whenever its loop
        # ran half a second late, and the loop's lateness has a probe of
        # its own (LoopLagProbe).
        tok = probe.begin() if probe is not None else None
        try:
            lane.loop.run_in_executor(None, self._sync_off_loop, lane, fut,
                                      probe, tok)
        except RuntimeError as e:   # the executor is shut down
            if tok is not None:
                probe.end(tok)      # a round that never reached a thread
            self._landed(lane, fut, e, None)

    def _timed_sync(self, probe, tok, off_loop: bool) -> tuple:
        """``engine.sync()`` and its pure in-thread interval; feeds the
        EWMA and the health probe from the thread that ran it.  ``tok``
        is the probe's stall token of an executor round: given back
        HERE, in the thread that does the I/O, the moment the fsync
        ends.  Returns ``(exception or None, interval)``."""
        exc: Optional[BaseException] = None
        t0 = time.perf_counter()
        try:
            self._engine.sync()
        except Exception as e:  # noqa: BLE001 — fails THIS round only
            exc = e
        finally:
            t1 = time.perf_counter()
            if tok is not None:
                probe.end(tok)
        dur = t1 - t0
        with self._lock:
            # smoothed: one writeback spike doesn't ban the loop-thread
            # close, a genuinely slow disk does (and keeps it banned
            # while the ewma stays above the ceiling); fed from the
            # executor rounds too, which is how a ban recovers
            self._cost_ewma = 0.7 * self._cost_ewma + 0.3 * dur
        if probe is not None:
            probe.note(dur)
        return exc, (t0, t1, off_loop)

    def _sync_off_loop(self, lane: _Lane, fut: _RoundFuture, probe,
                       tok) -> None:
        """An executor round: the fsync in this thread, then straight
        back to the round's loop with its outcome."""
        exc, interval = self._timed_sync(probe, tok, True)
        try:
            lane.loop.call_soon_threadsafe(self._landed, lane, fut, exc,
                                           interval)
        except RuntimeError:
            pass  # the round's loop closed under it: nobody is waiting

    def _landed(self, lane: _Lane, fut: _RoundFuture,
                exc: Optional[BaseException],
                interval: Optional[tuple]) -> None:
        """A round is done, on its loop: resolve it, and close the round
        that gathered behind it while it was with the executor."""
        if exc is not None:
            fut.set_exception(exc)
        else:
            fut.set_result(interval)
        with self._lock:
            if lane.open is None:
                lane.in_flight = False
                del self._lanes[lane.loop]
            else:
                # behind the callbacks just scheduled: a rider whose
                # flush failed withdraws its stake in the next round
                # before that round appends it
                lane.loop.call_soon(self._close, lane)


class MultiLogEngine:
    """One shared journal engine (ctypes handle) + its group-commit."""

    def __init__(self, dir_path: str, segment_max_bytes: int = 0):
        self._lib = _load()
        parent = os.path.dirname(dir_path.rstrip("/"))
        if parent:
            os.makedirs(parent, exist_ok=True)
        err = ctypes.create_string_buffer(256)
        self._h = self._lib.tlm_open(dir_path.encode(), segment_max_bytes,
                                     err, 256)
        if not self._h:
            raise IOError(f"multilog open failed: {err.value.decode()}")
        self.dir = dir_path
        self.group_commit = _GroupCommit(self)
        # capacity-fault hook (tests/soak): a callable taking the byte
        # count about to be staged, raising OSError(ENOSPC) to refuse it
        # — the C++ fd writes are out of Python interposition's reach,
        # so NativeJournalTracker.attach_quota enforces budgets here
        self.fault_gate = None
        self._refs = 0
        # serializes sync vs close: tlm_close deletes the native Store,
        # so closing while an fsync round is mid-flight in any thread
        # (executor, or a foreign loop's cancelled round whose job keeps
        # running) would be a use-after-free.  close() blocks the few ms
        # an in-flight fsync needs; later syncs fail cleanly.
        self._sync_lock = threading.Lock()

    def close(self) -> None:
        with self._sync_lock:
            if self._h:
                self._lib.tlm_close(self._h)
                self._h = None

    def register_group(self, name: str) -> int:
        err = ctypes.create_string_buffer(256)
        gid = self._lib.tlm_register_group(self._h, name.encode(), err, 256)
        if gid == 0:
            raise IOError(f"multilog register failed: {err.value.decode()}")
        return gid

    def append_round(self, items: list) -> None:
        """A flush round's staging: every rider's frames into the journal
        in ONE native call and ONE ``write()`` a touched journal
        (``tlm_append_round``; a withdrawn item is passed over).  A
        group whose append fails (its frames or their contiguity; a
        failed write, for every group with bytes in it) has the reason
        as its item's ``error``; the others are appended."""
        live = [it for it in items if it.frames is not None]
        n = len(live)
        if not n:
            return
        h = self._h
        if not h:
            # the riders' storages released the engine under their round
            for it in live:
                it.error = IOError("multilog engine closed")
            return
        gate = self.fault_gate
        if gate is not None:
            gate(sum(len(it.frames) for it in live))
        results = (ctypes.c_int64 * n)()
        err = ctypes.create_string_buffer(256)
        failed = self._lib.tlm_append_round(
            h, n, (ctypes.c_uint32 * n)(*[it.gid for it in live]),
            (ctypes.c_char_p * n)(*[it.frames for it in live]),
            (ctypes.c_int64 * n)(*[len(it.frames) for it in live]),
            results, err, 256)
        if failed:
            why = f"multilog append failed: {err.value.decode()}"
            for it, r in zip(live, results):
                if r < 0:
                    it.error = ValueError(why)

    def sync(self) -> None:
        with self._sync_lock:
            h = self._h
            if not h:
                raise IOError("multilog engine closed")
            err = ctypes.create_string_buffer(256)
            if self._lib.tlm_sync(h, err, 256) != 0:
                raise IOError(f"multilog sync failed: {err.value.decode()}")

    @property
    def sync_count(self) -> int:
        return self._lib.tlm_sync_count(self._h)

    @property
    def append_count(self) -> int:
        return self._lib.tlm_append_count(self._h)

    @property
    def file_count(self) -> int:
        return self._lib.tlm_file_count(self._h)

    def gc(self) -> int:
        return self._lib.tlm_gc(self._h)


# -- process-level engine registry (one engine per directory) ----------------

_engines_lock = threading.Lock()
_engines: dict[str, MultiLogEngine] = {}  # guarded-by: _engines_lock


_engine_keys = RealPathKeys()  # guarded-by: _engines_lock


def peek_engine(dir_path: str) -> Optional[MultiLogEngine]:
    """The live engine for a directory WITHOUT taking a reference —
    observability wiring (the StoreEngine attaching its health probe),
    never ownership."""
    with _engines_lock:
        return _engines.get(_engine_keys.key(dir_path))


def get_engine(dir_path: str, segment_max_bytes: int = 0) -> MultiLogEngine:
    with _engines_lock:
        key = _engine_keys.key(dir_path)
        eng = _engines.get(key)
        if eng is None or eng._h is None:
            eng = MultiLogEngine(dir_path, segment_max_bytes)
            _engines[key] = eng
        eng._refs += 1
        return eng


def _release_engine(eng: MultiLogEngine) -> None:
    with _engines_lock:
        eng._refs -= 1
        if eng._refs > 0:
            return
        key = _engine_keys.key(eng.dir)
        _engines.pop(key, None)
        _engine_keys.forget(key)
    # close() serializes against any in-flight fsync via the engine's
    # sync lock (blocks the few ms it needs), so closing here is safe
    # even while a round's executor job is still running; that round's
    # waiters — all belonging to already-shutdown stores — get a clean
    # "engine closed" failure if they sync after this point
    eng.close()


class MultiLogStorage(LogStorage):

    CHEAP_CONF_INDEXES = True  # C-side sidecar lookup, no disk I/O
    """Per-group view over the shared engine; selected by
    ``multilog://<dir>#<group_id>``."""

    def __init__(self, dir_path: str, group: str):
        self._dir = dir_path
        self._group = group
        self._eng: Optional[MultiLogEngine] = None
        self._gid = 0
        self._lib = _load()

    @property
    def engine(self) -> MultiLogEngine:
        assert self._eng is not None, "init() first"
        return self._eng

    def init(self) -> None:
        self._eng = get_engine(self._dir)
        self._gid = self._eng.register_group(self._group)

    def shutdown(self) -> None:
        if self._eng is not None:
            _release_engine(self._eng)
            self._eng = None

    def first_log_index(self) -> int:
        return self._lib.tlm_first(self._eng._h, self._gid)

    def last_log_index(self) -> int:
        return self._lib.tlm_last(self._eng._h, self._gid)

    def get_entry(self, index: int) -> Optional[LogEntry]:
        out = ctypes.POINTER(ctypes.c_uint8)()
        n = self._lib.tlm_get(self._eng._h, self._gid, index,
                              ctypes.byref(out))
        if n == -2:
            # the index says the record is live but its CRC fails: bit
            # rot of acked data — silently returning None here would
            # read as a hole and could ship garbage to a follower
            raise CorruptLogError(
                f"multilog record for group {self._group} index {index} "
                f"fails CRC — acked entry corrupted")
        if n < 0:
            return None
        try:
            blob = ctypes.string_at(out, n)
        finally:
            self._lib.tlm_free(out)
        return LogEntry.decode(blob)

    @staticmethod
    def _frames(entries: list[LogEntry]) -> bytes:
        parts = []
        for e in entries:
            blob = e.encode()
            parts.append(_FRAME.pack(len(blob)))
            parts.append(blob)
        return b"".join(parts)

    def _stage(self, entries: list[LogEntry]) -> int:
        frames = self._frames(entries)
        gate = self._eng.fault_gate
        if gate is not None:
            gate(len(frames))
        err = ctypes.create_string_buffer(256)
        n = self._lib.tlm_append(self._eng._h, self._gid, frames,
                                 len(frames), err, 256)
        if n < 0:
            raise ValueError(f"multilog append failed: {err.value.decode()}")
        return n

    def append_entries(self, entries: list[LogEntry], sync: bool = True) -> int:
        """Synchronous path (executor callers): per-call fsync, no
        cross-group round — prefer append_entries_async."""
        if not entries:
            return 0
        n = self._stage(entries)
        if sync:
            self._eng.sync()
        return n

    def append_entries_async(self, entries: list[LogEntry],
                             sync: bool = True,
                             follower: bool = False) -> Optional[_Staged]:
        """LogManager hook: encode NOW, in the caller's own turn (so a
        group's entries are staged in the caller's order), and join the
        store-wide flush round of this turn, which appends every
        rider's frames in ONE native call and syncs them with ONE fsync.
        Returns the group's stake in that round: awaitable (the fsync's
        interval, see ``_GroupCommit``, or what failed), uncancellable,
        with the round's shared ``future``.  None where nothing is to be
        synced (the entries are appended at once then).  ``follower``
        says whose staging this is, a follower's append or a leader's
        own entries, for the round's count of mixed rounds."""
        if not entries:
            return None
        if not sync:
            self._stage(entries)
            return None
        sec = _TRACE.enter("log.stage") if _TRACE.enabled else None
        try:
            item = _Staged(self._gid, self._frames(entries), follower)
        finally:
            if sec is not None:
                _TRACE.leave(sec)
        self._eng.group_commit.flush(item)
        return item

    def truncate_prefix(self, first_index_kept: int) -> None:
        if self._lib.tlm_truncate_prefix(self._eng._h, self._gid,
                                         first_index_kept) != 0:
            raise IOError("multilog truncate_prefix failed")
        self._eng.gc()  # opportunistic: drop fully-dead journal files

    def truncate_suffix(self, last_index_kept: int) -> None:
        if self._lib.tlm_truncate_suffix(self._eng._h, self._gid,
                                         last_index_kept) != 0:
            raise IOError("multilog truncate_suffix failed")

    def reset(self, next_log_index: int) -> None:
        if self._lib.tlm_reset(self._eng._h, self._gid, next_log_index) != 0:
            raise IOError("multilog reset failed")

    def configuration_indexes(self) -> list[int]:
        n = self._lib.tlm_conf_count(self._eng._h, self._gid)
        if n == 0:
            return []
        buf = (ctypes.c_int64 * n)()
        got = self._lib.tlm_conf_indexes(self._eng._h, self._gid, buf, n)
        return list(buf[:got])
