"""NodeManager: groupId -> Node routing on one shared RPC endpoint.

Reference parity: ``core:NodeManager`` + the per-request processors bound
to one RpcServer (SURVEY.md §2 "Key structural fact"): N raft groups
multiplex one server; requests route by (group_id, peer_id).
"""

from __future__ import annotations

import asyncio
import logging
from typing import Optional

from tpuraft.core.node import (_LEADER_CONFLICT, _STEP_DOWN, Node, State,
                               _Appending)
from tpuraft.entity import PeerId
from tpuraft.rpc.messages import (BatchResponse, BeatAck, ErrorResponse,
                                  StoreAppendResponse)
from tpuraft.errors import RaftError, Status
from tpuraft.rpc.transport import RpcError, RpcServer
from tpuraft.util.metrics import Histogram
from tpuraft.util.trace import TRACER as _TRACE

LOG = logging.getLogger(__name__)


def _row_error(exc: Exception) -> ErrorResponse:
    """What one row of a store_append round answers when serving it
    raised: the RPC error's own status, else EINTERNAL (one bad row
    only: the round's other rows are served)."""
    if isinstance(exc, RpcError):
        return ErrorResponse(exc.status.code, exc.status.error_msg)
    LOG.error("store_append row failed", exc_info=exc)
    return ErrorResponse(int(RaftError.EINTERNAL), repr(exc))


def _repeated(keys: list) -> set:
    """The keys that occur more than once."""
    seen: set = set()
    twice: set = set()
    for key in keys:
        (twice if key in seen else seen).add(key)
    return twice


class NodeManager:
    """One per process endpoint."""

    def __init__(self, server: RpcServer):
        self.server = server
        self._nodes: dict[tuple[str, str], Node] = {}
        # (group, leader) -> (FIFO, worker) of in-order AppendEntries execution
        self._append_lanes: dict[
            tuple[str, str], tuple[asyncio.Queue, asyncio.Task]] = {}
        for method in ("append_entries", "request_vote", "timeout_now",
                       "install_snapshot", "read_index"):
            server.register(method, self._make_handler(method))
        # get_file serves snapshot chunks; routed by reader_id not group
        self._file_readers: dict[int, object] = {}
        self._next_reader_id = 1
        server.register("get_file", self._handle_get_file)
        # coalesced heartbeats (HeartbeatHub): one RPC per endpoint pair
        server.register("multi_heartbeat", self._handle_multi_heartbeat)
        # batched send plane (SendPlane): votes + entry-bearing appends
        # coalesced the same way — O(endpoints) RPCs, O(endpoints)
        # standing sender tasks
        server.register("multi_append", self._handle_multi_append)
        server.register("multi_vote", self._handle_multi_vote)
        # store-wide append rounds (AppendBatcher): every led group's
        # pending entry window toward this endpoint in ONE RPC per
        # window — the write-plane mirror of multi_beat_fast
        server.register("store_append", self._handle_store_append)
        server.register("multi_beat_fast", self._handle_multi_beat_fast)
        # store-level liveness lease (quiescence): one tiny beat per
        # endpoint pair proves a whole store alive while its groups
        # hibernate (HeartbeatHub receiver side)
        server.register("store_lease", self._handle_store_lease)
        self._send_plane = None
        self._heartbeat_hub = None  # created on first coalescing leader
        # at most ONE outstanding beat handler per (group, peer): beats
        # behind a busy node lock must answer EBUSY, not stack a new
        # lock waiter every round (queue flooding starves vote handling)
        self._beat_inflight: set[tuple[str, str]] = set()
        # same guard for batched appends: a stuck node (long fsync /
        # snapshot load) must not accumulate one shielded handler —
        # each carrying a full entry window — per leader retry cycle
        self._append_inflight: set[tuple[str, str]] = set()
        # events, one sample each, so a window's ``count`` is the
        # number: rows of store_append RPCs served, and of them the rows
        # begun and finished in the handler's own turns (the rest
        # waited: the per-node coroutine, or a deadline that passed)
        self.follower_rows = Histogram()
        self.follower_rows_inline = Histogram()

    @property
    def heartbeat_hub(self):
        if self._heartbeat_hub is None:
            from tpuraft.core.heartbeat_hub import HeartbeatHub

            self._heartbeat_hub = HeartbeatHub()
        return self._heartbeat_hub

    @property
    def send_plane(self):
        if self._send_plane is None:
            from tpuraft.core.send_plane import SendPlane

            self._send_plane = SendPlane()
        return self._send_plane

    async def _handle_multi_beat_fast(self, request):
        """Beat-plane fast path: steady-state heartbeats processed
        INLINE — no node lock, no per-beat task.  At region density the
        classic per-beat handler fan-out is the dominant idle burn
        (G beats/s, each lock + shielded task on a 1-core host); here a
        beat that matches the receiver's (FOLLOWER, term, leader,
        committed) row just touches the election deadline.  Any
        deviation answers ok=False and the sender follows up with a
        classic full-semantics beat for that group only."""
        sec = _TRACE.enter("raft.heartbeat") if _TRACE.enabled else None
        try:
            return BatchResponse(items=self._answer_fast_beats(request.items))
        finally:
            if sec is not None:
                _TRACE.leave(sec)

    def _answer_fast_beats(self, beats: list) -> list:
        acks = []
        for b in beats:
            node = self._nodes.get((b.group_id, b.peer_id))
            if (node is not None
                    and node.state == State.FOLLOWER
                    and node.current_term == b.term
                    and str(node.leader_id) == b.server_id
                    and b.committed_index
                    <= node.ballot_box.last_committed_index):
                node._ctrl.note_leader_contact()
                node._last_leader_timestamp = node._clock.monotonic()
                ok = True
                if getattr(b, "quiesce", False):
                    # quiesce handshake: join the hibernation ONLY when
                    # this follower is provably at the leader's tail
                    # (the leader's committed == its last index == our
                    # last index and we applied it) — a lagging or
                    # timer-mode follower refuses, keeping the group
                    # active and its election timer live
                    enter = getattr(node._ctrl,
                                    "enter_quiescent_follower", None)
                    ok = (enter is not None
                          and node.log_manager.last_log_index()
                          == b.committed_index
                          and node.ballot_box.last_committed_index
                          == b.committed_index
                          and enter(PeerId.parse(b.server_id).endpoint,
                                    getattr(b, "lease_ms", 0)))
                else:
                    # a NORMAL beat from an active leader: a follower
                    # still hibernating (aborted handshake, leader woke)
                    # resumes fault detection with it
                    node._ctrl.note_activity()
                acks.append(BeatAck(ok=bool(ok), term=node.current_term,
                                    clock_ms=self._clock_ms()))
            else:
                acks.append(BeatAck(
                    ok=False,
                    term=node.current_term if node is not None else 0,
                    clock_ms=self._clock_ms()))
        return acks

    def _clock_ms(self) -> int:
        """This store's clock reading (monotonic ms) for ack piggyback —
        the peer-skew estimator's raw sample (ISSUE 18)."""
        return int(self.heartbeat_hub.clock.monotonic() * 1000)

    async def _handle_store_lease(self, request):
        """Receiver side of the store-level liveness lease: re-arm the
        sending store's lease; the hub's watcher wakes every dependent
        quiescent group the moment it expires."""
        from tpuraft.rpc.messages import StoreLeaseAck

        deps = self.heartbeat_hub.note_lease_from(
            request.endpoint, request.lease_ms)
        return StoreLeaseAck(ok=True, dependents=deps,
                             clock_ms=self._clock_ms())

    async def _handle_multi_vote(self, request):
        """Fan a vote BatchRequest out concurrently; vote handlers only
        hold the node lock briefly (no disk waits)."""
        from tpuraft.rpc.messages import BatchResponse, ErrorResponse

        async def one(req):
            try:
                node = self._nodes.get((req.group_id, req.peer_id))
                if node is None:
                    return ErrorResponse(int(RaftError.ENOENT),
                                         f"no node for {req.group_id}")
                return await node.handle_request_vote(req)
            except RpcError as e:
                return ErrorResponse(e.status.code, e.status.error_msg)
            except Exception as e:  # noqa: BLE001 — one bad item only
                LOG.exception("multi_vote item failed")
                return ErrorResponse(int(RaftError.EINTERNAL), repr(e))

        acks = await asyncio.gather(*(one(r) for r in request.items))
        return BatchResponse(items=list(acks))

    async def _handle_multi_append(self, request):
        """Fan an AppendEntries BatchRequest out: per TARGET NODE the
        items execute sequentially in batch order (the in-order
        execution contract pipelined replication needs — the sender
        guarantees no cross-RPC races by keeping one RPC in flight per
        endpoint); distinct nodes run concurrently, so their log
        flushes coalesce into the same multilog group-commit round.

        A node that cannot serve an item within half an election
        timeout gets EBUSY for that item AND every later item of the
        same node in this batch (executing later items while the stuck
        one still holds the lane would reorder the group's log writes);
        the shielded handler keeps running, the leader just rolls back
        and re-probes, exactly like a dropped direct RPC."""
        from tpuraft.rpc.messages import BatchResponse

        return BatchResponse(
            items=await self._serve_append_items(request.items))

    async def _handle_store_append(self, request):
        """AppendBatcher's store-wide append round, served in this
        coroutine's own turns: BEGIN every row (its node's lock taken
        without waiting, ``Node._begin_append``: checks, leader contact,
        the probe's answer, the entries staged into the log's flush
        round of this turn), AWAIT the round once under one deadline for
        the RPC, FINISH every row (``Node._finish_append``, the lock
        released).  No task, future, shield or timer a group: all the
        rows of one turn ride one log round, so there is one future to
        wait for.

        A row that cannot be begun without waiting takes the per-node
        coroutine (``_run_node_rows``: ``handle_append_entries`` row by
        row, in batch order) beside the others, and is awaited with
        them: a node whose lock is held or waited for, a node that has
        to step down first (a higher term, not a follower, a leader
        conflict), a log that has to truncate a suffix or has no shared
        round, a node with more than one row in this RPC.

        The deadline is half the shortest election timeout among the
        RPC's nodes.  When it passes, every row not finished answers
        EBUSY and the reply leaves; such a row's finish still runs when
        its round lands, as a callback on the round's future (the
        entries are staged: cancelling would tear durability ordering),
        releases the lock and the ``_append_inflight`` claim, and no
        longer touches the reply.  A node with a claim outstanding
        answers EBUSY at once, as it did."""
        rows = request.rows
        out: list = [None] * len(rows)
        keys = [(req.group_id, req.peer_id) for req in rows]
        # nodes with more than one row here (none, as a rule)
        repeated = _repeated(keys) if len(set(keys)) != len(keys) else ()
        inflight = self._append_inflight
        nodes = self._nodes
        riding: list = []       # (row, node, its _Appending): begun here
        slow: dict[tuple[str, str], list[int]] = {}
        eto_ms = 0
        sender = server = clock = None
        now = 0.0
        inline = 0
        sec = _TRACE.enter("raft.follower") if _TRACE.enabled else None
        try:
            for i, req in enumerate(rows):
                key = keys[i]
                node = nodes.get(key)
                if node is None:
                    out[i] = ErrorResponse(int(RaftError.ENOENT),
                                           f"no node for {key[0]}")
                    continue
                if key in slow:
                    slow[key].append(i)     # in batch order, behind its first
                    continue
                if key in inflight:
                    out[i] = ErrorResponse(int(RaftError.EBUSY),
                                           f"{key[0]} busy")
                    continue
                # claim the lane SYNCHRONOUSLY, before anything is staged
                # or scheduled: two concurrent rounds for the same node
                # must not both pass the busy-check above and interleave
                # the group's log writes (the in-order contract the guard
                # exists for)
                inflight.add(key)
                if not eto_ms or node.options.election_timeout_ms < eto_ms:
                    eto_ms = node.options.election_timeout_ms
                if key in repeated or not node._try_lock():
                    slow[key] = [i]
                    continue
                # the node's lock is ours until the row's finish
                began = None
                try:
                    if req.server_id != sender:
                        sender = req.server_id
                        server = PeerId.parse(sender)
                    if node._clock is not clock:
                        clock = node._clock
                        now = clock.monotonic()
                    began = node._begin_append(
                        req, server, node.node_manager is not None, now)
                    if began.__class__ is _Appending and \
                            began.ride is not None:
                        riding.append((i, node, began))
                        continue
                except Exception as e:  # noqa: BLE001 — one bad row only
                    began = _row_error(e)
                node._lock.release()
                if began.__class__ is _Appending or began is _STEP_DOWN \
                        or began is _LEADER_CONFLICT:
                    slow[key] = [i]     # it has to wait first; keeps its claim
                    continue
                inflight.discard(key)
                out[i] = began
                inline += 1
        finally:
            if sec is not None:
                _TRACE.leave(sec)
        waits: set = {ap.ride.future for _i, _n, ap in riding}
        tasks = []
        sent = [False]      # the reply left: a late row writes nothing
        for key, idxs in slow.items():
            tasks.append(asyncio.ensure_future(self._run_node_rows(
                nodes[key], key, idxs, rows, out, sent)))
        waits.update(tasks)
        try:
            if waits:
                await asyncio.wait(waits, timeout=eto_ms / 1000.0 / 2)
        finally:
            # also when this handler is cancelled under its wait: what
            # is staged is finished when its round lands
            sent[0] = True
            sec = _TRACE.enter("raft.follower") if _TRACE.enabled else None
            try:
                for i, node, ap in riding:
                    if ap.ride.future.done():
                        out[i] = self._finish_row(node, keys[i], rows[i], ap)
                        inline += 1
                    else:
                        ap.ride.future.add_done_callback(
                            lambda _f, node=node, key=keys[i], req=rows[i],
                            ap=ap: self._finish_row(node, key, req, ap))
            finally:
                if sec is not None:
                    _TRACE.leave(sec)
            for task in tasks:
                if not task.done():
                    # stuck (long fsync / snapshot load / a lock held):
                    # it keeps going, uncancelled, and is heard out
                    task.add_done_callback(
                        lambda t: t.cancelled() or t.exception())
        self.follower_rows.update(1, len(rows))
        if inline:
            self.follower_rows_inline.update(1, inline)
        for i, ack in enumerate(out):
            if ack is None:     # the deadline passed over it
                out[i] = ErrorResponse(int(RaftError.EBUSY),
                                       f"{keys[i][0]} busy")
        return StoreAppendResponse(acks=out)

    def _finish_row(self, node: Node, key: tuple[str, str], req, ap):
        """The finish of a row begun in ``_handle_store_append``, its
        round landed: the answer; the node's lock and the lane's claim
        are given back whatever it makes of it."""
        try:
            return node._finish_append(
                req, node.node_manager is not None, ap)
        except Exception as e:  # noqa: BLE001 — one bad row only
            return _row_error(e)
        finally:
            node._lock.release()
            self._append_inflight.discard(key)

    async def _run_node_rows(self, node: Node, key: tuple[str, str],
                             idxs: list[int], rows: list, out: list,
                             sent: list) -> None:
        """One node's rows of a store_append RPC that have to wait,
        in batch order, under the claim its handler made."""
        try:
            for i in idxs:
                try:
                    r = await node.handle_append_entries(rows[i])
                except Exception as e:  # noqa: BLE001 — one bad row only
                    r = _row_error(e)
                if sent[0]:
                    return  # reply already serialized: too late
                out[i] = r
        finally:
            self._append_inflight.discard(key)

    async def _serve_append_items(self, items) -> list:
        from tpuraft.rpc.messages import ErrorResponse

        out: list = [None] * len(items)
        by_node: dict[tuple[str, str], list[int]] = {}
        for i, req in enumerate(items):
            by_node.setdefault((req.group_id, req.peer_id), []).append(i)

        async def run_node(key, idxs):
            node = self._nodes.get(key)
            if node is None:
                err = ErrorResponse(int(RaftError.ENOENT),
                                    f"no node for {key[0]}")
                for i in idxs:
                    out[i] = err
                return
            if key in self._append_inflight:
                # a previous window's handler is still stuck on this
                # node: answering EBUSY NOW (without spawning) keeps
                # leader retries from stacking one shielded handler —
                # each holding a full entry window — per cycle
                busy = ErrorResponse(int(RaftError.EBUSY),
                                     f"{key[0]} busy")
                for i in idxs:
                    out[i] = busy
                return
            budget = node.options.election_timeout_ms / 1000.0 / 2
            for pos, i in enumerate(idxs):
                try:
                    self._append_inflight.add(key)
                    task = asyncio.ensure_future(
                        node.handle_append_entries(items[i]))

                    def _done(t, key=key):
                        self._append_inflight.discard(key)
                        if not t.cancelled():
                            t.exception()

                    task.add_done_callback(_done)
                    out[i] = await asyncio.wait_for(
                        asyncio.shield(task), budget)
                except asyncio.TimeoutError:
                    busy = ErrorResponse(int(RaftError.EBUSY),
                                         f"{key[0]} busy")
                    for j in idxs[pos:]:
                        out[j] = busy
                    return
                except RpcError as e:
                    out[i] = ErrorResponse(e.status.code,
                                           e.status.error_msg)
                except Exception as e:  # noqa: BLE001
                    LOG.exception("multi_append item failed")
                    out[i] = ErrorResponse(int(RaftError.EINTERNAL),
                                           repr(e))

        await asyncio.gather(*(run_node(k, v) for k, v in by_node.items()))
        return out

    async def _handle_multi_heartbeat(self, request):
        """Fan a MultiHeartbeatRequest out to the local nodes; each beat
        gets a full per-group response frame, in order."""
        from tpuraft.rpc.messages import (
            ErrorResponse,
            MultiHeartbeatResponse,
            decode_message,
            encode_message,
        )

        import asyncio

        async def one(blob: bytes) -> bytes:
            # concurrent fan-out: each beat takes its own node's lock; a
            # group mid-election (lock held across awaits) must not
            # head-of-line-block the whole batch's ack — the batch only
            # returns when its SLOWEST beat does.  A beat that can't be
            # served promptly answers EBUSY while the real handler keeps
            # running shielded (cancelling a handler mid-step-down would
            # corrupt state); the sender just misses one group's ack for
            # one round, exactly like a dropped direct heartbeat.
            try:
                beat = decode_message(blob)
                key = (beat.group_id, beat.peer_id)
                node = self._nodes.get(key)
                if node is None:
                    raise RpcError(Status.error(
                        RaftError.ENOENT, f"no node for {beat.group_id}"))
                if key in self._beat_inflight:
                    # previous beat still waiting on this node's lock
                    return encode_message(ErrorResponse(
                        int(RaftError.EBUSY), f"{beat.group_id} busy"))
                budget = node.options.election_timeout_ms / 1000.0 / 2
                self._beat_inflight.add(key)
                task = asyncio.ensure_future(
                    node.handle_append_entries(beat))

                def _done(t, key=key):
                    self._beat_inflight.discard(key)
                    if not t.cancelled():
                        t.exception()  # consume if we timed out below

                task.add_done_callback(_done)
                try:
                    resp = await asyncio.wait_for(
                        asyncio.shield(task), budget)
                except asyncio.TimeoutError:
                    resp = ErrorResponse(int(RaftError.EBUSY),
                                         f"{beat.group_id} busy")
            except RpcError as e:
                resp = ErrorResponse(e.status.code, e.status.error_msg)
            except Exception as e:  # noqa: BLE001 — one bad beat only
                LOG.exception("multi_heartbeat beat failed")
                resp = ErrorResponse(int(RaftError.EINTERNAL), repr(e))
            return encode_message(resp)

        acks = await asyncio.gather(*(one(b) for b in request.beats))
        return MultiHeartbeatResponse(acks=list(acks))

    def _make_handler(self, method: str):
        async def handler(request):
            node = self._nodes.get((request.group_id, request.peer_id))
            if node is None:
                raise RpcError(Status.error(
                    RaftError.ENOENT,
                    f"no node for group={request.group_id} peer={request.peer_id}"))
            if method == "append_entries" and request.entries:
                # pipelined replication: a leader keeps a window of
                # AppendEntries in flight; execution here must follow
                # arrival order per (group, leader) or in-window
                # requests would race to the node lock and shuffle,
                # tripping prev-log rejections on every dispatch
                # (reference: AppendEntriesRequestProcessor's
                # per-connection sequence-keyed executors).  EMPTY
                # appends (heartbeats, probes) bypass the lane: a beat
                # must not wait behind a window of synced disk appends
                # (head-of-line blocking would time out ReadIndex SAFE
                # rounds while replication is healthy)
                return await self._ordered_append(node, request)
            return await getattr(node, f"handle_{method}")(request)

        return handler

    async def _ordered_append(self, node: Node, request):
        key = (request.group_id, request.server_id)
        fut = asyncio.get_running_loop().create_future()
        entry = self._append_lanes.get(key)
        if entry is None:
            lane: asyncio.Queue = asyncio.Queue()
            worker = asyncio.ensure_future(self._lane_worker(key, lane))
            entry = self._append_lanes[key] = (lane, worker)
        entry[0].put_nowait((node, request, fut))
        return await fut

    async def _lane_worker(self, key, lane: "asyncio.Queue") -> None:
        idle_reap_s = 60.0
        try:
            while True:
                try:
                    node, req, fut = await asyncio.wait_for(
                        lane.get(), idle_reap_s)
                except asyncio.TimeoutError:
                    if lane.empty():
                        return
                    continue
                try:
                    resp = await node.handle_append_entries(req)
                    if not fut.done():
                        fut.set_result(resp)
                except asyncio.CancelledError:
                    if not fut.done():
                        fut.set_exception(RpcError(Status.error(
                            RaftError.ENODESHUTTING, "lane shut down")))
                    raise
                except Exception as e:  # noqa: BLE001 — per-request error
                    if not fut.done():
                        fut.set_exception(e)
        finally:
            entry = self._append_lanes.get(key)
            if entry is not None and entry[0] is lane:
                del self._append_lanes[key]
                while not lane.empty():
                    _node, _req, fut = lane.get_nowait()
                    if not fut.done():
                        fut.set_exception(RpcError(Status.error(
                            RaftError.ENODESHUTTING, "lane shut down")))

    def add(self, node: Node) -> None:
        self._nodes[(node.group_id, str(node.server_id))] = node

    def remove(self, node: Node) -> None:
        self._nodes.pop((node.group_id, str(node.server_id)), None)
        # tear down this group's append lanes: no worker may linger to
        # execute a queued append against a stopped node, and test
        # teardowns must not see pending-task warnings.  Lanes are keyed
        # by (group, LEADER) and serve every co-hosted node of the
        # group, so only reap once the LAST node of the group leaves —
        # else removing one follower cancels queued appends for its
        # siblings (in-proc topologies host several nodes per server).
        # While siblings remain, still purge THIS node's queued appends:
        # they'd otherwise head-of-line-delay siblings with per-entry
        # EHOSTDOWN rejections and pin the dead node in the queue.
        group_lane_keys = [k for k in self._append_lanes
                           if k[0] == node.group_id]
        if any(g == node.group_id for g, _ in self._nodes):
            for key in group_lane_keys:
                lane, _worker = self._append_lanes[key]
                keep = []
                while not lane.empty():
                    item = lane.get_nowait()
                    if item[0] is node:
                        if not item[2].done():
                            item[2].set_exception(RpcError(Status.error(
                                RaftError.ENODESHUTTING, "node removed")))
                    else:
                        keep.append(item)
                for item in keep:
                    lane.put_nowait(item)
            return
        for key in group_lane_keys:
            lane, worker = self._append_lanes.pop(key)
            worker.cancel()
            while not lane.empty():
                _n, _r, fut = lane.get_nowait()
                if not fut.done():
                    fut.set_exception(RpcError(Status.error(
                        RaftError.ENODESHUTTING, "node removed")))

    def get(self, group_id: str, peer_id: str) -> Optional[Node]:
        return self._nodes.get((group_id, peer_id))

    def list_nodes(self) -> list[Node]:
        return list(self._nodes.values())

    # -- snapshot file service (reference: core:storage/FileService) --------

    def register_file_reader(self, reader) -> int:
        rid = self._next_reader_id
        self._next_reader_id += 1
        self._file_readers[rid] = reader
        return rid

    def unregister_file_reader(self, reader_id: int) -> None:
        self._file_readers.pop(reader_id, None)

    async def _handle_get_file(self, request):
        from tpuraft.rpc.messages import GetFileResponse

        reader = self._file_readers.get(request.reader_id)
        if reader is None:
            raise RpcError(Status.error(
                RaftError.ENOENT, f"no file reader {request.reader_id}"))
        count = request.count
        throttle = getattr(reader, "throttle", None)
        if throttle is not None:
            count = await throttle.acquire_upto(count)
        data, eof = reader.read_file(request.filename, request.offset, count)
        return GetFileResponse(eof=eof, data=data)
