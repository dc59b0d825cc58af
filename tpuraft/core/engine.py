"""MultiRaftEngine: one device tick advances ALL raft groups in a process.

The north-star component (BASELINE.json): the per-group consensus
bookkeeping becomes rows of ``[G, P]`` tensors and ONE jitted
``raft_tick`` (tpuraft.ops.tick) per engine tick computes every group's
commit advancement, election-timeout firing, vote quorums, leader-lease
validity / dead-quorum step-down, and heartbeat scheduling on device —
the full SURVEY §8.1 device plane, not just the commit reduce.

Wiring: host Nodes get their ballot boxes from :meth:`ballot_box_factory`
(the analog of plugging TpuBallotBox through the reference's
``JRaftServiceLoader`` SPI).  With ``TickOptions.drive_protocol`` (the
default), the box also hands the node an :class:`EngineControl` — the
device-plane replacement for the reference's per-group RepeatedTimers
(``electionTimer``/``voteTimer``/``stepDownTimer``), the ``_peer_acks``
map behind ``NodeImpl#checkDeadNodes``, and the per-round vote tally of
``NodeImpl#handleRequestVoteResponse``.  The engine's numpy mirrors are
then the single source of truth for deadlines / acks / votes; the tick's
output masks schedule the slow-path protocol handlers, which re-verify
under the node lock (the host stays the single writer of protocol state,
mirroring NodeImpl's writeLock discipline).

Division of labor per event:
  election_due  -> Node._on_election_due (pre-vote / vote-timeout retry)
  elected       -> Node._on_engine_elected (becomeLeader)
  step_down     -> Node._on_engine_quorum_dead (checkDeadNodes)
  hb_due        -> batched empty-AppendEntries via HeartbeatHub.pulse
  commit        -> TpuBallotBox._advance -> FSMCaller.on_committed

The tick loop is ADAPTIVE: a dirty mark (new ack / vote / deadline
change) fires a tick immediately — commit acks are not quantized to a
fixed cadence — while consecutive ticks self-pace by the previous tick's
cost (a slow device batches more per dispatch).  Idle engines
sleep until the next election/heartbeat deadline, capped at
``tick_interval_ms``.

Index-domain note: the device works in int32 *relative* indexes
(``abs - base[g]``); the engine re-bases a group whenever its relative
window approaches 2^28, so unbounded absolute indexes never overflow.
Times are int32 ms since engine start, epoch-shifted before they near
2^30 (multi-week uptimes never overflow).
"""

from __future__ import annotations

import asyncio
import logging
import random
import time
from typing import Callable, Optional

import numpy as np

from tpuraft.conf import Configuration
from tpuraft.entity import ElectionPriority, PeerId
from tpuraft.errors import RaftError
from tpuraft.options import TickOptions
from tpuraft.util import clock as clockmod
from tpuraft.util.trace import RECORDER as _RECORDER
from tpuraft.util.trace import TRACER as _TRACE
from tpuraft.util.trace import store_proc
from tpuraft.ops.ballot import NEG_INF_I32 as _NEG_I32
from tpuraft.ops.tick import (
    ROLE_CANDIDATE,
    ROLE_FOLLOWER,
    ROLE_INACTIVE,
    ROLE_LEADER,
)

LOG = logging.getLogger(__name__)

_REBASE_LIMIT = 1 << 28
_TIME_REBASE_MS = 1 << 30        # epoch-shift threshold (int32 headroom)
# protocol-param defaults for slots no node has registered yet
_DEF_ETO_MS, _DEF_HB_MS, _DEF_LEASE_MS = 1000, 100, 900
# status code of a leader's step-down -> lane_stats()["leader_stepdowns"]
_STEPDOWN_LANES = {
    int(RaftError.ERAFTTIMEDOUT): "quorum",
    int(RaftError.EHIGHERTERMREQUEST): "term",
    int(RaftError.EHIGHERTERMRESPONSE): "term",
    int(RaftError.ENEWLEADER): "term",
    int(RaftError.ELEADERCONFLICT): "term",
}


class TpuBallotBox:
    """Drop-in for core.ballot_box.BallotBox backed by the engine tensors.

    Mutations write numpy mirrors and mark the engine dirty; quorum math
    happens on device at the next engine tick.
    """

    def __init__(self, engine: "MultiRaftEngine", slot: int,
                 on_committed: Callable[[int], None]):
        self._engine = engine
        self.slot = slot
        self._on_committed = on_committed
        self.last_committed_index = 0
        self.pending_index = 0

    # -- control-plane seam --------------------------------------------------

    def make_control(self, node) -> Optional["EngineControl"]:
        """Hand the node the engine's device control plane (or None to
        keep host timers, when drive_protocol is off)."""
        if not self._engine.opts.drive_protocol:
            return None
        return EngineControl(self._engine, node, self)

    # -- leader side ---------------------------------------------------------

    def reset_pending_index(self, new_pending_index: int) -> None:
        e = self._engine
        self.pending_index = new_pending_index
        e.base[self.slot] = new_pending_index - 1
        e.pending_rel[self.slot] = 1
        e.match_abs[self.slot, :] = 0
        # commit baseline for the device gate `q > commit_now`: nothing of
        # THIS leadership is committed yet (slot may be reused from a
        # prior node)
        e.commit_abs[self.slot] = new_pending_index - 1
        e.role[self.slot] = ROLE_LEADER
        e.mark_dirty()

    def clear_pending(self) -> None:
        self.pending_index = 0
        e = self._engine
        # a controlled slot stays an engine-scheduled follower; a bare
        # box (commit plane only) goes inactive
        e.role[self.slot] = (
            ROLE_FOLLOWER if e.has_ctrl[self.slot] else ROLE_INACTIVE)
        e.match_abs[self.slot, :] = 0

    def commit_at(self, peer: PeerId, match_index: int, conf: Configuration,
                  old_conf: Configuration) -> bool:
        """Record the ack.  With ``TickOptions.eager_commit`` (default)
        the ack that completes a quorum advances the commit point RIGHT
        HERE — one scalar order statistic over this slot's [P] row, the
        same joint math the device tick reduces — instead of waiting
        out the tick pace.  The tick remains the batch plane (and the
        safety net: it recomputes the same value); a hot group's
        quorum closes on the ack path, event-driven, exactly like the
        scalar BallotBox."""
        if self.pending_index == 0:
            return False
        e = self._engine
        col = e.peer_col(self.slot, peer)
        if col is None:
            return False
        if match_index > e.match_abs[self.slot, col]:
            e.match_abs[self.slot, col] = match_index
            if e.opts.eager_commit:
                # the ack path IS the commit tally now — no dirty mark:
                # a per-ack tick would re-reduce all [G] rows just to
                # find the commit this call already advanced (measured:
                # ack-driven ticks were ~2/3 of the loop's CPU at 1024
                # regions under write load).  Deadline-driven work
                # (beats, elections, snapshots) wakes the tick loop on
                # its own clock, and set_conf/role transitions keep
                # their explicit mark_dirty — a conf shrink that
                # advances the quorum without a new ack still gets its
                # discovery tick from set_conf's own mark.
                return e.eager_commit_slot(self.slot)
            e.mark_dirty()
        return False

    def update_conf(self, conf: Configuration, old_conf: Configuration) -> None:
        self._engine.set_conf(self.slot, conf, old_conf)

    def close(self) -> None:
        self._engine.release(self)

    # -- follower side -------------------------------------------------------

    def set_last_committed_index(self, index: int) -> bool:
        if self.pending_index != 0:
            return False
        if index <= self.last_committed_index:
            return False
        self.last_committed_index = index
        self._on_committed(index)
        return True

    # engine callback
    def _advance(self, new_commit: int) -> None:
        if self.pending_index == 0:
            return
        if new_commit > self.last_committed_index:
            self.last_committed_index = new_commit
            self._on_committed(new_commit)


class EngineControl:
    """Per-node handle to the engine's device control plane.

    Replaces, for engine-backed nodes, the reference's per-group timers
    and scalar tallies (SURVEY §3.1 "Timers & queues", §4.3):

      electionTimer/voteTimer  -> elect_deadline[g] + election_due mask
      vote tally (_VoteCtx)    -> granted[g,:] + elected mask
      stepDownTimer/_peer_acks -> last_ack[g,:] + step_down/lease masks
      heartbeat timers/hub tick-> hb_deadline[g] + hb_due mask

    Pre-vote tallies stay host-side scalars by design: the device role
    encoding has no pre-vote state (tpuraft.ops.tick) — pre-vote is a
    rare, transient probe that never mutates durable terms.

    One-off scalar queries (lease_valid for a single read, dead-quorum
    re-verification under the node lock) compute host-side from the SAME
    engine rows the device reduces — one [P] row, not a second copy of
    the state.
    """

    drives_heartbeats = True
    drives_snapshots = True
    # the device tick tallies SAFE ReadIndex rounds (fence_ok lane):
    # ReadConfirmBatcher checks this to skip its host-side per-ack set
    drives_read_fences = True

    def __init__(self, engine: "MultiRaftEngine", node, box: TpuBallotBox):
        self.engine = engine
        self.node = node
        self.slot = box.slot
        opts = node.options
        self._eto_ms = opts.election_timeout_ms
        # the lease is per-NODE (eto x ratio): the engine-wide lease_ms
        # param only feeds the device lease_valid mask, and a node whose
        # eto is shorter than the engine's must not inherit a lease
        # longer than its own election timeout (stale LEASE_BASED reads).
        # The (1 - rho) factor is the clock-drift safety margin (ISSUE
        # 18): the quorum granted us eto*ratio on THEIR clocks; ours may
        # run up to rho fast, so we only trust that fraction of it.
        self._lease_ms = self._lease_ms_of(self._eto_ms)
        self._jitter_range = max(1, min(opts.raft_options.max_election_delay_ms,
                                        self._eto_ms))
        self._jitter = random.randrange(self._jitter_range)
        self._scheduled: set = set()
        self._election_tid = 0      # the open ``election`` span, if sampled
        # quiescence ("hibernate raft") state
        self._quiesce_after = opts.raft_options.quiesce_after_rounds
        self._quiesce_streak = 0
        self._quiesce_await: Optional[set] = None   # peers yet to ack
        self._lease_eps: list[str] = []   # leader: endpoints on the lease
        self._lease_src: Optional[str] = None  # follower: leader's store
        snap_ms = 0
        if opts.snapshot_uri and opts.snapshot.interval_secs > 0:
            snap_ms = opts.snapshot.interval_secs * 1000
        eff = engine.register_ctrl(
            self, node.server_id,
            eto_ms=self._eto_ms,
            hb_ms=max(1, self._eto_ms
                      // opts.raft_options.election_heartbeat_factor),
            lease_ms=self._lease_ms,
            snapshot_ms=snap_ms)
        if eff != self._eto_ms:
            self._adopt_eto(eff)

    def _lease_ms_of(self, eto_ms: int) -> int:
        ro = self.node.options.raft_options
        return int(eto_ms * ro.leader_lease_time_ratio
                   * (1.0 - ro.clock_drift_bound))

    def _adopt_eto(self, eff_eto_ms: int) -> None:
        """The engine's density floor raised this group's effective
        election timeout: adopt it host-side too, so RPC budgets, the
        follower leader-contact lease and jitter all agree with the
        device rows (a host lease shorter than the device timeout would
        re-open the vote guards long before any deadline can fire)."""
        opts = self.node.options
        if eff_eto_ms != opts.election_timeout_ms:
            LOG.info("%s: density floor raised election timeout "
                     "%dms -> %dms", self.node,
                     opts.election_timeout_ms, eff_eto_ms)
            opts.election_timeout_ms = eff_eto_ms
        self._eto_ms = eff_eto_ms
        self._lease_ms = self._lease_ms_of(eff_eto_ms)
        self._jitter_range = max(1, min(
            opts.raft_options.max_election_delay_ms, eff_eto_ms))
        self._jitter = min(self._jitter, self._jitter_range - 1)

    # -- scheduling plumbing (engine tick -> node slow path) -----------------

    def schedule(self, name: str, handler) -> None:
        """Fire-and-dedupe: at most one outstanding handler per event
        kind — the tick may re-emit a mask for several ticks before the
        async handler flips the role."""
        if name in self._scheduled:
            return
        self._scheduled.add(name)

        async def run():
            try:
                await handler()
            except Exception:  # noqa: BLE001 — one group's handler only
                LOG.exception("engine event %s for %s failed",
                              name, self.node)
            finally:
                self._scheduled.discard(name)

        asyncio.ensure_future(run())

    def push_election_deadline(self, now_ms: Optional[int] = None,
                               new_jitter: bool = True) -> None:
        if now_ms is None:
            now_ms = self.engine.now_ms()
        if new_jitter:
            self._jitter = random.randrange(self._jitter_range)
        self.engine.elect_deadline[self.slot] = (
            now_ms + self._eto_ms + self._jitter)

    # -- node-facing API (mirrors TimerControl in tpuraft.core.node) ---------

    def start_follower(self) -> None:
        e = self.engine
        self._clear_quiesce_state()
        e.role[self.slot] = ROLE_FOLLOWER
        self.push_election_deadline()
        e.mark_dirty()

    def note_leader_contact(self) -> None:
        """Hot path (every AppendEntries): push the election deadline.
        Reuses the cached jitter — no RNG per append."""
        self.engine.elect_deadline[self.slot] = (
            self.engine.now_ms() + self._eto_ms + self._jitter)
        if self._election_tid:      # somebody else won
            self._drop_election_span()

    def on_candidate(self) -> None:
        e = self.engine
        self._clear_quiesce_state()
        e.tick_hists["elections_started"].update(
            self.node.current_term + 1)
        e.role[self.slot] = ROLE_CANDIDATE
        self.push_election_deadline()   # vote-round timeout
        e.mark_dirty()

    def stop_vote_wait(self) -> None:
        pass  # deadline is inert once the role leaves CANDIDATE

    def note_vote_round_lost(self) -> None:
        self.engine.tick_hists["vote_rounds_lost"].update(
            self.node.current_term)

    def note_election_yielded(self) -> None:
        self.engine.tick_hists["elections_yielded"].update(
            self.node.current_term)

    def note_leader_transfer(self) -> None:
        self.engine.tick_hists["leader_transfers"].update(
            self.node.current_term)

    def note_election_due(self) -> None:
        """The tick fired ``election_due`` for this row: where a sampled
        group's ``election`` span begins; ``on_leader`` ends it."""
        if _TRACE.enabled and not self._election_tid:
            self._election_tid = _TRACE.begin_op(
                "election", proc=store_proc(self.node.server_id))

    def _drop_election_span(self) -> None:
        if self._election_tid:
            _TRACE.abandon_op(self._election_tid)
            self._election_tid = 0

    def start_vote_round(self) -> bool:
        """Clear the vote row, grant self.  Returns True when self alone
        is a quorum (single-voter group) — the engine's elected mask
        handles the multi-voter async case."""
        e = self.engine
        e.granted[self.slot, :] = False
        col = e.peer_col(self.slot, self.node.server_id)
        if col is not None:
            e.granted[self.slot, col] = True
        e.mark_dirty()
        return self.vote_quorum_now()

    def grant_vote(self, peer: PeerId) -> bool:
        """Record a granted vote.  Always returns False: the tally is the
        device tick's elected mask (-> Node._on_engine_elected)."""
        e = self.engine
        col = e.peer_col(self.slot, peer)
        if col is not None:
            e.granted[self.slot, col] = True
            e.mark_dirty()
        return False

    def vote_quorum_now(self) -> bool:
        """Host-side row check of the SAME granted/voter rows the device
        reduces — used to confirm `elected` under the node lock."""
        e, s = self.engine, self.slot
        g, vm, ovm = e.granted[s], e.voter_mask[s], e.old_voter_mask[s]

        def ok(mask):
            n = int(mask.sum())
            return n > 0 and int((g & mask).sum()) >= n // 2 + 1

        return ok(vm) and (not ovm.any() or ok(ovm))

    def on_leader(self) -> None:
        e, s = self.engine, self.slot
        now = e.now_ms()
        self._clear_quiesce_state()
        e.role[s] = ROLE_LEADER
        # grace period (reference: becomeLeader resets the replicators'
        # lastRpcSendTimestamp): every peer counts as freshly acked, so
        # dead-quorum step-down fires one full election timeout later,
        # not instantly on a fresh leader with silent followers
        e.last_ack[s, :] = now
        e.hb_deadline[s] = now       # beat on the next tick
        # periodic stepdown/priority cadence (the reference's
        # stepDownTimer at eto/2): first check one half-timeout out
        e.stepdown_deadline[s] = now + max(1, self._eto_ms // 2)
        e.granted[s, :] = False
        e.mark_dirty()
        if self._election_tid:
            _TRACE.end_op(self._election_tid, term=self.node.current_term)
            self._election_tid = 0

    def on_step_down(self, was_candidate: bool, was_leader: bool,
                     status=None) -> None:
        self._clear_quiesce_state()
        e = self.engine
        e.granted[self.slot, :] = False
        if was_leader:
            code = status.code if status is not None else 0
            e.tick_hists["leader_stepdowns"].update(code)
            e.leader_stepdowns[_STEPDOWN_LANES.get(code, "other")] += 1

    def on_follower(self) -> None:
        self.start_follower()

    def priority_rounds_accrue(self) -> bool:
        """Does a stepdown round of this node do more than re-verify
        the quorum (Node._maybe_priority_transfer's first gate)?"""
        node = self.node
        return (node.server_id.priority != ElectionPriority.DISABLED
                and node.options.raft_options.priority_transfer_rounds > 0)

    # -- ack bookkeeping (replaces Node._peer_acks) --------------------------

    def record_ack(self, peer: PeerId, when: float) -> None:
        e = self.engine
        col = e.peer_col(self.slot, peer)
        if col is not None:
            ms = e.to_ms(when)
            if ms > e.last_ack[self.slot, col]:
                e.last_ack[self.slot, col] = ms
                e._input_seq += 1
                # acks deliberately don't wake the tick (eager_commit
                # note in TpuBallotBox.commit_at) — EXCEPT while a read
                # fence is pending: its resolution IS this tick's q_ack
                # reduction, so the ack that completes the fence quorum
                # must drive a tick instead of waiting out a deadline.
                # With a tick in flight the question waits for its end
                # (_tick_end asks it for every ack it has not seen): it
                # may confirm the fence without this ack
                if e.fence_start[self.slot] > _NEG_I32 \
                        and e._flight is None:
                    e.mark_dirty()

    # -- device read-fence plane (ReadConfirmBatcher rounds) -----------------

    def arm_read_fence(self, fence) -> None:
        """Register a pending SAFE ReadIndex round: the device tick's
        fence_ok lane calls ``fence.note_quorum()`` once the fused q_ack
        reduction reaches the round's start time.  ``fence`` needs
        ``note_quorum()`` and a ``done`` property (store_engine's
        _GroupFence); round-timeout cleanup stays with the caller."""
        self.engine.arm_read_fence(self.slot, fence)

    def _quorum_ack_ms(self) -> int:
        """q-th newest voter ack (joint-consensus aware), host-side from
        the engine row.  Counts self as acked now."""
        e, s = self.engine, self.slot
        now = e.now_ms()
        col = e.peer_col(s, self.node.server_id)
        row = e.last_ack[s].copy()
        if col is not None:
            row[col] = now

        def q_ack(mask):
            vals = np.sort(row[mask])[::-1]
            n = vals.size
            return int(vals[n // 2]) if n else _NEG_I32

        q = q_ack(e.voter_mask[s])
        if e.old_voter_mask[s].any():
            q = min(q, q_ack(e.old_voter_mask[s]))
        return q

    def quorum_ack_age_s(self) -> float:
        q = self._quorum_ack_ms()
        if q <= _NEG_I32:
            return float("inf")
        return max(0.0, (self.engine.now_ms() - q) / 1000.0)

    def lease_valid(self) -> bool:
        # a suspect local clock invalidates every timing argument the
        # lease rests on: fail closed (reads fall back to SAFE quorum
        # confirmation, which is clock-independent) — ISSUE 18
        sentinel = self.node.options.clock_sentinel
        if sentinel is not None and not sentinel.lease_check():
            return False
        e = self.engine
        # device lane fast path: the last tick's fused q_ack reduction
        # (ops/tick.py lease_valid lane) is a LOWER bound on the current
        # quorum-ack time — acks only ever arrive — so a lease check
        # that passes against it is sound without copying+sorting the
        # [P] row per read.  A miss (stale row, ack between ticks, or
        # genuinely expired) falls back to the exact host-side check.
        q = int(e.tick_q_ack[self.slot])
        if q > _NEG_I32 and e.now_ms() - q < self._lease_ms:
            e.lease_lane_hits += 1
            return True
        e.lease_lane_misses += 1
        if (e.now_ms() - self._quorum_ack_ms()
                < self._lease_ms):
            return True
        # quiescent leader: its per-group ack stream is suppressed, so
        # the store-level lease IS the leader lease (LEASE_BASED reads /
        # dead-quorum re-verification consult it through here).  The
        # rows are normally refreshed by note_store_ack, but an ack
        # landing between ticks must not fail a read spuriously.
        return self.is_quiescent() and self.store_lease_quorum_ok()

    def alive_peers(self) -> list[PeerId]:
        e, s = self.engine, self.slot
        horizon = e.now_ms() - self._eto_ms
        out = []
        for peer in self.node.list_peers():
            if peer == self.node.server_id:
                out.append(peer)
                continue
            col = e.peer_col(s, peer)
            if col is not None and e.last_ack[s, col] > horizon:
                out.append(peer)
        return out

    # -- quiescence ("hibernate raft") ---------------------------------------
    # A fully-replicated idle leader group hibernates after N consecutive
    # fully-acked beat rounds: the device masks skip it (hb_due /
    # election_due), its followers suppress election timeouts, and
    # liveness is delegated to ONE store-level lease beat per endpoint
    # pair (HeartbeatHub) — idle beat traffic collapses from O(G x P)
    # rows to O(stores^2) RPCs.  Any apply / conf change / vote request /
    # incoming entries instantly wakes the group; a store-lease expiry
    # wakes its dependents with randomized election timeouts.

    def is_quiescent(self) -> bool:
        return bool(self.engine.quiescent[self.slot])

    def note_activity(self) -> None:
        """Hot-path hook on protocol activity (apply staged, vote
        request, entries received): one array read when awake."""
        if self.engine.quiescent[self.slot]:
            self.wake_from_quiescence("activity")

    def _hub(self):
        nm = self.node.node_manager
        return None if nm is None else nm.heartbeat_hub

    def maybe_quiesce(self, now: int) -> None:
        """Called by the engine on every hb_due round for this (awake,
        leader) slot: track the idle streak; at the threshold this
        round's beats carry the quiesce handshake (hub.pulse reads the
        per-replicator intent), and the group hibernates only once
        EVERY follower acked — a refusal keeps it active, because a
        follower with a live election timer must keep receiving beats."""
        if self._quiesce_after <= 0 or self.engine.quiescent[self.slot]:
            return
        if not self._quiesce_eligible(now):
            self._quiesce_streak = 0
            self._quiesce_await = None
            return
        self._quiesce_streak += 1
        if self._quiesce_streak < self._quiesce_after:
            return
        reps = self.node.replicators.all()
        if not reps:
            # single-voter group: nobody to hand-shake, no lease needed
            # (its own self-ack keeps step_down quiet) — hibernate now
            self._finalize_quiesce()
            return
        self._quiesce_await = {r.peer for r in reps}
        for r in reps:
            r._quiesce_lease_ms = self._eto_ms

    def _quiesce_eligible(self, now: int) -> bool:
        """No pending appends, full match at the tail, not mid-change,
        every voter freshly acked — the 'provably idle' predicate."""
        node = self.node
        if node.node_manager is None or node.state.name != "LEADER":
            return False
        if node._conf_ctx is not None:
            return False
        e, s = self.engine, self.slot
        if e.old_voter_mask[s].any():
            return False
        tail = node.log_manager.last_log_index()
        if node.ballot_box.last_committed_index != tail:
            return False
        reps = node.replicators.all()
        for r in reps:
            if (not r._matched or r.retiring or r.match_index < tail
                    or not r.peer_multi_hb):
                return False
        if reps:
            # every voter acked within the last two beat intervals
            horizon = now - 2 * int(e.hb_ms[s]) - 50
            row, mask = e.last_ack[s], e.voter_mask[s].copy()
            col = int(e.self_col[s])
            if 0 <= col < mask.size:
                mask[col] = False
            if mask.any() and bool((row[mask] < horizon).any()):
                return False
        return True

    def note_quiesce_ack(self, peer: PeerId) -> None:
        """A follower acked a quiesce-handshake beat."""
        aw = self._quiesce_await
        if aw is None:
            return
        aw.discard(peer)
        if not aw:
            self._quiesce_await = None
            self._finalize_quiesce()

    def abort_quiesce(self) -> None:
        """A follower refused (or the fast path fell back): stay active."""
        self._quiesce_await = None
        self._quiesce_streak = 0

    def _finalize_quiesce(self) -> None:
        e, s = self.engine, self.slot
        node = self.node
        if e.quiescent[s] or node.node_manager is None:
            return
        if not self._quiesce_eligible(e.now_ms()):
            # an apply raced the handshake acks: stay active
            self._quiesce_streak = 0
            return
        e.quiescent[s] = True
        e.quiesce_events += 1
        # coalesced: a hibernation sweep at region density flips
        # thousands of groups at once — per-group rows would evict the
        # whole ring (the steady trickle keeps its per-group detail)
        _RECORDER.record_coalesced("quiesce", node.group_id,
                                   per_group=False,
                                   node=str(node.server_id),
                                   role="leader")
        hub = node.node_manager.heartbeat_hub
        hub.groups_quiesced += 1
        eps = sorted({r.peer.endpoint for r in node.replicators.all()})
        self._lease_eps = eps
        src = node.server_id.endpoint
        for ep in eps:
            hub.lease_add(ep, e, node.transport, src, self._eto_ms)
        e.note_quiesce_leader(s)

    def enter_quiescent_follower(self, leader_endpoint: str,
                                 lease_ms: int) -> bool:
        """The leader proposed hibernation via a quiesce beat and this
        node matched its row at the tail: suppress the election timeout
        and ride the leader store's liveness lease instead."""
        node = self.node
        e, s = self.engine, self.slot
        if node.node_manager is None:
            return False
        if e.quiescent[s]:
            return True
        e.quiescent[s] = True
        e.quiesce_events += 1
        _RECORDER.record_coalesced("quiesce", node.group_id,
                                   per_group=False,
                                   node=str(node.server_id),
                                   role="follower", src=leader_endpoint)
        self._lease_src = leader_endpoint
        hub = node.node_manager.heartbeat_hub
        hub.groups_quiesced += 1
        hub.lease_depend(leader_endpoint, self, lease_ms or self._eto_ms)
        return True

    def wake_from_quiescence(self, reason: str = "activity",
                             lease_expired: bool = False) -> None:
        e, s = self.engine, self.slot
        if not e.quiescent[s]:
            return
        _RECORDER.record_coalesced("wake", self.node.group_id,
                                   per_group=False,
                                   node=str(self.node.server_id),
                                   reason=reason)
        now = e.now_ms()
        # a follower waking under a FRESH store lease (e.g. a vote
        # solicitation from a restarted peer) must carry the delegated
        # liveness proof back into the per-group guard: clearing the
        # quiescent state kills quiescent_leader_alive(), and the raw
        # _last_leader_timestamp went stale by design while hibernating
        # — without this refresh the vote guards would swing open the
        # moment a group wakes, letting one restarted store depose
        # every healthy hibernating leader it pre-votes against
        leader_alive = self.quiescent_leader_alive()
        self._clear_quiesce_state()
        if leader_alive:
            self.node._last_leader_timestamp = self.node._clock.monotonic()
        if e.role[s] == ROLE_LEADER:
            e.hb_deadline[s] = now   # beat NOW; followers wake on it
        else:
            self._jitter = random.randrange(self._jitter_range)
            # store-lease expiry wakes WHOLE stores' worth of groups at
            # once: spread their elections over an extra full timeout so
            # the herd stays under the host's election capacity
            extra = random.randrange(self._eto_ms) if lease_expired else 0
            e.elect_deadline[s] = now + self._eto_ms + self._jitter + extra
        e.mark_dirty()

    def wake_for_lease_expiry(self) -> None:
        """Hub lease watcher: the store this group's (quiescent) leader
        lives on went silent past its lease — resume fault detection."""
        self.wake_from_quiescence("store-lease-expiry", lease_expired=True)

    def _clear_quiesce_state(self) -> None:
        e, s = self.engine, self.slot
        was = bool(e.quiescent[s])
        e.quiescent[s] = False
        self._quiesce_streak = 0
        self._quiesce_await = None
        hub = self._hub()
        if self._lease_eps:
            e.note_wake_leader(s)
            if hub is not None:
                for ep in self._lease_eps:
                    hub.lease_remove(ep, e)
            self._lease_eps = []
        if self._lease_src is not None:
            if hub is not None:
                hub.lease_undepend(self._lease_src, self)
            self._lease_src = None
        if was:
            e.wake_events += 1
            if hub is not None:
                hub.groups_woken += 1

    def quiescent_leader_alive(self) -> bool:
        """Follower-side vote-guard consult: while hibernating, 'my
        leader is alive' means 'its store's lease is fresh' — the
        per-group leader-contact timestamp legitimately goes stale."""
        e, s = self.engine, self.slot
        if not e.quiescent[s] or self._lease_src is None:
            return False
        hub = self._hub()
        return hub is not None and hub.lease_fresh(self._lease_src)

    def store_lease_quorum_ok(self) -> bool:
        """Leader-side lease-read consult for a QUIESCENT group: fresh
        store-lease acks must cover a voter quorum (the per-group ack
        stream is suppressed, so the store lease IS the leader lease)."""
        node = self.node
        hub = self._hub()
        if hub is None:
            return False
        voters = node.list_peers()
        if not voters:
            return False
        ok = sum(1 for p in voters
                 if p == node.server_id
                 or hub.lease_ack_fresh(p.endpoint, self._lease_ms))
        return ok >= len(voters) // 2 + 1

    # -- lifecycle -----------------------------------------------------------

    def deactivate(self) -> None:
        self._clear_quiesce_state()
        self.engine.role[self.slot] = ROLE_INACTIVE

    def shutdown(self) -> None:
        self._drop_election_span()
        self.deactivate()
        self.engine.unregister_ctrl(self.slot)


class _NpOutputs:
    """TickOutputs as numpy rows: what the numpy twin returns, and what
    ``_fetch`` unpacks the device tick's one downloaded buffer into."""

    __slots__ = ("commit_rel", "commit_advanced", "elected", "election_due",
                 "step_down", "hb_due", "lease_valid", "snap_due", "q_ack",
                 "stepdown_due", "fence_ok")

    def __init__(self, **kw):
        for k, v in kw.items():
            setattr(self, k, v)


class _Flight:
    """A tick between its halves: ``_tick_begin`` enqueued the program
    and ``_tick_end`` has not collected it yet.  ``out`` is the call's
    result (on a device: maybe not computed yet), ``now`` the snapshot's
    time, ``gen`` the layout generation it was taken under, ``base``
    the log bases its relative indexes hang on, ``seq`` the engine's
    input count at the snapshot, ``t0`` / ``t1`` / ``ts`` / ``tc``
    begin's clock reads (entry, views built, state built, call
    returned).  ``done`` and ``advanced`` are end's; ``fut``, if
    anybody but the caller that began it waits for its end, takes
    ``advanced`` there."""

    __slots__ = ("out", "now", "gen", "base", "seq", "t0", "t1", "ts", "tc",
                 "done", "advanced", "fut")

    def __init__(self, out, now, gen, base, seq, t0, t1, ts, tc):
        self.out, self.now, self.gen, self.base = out, now, gen, base
        self.seq = seq
        self.t0, self.t1, self.ts, self.tc = t0, t1, ts, tc
        self.done = False
        self.advanced = 0
        self.fut: Optional[asyncio.Future] = None


class MultiRaftEngine:
    """Per-process batched consensus plane.  Start once, register each
    node's ballot box through :meth:`ballot_box_factory`."""

    def __init__(self, opts: Optional[TickOptions] = None):
        self.opts = opts or TickOptions()
        g, p = self.opts.max_groups, self.opts.max_peers
        self.G, self.P = g, p
        # numpy mirrors (host-owned truth between ticks) — commit plane.
        # Every [G]-leading row below is a LANE under graftcheck's
        # lane-coverage rule: it must be handled at _grow (pad), release
        # (slot reset), set_conf (conf re-map/invalidation) and
        # _maybe_time_rebase (time epoch shift), or carry a reasoned
        # `# lane: no-<site>` waiver here on its declaration.
        # lane: no-shift — log-index domain (rebased by _rebase, not the
        # time epoch)
        self.match_abs = np.zeros((g, p), np.int64)
        # lane: no-conf no-shift — per-group log base, conf-independent
        self.base = np.zeros(g, np.int64)
        # lane: no-conf no-shift — leadership window, reset by
        # reset_pending_index on role transitions; log-index domain
        self.pending_rel = np.ones(g, np.int32)
        self.voter_mask = np.zeros((g, p), bool)    # lane: no-shift — bool mask
        self.old_voter_mask = np.zeros((g, p), bool)  # lane: no-shift — bool mask
        # lane: no-conf no-shift — absolute committed index; a conf
        # change never moves what is already committed
        self.commit_abs = np.zeros(g, np.int64)
        # protocol plane (SURVEY §8.1): roles, deadlines, acks, votes
        # lane: no-conf no-shift — host-applied role transitions only
        # (set_conf never changes who leads); not time-valued
        self.role = np.full(g, ROLE_INACTIVE, np.int32)
        # lane: no-conf — deadlines re-arm on role transitions and leader
        # contact, not on membership changes
        self.elect_deadline = np.zeros(g, np.int64)
        # lane: no-conf — beat cadence is role-driven; set_conf's fresh
        # peers get their grace stamp through last_ack instead
        self.hb_deadline = np.zeros(g, np.int64)
        self.last_ack = np.full((g, p), _NEG_I32, np.int64)
        self.granted = np.zeros((g, p), bool)   # lane: no-shift — bool votes
        # lane: no-shift — column index, not time-valued
        self.self_col = np.full(g, -1, np.int32)
        # lane: no-conf no-shift — registration bit (register_ctrl /
        # unregister_ctrl own it); not time-valued
        self.has_ctrl = np.zeros(g, bool)
        # quiescence ("hibernate raft"): a True row suppresses the
        # group's hb_due/election_due masks on device; liveness rides
        # the store-level lease (HeartbeatHub).  Host-owned like role.
        # lane: no-conf no-shift — set_conf wakes a hibernating group
        # THROUGH EngineControl.wake_from_quiescence (which clears this
        # row and the hub lease bookkeeping together — a bare row write
        # here would leak the lease); not time-valued
        self.quiescent = np.zeros(g, bool)
        # read plane: the last tick's fused q_ack reduction ([G] q-th
        # newest voter ack, ms).  Acks only ever arrive, so a stale row
        # is a LOWER bound on the true quorum-ack time — a lease check
        # that passes against it is sound, and one that fails falls back
        # to the exact host-side [P] sort (EngineControl.lease_valid).
        self.tick_q_ack = np.full(g, _NEG_I32, np.int64)
        self.lease_lane_hits = 0     # lease reads answered off the row
        self.lease_lane_misses = 0   # fell back to the host-side sort
        # witness voters (either config): metadata-only replicas — they
        # vote and ack, but the device commit reduce clamps to the best
        # DATA-replica match (ballot.witness_commit_clamp).
        # lane: no-shift — bool mask
        self.witness_mask = np.zeros((g, p), bool)
        self._n_witness_slots = 0    # steady-state clamp skip when zero
        # periodic stepdown/priority lane (the reference's stepDownTimer,
        # eto/2): fires Node._check_dead_nodes for engine leaders —
        # dead-quorum re-verification AND priority_transfer_rounds
        # accrual (decay-elected leaders hand leadership back).
        # lane: no-conf — re-armed on leadership transitions (on_leader)
        # and every fire, never by membership changes
        self.stepdown_deadline = np.zeros(g, np.int64)
        self.stepdown_ticks = 0      # stepdown_due fires applied
        # device read-fence plane: earliest pending ReadConfirmBatcher
        # round start per slot (NEG = none); the tick's fence_ok lane
        # resolves rounds against the fused q_ack reduction instead of a
        # host-side per-ack set tally.
        self.fence_start = np.full(g, _NEG_I32, np.int64)
        self._fence_waiters: dict[int, list] = {}  # slot -> [(start, fence)]
        self.fence_lane_armed = 0    # rounds armed on the device lane
        self.fence_lane_resolves = 0  # rounds resolved by fence_ok
        # store-lease plumbing for QUIESCENT LEADER slots: endpoint ->
        # {slot: [cols]} of last_ack cells refreshed by one store-lease
        # ack from that endpoint (flattened index arrays cached per
        # endpoint) — dead-quorum step-down and leader-lease reads for
        # hibernating groups consult the store lease through these rows.
        self._lease_cols: dict[str, dict[int, list[int]]] = {}
        self._lease_arrays: dict[str, tuple[np.ndarray, np.ndarray]] = {}
        self.quiesce_events = 0   # groups that entered hibernation
        self.wake_events = 0      # groups woken (activity / lease expiry)
        self._peer_cols: list[dict[PeerId, int]] = [dict() for _ in range(g)]
        self._boxes: list[Optional[TpuBallotBox]] = [None] * g
        self._ctrls: list[Optional[EngineControl]] = [None] * g
        self._ctrl_server: list[Optional[PeerId]] = [None] * g
        self._free = list(range(g - 1, -1, -1))
        self._dirty = False
        self._dirty_event = asyncio.Event()
        # perf_counter of the mark_dirty that set the event: a dirty
        # wake's lateness counts from here (tick_late_ms)
        self._dirty_at = 0.0
        # how late the loop resumed the tick task from its last wait
        self._late_s = 0.0
        self._task: Optional[asyncio.Task] = None
        self._stopped = False
        self._tick_fn = None  # jitted raft_tick outputs (None => numpy path)
        # single-device jax path: the ONE host array a tick uploads
        # (ops/tick.py pack_state), allocated on first use and again
        # after _grow.  Reused from tick to tick only because _fetch has
        # waited for the program before the next tick begins (ONE tick
        # in flight an engine: _flight): on the CPU backend JAX may
        # alias host memory instead of copying it.
        self._packed = False
        self._tick_buf: Optional[np.ndarray] = None
        # the tick between its halves (tick(): begun in one loop turn,
        # collected in the next), and the future of the tick AFTER it,
        # which every caller shares that asks during the flight for
        # something its snapshot lacks
        self._flight: Optional[_Flight] = None
        self._next_tick: Optional[asyncio.Future] = None
        # what a snapshot can lack: counts every dirty mark and every
        # ack that moved a last_ack row.  A flight whose count is still
        # the engine's has seen all a caller could be asking for, and
        # once it has landed (_seq_served) so has the loop's dirty mark
        self._input_seq = 0
        self._seq_served = -1
        # what a flight's snapshot was taken under: bumped wherever a
        # row changes its owner or its columns (_grow, alloc_slot,
        # release, unregister_ctrl, set_conf, a _rebase that moves a
        # base).  An output that comes back under another generation is
        # dropped whole: its row may belong to another group now
        self._layout_gen = 0
        self.ticks_dropped = 0   # outputs dropped for that (or at a stop)
        # the last tick's own seconds (begin + end, not the loop turn
        # between them): what _loop paces by and feeds the density floor
        self._tick_own_s = 0.0
        # arrays the last tick moved across the host/device boundary
        self._tick_transfers = 0
        self._deadline_fold = None  # mesh mode: sharded earliest-deadline min
        self._params_dev = None
        self.ticks = 0
        self.tick_failures = 0   # ticks that raised inside _loop
        self.commit_advances = 0
        # event-driven commit advancement (TickOptions.eager_commit):
        # quorums closed on the ack path by eager_commit_slot, without
        # waiting for the next device tick
        self.eager_commits = 0
        # device-tick profiling (fleet observability): per-tick wall
        # time attributed to the three phases every tick pays — host
        # mirror upkeep and the relative views (build), the device
        # phase (GroupState build + jit call + wait and download of the
        # outputs, or the numpy twin), host apply (commit callbacks +
        # protocol scheduling).  Always on: locked histogram updates
        # per TICK (not per op) — ticks are paced by their own cost, so
        # this stays noise even at max cadence.  The benchmark bounds
        # what it costs end to end (BENCHMARK.json, PERF.md).
        from tpuraft.util.metrics import Histogram
        self.tick_hists = {
            "tick_total_ms": Histogram(),
            "tick_build_ms": Histogram(),
            "tick_device_ms": Histogram(),
            "tick_apply_ms": Histogram(),
            # tick_device_ms in its three parts (they sum to it: the
            # same clock reads): GroupState build from the mirrors; the
            # jitted call (pack into the one upload buffer + its
            # transfer + enqueue; the whole numpy twin on that backend);
            # wait for the device + the one download and its unpacking
            "tick_state_ms": Histogram(),
            "tick_call_ms": Histogram(),
            "tick_fetch_ms": Histogram(),
            # ticks collected a loop turn after they were enqueued
            # (tick(): one sample each, so ``count`` is the number), of
            # them those whose output was there before the fetch asked
            # (``is_ready()``), and per such tick the time from the
            # enqueue to the start of the collecting half
            "tick_overlapped": Histogram(),
            "tick_ready": Histogram(),
            "tick_inflight_ms": Histogram(),
            # NOT per tick but per ARRAY that crosses the host/device
            # boundary (host arrays handed to the call + arrays
            # downloaded), sampled with its bytes: count / ticks is 2
            # packed, 27 over the mesh, 0 on the numpy twin.  The count
            # is what a tick pays for, the bytes are not (PERF.md 6)
            "tick_transfers": Histogram(),
            # _flush_heartbeats inside tick_apply_ms (0 on a tick with
            # no beat due)
            "tick_heartbeat_ms": Histogram(),
            # how late the event loop resumed the tick task from the
            # wait before this tick: past the time it asked for (timed
            # wake) or past the mark_dirty that woke it
            "tick_late_ms": Histogram(),
            # per resolved device read fence, arm to the tick that
            # resolved it; per waiter, so only while tracing is on
            "fence_resolve_ms": Histogram(),
            # NOT times but events, one sample each, so ``count`` over a
            # window is the number (the benchmark's summary line keeps
            # every histogram's count): real elections this engine's
            # nodes started (term bumped; sample = the new term),
            # leaders that stepped down (sample = the status code;
            # lane_stats splits them by lane), beat rows handed to the
            # hub or sent direct by _flush_heartbeats
            "elections_started": Histogram(),
            "leader_stepdowns": Histogram(),
            # vote rounds that ended with no winner (the candidate's
            # round timed out): a split vote, or no quorum reachable
            "vote_rounds_lost": Histogram(),
            # pre-vote quorums not acted on: the node had granted a
            # higher-ranked rival's pre-vote for the same term
            "elections_yielded": Histogram(),
            # leaderships this engine's nodes GAINED through TimeoutNow
            # (a transfer, not a timeout; each is one of
            # elections_started too; sample = the term)
            "leader_transfers": Histogram(),
            "beat_rows": Histogram(),
        }
        # leader step-downs by what fired: "quorum" = dead-quorum check
        # (the device's step_down mask or the stepdown_due cadence, both
        # through Node._check_dead_nodes), "term" = a higher term or a
        # new leader seen, "other" = transfer, storage error, removal
        self.leader_stepdowns = {"quorum": 0, "term": 0, "other": 0}
        self._hb_flush_s = 0.0
        # protocol params: [G] rows — each registered node's NodeOptions
        # timeouts apply to ITS groups only (mixed-timeout engines, e.g.
        # a PD group + region groups in one process, run correct
        # per-group constants; was engine-wide first-node-wins pre-r3).
        # lane: no-conf no-shift — registration-derived parameters
        # (register_ctrl + the density floor own them); they are
        # durations, not absolute times, so the epoch shift skips them
        self.eto_ms = np.full(g, _DEF_ETO_MS, np.int64)
        # lane: no-conf no-shift — same registration-derived duration row
        self.hb_ms = np.full(g, _DEF_HB_MS, np.int64)
        # lane: no-conf no-shift — same registration-derived duration row
        self.lease_ms = np.full(g, _DEF_LEASE_MS, np.int64)
        # density-aware timeout floors: the REQUESTED NodeOptions values
        # per slot; the effective rows above are max(requested, derived
        # floor) with hb/lease scaled proportionally.  The floor grows
        # with registered group count and the measured tick cost, so a
        # 16K-group process lands on a safe operating point without the
        # hand-tuned 60s timeouts that density used to need.
        # lane: no-conf no-shift — requested durations (register_ctrl
        # writes them; conf changes and the time epoch never do)
        self.req_eto_ms = np.full(g, _DEF_ETO_MS, np.int64)
        # lane: no-conf no-shift — same requested-duration row
        self.req_hb_ms = np.full(g, _DEF_HB_MS, np.int64)
        # lane: no-conf no-shift — same requested-duration row
        self.req_lease_ms = np.full(g, _DEF_LEASE_MS, np.int64)
        self._floor_applied_ms = 0
        self._tick_cost_ema_s = 0.0
        # the floor derivation scans every registered slot, so it runs
        # only at geometric registration counts (the floor is ~linear
        # in n, and the apply gate already tolerates 25% staleness) —
        # a 16K-group boot pays O(G) total floor work, not O(G^2)
        self._n_ctrls = 0
        self._floor_cached_ms = 0
        self._floor_next_n = 0
        # engine-scheduled snapshot cadence (the reference's 4th timer,
        # snapshotTimer): [G] interval row (0 = disabled) + deadline row
        # replace G per-group RepeatedTimers; fires staggered by jitter.
        # lane: no-conf no-shift — interval duration owned by
        # register_ctrl; membership changes don't move the cadence
        self.snap_ms = np.zeros(g, np.int64)
        # lane: no-conf — snapshot cadence is registration-driven, not
        # membership-driven (the deadline row IS epoch-shifted)
        self.snap_deadline = np.zeros(g, np.int64)
        # injectable store clock (ISSUE 18): the engine's whole time
        # plane — deadlines, ack stamps, leases — runs on this clock,
        # so a ChaosClock skews the STORE exactly like a bad machine
        self._clock = clockmod.resolve(self.opts.clock)
        self._t0 = self._clock.monotonic()

    # -- time ----------------------------------------------------------------

    def now_ms(self) -> int:
        return int((self._clock.monotonic() - self._t0) * 1000)

    def to_ms(self, monotonic_time: float) -> int:
        return int((monotonic_time - self._t0) * 1000)

    def _maybe_time_rebase(self, now: int) -> None:
        """Shift the time epoch before int32 ms overflows (~12 days)."""
        if now < _TIME_REBASE_MS:
            return
        shift = now - int(self.eto_ms.max()) * 4
        self._t0 += shift / 1000.0
        self.elect_deadline -= shift
        self.hb_deadline -= shift
        self.snap_deadline -= shift
        self.stepdown_deadline -= shift
        np.maximum(self.last_ack - shift, _NEG_I32, out=self.last_ack)
        np.maximum(self.tick_q_ack - shift, _NEG_I32, out=self.tick_q_ack)
        # NEG rows stay NEG (no fence pending); armed rows shift with
        # the epoch like the ack stamps they are compared against
        np.maximum(self.fence_start - shift, _NEG_I32, out=self.fence_start)
        for waiters in self._fence_waiters.values():
            waiters[:] = [(max(start - shift, _NEG_I32 + 1), fence)
                          for start, fence in waiters]

    # -- registry ------------------------------------------------------------

    def ballot_box_factory(self):
        """Returns a factory usable as Node(ballot_box_factory=...)."""

        def make(on_committed: Callable[[int], None]) -> TpuBallotBox:
            slot = self.alloc_slot()
            box = TpuBallotBox(self, slot, on_committed)
            self._boxes[slot] = box
            return box

        return make

    def register_ctrl(self, ctrl: EngineControl, server_id: PeerId,
                      eto_ms: int, hb_ms: int, lease_ms: int,
                      snapshot_ms: int = 0) -> int:
        """Register a node's control plane.  Returns the EFFECTIVE
        election timeout for the slot — the requested value raised to the
        engine's density floor when the process hosts more groups than
        the requested timeout can beat within the cpu budget."""
        s = ctrl.slot
        self._ctrls[s] = ctrl
        self._ctrl_server[s] = server_id
        self.has_ctrl[s] = True
        col = self._peer_cols[s].get(server_id)
        self.self_col[s] = -1 if col is None else col
        self.req_eto_ms[s], self.req_hb_ms[s], self.req_lease_ms[s] = \
            eto_ms, hb_ms, lease_ms
        self._n_ctrls += 1
        if self._n_ctrls >= self._floor_next_n:
            self._floor_cached_ms = self._density_floor_ms()
            self._floor_next_n = int(self._n_ctrls * 1.25) + 1
        floor = self._floor_cached_ms
        if floor > self._floor_applied_ms * 1.25:
            # the floor grew materially (more groups / slower ticks):
            # re-derive every controlled slot's effective rows.  Gated
            # to >25% growth so a 16K-registration boot costs O(G log G)
            # row rewrites, not O(G^2).
            self._floor_applied_ms = floor
            self._reapply_floor()
        else:
            self._apply_floor_slot(s)
        self.snap_ms[s] = snapshot_ms
        if snapshot_ms > 0:
            # first due staggered over [0.5, 1.5) intervals: groups
            # registered together must not snapshot as one herd
            self.snap_deadline[s] = self.now_ms() + int(
                snapshot_ms * (0.5 + random.random()))
        self._params_dev = None  # (re)built at next device tick
        return int(self.eto_ms[s])

    # -- density-aware timeout floors ---------------------------------------

    def _density_floor_ms(self) -> int:
        """Minimum safe election timeout at the CURRENT registered
        density, derived from group count and measured costs instead of
        operator hand-tuning.  Two terms:

        - beat-budget: idle beats/s = groups x followers x factor /
          eto_s; each beat costs ~``beat_cost_us`` end to end, and the
          idle beat plane may use at most ``beat_cpu_budget`` of one
          core — solve for eto.
        - tick-cost: one heartbeat interval must dwarf a measured tick
          dispatch (x50), or the engine cannot keep every group's beat
          schedule — a slow device raises the floor on its own.
        """
        if not self.opts.density_aware_timeouts:
            return 0
        n = int(self.has_ctrl.sum())
        if n == 0:
            return 0
        vm = self.voter_mask[self.has_ctrl]
        per = np.clip(vm.sum(axis=1) - 1, 0, None)
        followers = float(per.mean()) if per.size else 2.0
        req_eto = self.req_eto_ms[self.has_ctrl].astype(np.float64)
        req_hb = np.maximum(self.req_hb_ms[self.has_ctrl], 1)
        factor = float((req_eto / req_hb).mean()) if req_eto.size else 10.0
        beat_term = (n * followers * factor * self.opts.beat_cost_us
                     / (max(self.opts.beat_cpu_budget, 1e-3) * 1000.0))
        tick_term = self._tick_cost_ema_s * 1000.0 * factor * 50.0
        return int(max(beat_term, tick_term))

    def settle_floor(self) -> int:
        """Bring every controlled row to the floor of the density that
        is registered NOW, and return it.  ``register_ctrl`` re-derives
        the floor only at geometric counts and re-applies it only past
        25 % growth, so a burst can end with its rows up to a step
        behind (4,096 registrations stopped at the floor of 3,389);
        whoever registers in bulk calls this once the burst is over
        (``StoreEngine.start`` after its boot batches).  Never lowers a
        floor in force."""
        self._floor_cached_ms = self._density_floor_ms()
        self._floor_next_n = int(self._n_ctrls * 1.25) + 1
        if self._floor_cached_ms > self._floor_applied_ms:
            self._floor_applied_ms = self._floor_cached_ms
            self._reapply_floor()
        return self._floor_applied_ms

    def _apply_floor_slot(self, s: int) -> None:
        floor = self._floor_applied_ms
        req = int(self.req_eto_ms[s])
        if req >= floor or floor <= 0:
            self.eto_ms[s] = self.req_eto_ms[s]
            self.hb_ms[s] = self.req_hb_ms[s]
            self.lease_ms[s] = self.req_lease_ms[s]
            return
        ratio = floor / max(req, 1)
        self.eto_ms[s] = floor
        self.hb_ms[s] = max(1, int(self.req_hb_ms[s] * ratio))
        self.lease_ms[s] = max(1, int(self.req_lease_ms[s] * ratio))

    def _reapply_floor(self) -> None:
        floor = self._floor_applied_ms
        changed = 0
        for s in np.nonzero(self.has_ctrl)[0]:
            before = int(self.eto_ms[s])
            self._apply_floor_slot(int(s))
            after = int(self.eto_ms[s])
            if after != before:
                changed += 1
                ctrl = self._ctrls[s]
                if ctrl is not None:
                    ctrl._adopt_eto(after)
        if changed:
            LOG.info("engine density floor %dms raised %d groups' "
                     "election timeouts (%d registered)",
                     floor, changed, int(self.has_ctrl.sum()))
        self._params_dev = None

    def unregister_ctrl(self, slot: int) -> None:
        # idempotent per REGISTRATION, not per call: a controlled node's
        # shutdown reaches here twice (EngineControl.shutdown, then
        # ballot_box.close -> release), and a bare commit-plane box
        # (drive_protocol off) releases without ever registering — an
        # unconditional decrement drifted _n_ctrls negative under churn,
        # and the density-floor recompute trigger (_n_ctrls >=
        # _floor_next_n in register_ctrl) could then stay silent while
        # the REAL controlled density grew past the safe operating point
        if self.has_ctrl[slot]:
            self._n_ctrls -= 1
        self._layout_gen += 1
        self._ctrls[slot] = None
        self._ctrl_server[slot] = None
        self.has_ctrl[slot] = False
        self.self_col[slot] = -1

    def alloc_slot(self) -> int:
        if not self._free:
            self._grow()
        self._layout_gen += 1
        return self._free.pop()

    def _grow(self) -> None:
        """Double group capacity in place.  Region splits mint new raft
        groups at runtime; a full engine must absorb them, not crash
        the new RegionEngine.  The next tick recompiles once for the
        new shape (jit caches per shape); doubling preserves
        divisibility by mesh_devices for the sharded path."""
        old_g = self.G
        new_g = old_g * 2
        self._layout_gen += 1

        def pad(a: np.ndarray, fill=0) -> np.ndarray:
            extra = np.full((old_g,) + a.shape[1:], fill, a.dtype)
            return np.concatenate([a, extra])

        self.match_abs = pad(self.match_abs)
        self.base = pad(self.base)
        self.pending_rel = pad(self.pending_rel, 1)
        self.voter_mask = pad(self.voter_mask)
        self.old_voter_mask = pad(self.old_voter_mask)
        self.commit_abs = pad(self.commit_abs)
        self.role = pad(self.role, ROLE_INACTIVE)
        self.elect_deadline = pad(self.elect_deadline)
        self.hb_deadline = pad(self.hb_deadline)
        self.last_ack = pad(self.last_ack, _NEG_I32)
        self.tick_q_ack = pad(self.tick_q_ack, _NEG_I32)
        self.witness_mask = pad(self.witness_mask)
        self.stepdown_deadline = pad(self.stepdown_deadline)
        self.fence_start = pad(self.fence_start, _NEG_I32)
        self.granted = pad(self.granted)
        self.self_col = pad(self.self_col, -1)
        self.has_ctrl = pad(self.has_ctrl)
        self.quiescent = pad(self.quiescent)
        self.eto_ms = pad(self.eto_ms, _DEF_ETO_MS)
        self.hb_ms = pad(self.hb_ms, _DEF_HB_MS)
        self.lease_ms = pad(self.lease_ms, _DEF_LEASE_MS)
        self.req_eto_ms = pad(self.req_eto_ms, _DEF_ETO_MS)
        self.req_hb_ms = pad(self.req_hb_ms, _DEF_HB_MS)
        self.req_lease_ms = pad(self.req_lease_ms, _DEF_LEASE_MS)
        self.snap_ms = pad(self.snap_ms)
        self.snap_deadline = pad(self.snap_deadline)
        self._params_dev = None  # [G] rows must match the grown shape
        self._tick_buf = None    # and so must the upload buffer
        self._peer_cols.extend(dict() for _ in range(old_g))
        self._boxes.extend([None] * old_g)
        self._ctrls.extend([None] * old_g)
        self._ctrl_server.extend([None] * old_g)
        self._free = list(range(new_g - 1, old_g - 1, -1))
        self.G = new_g
        LOG.info("engine grew: %d -> %d group slots", old_g, new_g)

    def release(self, box: TpuBallotBox) -> None:
        s = box.slot
        self._layout_gen += 1
        self._boxes[s] = None
        self.unregister_ctrl(s)
        self.voter_mask[s] = False
        self.old_voter_mask[s] = False
        self.match_abs[s] = 0
        self.commit_abs[s] = 0
        self.base[s] = 0
        self.pending_rel[s] = 1
        self.role[s] = ROLE_INACTIVE
        self.elect_deadline[s] = 0
        self.hb_deadline[s] = 0
        self.last_ack[s] = _NEG_I32
        self.tick_q_ack[s] = _NEG_I32
        if self.witness_mask[s].any():
            self._n_witness_slots -= 1
        self.witness_mask[s] = False
        self.stepdown_deadline[s] = 0
        self.fence_start[s] = _NEG_I32
        # pending fences die with the slot; the batcher round's timeout
        # sweep resolves their futures False
        self._fence_waiters.pop(s, None)
        self.granted[s] = False
        self.quiescent[s] = False
        self.note_wake_leader(s)
        self.eto_ms[s], self.hb_ms[s], self.lease_ms[s] = \
            _DEF_ETO_MS, _DEF_HB_MS, _DEF_LEASE_MS
        self.req_eto_ms[s], self.req_hb_ms[s], self.req_lease_ms[s] = \
            _DEF_ETO_MS, _DEF_HB_MS, _DEF_LEASE_MS
        self.snap_ms[s] = 0
        self.snap_deadline[s] = 0
        self._params_dev = None
        self._peer_cols[s].clear()
        self._free.append(s)

    def set_conf(self, slot: int, conf: Configuration,
                 old_conf: Configuration) -> None:
        """Map peers to columns and set voter masks for a group."""
        self._layout_gen += 1
        cols = self._peer_cols[slot]
        all_peers = list(dict.fromkeys(
            conf.peers + old_conf.peers + conf.learners + old_conf.learners))
        # retain existing column assignments; add new peers to free columns
        used = set(cols.values())
        for peer in all_peers:
            if peer not in cols:
                col = next((i for i in range(self.P) if i not in used), None)
                if col is None:
                    raise RuntimeError(
                        f"group slot {slot}: {len(all_peers)} distinct peers "
                        f"exceed max_peers={self.P} engine columns")
                cols[peer] = col
                used.add(col)
        # drop stale peers
        for peer in [p for p in cols if p not in all_peers]:
            self.match_abs[slot, cols[peer]] = 0
            self.last_ack[slot, cols[peer]] = _NEG_I32
            self.granted[slot, cols[peer]] = False
            del cols[peer]
        vm = np.zeros(self.P, bool)
        ovm = np.zeros(self.P, bool)
        wm = np.zeros(self.P, bool)
        for peer in conf.peers:
            vm[cols[peer]] = True
        for peer in old_conf.peers:
            ovm[cols[peer]] = True
        # witness columns (either config): the union mirrors the host
        # BallotBox clamp's data set `conf.data_peers + old_conf
        # .data_peers` — a column is data only if NEITHER config marks
        # it witness
        for peer in getattr(conf, "witnesses", ()) or ():
            if peer in cols:
                wm[cols[peer]] = True
        for peer in getattr(old_conf, "witnesses", ()) or ():
            if peer in cols:
                wm[cols[peer]] = True
        had_witness = bool(self.witness_mask[slot].any())
        self.voter_mask[slot] = vm
        self.old_voter_mask[slot] = ovm
        self.witness_mask[slot] = wm
        self._n_witness_slots += int(wm.any()) - int(had_witness)
        # the cached read-plane q_ack was reduced over the OLD voter set;
        # a shrunk conf can make it overstate the new quorum's freshness
        # (no longer a lower bound) — drop it until the next tick
        self.tick_q_ack[slot] = _NEG_I32
        # pending read fences were armed against the old voter set too:
        # drop the device lane for them (the batcher round's own timeout
        # resolves their futures; a conf change mid-round is rare)
        if self.fence_start[slot] > _NEG_I32:
            self.fence_start[slot] = _NEG_I32
            self._fence_waiters.pop(slot, None)
        if self.role[slot] == ROLE_LEADER:
            # grace window for peers ADDED mid-leadership (reference:
            # addReplicator stamps lastRpcSendTimestamp at start): a
            # never-acked NEG column would otherwise pin the joint q_ack
            # reduce at NEG_INF, which the have-ack gate reads as "no
            # data" — so a dead new config could never fire step_down.
            # Invariant: a leader's (old_)voter columns are never NEG.
            row = self.last_ack[slot]
            fresh = (vm | ovm) & (row <= _NEG_I32)
            if fresh.any():
                row[fresh] = self.now_ms()
        server = self._ctrl_server[slot]
        if server is not None:
            col = cols.get(server)
            self.self_col[slot] = -1 if col is None else col
        if self.quiescent[slot]:
            # a configuration change is protocol activity: a hibernating
            # group must wake to drive it (and its lease bookkeeping no
            # longer matches the new peer set)
            ctrl = self._ctrls[slot]
            if ctrl is not None:
                ctrl.wake_from_quiescence("conf-change")
        self.mark_dirty()

    def peer_col(self, slot: int, peer: PeerId) -> Optional[int]:
        return self._peer_cols[slot].get(peer)

    def mark_dirty(self) -> None:
        self._dirty = True
        self._input_seq += 1
        if not self._dirty_event.is_set():
            self._dirty_at = time.perf_counter()
            self._dirty_event.set()

    # -- device read-fence plane (ReadConfirmBatcher rounds) -----------------

    def arm_read_fence(self, slot: int, fence) -> None:
        """Queue a SAFE ReadIndex round on the device tally: the round
        is confirmed once the fused q_ack reduction shows a voter quorum
        acked at-or-after *now*.  ``fence_start[slot]`` carries the
        EARLIEST pending round's start (a q_ack covering it covers every
        later round the resolve pass walks)."""
        start = self.now_ms()
        self._fence_waiters.setdefault(slot, []).append((start, fence))
        cur = self.fence_start[slot]
        self.fence_start[slot] = start if cur <= _NEG_I32 else min(cur, start)
        self.fence_lane_armed += 1
        self.mark_dirty()

    def discard_read_fence(self, slot: int, fence) -> None:
        """Drop one fence from the device lane (round end/timeout) and
        re-derive the row's earliest pending start.  Idempotent — a
        fence the resolve pass already removed just isn't found."""
        waiters = self._fence_waiters.get(slot)
        if not waiters:
            return
        keep = [(start, f) for start, f in waiters if f is not fence]
        if keep:
            self._fence_waiters[slot] = keep
            self.fence_start[slot] = min(start for start, _ in keep)
        else:
            self._fence_waiters.pop(slot, None)
            self.fence_start[slot] = _NEG_I32

    def _resolve_fences(self, s: int) -> None:
        """fence_ok fired for slot ``s``: confirm every pending round
        whose start the published q_ack covers, drop abandoned fences,
        re-arm the row to the earliest still-pending start."""
        waiters = self._fence_waiters.get(s)
        if not waiters:
            self.fence_start[s] = _NEG_I32
            return
        qa = int(self.tick_q_ack[s])
        keep = []
        waited = self.tick_hists["fence_resolve_ms"] if _TRACE.enabled \
            else None
        now = self.now_ms() if waited is not None else 0
        for start, fence in waiters:
            if start <= qa:
                self.fence_lane_resolves += 1
                fence.note_quorum()
                if waited is not None:
                    waited.update(float(now - start))
            elif not fence.done:
                keep.append((start, fence))
        if keep:
            self._fence_waiters[s] = keep
            self.fence_start[s] = min(start for start, _ in keep)
        else:
            self._fence_waiters.pop(s, None)
            self.fence_start[s] = _NEG_I32

    # -- store-lease plumbing (quiescent leader slots) -----------------------

    def note_quiesce_leader(self, slot: int) -> None:
        """A leader slot hibernated: its peers' last_ack cells are now
        refreshed from store-lease acks (one per endpoint per interval)
        instead of per-group beat acks."""
        self_col = int(self.self_col[slot])
        for peer, col in self._peer_cols[slot].items():
            if col == self_col:
                continue
            d = self._lease_cols.setdefault(peer.endpoint, {})
            d.setdefault(slot, []).append(col)
            self._lease_arrays.pop(peer.endpoint, None)

    def note_wake_leader(self, slot: int) -> None:
        for ep in list(self._lease_cols):
            if self._lease_cols[ep].pop(slot, None) is not None:
                self._lease_arrays.pop(ep, None)
                if not self._lease_cols[ep]:
                    del self._lease_cols[ep]

    def note_store_ack(self, endpoint: str,
                       when_ms: Optional[int] = None) -> None:
        """A store-lease ack from ``endpoint``: refresh every quiescent
        leader slot's last_ack cells toward it (vectorized — one fancy-
        indexed write per ack, not O(G) RPC bookkeeping).  Dead-quorum
        step-down and leader-lease reads then see a live quorum for
        hibernating groups exactly as long as the store lease flows."""
        d = self._lease_cols.get(endpoint)
        if not d:
            return
        arrs = self._lease_arrays.get(endpoint)
        if arrs is None:
            slots: list[int] = []
            cols: list[int] = []
            for s, cs in d.items():
                slots.extend([s] * len(cs))
                cols.extend(cs)
            arrs = (np.asarray(slots, np.int64), np.asarray(cols, np.int64))
            self._lease_arrays[endpoint] = arrs
        ms = self.now_ms() if when_ms is None else when_ms
        sl, co = arrs
        self.last_ack[sl, co] = np.maximum(self.last_ack[sl, co], ms)
        self._input_seq += 1

    def describe(self) -> str:
        """Live engine state for operators (the device-plane counterpart
        of Node#describe)."""
        used = sum(1 for b in self._boxes if b is not None)
        return (f"MultiRaftEngine<G={self.G} P={self.P} used={used} "
                f"ctrl={int(self.has_ctrl.sum())} "
                f"backend={self.opts.backend} "
                f"mesh={self.opts.mesh_devices or 1} "
                f"ticks={self.ticks} tick_failures={self.tick_failures} "
                f"commit_advances={self.commit_advances} "
                f"eager_commits={self.eager_commits} "
                f"leaders={int((self.role == ROLE_LEADER).sum())} "
                f"quiescent={int(self.quiescent.sum())} "
                f"quiesce_events={self.quiesce_events} "
                f"wake_events={self.wake_events} "
                f"lease_lane_hits={self.lease_lane_hits} "
                f"lease_lane_misses={self.lease_lane_misses} "
                f"witness_groups={self._n_witness_slots} "
                f"stepdown_ticks={self.stepdown_ticks} "
                f"fence_armed={self.fence_lane_armed} "
                f"fence_resolves={self.fence_lane_resolves} "
                f"eto_floor_ms={self._floor_applied_ms} "
                f"tick_p99_ms={self.tick_hists['tick_total_ms'].percentile(99):.3f}>")

    # -- device-tick profiling (fleet observability) -------------------------

    def tick_histograms(self) -> dict:
        """Per-tick phase wall-time histograms as snapshot dicts — the
        shape ``prometheus_text(histograms=...)`` renders (served by
        StoreEngine.metrics_text for engine-backed stores)."""
        return {k: h.snapshot() for k, h in self.tick_hists.items()}

    def lane_stats(self) -> dict:
        """[G]-lane occupancy gauges, computed as vectorized reductions
        over the host mirrors the tick already owns — no per-group
        Python.  ``hibernation_fraction`` is quiescent/controlled (the
        number the PD's ClusterView aggregates fleet-wide)."""
        hc = self.has_ctrl
        n = int(hc.sum())
        leaders = int(((self.role == ROLE_LEADER) & hc).sum())
        quiescent = int((self.quiescent & hc).sum())
        stats = {
            "groups": n,
            "leaders": leaders,
            "candidates": int(((self.role == ROLE_CANDIDATE) & hc).sum()),
            "followers": int(((self.role == ROLE_FOLLOWER) & hc).sum()),
            "quiescent": quiescent,
            "hibernation_fraction": round(quiescent / n, 4) if n else 0.0,
            "tick_cost_ema_ms": round(self._tick_cost_ema_s * 1e3, 3),
            # the density floor in force, and how many controlled rows
            # it raised above what their nodes asked for (0 = silent)
            "eto_floor_ms": self._floor_applied_ms,
            "eto_raised": int((self.eto_ms[hc] > self.req_eto_ms[hc]).sum()),
            "leader_stepdowns": dict(self.leader_stepdowns),
            "witness_groups": self._n_witness_slots,
            "stepdown_ticks": self.stepdown_ticks,
            "tick_failures": self.tick_failures,
            "ticks_dropped": self.ticks_dropped,
            "tick_transfers": self._tick_transfers,
            "fence_lane_armed": self.fence_lane_armed,
            "fence_lane_resolves": self.fence_lane_resolves,
            "fences_pending": sum(len(w) for w
                                  in self._fence_waiters.values()),
        }
        # q_ack distribution: age of the quorum-newest ack per AWAKE
        # leader row (quiescent leaders ride the store lease; their rows
        # age by design and would drown the signal) — the read plane's
        # lease headroom at a glance
        lead = (self.role == ROLE_LEADER) & hc & ~self.quiescent
        qa = self.tick_q_ack[lead]
        qa = qa[qa > _NEG_I32]
        if qa.size:
            ages = np.clip(self.now_ms() - qa, 0, None)
            stats["q_ack_age_ms_p50"] = float(np.percentile(ages, 50))
            stats["q_ack_age_ms_p99"] = float(np.percentile(ages, 99))
            stats["q_ack_age_ms_max"] = float(ages.max())
        else:
            stats["q_ack_age_ms_p50"] = 0.0
            stats["q_ack_age_ms_p99"] = 0.0
            stats["q_ack_age_ms_max"] = 0.0
        return stats

    # -- tick loop -----------------------------------------------------------

    def _resolve_backend(self) -> str:
        """backend="auto": the jax device plane exists FOR accelerators —
        on a CPU-only host the vectorized numpy twin of the tick beats
        XLA-CPU dispatch overhead at any G that fits one box (profiled:
        per-tick jit call overhead dominated small-G CPU ticks).  A mesh
        request always means jax.  Choosing numpy on a CPU-only host is a
        choice; a backend that fails to initialise is an error and
        raises from here (it must never read as "no accelerator")."""
        b = self.opts.backend
        if b != "auto":
            return b
        if self.opts.mesh_devices and self.opts.mesh_devices > 1:
            return "jax"
        import jax

        return "jax" if jax.default_backend() != "cpu" else "numpy"

    async def start(self) -> None:
        if self._resolve_backend() != "numpy":
            import jax

            from tpuraft.ops.tick import raft_tick_packed_jit
            from tpuraft.util.jax_cache import ensure_compile_cache

            ensure_compile_cache()
            if self.opts.mesh_devices and self.opts.mesh_devices > 1:
                # SPMD over the group axis: each chip advances its own
                # group rows; upload scatters, download gathers (the
                # "vote-matrix over ICI" configuration in BASELINE.md).
                # The whole compilation lives in parallel/mesh.py
                # (sharded_tick) — the engine consumes only the outputs
                # half of the (new_state, outputs) pair, so with
                # donate_state the input buffers are recycled into the
                # (discarded) new_state on device and nothing but the
                # [G] output rows crosses back to host.
                from tpuraft.parallel.mesh import (make_mesh, sharded_tick,
                                                   sharded_deadline_fold)

                n = self.opts.mesh_devices
                if self.G % n != 0:
                    raise ValueError(
                        f"max_groups={self.G} not divisible by "
                        f"mesh_devices={n}")
                mesh = make_mesh(n)  # raises if fewer devices exist
                full_tick = sharded_tick(
                    mesh, donate=self.opts.donate_state)
                self._tick_fn = lambda state, now, params: \
                    full_tick(state, now, params)[1]
                # earliest-deadline scan as one sharded fold + collective
                # min, instead of a host gather over every sharded row
                # per loop iteration
                self._deadline_fold = sharded_deadline_fold(mesh)
            else:
                # the PROCESS-WIDE jitted instance: all engines share one
                # trace cache, so only the first engine (per [G, P]
                # shape) pays a compile.  One device: one packed array
                # up, one down (_call_tick / _fetch)
                self._tick_fn = raft_tick_packed_jit
                self._packed = True
            # warm the compile NOW, before any node registers: a first
            # tick mid-protocol would block the event loop for the
            # compile and miss every group's heartbeat window at once
            self.tick_once()
            # say where the tick runs: with JAX_PLATFORMS unset JAX
            # itself settles for the CPU when it cannot open the chip
            dev = jax.devices()[0]
            LOG.info("engine tick on %s (%s) x%d", dev.platform,
                     dev.device_kind, self.opts.mesh_devices or 1)
        if self.opts.profile_dir:
            if self._resolve_backend() == "numpy":
                LOG.warning("profile_dir set but backend is numpy: the "
                            "XLA profiler only traces the jax tick path")
            else:
                import jax

                try:
                    # process-global: a second engine in the same
                    # process cannot start another trace — it keeps
                    # running without one instead of failing startup
                    jax.profiler.start_trace(self.opts.profile_dir)
                    self._profiling = True
                except Exception as e:  # noqa: BLE001
                    LOG.warning("profiler trace not started (another "
                                "engine's trace active?): %s", e)
        from tpuraft.util import describer

        describer.register(self)
        self._task = asyncio.ensure_future(self._loop())

    async def shutdown(self) -> None:
        self._stopped = True
        from tpuraft.util import describer

        describer.unregister(self)
        if getattr(self, "_profiling", False):
            import jax

            self._profiling = False
            try:
                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001 — trace already stopped
                LOG.warning("profiler stop: %s", e)
        if self._task:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None

    def crash(self) -> None:
        """The tick loop stops where it is; nothing is awaited (the
        orderly way is ``shutdown``)."""
        from tpuraft.util import describer

        self._stopped = True
        describer.unregister(self)
        if self._task is not None:
            self._task.cancel()
            self._task = None

    def _next_deadline(self) -> int:
        """Earliest engine-scheduled deadline (election, heartbeat or
        stepdown check) over controlled slots; a huge sentinel when
        none.  Quiescent slots schedule NOTHING — a fully hibernated
        engine sleeps until a dirty mark (wake, lease round, client
        traffic) arrives.  Mesh mode folds the scan on device (one
        sharded reduction + collective min) instead of gathering every
        sharded row back per loop iteration."""
        if self._deadline_fold is not None:
            from tpuraft.parallel.mesh import DEADLINE_NONE_I32

            nxt = int(self._deadline_fold(
                self.role, self.quiescent, self.has_ctrl,
                self.elect_deadline.astype(np.int32),
                self.hb_deadline.astype(np.int32),
                self.stepdown_deadline.astype(np.int32)))
            return (1 << 60) if nxt >= int(DEADLINE_NONE_I32) else nxt
        hc = self.has_ctrl & ~self.quiescent
        ec = hc & ((self.role == ROLE_FOLLOWER) | (self.role == ROLE_CANDIDATE))
        ld = hc & (self.role == ROLE_LEADER)
        nxt = 1 << 60
        if ec.any():
            nxt = min(nxt, int(self.elect_deadline[ec].min()))
        if ld.any():
            nxt = min(nxt, int(self.hb_deadline[ld].min()))
            nxt = min(nxt, int(self.stepdown_deadline[ld].min()))
        return nxt

    async def _loop(self) -> None:
        """Adaptive cadence: dirty -> tick now (sub-ms commit ack at low
        load); consecutive ticks pace by the previous tick's cost (a
        slow device batches more per dispatch); idle -> sleep to the
        next deadline, capped at tick_interval_ms."""
        max_idle_s = self.opts.tick_interval_ms / 1000.0
        min_pace_s = self.opts.min_tick_interval_ms / 1000.0
        while not self._stopped:
            # the [G]-row deadline scan every wake pays: the tick
            # layer's host work too, on the loop's clock
            sec = _TRACE.enter("tick.build") if _TRACE.enabled else None
            now = self.now_ms()
            due = self._next_deadline() <= now
            if sec is not None:
                _TRACE.leave(sec)
            if self._dirty and self._seq_served == self._input_seq:
                # a tick somebody else began after the mark (a confirm
                # round, tick_soon) has landed: the mark is served
                self._dirty = False
                self._dirty_event.clear()
            if self._dirty or due:
                self._dirty_event.clear()
                self._dirty = False
                self.tick_hists["tick_late_ms"].update(self._late_s * 1e3)
                self._late_s = 0.0
                t0 = time.perf_counter()
                advanced = 0
                try:
                    advanced = await self.tick()
                    # the two halves' own seconds: the loop turn between
                    # them is other tasks' work, not the tick's cost
                    dur = self._tick_own_s
                except Exception:
                    # the loop must outlive one bad tick, but a tick
                    # that raises (lost device, OOM, a refused compile
                    # after _grow) is counted where operators look —
                    # it must not read as "slow elections"
                    self.tick_failures += 1
                    LOG.exception("engine tick failed")
                    self._dirty = True  # re-process pending acks next tick
                    dur = time.perf_counter() - t0
                # measured tick dispatch cost: one input to the density-
                # aware election-timeout floor (_density_floor_ms)
                self._tick_cost_ema_s = (
                    dur if self._tick_cost_ema_s == 0.0
                    else 0.9 * self._tick_cost_ema_s + 0.1 * dur)
                pace = max(min_pace_s, dur * self.opts.pace_factor)
                if advanced == 0:
                    # a no-op tick (e.g. the leader's OWN ack before any
                    # follower responded) must not make the next real
                    # ack wait out the full pace window — that alone
                    # added ~1.5ms to the low-load commit-ack path.
                    # Debounce briefly (bounds tick spin under dirty
                    # storms), then let a dirty mark cut the remainder.
                    await self._sleep(min(pace, 0.0003))
                    await self._wait_dirty(pace)
                else:
                    await self._sleep(pace)
                continue
            wait = min(max_idle_s,
                       max(0.0, (self._next_deadline() - now) / 1000.0))
            if self._dirty:
                continue
            await self._wait_dirty(wait)

    async def _sleep(self, seconds: float) -> None:
        """A timed wake: late by what the loop took past ``seconds``."""
        t = time.perf_counter()
        await asyncio.sleep(seconds)
        self._late_s = max(0.0, time.perf_counter() - t - seconds)

    async def _wait_dirty(self, timeout_s: float) -> None:
        """Wait for a dirty mark or ``timeout_s``.  A dirty wake is late
        from the ``mark_dirty`` that set the event, a timed one from its
        timeout; a mark that was already there wakes nothing."""
        if self._dirty_event.is_set():
            return
        t = time.perf_counter()
        try:
            await asyncio.wait_for(self._dirty_event.wait(), timeout_s)
            due = self._dirty_at
        except asyncio.TimeoutError:
            due = t + timeout_s
        self._late_s = max(0.0, time.perf_counter() - due)

    # -- the tick ------------------------------------------------------------

    def _rebase(self) -> None:
        hot = (self.match_abs.max(axis=1) - self.base) > _REBASE_LIMIT
        if hot.any():
            self._layout_gen += 1
            for s in np.nonzero(hot)[0]:
                new_base = self.commit_abs[s]
                self.pending_rel[s] = max(
                    1, self.pending_rel[s] - (new_base - self.base[s]))
                self.base[s] = new_base

    def tick_once(self) -> int:
        """One batched device tick for all groups: commit advancement,
        election/heartbeat scheduling, lease & step-down.  Returns the
        number of groups whose commit advanced.  Synchronous: both
        halves in one go, the wait for the device between them (the
        warm-up at start, the numpy twin, the mesh path, a round that
        closes while it is being cancelled).  A tick that ``tick()``
        left in flight is collected first: one at a time."""
        if self._flight is not None:
            self._tick_end(self._flight, time.perf_counter())
        flight = self._tick_begin()
        return self._tick_end(flight, flight.tc)

    async def tick(self) -> int:
        """``tick_once`` with the loop free while the device has the
        program: the first half enqueues it in this loop turn, the
        second collects it in the next (a turn under load lasts many
        times what the program and the copy need; on an idle loop the
        fetch waits out the rest, as ``tick_once`` does).  Taken where
        there is a device call to overlap (single-device jax); the numpy
        twin and the mesh path tick synchronously, and so does an engine
        whose ``tick_once`` is not this class's (a subclass, a spy around
        it: whoever replaced it expects every tick to pass through it).

        ONE tick in flight an engine.  A caller that asks during a
        flight gets the NEXT tick, begun right after the flight's end:
        its snapshot is taken after whatever the caller recorded before
        asking (what a read fence needs), and every caller that asks
        meanwhile shares it.  Unless nothing was recorded since the
        flight's own snapshot (no dirty mark, no ack: ``_input_seq``):
        then the flight IS the tick the caller asks for, and rounds
        that close in one turn cost one device call."""
        if not self._overlaps():
            return self.tick_once()
        flight = self._flight
        if flight is not None:
            loop = asyncio.get_running_loop()
            if flight.seq == self._input_seq:
                fut = flight.fut = flight.fut or loop.create_future()
            else:
                fut = self._next_tick = \
                    self._next_tick or loop.create_future()
            # shielded: one caller's cancellation is not the others'
            return await asyncio.shield(fut)
        flight = self._tick_begin()
        try:
            await asyncio.sleep(0)
        except asyncio.CancelledError:
            # collected all the same (by the loop, or by the caller's
            # own synchronous close before that)
            asyncio.get_running_loop().call_soon(self._land, flight)
            raise
        return self._land(flight)

    def _overlaps(self) -> bool:
        """Is there a device call to overlap (single-device jax), and
        is ``tick_once`` this class's own?"""
        return (self._tick_fn is not None and self._packed
                and getattr(self.tick_once, "__func__", None)
                is MultiRaftEngine.tick_once)

    def tick_soon(self) -> None:
        """For a caller that has just recorded what a waiting read
        fence needs and awaits nothing (ReadConfirmBatcher, after a
        destination's acks): the tick begins NOW, in the caller's turn,
        and the loop collects it in its next, the turn in which the
        engine loop would only have woken up to begin it.  With a tick
        in flight, or nothing to overlap, the caller's dirty mark does
        what it always did."""
        if self._flight is None and not self._stopped and self._overlaps():
            asyncio.get_running_loop().call_soon(
                self._land, self._tick_begin())

    def _land(self, flight: _Flight) -> int:
        """The collecting half of an overlapped tick, one loop turn
        after its begin (the caller that began it calls, or the loop
        for a tick begun here), then the begin of the tick its flight
        made others wait for, which the loop collects in its next
        turn."""
        try:
            if not flight.done:     # else a tick_once collected it
                self._tick_end(flight, time.perf_counter(), yielded=True)
            return flight.advanced
        finally:
            nxt = self._next_tick
            if nxt is not None and self._flight is None:
                # (a flight begun since, after a tick_once collected
                # this one, begins theirs when it lands)
                self._next_tick = None
                if self._stopped:
                    nxt.set_result(0)
                else:
                    try:
                        self._tick_begin().fut = nxt
                    except Exception as e:
                        nxt.set_exception(e)
                    else:
                        asyncio.get_running_loop().call_soon(
                            self._land, self._flight)

    def _tick_begin(self) -> _Flight:
        """A tick's first half: snapshot the mirrors at ``now``, enqueue
        the program, start the output's copy to the host.  Nothing is
        waited for.  (The numpy twin computes here: all "call".)"""
        pc = time.perf_counter
        t0 = pc()
        # one loop section open at a time, switched at the clock reads
        # the histograms take: tick.build | tick.call here, tick.fetch |
        # tick.apply in _tick_end (None while tracing is off)
        sec = _TRACE.enter("tick.build", t0) if _TRACE.enabled else None
        tc = 0.0
        try:
            now = self.now_ms()
            self._maybe_time_rebase(now)
            now = self.now_ms()
            self._rebase()
            # the leader's own slot counts as acked *now* (tick.py
            # contract)
            lead_rows = np.nonzero((self.role == ROLE_LEADER)
                                   & (self.self_col >= 0))[0]
            if lead_rows.size:
                self.last_ack[lead_rows, self.self_col[lead_rows]] = now
            rel, commit_rel_now = self._rel_views()

            t1 = pc()
            if self._tick_fn is not None:
                state = self._group_state(rel, commit_rel_now)
                ts = pc()
                if sec is not None:
                    sec = _TRACE.switch(sec, "tick.call", ts)
                out = self._call_tick(state, now)
                if self._packed:
                    out.copy_to_host_async()
            else:  # numpy twin (tiny deployments / no jax)
                self._tick_transfers = 0
                ts = t1
                if sec is not None:
                    sec = _TRACE.switch(sec, "tick.call", ts)
                out = self._np_tick(rel, commit_rel_now, now)
            tc = pc()
        finally:
            if sec is not None:
                _TRACE.leave(sec, tc)
        flight = self._flight = _Flight(
            out, now, self._layout_gen, self.base.copy(), self._input_seq,
            t0, t1, ts, tc)
        return flight

    def _tick_end(self, flight: _Flight, te: float,
                  yielded: bool = False) -> int:
        """A tick's second half, begun at clock read ``te``: wait for
        what is left of the program, download, publish ``tick_q_ack``,
        apply.  The outputs are hints about the mirrors as they were at
        begin, a loop turn ago where the tick was overlapped: commits
        only ever rise, a fence is confirmed by its own ``start <=
        q_ack``, a timer mask is held against its mirror row again
        (_apply_protocol), the handlers re-verify under the node lock.
        An output taken under another layout generation, or collected
        after a stop, is dropped whole."""
        pc = time.perf_counter
        hists = self.tick_hists
        sec = _TRACE.enter("tick.fetch", te) if _TRACE.enabled else None
        # over whatever happens below: the next tick may begin
        self._flight = None
        flight.done = True
        try:
            out = flight.out
            if yielded:
                hists["tick_overlapped"].update(1)
                if out.is_ready():
                    hists["tick_ready"].update(1)
                hists["tick_inflight_ms"].update((te - flight.tc) * 1e3)
            if self._tick_fn is not None:
                out = self._fetch(out)
            t2 = pc()
            self.ticks += 1
            self._hb_flush_s = 0.0
            if flight.gen != self._layout_gen or (yielded and self._stopped):
                self.ticks_dropped += 1
                self.mark_dirty()
            else:
                # publish the read-plane lane: the fused q_ack reduce is
                # exactly what per-read lease checks need, and the row
                # it replaces is a per-read [P] copy+sort on the hot GET
                # path
                np.copyto(self.tick_q_ack, np.asarray(out.q_ack))
                self._seq_served = flight.seq
                if sec is not None:
                    sec = _TRACE.switch(sec, "tick.apply")
                flight.advanced = self._apply_commits(out, flight.base)
                self._apply_protocol(out, flight.now)
                if flight.seq != self._input_seq \
                        and (self.fence_start > _NEG_I32).any():
                    # acks landed during the flight and a fence is
                    # still waiting: what record_ack left undecided
                    self.mark_dirty()
            t3 = pc()
        except Exception as e:
            if flight.fut is not None:
                flight.fut.set_exception(e)
            raise
        finally:
            if sec is not None:
                _TRACE.leave(sec)
        if flight.fut is not None:
            flight.fut.set_result(flight.advanced)
        # tick_device_ms is its three parts, and the parts are the
        # halves' own clock reads: what passed between the enqueue and
        # the start of this half is in none of them
        fetch_s = t2 - te
        hists["tick_build_ms"].update((flight.t1 - flight.t0) * 1e3)
        hists["tick_state_ms"].update((flight.ts - flight.t1) * 1e3)
        hists["tick_call_ms"].update((flight.tc - flight.ts) * 1e3)
        hists["tick_fetch_ms"].update(fetch_s * 1e3)
        hists["tick_device_ms"].update(
            (flight.tc - flight.t1 + fetch_s) * 1e3)
        hists["tick_apply_ms"].update((t3 - t2) * 1e3)
        self._tick_own_s = flight.tc - flight.t0 + t3 - te
        hists["tick_total_ms"].update(self._tick_own_s * 1e3)
        hists["tick_heartbeat_ms"].update(self._hb_flush_s * 1e3)
        return flight.advanced

    def _rel_views(self) -> tuple[np.ndarray, np.ndarray]:
        """(match_rel [G,P], commit_rel [G]): the int32 base-relative
        views of the absolute-index mirrors, as the tick reduces them."""
        rel = np.clip(self.match_abs - self.base[:, None], 0, None
                      ).astype(np.int32)
        commit_rel_now = np.clip(self.commit_abs - self.base, 0, None
                                 ).astype(np.int32)
        return rel, commit_rel_now

    def _group_state(self, rel, commit_rel_now):
        """The tick's input as a GroupState of numpy rows, built from
        the host mirrors (int32 views of the int64 time rows).  Nothing
        crosses to the device here: ``_call_tick`` packs the rows into
        its one upload buffer (the mesh path hands them over as they
        are)."""
        from tpuraft.ops.tick import GroupState

        return GroupState(
            role=self.role,
            commit_rel=commit_rel_now,
            pending_rel=self.pending_rel,
            match_rel=rel,
            granted=self.granted,
            voter_mask=self.voter_mask,
            old_voter_mask=self.old_voter_mask,
            elect_deadline=self.elect_deadline.astype(np.int32),
            hb_deadline=self.hb_deadline.astype(np.int32),
            last_ack=self.last_ack.astype(np.int32),
            snap_deadline=self.snap_deadline.astype(np.int32),
            quiescent=self.quiescent,
            witness_mask=self.witness_mask,
            stepdown_deadline=self.stepdown_deadline.astype(np.int32),
            fence_start=self.fence_start.astype(np.int32),
        )

    def _device_tick(self, rel, commit_rel_now, now):
        """State build, jitted call and download in one go, applying
        nothing (a probe's call).  It leaves a tick in flight alone:
        ``_call_tick`` then packs into a buffer of its own."""
        return self._fetch(self._call_tick(
            self._group_state(rel, commit_rel_now), now))

    def _call_tick(self, state, now):
        """The jitted call: copies the fifteen rows and ``now`` into the
        one int32 upload buffer, hands that array over and enqueues the
        program; returns one packed device array that may not be
        computed yet.  Over a mesh the rows go up as they are (sixteen
        host arrays) and a TickOutputs of device rows comes back.
        TickParams stay prefetched on the device either way."""
        import jax

        from tpuraft.ops.tick import (TickParams, pack_state,
                                      packed_state_shape)

        if self._params_dev is None:
            self._params_dev = TickParams.make(self.eto_ms, self.hb_ms,
                                               self.lease_ms, self.snap_ms)
        if self._packed:
            buf = self._tick_buf
            if buf is None or self._flight is not None:
                # the program in flight may still read the reusable one
                buf = np.empty(packed_state_shape(self.G, self.P), np.int32)
                if self._flight is None:
                    self._tick_buf = buf
            args = (pack_state(state, now, buf),)
        else:
            args = (state, np.int32(now))
        self._tick_transfers = 0
        for a in jax.tree_util.tree_leaves(args):
            self._note_transfer(a)
        with jax.profiler.TraceAnnotation("tpuraft.raft_tick"):
            return self._tick_fn(*args, self._params_dev)

    def _fetch(self, out) -> _NpOutputs:
        """Wait for the device, download the one packed output array and
        name its eleven rows (over a mesh: eleven downloads)."""
        from tpuraft.ops.tick import unpack_outputs

        if self._packed:
            self._note_transfer(out)
            return _NpOutputs(**unpack_outputs(np.asarray(out)))
        rows = {name: np.asarray(getattr(out, name))
                for name in _NpOutputs.__slots__}
        for a in rows.values():
            self._note_transfer(a)
        return _NpOutputs(**rows)

    def _note_transfer(self, a) -> None:
        """One array crossed the host/device boundary."""
        self._tick_transfers += 1
        self.tick_hists["tick_transfers"].update(a.nbytes)

    def _np_tick(self, rel, commit_rel_now, now) -> _NpOutputs:
        """Bit-exact numpy twin of tpuraft.ops.tick.raft_tick (the
        engine's no-jax fallback; also the oracle in engine tests)."""
        vm, ovm = self.voter_mask, self.old_voter_mask
        is_leader = self.role == ROLE_LEADER
        is_follower = self.role == ROLE_FOLLOWER
        is_candidate = self.role == ROLE_CANDIDATE

        q = _np_joint_quorum(rel, vm, ovm)
        if self._n_witness_slots:
            # witness commit clamp (ballot.witness_commit_clamp's numpy
            # twin): acked-by-witnesses-only indexes are not durable —
            # clamp to the best data-replica match.  Skipped entirely
            # while no registered conf carries witnesses (the steady
            # state for most engines).
            voters = vm | ovm
            wm = self.witness_mask
            has_w = (voters & wm).any(axis=1)
            data_best = np.where(voters & ~wm, rel, 0).max(axis=1)
            q = np.where(has_w, np.minimum(q, data_best), q).astype(np.int32)
        can_commit = is_leader & (q >= self.pending_rel)
        new_commit = np.where(can_commit, np.maximum(commit_rel_now, q),
                              commit_rel_now)

        def vote_ok(mask):
            n = mask.sum(axis=1)
            votes = (self.granted & mask).sum(axis=1)
            return (n > 0) & (votes >= n // 2 + 1)

        el = vote_ok(vm)
        in_joint = ovm.any(axis=1)
        if in_joint.any():
            elected_q = np.where(in_joint, el & vote_ok(ovm), el)
        else:
            elected_q = el  # steady state: no joint-config vote count
        # joint consensus: the lease needs BOTH configs responsive
        # (NodeImpl#checkDeadNodes walks conf and oldConf)
        ack64 = np.clip(self.last_ack, _NEG_I32, None).astype(np.int64)
        q_ack = _np_joint_order_stat(ack64, vm, ovm)
        have_ack = q_ack > _NEG_I32
        awake = ~self.quiescent
        return _NpOutputs(
            commit_rel=new_commit,
            commit_advanced=new_commit > commit_rel_now,
            elected=is_candidate & elected_q,
            election_due=(is_follower | is_candidate) & awake
            & (now >= self.elect_deadline),
            # step_down stays LIVE for quiescent leaders: store-lease
            # acks refresh their rows, so a dead store still deposes
            # its hibernating leaders (mirrors ops/tick.py)
            step_down=is_leader & have_ack & (now - q_ack >= self.eto_ms),
            hb_due=is_leader & awake & (now >= self.hb_deadline),
            lease_valid=is_leader & have_ack & (now - q_ack < self.lease_ms),
            snap_due=(self.role != ROLE_INACTIVE) & (self.snap_ms > 0)
            & (now >= self.snap_deadline),
            q_ack=q_ack,
            stepdown_due=is_leader & awake & (now >= self.stepdown_deadline),
            fence_ok=is_leader & (self.fence_start > _NEG_I32) & have_ack
            & (q_ack >= self.fence_start),
        )

    def eager_commit_slot(self, s: int) -> bool:
        """Event-driven commit advancement for ONE slot, on the ack path
        (TickOptions.eager_commit): the scalar mirror of the device
        tick's joint quorum reduce over this slot's [P] match row —
        joint-consensus aware (both quorums while ``old_voter_mask`` is
        populated), gated on the leadership window (``pending_rel``)
        exactly like ops/tick.py's ``can_commit``.  ~O(P log P) per
        ack on one row; the win is that a hot group's quorum closes on
        the ack that completes it instead of waiting out the tick
        pace.  The next tick recomputes the same value and finds
        nothing to advance (``commit_abs`` already moved)."""
        row = self.match_abs[s]

        def order_stat(mask: np.ndarray) -> int:
            vals = np.sort(row[mask])[::-1]
            n = vals.size
            return int(vals[n // 2]) if n else -1

        q = order_stat(self.voter_mask[s])
        if self.old_voter_mask[s].any():
            q = min(q, order_stat(self.old_voter_mask[s]))
        if self._n_witness_slots:
            # witness commit clamp, absolute-index domain (the scalar
            # mirror of the device tick's ballot.witness_commit_clamp)
            wm = self.witness_mask[s]
            voters = self.voter_mask[s] | self.old_voter_mask[s]
            if (voters & wm).any():
                data = voters & ~wm
                q = min(q, int(row[data].max()) if data.any() else 0)
        if q < self.base[s] + self.pending_rel[s] or q <= self.commit_abs[s]:
            return False
        self.commit_abs[s] = q
        self.eager_commits += 1
        box = self._boxes[s]
        if box is not None:
            box._advance(q)
        return True

    def _apply_commits(self, out, base: np.ndarray) -> int:
        """``base``: the bases ``out.commit_rel`` is relative to (the
        snapshot's).  The output may be a loop turn old: a row that no
        longer leads, or leads anew (``reset_pending_index`` moved its
        base), takes nothing from it."""
        advanced = 0
        for s in np.nonzero(np.asarray(out.commit_advanced))[0]:
            box = self._boxes[s]
            if box is None or self.role[s] != ROLE_LEADER \
                    or self.base[s] != base[s]:
                continue
            new_commit = int(base[s] + out.commit_rel[s])
            if new_commit > self.commit_abs[s]:
                self.commit_abs[s] = new_commit
                advanced += 1
                box._advance(new_commit)
        self.commit_advances += advanced
        return advanced

    def _apply_protocol(self, out, now: int) -> None:
        """Schedule slow-path handlers from the tick's event masks
        (controlled slots only); handlers re-verify under the node lock.
        ``now`` is the snapshot's.  A timer mask is held against its own
        mirror row again (``still_due``): where the tick was overlapped
        the masks are a loop turn old, and a leader contact, a beat or a
        role change that landed in that turn has pushed the deadline or
        left the role; a timer it pushed must not fire."""
        hc = self.has_ctrl

        def still_due(mask, deadline, leader: bool) -> np.ndarray:
            slots = np.nonzero(np.asarray(mask) & hc)[0]
            if slots.size:
                keep = (deadline[slots] <= now) & ~self.quiescent[slots]
                keep &= (self.role[slots] == ROLE_LEADER) == leader
                slots = slots[keep]
            return slots

        for s in still_due(out.election_due, self.elect_deadline, False):
            ctrl = self._ctrls[s]
            if ctrl is None:
                continue
            # push the deadline NOW: the handler runs async, and a
            # same-deadline refire every tick until it runs would storm
            ctrl.push_election_deadline(now)
            ctrl.note_election_due()
            ctrl.schedule("election_due", ctrl.node._on_election_due)
        for s in np.nonzero(np.asarray(out.elected) & hc)[0]:
            ctrl = self._ctrls[s]
            if ctrl is not None:
                ctrl.schedule("elected", ctrl.node._on_engine_elected)
        for s in np.nonzero(np.asarray(out.step_down) & hc)[0]:
            ctrl = self._ctrls[s]
            if ctrl is not None:
                ctrl.schedule("quorum_dead",
                              ctrl.node._on_engine_quorum_dead)
        sd_slots = still_due(out.stepdown_due, self.stepdown_deadline, True)
        if sd_slots.size:
            # re-arm the host mirror NOW (the handler runs async; a
            # same-deadline refire every tick would storm) on the
            # timer-mode cadence: eto/2, the reference stepDownTimer.
            self.stepdown_deadline[sd_slots] = now + np.maximum(
                1, self.eto_ms[sd_slots] // 2)
            self.stepdown_ticks += int(sd_slots.size)
            # the whole lane in one pass: this tick's q_ack reduction is
            # a LOWER bound on each leader's quorum-ack time (acks only
            # arrive), so a leader it puts inside its election timeout
            # cannot fail _check_dead_nodes' re-verification — at
            # density that is every leader every eto/2, and one task
            # plus one node lock each is the cost this saves.  The node
            # is asked only where the row is stale, or where the round
            # itself matters: _check_dead_nodes also accrues
            # priority_transfer_rounds — the exact handler timer-mode
            # runs, so decay-elected engine leaders transfer back with
            # zero node-side special casing
            stale = (now - np.asarray(out.q_ack)[sd_slots]
                     >= self.eto_ms[sd_slots])
            for s, is_stale in zip(sd_slots, stale):
                ctrl = self._ctrls[s]
                if ctrl is not None and (
                        is_stale or ctrl.priority_rounds_accrue()):
                    ctrl.schedule("stepdown_tick",
                                  ctrl.node._check_dead_nodes)
        for s in np.nonzero(np.asarray(out.fence_ok) & hc)[0]:
            self._resolve_fences(int(s))
        hb_slots = still_due(out.hb_due, self.hb_deadline, True)
        if hb_slots.size:
            h0 = time.perf_counter()
            sec = _TRACE.enter("raft.heartbeat", h0) if _TRACE.enabled \
                else None
            try:
                self._flush_heartbeats(hb_slots, now)
            finally:
                h1 = time.perf_counter()
                if sec is not None:
                    _TRACE.leave(sec, h1)
                self._hb_flush_s = h1 - h0
        snap_slots = np.nonzero(np.asarray(out.snap_due) & hc)[0]
        for s in snap_slots:
            ctrl = self._ctrls[s]
            if ctrl is None or self.snap_deadline[s] > now:
                continue
            # advance the host mirror NOW (the handler runs async; a
            # same-deadline refire every tick would herd), keeping each
            # group on its own staggered phase
            self.snap_deadline[s] = now + int(self.snap_ms[s])
            ctrl.schedule("snapshot_due", ctrl.node._on_snapshot_due)

    def _flush_heartbeats(self, slots, now: int) -> None:
        """Batched heartbeat fan-out for all due leader groups: ONE
        HeartbeatHub.pulse per hub covering every due group this tick
        (the send-matrix plane — O(endpoints) RPCs, not O(groups))."""
        by_hub: dict[int, tuple[object, list]] = {}
        direct: list = []
        for s in slots:
            ctrl = self._ctrls[s]
            if ctrl is None:
                continue
            node = ctrl.node
            if not node.is_leader():
                continue
            # quiescence bookkeeping: count consecutive fully-acked idle
            # rounds; at the threshold the round's beats carry the
            # quiesce handshake (every follower must ack before the
            # group hibernates — see EngineControl.maybe_quiesce)
            ctrl.maybe_quiesce(now)
            if self.quiescent[s]:
                continue  # hibernated (e.g. single-voter: no handshake)
            reps = node.replicators.all()
            if not reps:
                continue
            nm = node.node_manager
            opt = node.options.raft_options.coalesce_heartbeats
            if nm is None or opt is False:
                direct.extend(reps)
                continue
            # AUTO (None): coalesce per peer once its responses advertise
            # multi_heartbeat — idle beats become O(endpoints) by default
            hub = nm.heartbeat_hub
            for r in reps:
                if opt is True or r.peer_multi_hb:
                    by_hub.setdefault(id(hub), (hub, []))[1].append(r)
                else:
                    direct.append(r)
        rows = sum(len(reps) for _, reps in by_hub.values()) + len(direct)
        if rows:
            self.tick_hists["beat_rows"].update(1, rows)
        # phase-align each next beat to its group's hb_ms grid: groups
        # sharing an interval then fall due on the SAME tick, so one
        # pulse per interval carries every such group's beat (max hub
        # batching — staggered per-group beats degrade to ~1 per RPC).
        # Mirrors the device's deadline advance so masks don't refire.
        # A round is ONE beat RPC a peer store: the hub cuts a pulse into
        # RPCs of max_fast_beats_per_rpc rows a destination and a led
        # group sends each peer store one row, so the grid has as many
        # evenly spaced phases as a destination would get RPCs, a slot
        # on phase slot % k (k = 1: phase 0, the grid).  More rows on
        # one tick batch no better and hold the loop k times as long:
        # 4,096 leaders' beats on one tick kept one operation in five
        # waiting 0.4 s behind them (PERF.md section 6, PR 29).  Each
        # group still beats once an interval.  Beats sent direct fill no
        # RPC: the grid.
        per_rpc = min((hub.max_fast_beats_per_rpc
                       for hub, _ in by_hub.values()), default=0)
        k = 1
        if per_rpc > 0:
            led = int(np.count_nonzero(
                (self.role == ROLE_LEADER) & self.has_ctrl))
            k = max(1, -(-led // per_rpc))
        hbs = self.hb_ms[slots]
        phase = (slots % k) * hbs // k
        self.hb_deadline[slots] = ((now - phase) // hbs + 1) * hbs + phase
        for hub, reps in by_hub.values():
            hub.pulse(reps)
        for r in direct:
            t = asyncio.ensure_future(r.send_heartbeat())
            t.add_done_callback(
                lambda tt: tt.cancelled() or tt.exception())


def _np_order_stat(values: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """Per-row q-th largest among masked slots (q = n//2 + 1), NEG for
    empty masks — the numpy oracle of ops.ballot.quorum_match_index."""
    NEG = np.int64(_NEG_I32)
    v = np.where(mask, values, NEG)
    sd = -np.sort(-v, axis=1)
    n = mask.sum(axis=1)
    qi = np.clip(n // 2, 0, values.shape[1] - 1)
    picked = np.take_along_axis(sd, qi[:, None], axis=1)[:, 0]
    return np.where(n > 0, picked, NEG)


def _np_joint_order_stat(values: np.ndarray, vm: np.ndarray,
                         ovm: np.ndarray) -> np.ndarray:
    """Joint-consensus order statistic: min of both configs' q-th
    largest where a row is in joint mode — the shared shape of
    ballot.joint_quorum_match_index AND joint_quorum_ack_time."""
    new_q = _np_order_stat(values, vm)
    joint = ovm.any(axis=1)
    if not joint.any():
        # no group is mid membership-change (the steady state): skip
        # the old-config order statistic entirely — it is half the
        # tick's sort work (profiled: 4 sorts/tick -> 2)
        return new_q
    old_q = _np_order_stat(values, ovm)
    return np.where(joint, np.minimum(new_q, old_q), new_q)


def _np_joint_quorum(rel: np.ndarray, vm: np.ndarray, ovm: np.ndarray
                     ) -> np.ndarray:
    return _np_joint_order_stat(rel.astype(np.int64), vm, ovm
                                ).astype(np.int32)
