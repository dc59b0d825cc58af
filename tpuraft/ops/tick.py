"""The fused multi-group tick kernel.

One jitted function advances ALL G raft groups' quorum math at once
(SURVEY.md §8 "Device plane"): commit-index advancement, election vote
tallies, election-timeout firing, leader-lease/step-down checks, and
heartbeat scheduling.  The host runtime (tpuraft.core.engine) merges
protocol events (RPC responses, fsync acks) into the state arrays between
ticks and applies the emitted event masks (elected / step_down /
start_prevote) through the slow-path protocol code.

Division of labor:
  - device mutates only *derived, monotone* state (commit_rel, hb_deadline);
  - role/term/vote transitions are host-applied from output masks, so the
    host remains the single writer of protocol state (the functional
    analog of NodeImpl's writeLock discipline).

All times are int32 milliseconds relative to engine start; all log indexes
are int32 relative to a per-group host-managed base (see tpuraft.ops).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from tpuraft.ops.ballot import NEG_INF_I32, witness_commit_clamp
from tpuraft.ops.quorum_pallas import fused_quorum

# Role encoding (device plane). Learners are not a role: they sit in peer
# slots with voter_mask=False.
ROLE_FOLLOWER = 0
ROLE_CANDIDATE = 1
ROLE_LEADER = 2
ROLE_INACTIVE = 3  # unallocated group slot


@jax.tree_util.register_dataclass
@dataclass
class GroupState:
    """Structure-of-arrays consensus state for G groups x P peer slots.

    This is this *node's* local view of each group it participates in —
    the vectorized replacement for the reference's per-group object graph
    (NodeImpl + BallotBox + ReplicatorGroup matchIndex bookkeeping).
    """

    role: jnp.ndarray          # int32 [G]
    commit_rel: jnp.ndarray    # int32 [G]  committed index - base
    pending_rel: jnp.ndarray   # int32 [G]  first index of current leadership
    match_rel: jnp.ndarray     # int32 [G,P] acked matchIndex - base (self slot = lastLog)
    granted: jnp.ndarray       # bool  [G,P] votes granted this election round
    voter_mask: jnp.ndarray    # bool  [G,P] voters in current conf
    old_voter_mask: jnp.ndarray  # bool [G,P] voters in old conf (joint) else False
    elect_deadline: jnp.ndarray  # int32 [G] ms: follower election-timeout deadline
    hb_deadline: jnp.ndarray   # int32 [G] ms: leader next-heartbeat time
    last_ack: jnp.ndarray      # int32 [G,P] ms: last response time per peer
    snap_deadline: jnp.ndarray  # int32 [G] ms: next snapshot due (engine-
    # scheduled snapshotTimer: one [G] row + mask replaces G RepeatedTimers)
    quiescent: jnp.ndarray     # bool [G] hibernating group: beats and
    # election timeouts suppressed on device; liveness is delegated to the
    # store-level lease (HeartbeatHub), which wakes the group on expiry.
    # step_down stays LIVE for quiescent leaders — the host refreshes
    # their last_ack rows from store-lease acks, so a dead store still
    # deposes its quiescent leaders through ordinary ack staleness.
    witness_mask: jnp.ndarray  # bool [G,P] witness voters (either config):
    # metadata-only replicas that vote and ack but hold no log payload —
    # the commit point is clamped to the best data-replica match
    # (ballot.witness_commit_clamp, the vectorized BallotBox clamp)
    stepdown_deadline: jnp.ndarray  # int32 [G] ms: leader's next periodic
    # stepdown/priority check (the reference's stepDownTimer cadence,
    # eto/2) — fires Node._check_dead_nodes, which re-verifies the quorum
    # AND accrues priority_transfer_rounds toward transfer-back
    fence_start: jnp.ndarray   # int32 [G] ms: earliest pending read-fence
    # start time, NEG_INF when no fence is pending — the device resolves
    # a ReadConfirmBatcher round when the fused q_ack reduction reaches
    # it (fence_ok), replacing the per-round host-side ack-set tally

    @staticmethod
    def zeros(g: int, p: int) -> "GroupState":
        return GroupState(
            role=jnp.full((g,), ROLE_INACTIVE, jnp.int32),
            commit_rel=jnp.zeros((g,), jnp.int32),
            pending_rel=jnp.ones((g,), jnp.int32),
            match_rel=jnp.zeros((g, p), jnp.int32),
            granted=jnp.zeros((g, p), bool),
            voter_mask=jnp.zeros((g, p), bool),
            old_voter_mask=jnp.zeros((g, p), bool),
            elect_deadline=jnp.zeros((g,), jnp.int32),
            hb_deadline=jnp.zeros((g,), jnp.int32),
            last_ack=jnp.zeros((g, p), jnp.int32),
            snap_deadline=jnp.zeros((g,), jnp.int32),
            quiescent=jnp.zeros((g,), bool),
            witness_mask=jnp.zeros((g, p), bool),
            stepdown_deadline=jnp.zeros((g,), jnp.int32),
            fence_start=jnp.full((g,), NEG_INF_I32, jnp.int32),
        )


@jax.tree_util.register_dataclass
@dataclass
class TickParams:
    """Protocol parameters: int32 scalars (engine-wide) or [G] rows
    (per-group — the reference's per-node NodeOptions timeouts; a PD
    group and region groups in one engine each honor their own).  Either
    shape broadcasts through the tick; prefetched once, not retraced."""

    election_timeout_ms: jnp.ndarray  # int32 scalar or [G]
    heartbeat_ms: jnp.ndarray         # int32 scalar or [G]
    lease_ms: jnp.ndarray             # int32 scalar or [G]
    snapshot_ms: jnp.ndarray          # int32 scalar or [G]; 0 = disabled

    @staticmethod
    def make(election_timeout_ms, heartbeat_ms, lease_ms,
             snapshot_ms=0) -> "TickParams":
        return TickParams(
            jnp.asarray(election_timeout_ms, jnp.int32),
            jnp.asarray(heartbeat_ms, jnp.int32),
            jnp.asarray(lease_ms, jnp.int32),
            jnp.asarray(snapshot_ms, jnp.int32),
        )


@jax.tree_util.register_dataclass
@dataclass
class TickOutputs:
    """Per-tick event masks + advanced indexes the host applies."""

    commit_rel: jnp.ndarray     # int32 [G] new commit (== old where unchanged)
    commit_advanced: jnp.ndarray  # bool [G]
    elected: jnp.ndarray        # bool [G] candidate reached vote quorum
    election_due: jnp.ndarray   # bool [G] follower/candidate election timer fired
    step_down: jnp.ndarray      # bool [G] leader lost quorum within lease window
    hb_due: jnp.ndarray         # bool [G] leader heartbeat due this tick
    lease_valid: jnp.ndarray    # bool [G] leader lease currently valid (for reads)
    snap_due: jnp.ndarray       # bool [G] snapshot interval elapsed (any role)
    q_ack: jnp.ndarray          # int32 [G] q-th newest voter ack time (the
    # lease_valid lane's raw input, NEG_INF when no data) — the host keeps
    # the last tick's row as a LOWER bound on the current quorum-ack time,
    # so per-read lease checks (ReadOnlyOption.LEASE_BASED) answer off the
    # fused reduction instead of re-sorting a [P] row per read
    stepdown_due: jnp.ndarray   # bool [G] leader's periodic stepdown/
    # priority check fired (Node._check_dead_nodes slow path)
    fence_ok: jnp.ndarray       # bool [G] pending read fence satisfied:
    # the quorum-ack point reached fence_start (host resolves + re-arms)


def raft_tick(state: GroupState, now_ms: jnp.ndarray, params: TickParams,
              quorum_impl: str | None = None
              ) -> tuple[GroupState, TickOutputs]:
    """Advance all groups one tick. Pure; jit/shard_map over the G axis.

    quorum_impl selects the [G,P]-reduction backend (see
    tpuraft.ops.quorum_pallas.fused_quorum); it must be static under jit.
    """
    is_leader = state.role == ROLE_LEADER
    is_follower = state.role == ROLE_FOLLOWER
    is_candidate = state.role == ROLE_CANDIDATE

    # The three [G,P] -> [G] quorum reductions in one (fusable) pass.
    quorum_idx, vote_ok, q_ack = fused_quorum(
        state.match_rel, state.granted, state.last_ack,
        state.voter_mask, state.old_voter_mask, impl=quorum_impl)

    # --- commit advancement (BallotBox#commitAt, vectorized) ---------------
    # Entries before pending_rel belong to prior leaderships: never counted
    # (this IS the Raft §5.4.2 current-term commit gate — pending_rel is set
    # to lastLogIndex+1 at becomeLeader, mirroring BallotBox#resetPendingIndex).
    # Witness confs: votes and acks count every voter (quorums above are
    # correct as-is), but the COMMIT point is clamped to the best
    # data-replica match — an index held only by metadata witnesses is
    # not durable on any log.  Applied after fused_quorum so the fused
    # reduction (including its pallas backend) stays witness-agnostic.
    quorum_idx = witness_commit_clamp(
        quorum_idx, state.match_rel, state.voter_mask,
        state.old_voter_mask, state.witness_mask)
    can_commit = is_leader & (quorum_idx >= state.pending_rel)
    new_commit = jnp.where(
        can_commit, jnp.maximum(state.commit_rel, quorum_idx), state.commit_rel
    )
    commit_advanced = new_commit > state.commit_rel

    # --- election tally (NodeImpl#handleRequestVoteResponse, vectorized) ---
    elected = is_candidate & vote_ok

    # --- election timeout (RepeatedTimer electionTimer, vectorized) --------
    # Quiescent followers suppress their election timeout: liveness for a
    # hibernating group rides the store-level lease, and the lease-expiry
    # wake path re-arms the deadline (with fresh jitter) before clearing
    # the quiescent bit — so the mask can never fire on stale deadlines.
    election_due = (is_follower | is_candidate) & ~state.quiescent & (
        now_ms >= state.elect_deadline)

    # --- leader lease / step-down (NodeImpl#checkDeadNodes) ----------------
    # Count the leader itself as acked "now" via its self slot: the host
    # keeps last_ack[g, self] == now. Quorum ack time = q-th newest response.
    # The NEG gate below means "no data", not "dead quorum"; the host
    # upholds the invariant that a LEADER's voter columns are never NEG
    # (grace stamps at on_leader and for set_conf-added peers), so a
    # config that stops responding always reaches step_down via staleness.
    have_quorum_ack = q_ack > NEG_INF_I32
    lease_valid = is_leader & have_quorum_ack & (now_ms - q_ack < params.lease_ms)
    step_down = is_leader & have_quorum_ack & (
        now_ms - q_ack >= params.election_timeout_ms
    )

    # --- periodic stepdown/priority lane (RepeatedTimer stepDownTimer) -----
    # Timer-mode nodes run _check_dead_nodes every eto/2 regardless of
    # quorum health, and that cadence is what accrues
    # priority_transfer_rounds (a decay-elected leader hands leadership
    # back when a higher-priority peer recovers).  The engine previously
    # only fired the handler on DEAD quorums, so engine leaders never
    # transferred back — this lane restores the periodic cadence on
    # device.  Quiescent leaders skip it: their quorum rides the store
    # lease, and waking for a priority scan would defeat hibernation.
    stepdown_due = is_leader & ~state.quiescent & (
        now_ms >= state.stepdown_deadline)
    new_stepdown_deadline = jnp.where(
        stepdown_due,
        now_ms + jnp.maximum(params.election_timeout_ms // 2, 1),
        state.stepdown_deadline)

    # --- device read-fence tally (ReadConfirmBatcher rounds) ---------------
    # A pending SAFE ReadIndex round armed fence_start = its start time;
    # the round is confirmed once a voter quorum acked AT OR AFTER it —
    # exactly the fused q_ack order statistic already computed above, so
    # the tally rides the existing reduction instead of a host-side
    # per-round ack-set.  The host clears/re-arms fence_start (it owns
    # the pending-fence queue); the row passes through unchanged.
    fence_ok = is_leader & (state.fence_start > NEG_INF_I32) & \
        have_quorum_ack & (q_ack >= state.fence_start)

    # --- heartbeat scheduling ---------------------------------------------
    # Quiescent leaders beat nothing: idle beat traffic collapses from
    # O(G x P) rows to the store-level lease's O(stores^2) RPCs.  The
    # step_down mask above intentionally stays ungated — store-lease acks
    # refresh quiescent leaders' last_ack rows host-side, so a silent
    # store still deposes its hibernating leaders within one timeout.
    hb_due = is_leader & ~state.quiescent & (now_ms >= state.hb_deadline)
    new_hb_deadline = jnp.where(hb_due, now_ms + params.heartbeat_ms, state.hb_deadline)

    # --- snapshot cadence (RepeatedTimer snapshotTimer, vectorized) --------
    # Any ACTIVE role snapshots (followers compact their logs too, like
    # the reference's per-node snapshotTimer); 0 disables.  The deadline
    # row advances on device; the host re-mirrors + jitters on fire.
    active = state.role != ROLE_INACTIVE
    snap_due = active & (params.snapshot_ms > 0) & (
        now_ms >= state.snap_deadline)
    new_snap_deadline = jnp.where(
        snap_due, now_ms + params.snapshot_ms, state.snap_deadline)

    new_state = GroupState(
        role=state.role,
        commit_rel=new_commit,
        pending_rel=state.pending_rel,
        match_rel=state.match_rel,
        granted=state.granted,
        voter_mask=state.voter_mask,
        old_voter_mask=state.old_voter_mask,
        elect_deadline=state.elect_deadline,
        hb_deadline=new_hb_deadline,
        last_ack=state.last_ack,
        snap_deadline=new_snap_deadline,
        quiescent=state.quiescent,
        witness_mask=state.witness_mask,
        stepdown_deadline=new_stepdown_deadline,
        fence_start=state.fence_start,
    )
    outputs = TickOutputs(
        commit_rel=new_commit,
        commit_advanced=commit_advanced,
        elected=elected,
        election_due=election_due,
        step_down=step_down,
        hb_due=hb_due,
        lease_valid=lease_valid,
        snap_due=snap_due,
        q_ack=q_ack,
        stepdown_due=stepdown_due,
        fence_ok=fence_ok,
    )
    return new_state, outputs


raft_tick_jit = jax.jit(raft_tick, donate_argnums=(0,),
                        static_argnames=("quorum_impl",))


def raft_tick_outputs(state: GroupState, now_ms: jnp.ndarray,
                      params: TickParams) -> TickOutputs:
    """Outputs-only tick — what the engine consumes (its numpy mirrors
    are the state of record between ticks, so the new GroupState is
    never fetched)."""
    return raft_tick(state, now_ms, params)[1]


# ONE process-wide jitted instance: every MultiRaftEngine in the process
# shares this trace cache, so the N-th engine's first tick does not
# re-trace/re-compile (a ~0.5s event-loop stall per engine that round-1
# style multi-engine tests turned into election storms).
raft_tick_outputs_jit = jax.jit(raft_tick_outputs)


# ---------------------------------------------------------------------------
# Packed host/device layout: one int32 buffer each way.
#
# A transfer costs the host per ARRAY, not per byte (PERF.md section 6,
# PR 28), so the single-device engine crosses the boundary with one
# array up and one down.  Field-major: every field is a contiguous row
# and G stays the minor (lane) axis.
#
#   up, int32 [3P + 10, G]:
#     rows 0..8         the nine [G] fields of PACKED_STATE_ROWS
#     rows 9..9+P       match_rel.T
#     rows 9+P..9+2P    last_ack.T
#     rows 9+2P..9+3P   the four [G, P] masks of PACKED_STATE_MASKS,
#                       transposed, one bit each (bit i = i-th name)
#     row  9+3P         now_ms, in element 0
#   down, int32 [3, G]: commit_rel, q_ack, the nine masks of
#     PACKED_OUTPUT_MASKS as bits of one row.
#
# The layout is a function of (G, P) alone; P is read back off the row
# count.  pack_state / unpack_outputs run on the host (numpy),
# unpack_state / pack_outputs inside the jitted program; the unpackers
# use operators only, so each also inverts its packer in plain numpy.
# ---------------------------------------------------------------------------

PACKED_STATE_ROWS = ("role", "commit_rel", "pending_rel", "elect_deadline",
                     "hb_deadline", "snap_deadline", "stepdown_deadline",
                     "fence_start", "quiescent")
PACKED_STATE_MASKS = ("granted", "voter_mask", "old_voter_mask",
                      "witness_mask")
PACKED_OUTPUT_MASKS = ("commit_advanced", "elected", "election_due",
                       "step_down", "hb_due", "lease_valid", "snap_due",
                       "stepdown_due", "fence_ok")
_N_ROWS = len(PACKED_STATE_ROWS)


def packed_state_shape(g: int, p: int) -> tuple[int, int]:
    return (_N_ROWS + 3 * p + 1, g)


def pack_state(state: GroupState, now_ms: int,
               out: np.ndarray | None = None) -> np.ndarray:
    """Host side: copy a GroupState of numpy rows and ``now_ms`` into
    one int32 ``[3P + 10, G]`` buffer (``out``, or a new one)."""
    g, p = state.match_rel.shape
    if out is None:
        out = np.empty(packed_state_shape(g, p), np.int32)
    for i, name in enumerate(PACKED_STATE_ROWS):
        out[i] = getattr(state, name)
    a = _N_ROWS
    out[a:a + p] = np.asarray(state.match_rel).T
    out[a + p:a + 2 * p] = np.asarray(state.last_ack).T
    bits = np.zeros((g, p), np.uint8)
    for i, name in enumerate(PACKED_STATE_MASKS):
        bits |= np.asarray(getattr(state, name), bool).view(np.uint8) << i
    out[a + 2 * p:a + 3 * p] = bits.T
    out[a + 3 * p, 0] = now_ms
    return out


def unpack_state(buf) -> tuple[GroupState, jnp.ndarray]:
    """(GroupState, now_ms) from a packed buffer: pack_state's inverse.
    Slices and operators only, so it traces under jit and also runs on
    a numpy buffer."""
    p = (buf.shape[0] - _N_ROWS - 1) // 3
    a = _N_ROWS
    fields = {name: buf[i] for i, name in enumerate(PACKED_STATE_ROWS)}
    fields["quiescent"] = fields["quiescent"] != 0
    fields["match_rel"] = buf[a:a + p].T
    fields["last_ack"] = buf[a + p:a + 2 * p].T
    bits = buf[a + 2 * p:a + 3 * p].T
    for i, name in enumerate(PACKED_STATE_MASKS):
        fields[name] = (bits & (1 << i)) != 0
    return GroupState(**fields), buf[a + 3 * p, 0]


def pack_outputs(out: TickOutputs) -> jnp.ndarray:
    """Device side: the eleven output rows as one int32 ``[3, G]``."""
    bits = jnp.zeros_like(out.commit_rel)
    for i, name in enumerate(PACKED_OUTPUT_MASKS):
        bits |= getattr(out, name).astype(jnp.int32) << i
    return jnp.stack([out.commit_rel, out.q_ack, bits])


def unpack_outputs(buf) -> dict:
    """The eleven named rows of a packed ``[3, G]`` output buffer:
    pack_outputs' inverse (numpy in, numpy out)."""
    rows = {"commit_rel": buf[0], "q_ack": buf[1]}
    for i, name in enumerate(PACKED_OUTPUT_MASKS):
        rows[name] = (buf[2] & (1 << i)) != 0
    return rows


def _raft_tick_packed(buf: jnp.ndarray, params: TickParams) -> jnp.ndarray:
    state, now_ms = unpack_state(buf)
    return pack_outputs(raft_tick(state, now_ms, params)[1])


# The device program keeps the name the trace readers key on
# (jit_raft_tick_outputs: raft_tick_us, raft_tick_roofline): unpack,
# raft_tick and pack are ONE program per tick.
_raft_tick_packed.__name__ = raft_tick_outputs.__name__
# process-wide like raft_tick_outputs_jit above: one trace cache for
# every engine of a [G, P] shape
raft_tick_packed_jit = jax.jit(_raft_tick_packed)


def witness_lanes_available() -> bool:
    """Does the loaded device plane carry the witness/priority/fence
    parity lanes?  StoreEngine consults this before accepting a witness
    conf on an engine-backed store: against an older tick kernel (e.g. a
    stale deployment mixing wheel versions) the [G,P] ballot plane would
    count witness acks as durable and commit unreplicated entries, so
    the boot refusal stays — with an error that names the missing lane
    rather than a blanket "engines can't do witnesses"."""
    return ("witness_mask" in GroupState.__dataclass_fields__
            and "fence_ok" in TickOutputs.__dataclass_fields__)
