"""The plain reference: what the deployment's guarantees allow a run to say.

Independent of ``tpuraft``: nothing here imports the program or takes
anything it made.  Two parts.

``check_history`` is a sequential model of a replicated register per record.
256 clients update the same hot records concurrently, so a record's value at
any moment is one of several acknowledged writes; values name their writer and
sequence number (``traffic.Values``), which makes the question "which write did
this read see, and was that allowed?" cheap.  A read (or the final state) may
return write ``w`` of its record unless

- no such write exists, or its bytes are not what the write wrote (wrong),
- ``w`` was invoked after the read had returned (wrong: from the future), or
- another write ``w'`` was invoked after ``w`` was acknowledged and was itself
  acknowledged before the read was invoked (stale: linearizability orders
  ``w`` before ``w'`` before the read).  For the final state, read after
  everything has settled, that is an acknowledged update lost.

These are necessary conditions of a linearizable register, checked on every
operation; they do not order two reads of concurrent writes against each other.

``tick_reference`` is the Raft arithmetic of one engine tick over ``[G, P]``
state in plain numpy, row by sorted row: commit point, election tally,
timers, lease, read fence.  It is compared, bit for bit, with what the
compiled device tick returns for the same inputs.
"""

from __future__ import annotations

import bisect
import math
from collections import defaultdict
from typing import NamedTuple

import numpy as np

INF = math.inf


class Write(NamedTuple):
    record: int
    writer: int
    seq: int
    invoke: float
    complete: float     # INF: never acknowledged (it may still have applied)


class Read(NamedTuple):
    record: int
    invoke: float
    complete: float
    seen: tuple | None  # (writer, seq, record) parsed from the value, or None


class _Register:
    """One record's writes, indexed to answer "was ``w`` overwritten for
    certain before time ``t``?"."""

    def __init__(self, writes: list):
        self.by_id = {(w.writer, w.seq): w for w in writes}
        order = sorted(writes, key=lambda w: w.invoke)
        self._invokes = [w.invoke for w in order]
        # suffix minimum of acknowledgement times, by invoke order
        self._min_complete_from = [INF] * (len(order) + 1)
        for i in range(len(order) - 1, -1, -1):
            self._min_complete_from[i] = min(order[i].complete,
                                             self._min_complete_from[i + 1])

    def overwritten_before(self, w: Write, t: float) -> bool:
        i = bisect.bisect_right(self._invokes, w.complete)
        return self._min_complete_from[i] < t


def check_history(writes: list, reads: list, final: dict,
                  final_time: float) -> dict:
    """Counts of what the guarantees forbid.  ``final`` maps a record to the
    parsed value read after the run settled at ``final_time`` (None where the
    bytes were no write's)."""
    per_record = defaultdict(list)
    for w in writes:
        per_record[w.record].append(w)
    registers = {rec: _Register(ws) for rec, ws in per_record.items()}
    wrong = stale = 0
    for r in reads:
        reg = registers.get(r.record)
        w = None
        if r.seen is not None and r.seen[2] == r.record and reg is not None:
            w = reg.by_id.get((r.seen[0], r.seen[1]))
        if w is None or w.invoke > r.complete:
            wrong += 1
        elif reg.overwritten_before(w, r.invoke):
            stale += 1
    final_wrong = lost = 0
    for rec, seen in final.items():
        reg = registers.get(rec)
        w = None
        if seen is not None and seen[2] == rec and reg is not None:
            w = reg.by_id.get((seen[0], seen[1]))
        if w is None:
            final_wrong += 1
        elif reg.overwritten_before(w, final_time):
            lost += 1
    return {"reads_wrong": wrong, "reads_stale": stale,
            "final_wrong": final_wrong, "updates_lost": lost}


# ---------------------------------------------------------------------------
# one engine tick, from the Raft rules
# ---------------------------------------------------------------------------

FOLLOWER, CANDIDATE, LEADER, INACTIVE = 0, 1, 2, 3
NEG = -(2 ** 30)        # "no data" in an int32 row

TICK_OUTPUTS = ("commit_rel", "commit_advanced", "elected", "election_due",
                "step_down", "hb_due", "lease_valid", "snap_due", "q_ack",
                "stepdown_due", "fence_ok")


def _quorum_value(values, mask):
    """Per row: the largest value that a majority of the masked slots reach
    (the q-th largest, q = n // 2 + 1); NEG where no slot is masked."""
    v = np.where(mask, values.astype(np.int64), NEG)
    desc = -np.sort(-v, axis=1)
    n = mask.sum(axis=1)
    q = np.clip(n // 2, 0, values.shape[1] - 1)      # index of the q-th
    picked = desc[np.arange(len(desc)), q]
    return np.where(n > 0, picked, NEG)


def _joint(fn, values, voters, old_voters):
    new = fn(values, voters)
    old = fn(values, old_voters)
    return np.where(old_voters.any(axis=1), np.minimum(new, old), new)


def _votes(granted, mask):
    n = mask.sum(axis=1)
    return (n > 0) & ((granted & mask).sum(axis=1) >= n // 2 + 1)


def tick_reference(s: dict, now: int, p: dict) -> dict:
    """``s``: the tick's input rows by name (numpy), ``p``: the four protocol
    parameter rows.  Returns the eleven output rows by name."""
    role = s["role"]
    leader, follower, candidate = (role == LEADER, role == FOLLOWER,
                                   role == CANDIDATE)
    vm, ovm = s["voter_mask"], s["old_voter_mask"]
    quiescent = s["quiescent"]

    q_idx = _joint(_quorum_value, s["match_rel"], vm, ovm)
    # an index only witnesses hold is on no log: clamp to the best data replica
    voters = vm | ovm
    has_witness = (voters & s["witness_mask"]).any(axis=1)
    data_best = np.where(voters & ~s["witness_mask"],
                         s["match_rel"].astype(np.int64), 0).max(axis=1)
    q_idx = np.where(has_witness, np.minimum(q_idx, data_best), q_idx)
    can_commit = leader & (q_idx >= s["pending_rel"])
    commit = np.where(can_commit, np.maximum(s["commit_rel"], q_idx),
                      s["commit_rel"])

    vote_new, vote_old = _votes(s["granted"], vm), _votes(s["granted"], ovm)
    vote_ok = np.where(ovm.any(axis=1), vote_new & vote_old, vote_new)

    q_ack = _joint(_quorum_value, s["last_ack"], vm, ovm)
    have_ack = q_ack > NEG
    return {
        "commit_rel": commit.astype(np.int32),
        "commit_advanced": commit > s["commit_rel"],
        "elected": candidate & vote_ok,
        "election_due": (follower | candidate) & ~quiescent
        & (now >= s["elect_deadline"]),
        "step_down": leader & have_ack
        & (now - q_ack >= p["election_timeout_ms"]),
        "hb_due": leader & ~quiescent & (now >= s["hb_deadline"]),
        "lease_valid": leader & have_ack & (now - q_ack < p["lease_ms"]),
        "snap_due": (role != INACTIVE) & (p["snapshot_ms"] > 0)
        & (now >= s["snap_deadline"]),
        "q_ack": q_ack.astype(np.int32),
        "stepdown_due": leader & ~quiescent & (now >= s["stepdown_deadline"]),
        "fence_ok": leader & (s["fence_start"] > NEG) & have_ack
        & (q_ack >= s["fence_start"]),
    }


def tick_mismatches(got: dict, want: dict) -> int:
    """Rows, over all eleven outputs, on which the device tick and the
    reference differ."""
    bad = 0
    for name in TICK_OUTPUTS:
        a, b = np.asarray(got[name]), np.asarray(want[name])
        if a.shape != b.shape:
            bad += max(a.size, b.size)
        else:
            bad += int((a != b).sum())
    return bad
