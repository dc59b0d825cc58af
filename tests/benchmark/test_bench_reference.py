"""The plain reference: the register model on short concurrent histories, and
the tick's Raft arithmetic against the program's compiled tick."""

import numpy as np
import pytest

from benchmark.reference import (INF, LEADER, NEG, TICK_OUTPUTS, Read, Write,
                                 check_history, tick_mismatches,
                                 tick_reference)
from benchmark.traffic import LOADER

LOAD = Write(0, LOADER, 0, -INF, -INF)
A = Write(0, 1, 1, 1.0, 2.0)        # client 1's update, acknowledged at 2
B = Write(0, 2, 1, 1.5, 2.5)        # client 2's, concurrent with A
C = Write(0, 1, 2, 3.0, 4.0)        # after both were acknowledged


def _counts(reads, final, writes=(LOAD, A, B, C), final_time=10.0):
    return check_history(list(writes), reads, {0: final}, final_time)


def test_concurrent_writes_may_be_read_in_either_order():
    reads = [Read(0, 2.6, 2.7, (1, 1, 0)), Read(0, 2.6, 2.7, (2, 1, 0)),
             Read(0, 0.5, 1.2, (LOADER, 0, 0)),    # overlaps A: old value ok
             Read(0, 3.5, 3.6, (1, 2, 0)),         # C, while C is in flight
             Read(0, 3.5, 3.6, (2, 1, 0))]         # still B: C not yet acked
    assert _counts(reads, (1, 2, 0)) == {
        "reads_wrong": 0, "reads_stale": 0, "final_wrong": 0,
        "updates_lost": 0}


@pytest.mark.parametrize("read, final, key", [
    # the load's value after A was acknowledged and before the read began
    (Read(0, 2.1, 2.2, (LOADER, 0, 0)), (1, 2, 0), "reads_stale"),
    # A read after C was acknowledged
    (Read(0, 4.5, 4.6, (1, 1, 0)), (1, 2, 0), "reads_stale"),
    # a value no write wrote; bytes that failed the check; another record's
    (Read(0, 2.1, 2.2, (9, 9, 0)), (1, 2, 0), "reads_wrong"),
    (Read(0, 2.1, 2.2, None), (1, 2, 0), "reads_wrong"),
    (Read(0, 2.1, 2.2, (1, 1, 5)), (1, 2, 0), "reads_wrong"),
    # a value from the future: C seen before it was invoked
    (Read(0, 2.1, 2.2, (1, 2, 0)), (1, 2, 0), "reads_wrong"),
    # the final state lost the acknowledged C; or is no write at all
    (Read(0, 2.6, 2.7, (1, 1, 0)), (2, 1, 0), "updates_lost"),
    (Read(0, 2.6, 2.7, (1, 1, 0)), None, "final_wrong"),
])
def test_what_the_guarantees_forbid_is_counted(read, final, key):
    counts = _counts([read], final)
    assert counts.pop(key) == 1 and not any(counts.values())


def test_an_unacknowledged_write_may_or_may_not_have_applied():
    failed = Write(0, 3, 1, 5.0, INF)
    for final in ((1, 2, 0), (3, 1, 0)):
        counts = _counts([], final, writes=(LOAD, A, B, C, failed))
        assert not any(counts.values())


def _random_state(rng, g, p):
    voters = np.zeros((g, p), bool)
    voters[:, :3] = True
    old = voters & (rng.random((g, 1)) < 0.2) & (rng.random((g, p)) < 0.8)
    return {
        "role": rng.integers(0, 4, g).astype(np.int32),
        "commit_rel": rng.integers(0, 40, g).astype(np.int32),
        "pending_rel": rng.integers(0, 60, g).astype(np.int32),
        "match_rel": rng.integers(0, 100, (g, p)).astype(np.int32),
        "granted": rng.random((g, p)) < 0.5,
        "voter_mask": voters, "old_voter_mask": old,
        "elect_deadline": rng.integers(0, 3000, g).astype(np.int32),
        "hb_deadline": rng.integers(0, 3000, g).astype(np.int32),
        "last_ack": np.where(rng.random((g, p)) < 0.1, NEG,
                             rng.integers(0, 2000, (g, p))).astype(np.int32),
        "snap_deadline": rng.integers(0, 3000, g).astype(np.int32),
        "quiescent": rng.random(g) < 0.2,
        "witness_mask": voters & (rng.random((g, p)) < 0.1),
        "stepdown_deadline": rng.integers(0, 3000, g).astype(np.int32),
        "fence_start": np.where(rng.random(g) < 0.5, NEG,
                                rng.integers(0, 2000, g)).astype(np.int32),
    }


def test_tick_reference_by_hand():
    """Three voters, matches 7, 5, 2: a majority holds 5.  Acks 90, 80, 10: a
    majority answered at or after 80, so a fence armed at 85 is still open
    and one armed at 80 is confirmed."""
    s = _random_state(np.random.default_rng(0), 2, 4)
    s.update(role=np.array([LEADER, LEADER], np.int32),
             commit_rel=np.array([3, 3], np.int32),
             pending_rel=np.array([4, 6], np.int32),    # row 1: prior term
             match_rel=np.array([[7, 5, 2, 99]] * 2, np.int32),
             last_ack=np.array([[90, 80, 10, 99]] * 2, np.int32),
             old_voter_mask=np.zeros((2, 4), bool),
             witness_mask=np.zeros((2, 4), bool),
             quiescent=np.zeros(2, bool),
             fence_start=np.array([85, 80], np.int32))
    p = {"election_timeout_ms": np.full(2, 1000), "lease_ms": np.full(2, 900),
         "heartbeat_ms": np.full(2, 100), "snapshot_ms": np.zeros(2, int)}
    out = tick_reference(s, 100, p)
    assert out["commit_rel"].tolist() == [5, 3]
    assert out["commit_advanced"].tolist() == [True, False]
    assert out["q_ack"].tolist() == [80, 80]
    assert out["fence_ok"].tolist() == [False, True]
    assert out["lease_valid"].tolist() == [True, True]
    assert not out["step_down"].any() and not out["snap_due"].any()


def test_tick_reference_equals_the_compiled_tick_and_catches_a_wrong_row():
    """The comparison the benchmark makes after every window, here on seeded
    random rows through the program's jitted tick on the CPU."""
    from tpuraft.ops.tick import (GroupState, TickParams,
                                  raft_tick_outputs_jit)

    rng = np.random.default_rng(5)
    g, p = 256, 4
    s = _random_state(rng, g, p)
    params = {"election_timeout_ms": rng.integers(500, 1500, g),
              "heartbeat_ms": np.full(g, 100), "lease_ms": np.full(g, 900),
              "snapshot_ms": np.where(rng.random(g) < 0.5, 0, 1000)}
    out = raft_tick_outputs_jit(
        GroupState(**s), np.int32(1500),
        TickParams.make(params["election_timeout_ms"], params["heartbeat_ms"],
                        params["lease_ms"], params["snapshot_ms"]))
    got = {name: np.asarray(getattr(out, name)) for name in TICK_OUTPUTS}
    want = tick_reference(s, 1500, params)
    assert tick_mismatches(got, want) == 0
    for name in ("commit_advanced", "elected", "fence_ok", "step_down"):
        assert want[name].any(), name       # the rows exercise the lanes
    got["commit_rel"] = got["commit_rel"].copy()
    got["commit_rel"][3] += 1
    assert tick_mismatches(got, want) == 1
