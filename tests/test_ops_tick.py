"""Tests for the fused multi-group tick kernel (tpuraft.ops.tick)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tpuraft.ops.tick import (  # noqa: E402
    ROLE_CANDIDATE,
    ROLE_FOLLOWER,
    ROLE_INACTIVE,
    ROLE_LEADER,
    GroupState,
    TickOutputs,
    TickParams,
    raft_tick,
)

P = 4
PARAMS = TickParams.make(election_timeout_ms=1000, heartbeat_ms=100, lease_ms=900)


def mk_state(g=3):
    s = GroupState.zeros(g, P)
    return s


def test_leader_commit_advances():
    s = mk_state(2)
    s.role = jnp.array([ROLE_LEADER, ROLE_FOLLOWER], jnp.int32)
    s.voter_mask = jnp.array([[1, 1, 1, 0]] * 2, bool)
    s.pending_rel = jnp.array([1, 1], jnp.int32)
    # leader self slot 0 at 10, peers at 8 and 3 -> quorum idx 8
    s.match_rel = jnp.array([[10, 8, 3, 0], [10, 8, 3, 0]], jnp.int32)
    s.last_ack = jnp.zeros((2, P), jnp.int32)
    ns, out = raft_tick(s, jnp.int32(0), PARAMS)
    assert int(out.commit_rel[0]) == 8
    assert bool(out.commit_advanced[0])
    # follower's quorum math never advances commit on device
    assert int(out.commit_rel[1]) == 0
    assert not bool(out.commit_advanced[1])


def test_commit_gated_by_pending_index():
    """Entries from a previous leadership (below pending) never commit on
    quorum math alone — Raft §5.4.2 via pending_rel gate."""
    s = mk_state(1)
    s.role = jnp.array([ROLE_LEADER], jnp.int32)
    s.voter_mask = jnp.ones((1, P), bool)
    s.pending_rel = jnp.array([20], jnp.int32)
    s.match_rel = jnp.array([[15, 15, 15, 15]], jnp.int32)
    _, out = raft_tick(s, jnp.int32(0), PARAMS)
    assert int(out.commit_rel[0]) == 0
    assert not bool(out.commit_advanced[0])


def test_commit_monotone():
    s = mk_state(1)
    s.role = jnp.array([ROLE_LEADER], jnp.int32)
    s.voter_mask = jnp.array([[1, 1, 1, 0]], bool)
    s.pending_rel = jnp.array([1], jnp.int32)
    s.commit_rel = jnp.array([9], jnp.int32)
    s.match_rel = jnp.array([[5, 5, 5, 0]], jnp.int32)
    _, out = raft_tick(s, jnp.int32(0), PARAMS)
    assert int(out.commit_rel[0]) == 9  # never regresses


def test_candidate_elected():
    s = mk_state(2)
    s.role = jnp.array([ROLE_CANDIDATE, ROLE_CANDIDATE], jnp.int32)
    s.voter_mask = jnp.array([[1, 1, 1, 0]] * 2, bool)
    s.granted = jnp.array([[1, 1, 0, 0], [1, 0, 0, 0]], bool)
    _, out = raft_tick(s, jnp.int32(0), PARAMS)
    assert bool(out.elected[0])
    assert not bool(out.elected[1])


def test_election_due_and_inactive_silent():
    s = mk_state(3)
    s.role = jnp.array([ROLE_FOLLOWER, ROLE_FOLLOWER, ROLE_INACTIVE], jnp.int32)
    s.elect_deadline = jnp.array([100, 5000, 0], jnp.int32)
    _, out = raft_tick(s, jnp.int32(200), PARAMS)
    assert bool(out.election_due[0])
    assert not bool(out.election_due[1])
    assert not bool(out.election_due[2])


def test_leader_step_down_on_dead_quorum():
    s = mk_state(1)
    s.role = jnp.array([ROLE_LEADER], jnp.int32)
    s.voter_mask = jnp.ones((1, P), bool)
    # self slot acked recently; all others stale -> quorum(3) ack is stale
    s.last_ack = jnp.array([[5000, 100, 90, 80]], jnp.int32)
    _, out = raft_tick(s, jnp.int32(5000), PARAMS)
    assert bool(out.step_down[0])
    assert not bool(out.lease_valid[0])


def test_joint_step_down_when_old_config_quorum_dead():
    """During joint consensus the lease needs BOTH configs responsive
    (NodeImpl#checkDeadNodes walks conf AND oldConf): a leader whose
    old-config quorum is dead must step down even if the new config is
    fully live (ADVICE r2: q_ack previously used voter_mask only)."""
    s = mk_state(1)
    s.role = jnp.array([ROLE_LEADER], jnp.int32)
    # new config = slots {0,1}, old config = slots {2,3}
    s.voter_mask = jnp.array([[1, 1, 0, 0]], bool)
    s.old_voter_mask = jnp.array([[0, 0, 1, 1]], bool)
    # new-config voters fresh, old-config voters stale beyond eto
    s.last_ack = jnp.array([[5000, 5000, 100, 90]], jnp.int32)
    _, out = raft_tick(s, jnp.int32(5000), PARAMS)
    assert bool(out.step_down[0])
    assert not bool(out.lease_valid[0])
    # same ack state outside joint mode: new config alone holds the lease
    s.old_voter_mask = jnp.zeros((1, P), bool)
    _, out2 = raft_tick(s, jnp.int32(5000), PARAMS)
    assert not bool(out2.step_down[0])
    assert bool(out2.lease_valid[0])


def test_leader_lease_valid_with_live_quorum():
    s = mk_state(1)
    s.role = jnp.array([ROLE_LEADER], jnp.int32)
    s.voter_mask = jnp.ones((1, P), bool)
    s.last_ack = jnp.array([[5000, 4900, 4800, 100]], jnp.int32)
    _, out = raft_tick(s, jnp.int32(5000), PARAMS)
    assert not bool(out.step_down[0])
    assert bool(out.lease_valid[0])


def test_heartbeat_scheduling():
    s = mk_state(1)
    s.role = jnp.array([ROLE_LEADER], jnp.int32)
    s.voter_mask = jnp.ones((1, P), bool)
    s.last_ack = jnp.full((1, P), 5000, jnp.int32)
    s.hb_deadline = jnp.array([4000], jnp.int32)
    ns, out = raft_tick(s, jnp.int32(5000), PARAMS)
    assert bool(out.hb_due[0])
    assert int(ns.hb_deadline[0]) == 5100
    # next tick before new deadline: not due
    _, out2 = raft_tick(ns, jnp.int32(5050), PARAMS)
    assert not bool(out2.hb_due[0])


def test_jit_and_large_g():
    G = 2048
    s = GroupState.zeros(G, 8)
    rng = np.random.default_rng(0)
    s.role = jnp.asarray(rng.integers(0, 3, G).astype(np.int32))
    s.voter_mask = jnp.asarray(rng.random((G, 8)) < 0.6)
    s.match_rel = jnp.asarray(rng.integers(0, 1000, (G, 8)).astype(np.int32))
    tick = jax.jit(raft_tick)
    ns, out = tick(s, jnp.int32(123), PARAMS)
    assert out.commit_rel.shape == (G,)
    assert ns.match_rel.shape == (G, 8)


TICK_OUTPUT_ROWS = tuple(TickOutputs.__dataclass_fields__)


def randomize_mirrors(eng, rng) -> None:
    """Live-looking rows in every mirror and parameter row the tick
    reads, in place (no controls registered, so a tick applies nothing
    from its outputs)."""
    from tpuraft.core.engine import _NEG_I32

    G, P = eng.G, eng.P
    # per-group protocol params ([G] rows, VERDICT r2 #5): the twin
    # and the device tick must agree under MIXED timeouts too
    eng.eto_ms[:] = rng.integers(200, 2000, G)
    eng.hb_ms[:] = rng.integers(20, 200, G)
    eng.lease_ms[:] = rng.integers(100, 1800, G)
    eng.snap_ms[:] = rng.integers(0, 2, G) * 700
    eng._params_dev = None
    eng.role[:] = rng.integers(0, 4, G)
    eng.pending_rel[:] = rng.integers(1, 20, G)
    eng.match_abs[:] = rng.integers(0, 100, (G, P))
    eng.commit_abs[:] = rng.integers(0, 40, G)
    eng.voter_mask[:] = rng.random((G, P)) < 0.7
    eng.old_voter_mask[:] = np.where(
        (rng.random(G) < 0.2)[:, None], rng.random((G, P)) < 0.5, False)
    eng.granted[:] = rng.random((G, P)) < 0.4
    for row in (eng.elect_deadline, eng.hb_deadline, eng.snap_deadline,
                eng.stepdown_deadline):
        row[:] = rng.integers(0, 2000, G)
    eng.last_ack[:] = np.where(rng.random((G, P)) < 0.8,
                               rng.integers(0, 1500, (G, P)), _NEG_I32)
    # quiescence lane: hibernating groups must suppress hb_due /
    # election_due identically in both formulations (step_down and
    # lease_valid stay LIVE for quiescent leaders)
    eng.quiescent[:] = rng.random(G) < 0.3
    # witness lane (ISSUE 19): witness columns clamp the commit
    # reduce to the best data-replica match in both formulations
    eng.witness_mask[:] = rng.random((G, P)) < 0.2
    eng._n_witness_slots = int(eng.witness_mask.any(axis=1).sum())
    # read-fence lane
    eng.fence_start[:] = np.where(rng.random(G) < 0.4,
                                  rng.integers(0, 1500, G), _NEG_I32)


def _random_engine(rng, G, P):
    """A numpy-backend engine on randomized mirrors, and the
    (rel, commit_rel, now) its tick would see."""
    from tpuraft.core.engine import MultiRaftEngine
    from tpuraft.options import TickOptions

    eng = MultiRaftEngine(TickOptions(
        max_groups=G, max_peers=P, backend="numpy"))
    randomize_mirrors(eng, rng)
    return eng, *eng._rel_views(), int(rng.integers(500, 1500))


def test_numpy_twin_matches_device_tick_randomized():
    """The engine's no-jax fallback (MultiRaftEngine._np_tick) must stay
    BIT-IDENTICAL to ops.tick.raft_tick — quorum semantics now live in
    several formulations (jnp kernel, numpy twin, scalar BallotBox) and
    this differential test is the drift tripwire for the first two."""
    rng = np.random.default_rng(42)
    G, P = 64, 5
    for trial in range(10):
        eng, rel, commit_now, now = _random_engine(rng, G, P)
        np_out = eng._np_tick(rel, commit_now, now)
        _, dev_out = raft_tick(eng._group_state(rel, commit_now),
                               np.int32(now),
                               TickParams.make(eng.eto_ms, eng.hb_ms,
                                               eng.lease_ms, eng.snap_ms))
        for field in TICK_OUTPUT_ROWS:
            np.testing.assert_array_equal(
                np.asarray(getattr(dev_out, field)),
                np.asarray(getattr(np_out, field)),
                err_msg=f"trial {trial}: {field} diverged")


def _extreme_state(rng, G, P):
    """A GroupState of numpy rows over the whole int32 range: all four
    roles, NEG_INF acks and fences, joint and witness masks, quiescent
    rows, negative and near-2**31 values."""
    from tpuraft.ops.ballot import NEG_INF_I32

    lo, hi = np.iinfo(np.int32).min, np.iinfo(np.int32).max
    edge = np.array([lo, lo + 1, -1, 0, 1, hi - 1, hi], np.int32)

    def ints(shape, neg_inf_share=0.0):
        a = np.where(rng.random(shape) < 0.3, rng.choice(edge, shape),
                     rng.integers(lo, hi, shape, np.int32, endpoint=True))
        return np.where(rng.random(shape) < neg_inf_share,
                        np.int32(NEG_INF_I32), a).astype(np.int32)

    return GroupState(
        role=rng.permutation(np.arange(G) % 4).astype(np.int32),
        commit_rel=ints(G),
        pending_rel=ints(G),
        match_rel=ints((G, P)),
        granted=rng.random((G, P)) < 0.5,
        voter_mask=rng.random((G, P)) < 0.7,
        old_voter_mask=np.where((rng.random(G) < 0.3)[:, None],
                                rng.random((G, P)) < 0.5, False),
        elect_deadline=ints(G),
        hb_deadline=ints(G),
        last_ack=ints((G, P), neg_inf_share=0.3),
        snap_deadline=ints(G),
        quiescent=rng.random(G) < 0.3,
        witness_mask=rng.random((G, P)) < 0.3,
        stepdown_deadline=ints(G),
        fence_start=ints(G, neg_inf_share=0.5),
    )


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("G,P", [(8, 3), (128, 4), (2048, 4), (64, 7)])
def test_packed_layout_is_the_identity_and_the_same_tick(G, P, seed):
    """One int32 buffer each way (ops/tick.py): pack -> unpack gives the
    GroupState back bit for bit, and the packed program's unpacked
    outputs are raft_tick_outputs' and the numpy twin's, row for row."""
    from tpuraft.ops.tick import (
        PACKED_OUTPUT_MASKS, pack_outputs, pack_state,
        packed_state_shape, raft_tick_outputs_jit, raft_tick_packed_jit,
        unpack_outputs, unpack_state)

    rng = np.random.default_rng(1000 * G + 10 * P + seed)
    lo, hi = np.iinfo(np.int32).min, np.iinfo(np.int32).max
    state = _extreme_state(rng, G, P)
    now = int(rng.choice([lo, -7, 0, 12345, hi]))

    def same_state(back, back_now):
        assert int(back_now) == now
        for name in GroupState.__dataclass_fields__:
            a, b = np.asarray(getattr(back, name)), getattr(state, name)
            assert a.dtype == b.dtype and a.shape == b.shape, name
            np.testing.assert_array_equal(a, b, err_msg=name)

    # into a dirty reused buffer, as the engine packs
    buf = np.full(packed_state_shape(G, P), -1, np.int32)
    assert pack_state(state, now, buf) is buf
    assert buf.shape == (3 * P + 10, G)
    np.testing.assert_array_equal(pack_state(state, now)[:-1], buf[:-1])
    same_state(*unpack_state(buf))                    # the numpy inverse
    same_state(*jax.jit(unpack_state)(buf))           # as the program does

    # outputs: the device packer against its numpy inverse
    rows = {name: rng.random(G) < 0.5 for name in PACKED_OUTPUT_MASKS}
    rows["commit_rel"] = state.commit_rel
    rows["q_ack"] = state.fence_start
    packed = np.asarray(jax.jit(pack_outputs)(TickOutputs(**rows)))
    assert packed.shape == (3, G) and packed.dtype == np.int32
    back = unpack_outputs(packed)
    assert set(back) == set(TICK_OUTPUT_ROWS)
    for name in TICK_OUTPUT_ROWS:
        assert back[name].dtype == rows[name].dtype, name
        np.testing.assert_array_equal(back[name], rows[name], err_msg=name)

    # the packed program is the same tick, on the extreme rows ...
    params = TickParams.make(rng.integers(200, 2000, G),
                             rng.integers(20, 200, G),
                             rng.integers(100, 1800, G),
                             rng.integers(0, 2, G) * 500)
    want = raft_tick_outputs_jit(state, np.int32(now), params)
    got = unpack_outputs(np.asarray(raft_tick_packed_jit(buf, params)))
    for name in TICK_OUTPUT_ROWS:
        np.testing.assert_array_equal(
            got[name], np.asarray(getattr(want, name)), err_msg=name)

    # ... and on an engine's rows, where it is also the numpy twin's
    eng, rel, commit_now, now = _random_engine(rng, G, P)
    state = eng._group_state(rel, commit_now)
    params = TickParams.make(eng.eto_ms, eng.hb_ms, eng.lease_ms,
                             eng.snap_ms)
    want = raft_tick_outputs_jit(state, np.int32(now), params)
    twin = eng._np_tick(rel, commit_now, now)
    got = unpack_outputs(np.asarray(
        raft_tick_packed_jit(pack_state(state, now), params)))
    for name in TICK_OUTPUT_ROWS:
        np.testing.assert_array_equal(
            got[name], np.asarray(getattr(want, name)), err_msg=name)
        np.testing.assert_array_equal(
            got[name], np.asarray(getattr(twin, name)), err_msg=name)


def test_witness_clamp_enumeration_matches_host_and_quorum_math():
    """Enumerate EVERY witness subset of 3..6-voter confs (plus seeded
    joint-consensus variants) and cross-check the three formulations of
    the witness commit clamp against each other:

    - the device kernel: ops.ballot.joint_quorum_match_index followed
      by ops.ballot.witness_commit_clamp, batched as one [G] row per
      enumerated case;
    - the scalar host oracle: ballot_box.commit_point (the BallotBox
      data-clamp the device plane mirrors since ISSUE 19);
    - util.quorum's enumeration-by-majorities classification: for any
      VALID conf (witness_minority) every majority holds a data peer,
      so the clamp provably never binds — and for degenerate
      witness-majority rows (witness_only_majorities non-empty) the
      clamped commit never exceeds the best data-replica match.
    """
    from itertools import combinations

    from tpuraft.conf import Configuration
    from tpuraft.core.ballot_box import commit_point
    from tpuraft.entity import PeerId
    from tpuraft.ops.ballot import (
        joint_quorum_match_index,
        witness_commit_clamp,
    )
    from tpuraft.util import quorum as uq

    rng = np.random.default_rng(19)
    COLS = 8
    peers = [PeerId(f"10.0.0.{i + 1}", 80, 0) for i in range(COLS)]
    col = {p: i for i, p in enumerate(peers)}

    cases = []  # (conf, old_conf, match row)
    for n in range(3, 7):
        voters = peers[:n]
        for wn in range(0, n + 1):
            for wit in combinations(range(n), wn):
                for _ in range(2):
                    conf = Configuration(
                        list(voters), witnesses=[voters[i] for i in wit])
                    cases.append((conf, Configuration(),
                                  rng.integers(0, 30, COLS)))
    # joint variants: overlapping old/new windows, independent subsets
    for _ in range(60):
        n_new, n_old = int(rng.integers(3, 6)), int(rng.integers(3, 6))
        lo = int(rng.integers(0, 3))
        new_v, old_v = peers[:n_new], peers[lo:lo + n_old]
        conf = Configuration(
            list(new_v), witnesses=[p for p in new_v if rng.random() < 0.3])
        old = Configuration(
            list(old_v), witnesses=[p for p in old_v if rng.random() < 0.3])
        cases.append((conf, old, rng.integers(0, 30, COLS)))

    G = len(cases)
    match_m = np.zeros((G, COLS), np.int32)
    vm = np.zeros((G, COLS), bool)
    ovm = np.zeros((G, COLS), bool)
    wm = np.zeros((G, COLS), bool)
    for g, (conf, old, match) in enumerate(cases):
        match_m[g] = match
        for p in conf.peers:
            vm[g, col[p]] = True
        for p in old.peers:
            ovm[g, col[p]] = True
        for p in list(conf.witnesses) + list(old.witnesses):
            wm[g, col[p]] = True

    unclamped = np.asarray(joint_quorum_match_index(
        jnp.asarray(match_m), jnp.asarray(vm), jnp.asarray(ovm)))
    clamped = np.asarray(witness_commit_clamp(
        jnp.asarray(unclamped), jnp.asarray(match_m), jnp.asarray(vm),
        jnp.asarray(ovm), jnp.asarray(wm)))

    for g, (conf, old, match) in enumerate(cases):
        md = {p: int(match[col[p]])
              for p in set(conf.peers) | set(old.peers)}
        want = commit_point(md, conf, old)
        assert clamped[g] == want, (
            f"case {g}: device clamp {clamped[g]} != host commit_point "
            f"{want} (conf={conf}, old={old}, match={md})")
        if not old.is_empty():
            continue  # the majority classification below is single-conf
        voters, wits = set(conf.peers), set(conf.witnesses)
        if uq.witness_minority(voters, wits):
            # valid conf: every majority has a data peer (by
            # enumeration), so the q-th-largest match is always covered
            # by some data replica and the clamp must be a NO-OP
            assert uq.every_majority_has_data_peer(voters, wits)
            assert not uq.witness_only_majorities(voters, wits)
            assert clamped[g] == unclamped[g], (
                f"case {g}: clamp bound on a witness_minority conf "
                f"(conf={conf}, match={md})")
        elif wits:
            # degenerate witness-majority row (set_conf does not
            # validate; node-level is_valid() does): whatever commits
            # must be held by a data replica — never a witness-only
            # certification
            data_best = max((md[p] for p in conf.data_peers()), default=0)
            assert clamped[g] <= data_best

    # deterministic binding case (chip_smoke.drive_lanes' clamp probe in
    # miniature): 1 data voter at 3, 2 witnesses at 9 -> the unclamped
    # order statistic says 9, the clamp must pin commit to 3
    probe_match = jnp.asarray([[3, 9, 9]], jnp.int32)
    probe_vm = jnp.ones((1, 3), bool)
    probe_ovm = jnp.zeros((1, 3), bool)
    probe_wm = jnp.asarray([[False, True, True]])
    q_idx = joint_quorum_match_index(probe_match, probe_vm, probe_ovm)
    assert int(q_idx[0]) == 9
    assert int(witness_commit_clamp(
        q_idx, probe_match, probe_vm, probe_ovm, probe_wm)[0]) == 3
