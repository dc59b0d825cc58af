"""Multi-node integration tests: the reference's NodeTest tier
(SURVEY.md §5) — elections, replication, fail-over, restart recovery,
partitions, leadership transfer, membership change, linearizable reads.
"""

import asyncio
import time

import pytest

from tests.cluster import MockStateMachine, TestCluster
from tpuraft.core.node import State
from tpuraft.core.read_only import ReadIndexError
from tpuraft.entity import PeerId, Task
from tpuraft.errors import RaftError, Status


async def test_single_node_becomes_leader_and_applies():
    c = TestCluster(1)
    await c.start_all()
    leader = await c.wait_leader()
    st = await c.apply_ok(leader, b"hello")
    assert st.is_ok()
    await c.wait_applied(1)
    assert c.fsms[leader.server_id].logs == [b"hello"]
    await c.stop_all()


async def test_triple_node_elect_and_replicate():
    c = TestCluster(3)
    await c.start_all()
    leader = await c.wait_leader()
    for i in range(10):
        st = await c.apply_ok(leader, b"op%d" % i)
        assert st.is_ok(), str(st)
    await c.wait_applied(10)
    for p in c.peers:
        assert c.fsms[p].logs == [b"op%d" % i for i in range(10)]
    # exactly one leader, others followers
    assert sum(1 for n in c.nodes.values() if n.state == State.LEADER) == 1
    await c.stop_all()


async def test_apply_on_follower_rejected():
    c = TestCluster(3)
    await c.start_all()
    leader = await c.wait_leader()
    follower = next(n for n in c.nodes.values() if n is not leader)
    st = await c.apply_ok(follower, b"nope", retry=False)
    assert not st.is_ok()
    assert st.raft_error == RaftError.EPERM
    await c.stop_all()


async def test_leader_failover():
    c = TestCluster(3)
    await c.start_all()
    leader = await c.wait_leader()
    await c.apply_ok(leader, b"before")
    await c.wait_applied(1)
    dead = leader.server_id
    await c.stop(dead)
    leader2 = await c.wait_leader()
    assert leader2.server_id != dead
    st = await c.apply_ok(leader2, b"after")
    assert st.is_ok()
    await c.wait_applied(2)
    for p, n in c.nodes.items():
        assert c.fsms[p].logs == [b"before", b"after"]
    await c.stop_all()


async def test_restart_recovery_from_log(tmp_path):
    c = TestCluster(3, tmp_path=tmp_path)
    await c.start_all()
    leader = await c.wait_leader()
    for i in range(5):
        await c.apply_ok(leader, b"v%d" % i)
    await c.wait_applied(5)
    await c.stop_all()
    # full restart: state must replay from durable log
    c2 = TestCluster(3, tmp_path=tmp_path)
    c2.net = c.net
    await c2.start_all()
    leader2 = await c2.wait_leader()
    await c2.apply_ok(leader2, b"v5")
    await c2.wait_applied(6)
    for p in c2.peers:
        assert c2.fsms[p].logs == [b"v%d" % i for i in range(6)]
    await c2.stop_all()


async def test_restart_recovery_with_multimeta(tmp_path):
    """multimeta:// {term, votedFor} journal end-to-end: terms persist
    across a full restart (a node must never vote twice in a term it
    already voted in) and the cluster keeps working."""
    c = TestCluster(3, tmp_path=tmp_path, meta_scheme="multimeta")
    await c.start_all()
    leader = await c.wait_leader()
    term1 = leader.current_term
    await c.apply_ok(leader, b"m0")
    await c.wait_applied(1)
    # force a term bump so there's a non-trivial value to persist
    await c.stop(leader.server_id)
    leader2 = await c.wait_leader()
    assert leader2.current_term > term1
    await c.apply_ok(leader2, b"m1")
    terms = {str(p): n._meta.term for p, n in c.nodes.items()}
    await c.stop_all()
    c2 = TestCluster(3, tmp_path=tmp_path, meta_scheme="multimeta")
    c2.net = c.net
    await c2.start_all()
    # recovered terms must be >= what was durably recorded pre-restart
    for p, n in c2.nodes.items():
        if str(p) in terms:
            assert n._meta.term >= terms[str(p)], (str(p), n._meta.term)
    leader3 = await c2.wait_leader()
    await c2.apply_ok(leader3, b"m2")
    await c2.wait_applied(3)
    await c2.stop_all()


async def test_partitioned_leader_steps_down_and_rejoins():
    c = TestCluster(3, election_timeout_ms=200)
    await c.start_all()
    leader = await c.wait_leader()
    await c.apply_ok(leader, b"a")
    await c.wait_applied(1)
    # isolate the leader: remaining majority elects a new one
    c.net.isolate(leader.server_id.endpoint)
    others = [n for n in c.nodes.values() if n is not leader]
    deadline = asyncio.get_running_loop().time() + 5
    new_leader = None
    while asyncio.get_running_loop().time() < deadline:
        cands = [n for n in others if n.state == State.LEADER]
        if cands:
            new_leader = cands[0]
            break
        await asyncio.sleep(0.02)
    assert new_leader is not None, "majority side failed to elect"
    st = await c.apply_ok(new_leader, b"b")
    assert st.is_ok()
    # old leader must have stepped down (lost quorum)
    deadline = asyncio.get_running_loop().time() + 3
    while asyncio.get_running_loop().time() < deadline:
        if leader.state != State.LEADER:
            break
        await asyncio.sleep(0.02)
    assert leader.state != State.LEADER, "isolated leader still thinks it leads"
    # heal: old leader rejoins as follower and catches up
    c.net.heal()
    await c.wait_applied(2)
    assert c.fsms[leader.server_id].logs == [b"a", b"b"]
    # pre-vote means terms didn't explode while partitioned
    assert new_leader.current_term <= leader.current_term + 2
    await c.stop_all()


async def test_symmetric_partition_no_term_explosion():
    """Pre-vote: an isolated node must NOT bump its term while cut off."""
    c = TestCluster(3, election_timeout_ms=150)
    await c.start_all()
    leader = await c.wait_leader()
    victim = next(n for n in c.nodes.values() if n is not leader)
    term_before = victim.current_term
    c.net.isolate(victim.server_id.endpoint)
    await asyncio.sleep(1.0)  # several election timeouts worth
    assert victim.current_term == term_before, (
        f"term exploded: {term_before} -> {victim.current_term}")
    c.net.heal()
    await c.stop_all()


async def test_transfer_leadership():
    c = TestCluster(3)
    await c.start_all()
    leader = await c.wait_leader()
    await c.apply_ok(leader, b"x")
    # re-resolve + retry: under suite load the leader can step down between
    # the apply ack and the transfer call (EPERM "not leader")
    st = Status.error(RaftError.EPERM)
    for _ in range(3):
        leader = await c.wait_leader()
        target = next(p for p in c.peers if p != leader.server_id)
        st = await leader.transfer_leadership_to(target)
        if st.is_ok():
            break
        await asyncio.sleep(0.1)
    assert st.is_ok(), str(st)
    deadline = asyncio.get_running_loop().time() + 5
    while asyncio.get_running_loop().time() < deadline:
        t_node = c.nodes[target]
        if t_node.state == State.LEADER:
            break
        await asyncio.sleep(0.02)
    assert c.nodes[target].state == State.LEADER
    st = await c.apply_ok(c.nodes[target], b"y")
    assert st.is_ok()
    await c.wait_applied(2)
    await c.stop_all()


async def test_read_index_leader_and_follower():
    c = TestCluster(3)
    await c.start_all()
    leader = await c.wait_leader()
    await c.apply_ok(leader, b"r1")
    await c.wait_applied(1)
    idx = await leader.read_index()
    assert idx >= 1
    follower = next(n for n in c.nodes.values() if n is not leader)
    idx_f = await follower.read_index()
    assert idx_f >= 1
    # follower FSM has applied through idx_f: linearizable local read
    assert len(c.fsms[follower.server_id].logs) >= 1
    await c.stop_all()


async def test_read_index_burst_no_orphans():
    """Regression: readers arriving WHILE a confirmation round is in
    flight must be served by a follow-up round, not orphaned until the
    next unrelated request (observed as client-timeout p99 tails)."""
    c = TestCluster(3)
    await c.start_all()
    leader = await c.wait_leader()
    await c.apply_ok(leader, b"r1")
    # staggered burst: waves land mid-round repeatedly
    async def one(delay):
        await asyncio.sleep(delay)
        return await leader.read_index()
    results = await asyncio.wait_for(
        asyncio.gather(*(one((i % 7) * 0.001) for i in range(40))), 5.0)
    assert all(r >= 1 for r in results)
    await c.stop_all()


async def test_read_index_fails_without_quorum():
    c = TestCluster(3, election_timeout_ms=200)
    await c.start_all()
    leader = await c.wait_leader()
    c.net.isolate(leader.server_id.endpoint)
    with pytest.raises(ReadIndexError):
        await asyncio.wait_for(leader.read_index(), 3)
    c.net.heal()
    await c.stop_all()


async def test_add_peer():
    c = TestCluster(3)
    await c.start_all()
    leader = await c.wait_leader()
    for i in range(5):
        await c.apply_ok(leader, b"d%d" % i)
    await c.wait_applied(5)
    # boot a 4th node with empty conf: it learns via replication
    new_peer = PeerId.parse("127.0.0.1:5003")
    c.peers.append(new_peer)
    from tpuraft.conf import Configuration
    save_conf = c.conf
    c.conf = Configuration()  # joiner starts with empty conf
    await c.start(new_peer)
    c.conf = save_conf
    st = await asyncio.wait_for(leader.add_peer(new_peer), 10)
    assert st.is_ok(), str(st)
    assert new_peer in leader.list_peers()
    st = await c.apply_ok(leader, b"d5")
    assert st.is_ok()
    await c.wait_applied(6)
    assert c.fsms[new_peer].logs == [b"d%d" % i for i in range(6)]
    await c.stop_all()


async def test_remove_peer():
    c = TestCluster(3)
    await c.start_all()
    leader = await c.wait_leader()
    await c.apply_ok(leader, b"a")
    victim = next(p for p in c.peers if p != leader.server_id)
    st = await asyncio.wait_for(leader.remove_peer(victim), 10)
    assert st.is_ok(), str(st)
    assert victim not in leader.list_peers()
    assert len(leader.list_peers()) == 2
    # still works with 2 voters
    st = await c.apply_ok(leader, b"b")
    assert st.is_ok()
    await c.wait_applied(2, nodes=[leader])
    await c.stop_all()


async def test_remove_leader_steps_down():
    c = TestCluster(3)
    await c.start_all()
    leader = await c.wait_leader()
    st = await asyncio.wait_for(leader.remove_peer(leader.server_id), 10)
    assert st.is_ok(), str(st)
    # leader must step down; remaining two elect a new leader
    deadline = asyncio.get_running_loop().time() + 5
    while asyncio.get_running_loop().time() < deadline:
        if leader.state != State.LEADER:
            break
        await asyncio.sleep(0.02)
    assert leader.state != State.LEADER
    others = {p: n for p, n in c.nodes.items() if n is not leader}
    new_leader = None
    deadline = asyncio.get_running_loop().time() + 5
    while asyncio.get_running_loop().time() < deadline:
        cands = [n for n in others.values() if n.state == State.LEADER]
        if cands:
            new_leader = cands[0]
            break
        await asyncio.sleep(0.02)
    assert new_leader is not None
    assert len(new_leader.list_peers()) == 2
    await c.stop_all()


async def test_learner_replicates_but_does_not_vote():
    c = TestCluster(3)
    await c.start_all()
    leader = await c.wait_leader()
    learner = PeerId.parse("127.0.0.1:5003")
    c.peers.append(learner)
    from tpuraft.conf import Configuration
    save = c.conf
    c.conf = Configuration()
    await c.start(learner)
    c.conf = save
    st = await asyncio.wait_for(leader.add_learners([learner]), 10)
    assert st.is_ok(), str(st)
    assert learner in leader.list_learners()
    assert learner not in leader.list_peers()
    await c.apply_ok(leader, b"l1")
    await c.wait_applied(1)
    assert c.fsms[learner].logs == [b"l1"]
    await c.stop_all()


async def test_expected_term_guard():
    c = TestCluster(1)
    await c.start_all()
    leader = await c.wait_leader()
    fut = asyncio.get_running_loop().create_future()
    await leader.apply(Task(data=b"x", done=fut.set_result,
                            expected_term=leader.current_term + 5))
    st = await fut
    assert not st.is_ok()
    await c.stop_all()


async def test_change_peers_joint_consensus():
    """Arbitrary membership change (reference: NodeTest changePeers):
    {a,b,c} -> {a,d,e} goes through joint consensus; the new majority
    carries writes, the removed peers are gone from the conf."""
    c = TestCluster(3)
    await c.start_all()
    leader = await c.wait_leader()
    for i in range(3):
        await c.apply_ok(leader, b"pre%d" % i)
    await c.wait_applied(3)

    from tpuraft.conf import Configuration

    d = PeerId.parse("127.0.0.1:5003")
    e = PeerId.parse("127.0.0.1:5004")
    save = c.conf
    c.conf = Configuration()  # joiners start empty, learn via replication
    c.peers.extend([d, e])
    await c.start(d)
    await c.start(e)
    c.conf = save

    new_conf = Configuration([leader.server_id, d, e])
    st = await asyncio.wait_for(leader.change_peers(new_conf), 15)
    assert st.is_ok(), str(st)
    assert set(leader.list_peers()) == {leader.server_id, d, e}

    st = await c.apply_ok(leader, b"post")
    assert st.is_ok(), str(st)
    await c.wait_applied(4, nodes=[c.nodes[d], c.nodes[e]])
    assert c.fsms[d].logs == [b"pre0", b"pre1", b"pre2", b"post"]
    # removed voters are no longer in the committed conf
    removed = [p for p in save.peers if p != leader.server_id]
    for p in removed:
        assert p not in leader.list_peers()
    await c.stop_all()


async def test_reset_peers_recovers_lost_quorum():
    """Unsafe manual reset when a majority is permanently dead
    (reference: NodeTest resetPeers): the survivor, told it is now a
    single-voter group, elects itself and serves writes again."""
    c = TestCluster(3)
    await c.start_all()
    leader = await c.wait_leader()
    await c.apply_ok(leader, b"before")
    await c.wait_applied(1)
    # kill both followers: quorum permanently lost
    followers = [p for p in c.peers if p != leader.server_id]
    for p in followers:
        await c.stop(p)
    # a write cannot commit now
    fut = asyncio.get_running_loop().create_future()
    await leader.apply(Task(data=b"stuck", done=lambda s: fut.set_result(s)))
    from tpuraft.conf import Configuration

    st = await asyncio.wait_for(
        leader.reset_peers(Configuration([leader.server_id])), 5)
    assert st.is_ok(), str(st)
    # it re-elects itself as the sole voter and accepts writes
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and leader.state != State.LEADER:
        await asyncio.sleep(0.02)
    assert leader.state == State.LEADER
    st = await c.apply_ok(leader, b"after-reset")
    assert st.is_ok(), str(st)
    await c.stop_all()


async def test_chaos_rolling_crashes_converge():
    """Chaos tier (reference: rheakv ChaosTest-style): sustained client
    load while nodes crash and restart one at a time; at the end all
    replicas converge to identical, gap-free, duplicate-free logs."""
    import random

    rng = random.Random(7)
    c = TestCluster(3, election_timeout_ms=150)
    await c.start_all()
    await c.wait_leader()

    applied: list[bytes] = []
    stop_writer = asyncio.Event()

    async def writer():
        # unique payload per ATTEMPT: an attempt whose ack timed out may
        # still have committed, so reusing its payload on retry would
        # legitimately commit the same bytes twice and break the
        # exactly-once assertion below
        attempt = 0
        while not stop_writer.is_set():
            data = b"chaos-%d" % attempt
            attempt += 1
            try:
                leader = await c.wait_leader(3.0)
                st = await c.apply_ok(leader, data, timeout_s=3.0)
                if st.is_ok():
                    applied.append(data)
            except (TimeoutError, asyncio.TimeoutError):
                pass
            await asyncio.sleep(0)

    wtask = asyncio.ensure_future(writer())
    try:
        for _round in range(4):
            await asyncio.sleep(0.3)
            victim = rng.choice(c.peers)
            if victim not in c.nodes:
                continue
            await c.stop(victim)
            await asyncio.sleep(0.3)
            # memory:// log: the node rejoins empty and is re-replicated
            # from scratch, so give it a fresh FSM recorder too
            await c.start(victim, fsm=MockStateMachine())
    finally:
        stop_writer.set()
        await wtask

    assert len(applied) > 10, f"only {len(applied)} writes survived chaos"
    # quiesce: every replica must contain every acked write (a raw count
    # would under-wait, since logs also hold timed-out-but-committed
    # attempts)
    acked_set = set(applied)
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline:
        if all(acked_set <= set(c.fsms[p].logs) for p in c.peers):
            break
        await asyncio.sleep(0.05)
    logs = {str(p): c.fsms[p].logs for p in c.peers}
    reference_log = None
    for p, log in logs.items():
        acked = [x for x in log if x in acked_set]
        # every acked write appears exactly once, in order
        assert acked == applied, (
            f"{p}: {len(acked)} acked in log vs {len(applied)} acked")
        if reference_log is None:
            reference_log = log
        else:
            assert log == reference_log, f"{p} diverged"
    await c.stop_all()


async def test_join_unblocks_on_shutdown():
    """Node#join / RaftGroupService#join parity: join() blocks until
    shutdown completes."""
    c = TestCluster(1)
    await c.start_all()
    leader = await c.wait_leader()
    joiner = asyncio.ensure_future(leader.join())
    await asyncio.sleep(0.05)
    assert not joiner.done()
    await c.stop_all()
    await asyncio.wait_for(joiner, 2.0)


async def test_lease_based_read_index():
    """LEASE_BASED linearizable reads skip the quorum heartbeat round
    while the leader lease holds (reference: ReadOnlyOption.LEASE_BASED),
    and still fail when the lease lapses under isolation."""
    from tpuraft.options import ReadOnlyOption

    c = TestCluster(3, election_timeout_ms=300)
    await c.start_all()
    leader = await c.wait_leader()
    for n in c.nodes.values():
        n.options.raft_options.read_only_option = ReadOnlyOption.LEASE_BASED
    await c.apply_ok(leader, b"lr")
    await c.wait_applied(1)
    # the lease path must answer WITHOUT invoking the quorum heartbeat
    # round at all (that's the whole point vs SAFE)
    rounds = []
    orig_round = leader.replicators.heartbeat_round

    async def counting_round():
        rounds.append(1)
        return await orig_round()

    leader.replicators.heartbeat_round = counting_round
    idx = await leader.read_index()
    assert idx >= 1
    assert rounds == [], "lease read fell back to the SAFE quorum round"
    leader.replicators.heartbeat_round = orig_round
    # isolated leader: the lease lapses and lease reads stop succeeding
    c.net.isolate(leader.server_id.endpoint)
    await asyncio.sleep(0.8)  # > lease window
    with pytest.raises(ReadIndexError):
        await asyncio.wait_for(leader.read_index(), 3)
    c.net.heal()
    await c.stop_all()


async def test_adversarial_network_invariants():
    """Short adversarial soak: 5% packet drop + 3ms delay + rolling
    one-way partitions under sustained writes, with an election-safety
    monitor (never two leaders in one term) and exactly-once + identical
    convergent logs asserted at the end."""
    import random

    rng = random.Random(42)
    c = TestCluster(3, election_timeout_ms=300)
    await c.start_all()
    await c.wait_leader()
    c.net.set_delay_ms(3)
    c.net.set_drop_rate(0.05)

    violations: list[str] = []
    stop = False

    async def monitor():
        while not stop:
            by_term: dict[int, list[str]] = {}
            for p, n in c.nodes.items():
                if n.state == State.LEADER:
                    by_term.setdefault(n.current_term, []).append(str(p))
            for t, ls in by_term.items():
                if len(ls) > 1:
                    violations.append(f"two leaders in term {t}: {ls}")
            await asyncio.sleep(0.005)

    acked: list[bytes] = []

    async def writer(wid):
        i = 0
        while not stop:
            try:
                leader = await c.wait_leader(3.0)
                st = await c.apply_ok(leader, b"w%d-%05d" % (wid, i),
                                      timeout_s=3.0)
                if st.is_ok():
                    acked.append(b"w%d-%05d" % (wid, i))
            except Exception:
                pass
            i += 1
            await asyncio.sleep(0.002)

    mon = asyncio.ensure_future(monitor())
    writers = [asyncio.ensure_future(writer(w)) for w in range(2)]
    t0 = time.monotonic()
    while time.monotonic() - t0 < 8:
        await asyncio.sleep(1.5)
        a, b = rng.choice(c.peers), rng.choice(c.peers)
        if a != b:
            c.net.partition_one_way({a.endpoint}, {b.endpoint})
            await asyncio.sleep(0.5)
            c.net.heal()  # note: heal() clears partitions only; the
            # delay/drop settings stay in effect throughout
    stop = True
    await asyncio.gather(*writers)
    mon.cancel()
    c.net.set_drop_rate(0)
    c.net.set_delay_ms(0)

    assert not violations, violations[:3]
    assert len(acked) > 50, len(acked)
    acked_set = set(acked)
    # converge on the condition actually asserted below: identical logs
    # containing every acked entry (a leader can briefly hold applied
    # tail entries its followers haven't applied yet)
    deadline = time.monotonic() + 15
    converged = False
    while time.monotonic() < deadline:
        logs = [c.fsms[p].logs for p in c.peers]
        if (logs[0] == logs[1] == logs[2]
                and acked_set <= set(logs[0])):
            converged = True
            break
        await asyncio.sleep(0.1)
    assert converged, "replicas failed to converge on identical logs"
    # exactly-once PER ENTRY: a compensating duplicate+loss pair must
    # not cancel out in an aggregate count
    from collections import Counter

    occurrences = Counter(logs[0])
    for entry in acked_set:
        assert occurrences[entry] == 1, (entry, occurrences[entry])
    await c.stop_all()


async def test_cluster_on_native_log_engine(tmp_path):
    """A raft cluster whose durable log is the C++ engine
    (native/logstore.cc via log_uri=native://): replicate, crash the
    leader, restart it, recover from the native log."""
    from tests.test_storage import _native_available

    if not _native_available():
        pytest.skip("C++ engine not buildable")
    c = TestCluster(3, tmp_path=tmp_path, log_scheme="native")
    await c.start_all()
    leader = await c.wait_leader()
    for i in range(10):
        st = await c.apply_ok(leader, b"n%d" % i)
        assert st.is_ok(), st
    await c.wait_applied(10)
    dead = leader.server_id
    await c.stop(dead)
    leader2 = await c.wait_leader()
    st = await c.apply_ok(leader2, b"post")
    assert st.is_ok()
    await c.start(dead, fsm=MockStateMachine())
    await c.wait_applied(11)
    assert c.fsms[dead].logs == [b"n%d" % i for i in range(10)] + [b"post"]
    await c.stop_all()


async def test_five_node_quorum_survives_two_failures():
    """5 voters tolerate 2 crashes (reference NodeTest's larger-quorum
    coverage): writes keep committing with 3/5, and the crashed pair
    catches up on restart."""
    c = TestCluster(5)
    await c.start_all()
    leader = await c.wait_leader()
    for i in range(5):
        st = await c.apply_ok(leader, b"q%d" % i)
        assert st.is_ok()
    await c.wait_applied(5)
    victims = [p for p in c.peers if p != leader.server_id][:2]
    for v in victims:
        await c.stop(v)
    leader = await c.wait_leader()
    st = await c.apply_ok(leader, b"with-3-of-5")
    assert st.is_ok(), st
    # a third failure would break quorum: verify 3/5 still commits but
    # don't go below (that's covered by reset_peers tests)
    for v in victims:
        await c.start(v, fsm=MockStateMachine())
    await c.wait_applied(6)
    for v in victims:
        assert c.fsms[v].logs == [b"q%d" % i for i in range(5)] + \
            [b"with-3-of-5"]
    await c.stop_all()


async def test_change_peers_under_sustained_load():
    """Membership change under fire (reference: NodeTest changePeers
    with concurrent applies): grow 3 -> 5 while writers run, then
    shrink back to the new pair + leader, losing no acked write."""
    c = TestCluster(3)
    await c.start_all()
    leader = await c.wait_leader()

    acked: list[bytes] = []
    stop = False

    async def writer():
        i = 0
        while not stop:
            try:
                ld = await c.wait_leader(3.0)
                st = await c.apply_ok(ld, b"m%05d" % i, timeout_s=3.0)
                if st.is_ok():
                    acked.append(b"m%05d" % i)
            except Exception:
                pass
            i += 1
            await asyncio.sleep(0.002)

    w = asyncio.ensure_future(writer())
    try:
        from tpuraft.conf import Configuration

        d = PeerId.parse("127.0.0.1:5005")
        e = PeerId.parse("127.0.0.1:5006")
        c.peers.extend([d, e])
        save = c.conf
        c.conf = Configuration()
        await c.start(d)
        await c.start(e)
        c.conf = save
        leader = await c.wait_leader()
        st = await asyncio.wait_for(
            leader.change_peers(Configuration(
                list(save.peers) + [d, e])), 20)
        assert st.is_ok(), st
        assert len(leader.list_peers()) == 5
        await asyncio.sleep(0.3)  # writes through the 5-voter quorum
        leader = await c.wait_leader()
        st = await asyncio.wait_for(
            leader.change_peers(Configuration(
                [leader.server_id, d, e])), 20)
        assert st.is_ok(), st
        assert set(leader.list_peers()) == {leader.server_id, d, e}
        await asyncio.sleep(0.3)
    finally:
        stop = True
        await w
    assert len(acked) > 30, len(acked)
    # every acked write is exactly-once on the final membership
    acked_set = set(acked)
    final_nodes = [n for n in c.nodes.values()
                   if n.server_id in leader.list_peers()]
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        if all(acked_set <= set(c.fsms[n.server_id].logs)
               for n in final_nodes):
            break
        await asyncio.sleep(0.1)
    from collections import Counter
    for n in final_nodes:
        occ = Counter(c.fsms[n.server_id].logs)
        for entry in acked_set:
            assert occ[entry] == 1, (str(n.server_id), entry, occ[entry])
    await c.stop_all()


async def test_divergence_below_applied_fails_node_not_rpc_storm():
    """A replica whose applied state diverges from the leader's
    committed log (only reachable via storage loss / amnesiac restart)
    must fail FATALLY — enter ERROR state and answer EHOSTDOWN so
    leaders take the paced-retry path — instead of rejecting the same
    AppendEntries forever (reference: NodeImpl#onError semantics)."""
    from tpuraft.conf import Configuration
    from tpuraft.entity import EntryType, LogEntry, LogId
    from tpuraft.errors import RaftError
    from tpuraft.rpc.messages import AppendEntriesRequest
    from tpuraft.rpc.transport import RpcError

    c = TestCluster(3)
    await c.start_all()
    try:
        leader = await c.wait_leader()
        for i in range(3):
            st = await c.apply_ok(leader, b"e%d" % i)
            assert st.is_ok(), str(st)
        follower_id = next(p for p in c.peers if p != leader.server_id)
        await c.wait_applied(3, nodes=[c.nodes[follower_id]])
        fnode = c.nodes[follower_id]
        lm = fnode.log_manager
        # fabricate a conflicting entry BELOW the applied index, as a
        # fake higher-term leader would present after divergence
        bad_term = fnode.current_term + 5
        idx = lm.last_log_index()          # an applied, committed index
        prev = idx - 1
        req = AppendEntriesRequest(
            group_id=c.group_id, server_id="127.0.0.1:9999",
            peer_id=str(follower_id), term=bad_term,
            prev_log_index=prev, prev_log_term=lm.get_term(prev),
            committed_index=0,
            entries=[LogEntry(type=EntryType.NO_OP,
                              id=LogId(index=idx, term=bad_term))])
        try:
            await fnode.handle_append_entries(req)
            raise AssertionError("conflicting append below applied "
                                 "index was accepted")
        except RpcError as e:
            assert e.status.code == int(RaftError.EHOSTDOWN), e.status
        assert fnode.state == State.ERROR
        # and it stays failed: the retry answers the same way
        try:
            await fnode.handle_append_entries(req)
            raise AssertionError("ERROR-state node served an RPC")
        except RpcError as e:
            assert e.status.code == int(RaftError.EHOSTDOWN), e.status
        # the application's StateMachine#onError hook hears about it
        for _ in range(100):
            if c.fsms[follower_id].errors:
                break
            await asyncio.sleep(0.02)
        assert c.fsms[follower_id].errors, "fsm.on_error never fired"
        # ERROR is sticky: a straggler higher-term response must not
        # resurrect the node into FOLLOWER with live timers
        await fnode.step_down_on_higher_term(bad_term + 1, "straggler")
        assert fnode.state == State.ERROR
        # the apply pipeline is poisoned (no further commits reach the
        # FSM) and InstallSnapshot is refused like AppendEntries
        assert fnode.fsm_caller._error is not None
        try:
            await fnode.handle_install_snapshot(object())
            raise AssertionError("ERROR-state node accepted a snapshot")
        except RpcError as e:
            assert e.status.code == int(RaftError.EHOSTDOWN), e.status
        # conf surgery can't revive it — and must say so
        st = await fnode.reset_peers(
            Configuration([follower_id]))
        assert st.code == int(RaftError.EHOSTDOWN), str(st)
    finally:
        await c.stop_all()


async def test_read_committed_user_log():
    """Node#readCommittedUserLog parity: first DATA entry at/after the
    index; EINVAL beyond commit; ENOENT once compacted."""
    from tpuraft.errors import RaftError, RaftException

    c = TestCluster(3)
    await c.start_all()
    try:
        leader = await c.wait_leader()
        for i in range(5):
            st = await c.apply_ok(leader, b"u%d" % i)
            assert st.is_ok(), str(st)
        # index 1 is the leader's no-op CONFIGURATION entry: skipped
        # forward to the first DATA entry
        e = leader.read_committed_user_log(1)
        assert e.data == b"u0"
        assert leader.read_committed_user_log(e.id.index + 1).data == b"u1"
        try:
            leader.read_committed_user_log(
                leader.ballot_box.last_committed_index + 1)
            raise AssertionError("index beyond commit accepted")
        except RaftException as ex:
            assert ex.status.raft_error == RaftError.EINVAL
    finally:
        await c.stop_all()


async def test_read_committed_user_log_compacted(tmp_path):
    from tpuraft.errors import RaftError, RaftException

    c = TestCluster(3, tmp_path=tmp_path, snapshot=True)
    await c.start_all()
    try:
        leader = await c.wait_leader()
        for i in range(8):
            await c.apply_ok(leader, b"c%d" % i)
        await c.wait_applied(8)
        st = await leader.snapshot()
        assert st.is_ok(), str(st)
        try:
            leader.read_committed_user_log(2)
            raise AssertionError("compacted index served")
        except RaftException as ex:
            assert ex.status.raft_error == RaftError.ENOENT
    finally:
        await c.stop_all()


async def test_transfer_timeout_reverts_to_leader():
    """Transferring to an unreachable target must not wedge the group:
    applies are rejected EBUSY during the handoff window, then the
    watchdog reverts to LEADER after an election timeout and service
    resumes (reference: NodeImpl transfer deadline handling)."""
    c = TestCluster(3, election_timeout_ms=300)
    await c.start_all()
    try:
        leader = await c.wait_leader()
        st = await c.apply_ok(leader, b"pre")
        assert st.is_ok(), str(st)
        target = next(p for p in c.peers if p != leader.server_id)
        # cut the target off so TimeoutNow can never reach it
        c.net.isolate(target.endpoint)
        st = await leader.transfer_leadership_to(target)
        assert st.is_ok(), str(st)   # transfer is initiated
        assert leader.state == State.TRANSFERRING
        st = await c.apply_ok(leader, b"during", retry=False)
        assert not st.is_ok() and st.raft_error == RaftError.EBUSY, str(st)
        # the watchdog gives up after one election timeout
        deadline = asyncio.get_running_loop().time() + 3
        while asyncio.get_running_loop().time() < deadline:
            if leader.state == State.LEADER:
                break
            await asyncio.sleep(0.02)
        assert leader.state == State.LEADER, leader.state
        c.net.heal()
        st = await c.apply_ok(leader, b"post")
        assert st.is_ok(), str(st)
        await c.wait_applied(2)
    finally:
        await c.stop_all()


async def test_follower_read_index_forward_batches():
    """Concurrent forwarded readIndex calls on a follower share RPC
    rounds (reference: ReadOnlyServiceImpl batches on every node), and
    late arrivals get a FRESH round, never an already-in-flight one."""
    c = TestCluster(3)
    await c.start_all()
    try:
        leader = await c.wait_leader()
        await c.apply_ok(leader, b"rr")
        follower = next(n for n in c.nodes.values() if n is not leader)

        calls = {"n": 0}
        real = follower.transport.read_index

        async def counting(dst, req, timeout_ms=None):
            calls["n"] += 1
            return await real(dst, req, timeout_ms)

        follower.transport.read_index = counting
        # 30 concurrent readers -> far fewer forward RPCs than readers
        results = await asyncio.gather(
            *(follower.read_index() for _ in range(30)))
        assert all(r >= 1 for r in results)
        assert calls["n"] < 10, calls["n"]
        # staggered waves keep landing mid-round without orphaning
        calls["n"] = 0
        async def one(delay):
            await asyncio.sleep(delay)
            return await follower.read_index()
        results = await asyncio.wait_for(
            asyncio.gather(*(one((i % 5) * 0.001) for i in range(25))), 5.0)
        assert all(r >= 1 for r in results)
    finally:
        await c.stop_all()


async def test_replication_pipelines_under_latency():
    """Pipelined replication (reference: maxReplicatorInflightMsgs):
    with 12ms one-way delay and small batches, a serial replicator
    moves ~1 batch per RTT; the window must keep multiple AppendEntries
    in flight and commit 60 entries far faster than the serial bound."""
    c = TestCluster(3, election_timeout_ms=1500)
    await c.start_all()
    try:
        leader = await c.wait_leader()
        await c.apply_ok(leader, b"warm")
        await c.wait_applied(1)
        # tiny batches force many RPCs; the delay makes serial painful
        for n in c.nodes.values():
            n.options.raft_options.max_entries_size = 1
        c.net.set_delay_ms(12)
        N = 60
        t0 = time.monotonic()
        futs = []
        loop = asyncio.get_running_loop()
        for i in range(N):
            fut = loop.create_future()
            await leader.apply(Task(
                data=b"p%03d" % i,
                done=lambda st, fut=fut: fut.done() or fut.set_result(st)))
            futs.append(fut)
        sts = await asyncio.wait_for(asyncio.gather(*futs), 30)
        dt = time.monotonic() - t0
        c.net.set_delay_ms(0)
        assert all(st.is_ok() for st in sts)
        # serial bound: 60 batches x ~24ms RTT = ~1.44s per follower;
        # the margin is generous for full-suite CPU contention — the
        # inflight_peak assert below is the primary pipelining proof
        assert dt < 1.3, f"took {dt:.2f}s — pipeline not engaging?"
        peaks = [r.inflight_peak for r in
                 (leader.replicators.get(p) for p in c.peers
                  if p != leader.server_id) if r is not None]
        assert any(pk > 3 for pk in peaks), peaks
        await c.wait_applied(N + 1, timeout_s=10)
        logs = [c.fsms[p].logs for p in c.peers]
        assert logs[0] == logs[1] == logs[2]
    finally:
        await c.stop_all()


async def test_read_index_refused_until_term_first_commit():
    """A fresh leader must refuse readIndex until the first entry of
    ITS OWN term commits: the carried-over commit marker may lag
    entries the old leader committed and acked, and serving reads
    against it loses acked writes (found by the linearizability soak;
    reference: ReadOnlyServiceImpl rejects until current-term commit)."""
    c = TestCluster(3)
    await c.start_all()
    try:
        leader = await c.wait_leader()
        await c.apply_ok(leader, b"g1")
        idx = await leader.read_index()
        assert idx >= 1
        # simulate the fresh-leader window: first-term entry not yet
        # committed -> reads must fail closed, not serve the stale index
        leader._term_first_index = leader.log_manager.last_log_index() + 5
        with pytest.raises(ReadIndexError):
            await asyncio.wait_for(leader.read_index(), 5)
        # once the term's first entry is committed, reads resume
        leader._term_first_index = 0
        assert await leader.read_index() >= idx
    finally:
        await c.stop_all()


async def test_read_after_leader_kill_sees_acked_write():
    """Kill the leader immediately after an acked write (followers'
    commit markers typically lag it); a linearizable read through the
    new leader must include the acked write — the safety gate makes the
    read wait for the new term's no-op commit instead of serving the
    stale carried-over index."""
    for round_i in range(3):
        c = TestCluster(3, election_timeout_ms=200)
        await c.start_all()
        try:
            leader = await c.wait_leader()
            st = await c.apply_ok(leader, b"pre-%d" % round_i)
            assert st.is_ok()
            st = await c.apply_ok(leader, b"acked-%d" % round_i)
            assert st.is_ok(), str(st)
            # kill within the heartbeat gap: commit-marker propagation
            # to followers likely hasn't happened yet
            await c.stop(leader.server_id)
            new_leader = await c.wait_leader()
            idx = await asyncio.wait_for(new_leader.read_index(), 10)
            applied = c.fsms[new_leader.server_id].logs
            assert b"acked-%d" % round_i in applied, (
                f"round {round_i}: acked write missing after "
                f"read_index={idx}: {applied}")
        finally:
            await c.stop_all()


async def test_a_vote_round_that_times_out_probes_again_at_once():
    """Reference NodeImpl#handleVoteTimeout: step down AND pre-vote.  A
    candidate whose round found no quorum already waited one election
    timeout; waiting a second one as a follower made every split vote
    cost two (33 s at kv3x4096's 16 s density floor)."""
    c = TestCluster(3, election_timeout_ms=60_000)  # no timer fires by itself
    await c.start(c.peers[0])           # alone: no vote can be granted
    node = c.nodes[c.peers[0]]
    async with node._lock:
        await node._elect_self()
    assert node.state == State.CANDIDATE
    term = node.current_term
    probes = []
    pre_vote = node._pre_vote

    async def counted():
        probes.append(node.state)
        await pre_vote()

    node._pre_vote = counted
    await node._handle_vote_timeout()
    # stepped down in the same term, and probed as a follower, once
    assert probes == [State.FOLLOWER]
    assert node.state == State.FOLLOWER and node.current_term == term
    await node._handle_vote_timeout()   # not a candidate: nothing more
    assert probes == [State.FOLLOWER]
    # the two peers come up: the probe after the next round wins
    await c.start(c.peers[1])
    await c.start(c.peers[2])
    async with node._lock:
        await node._elect_self()
    leader = await c.wait_leader()
    assert leader.current_term > term
    await c.stop_all()
