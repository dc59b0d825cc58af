"""Two survivors do not campaign in one term (PR 34, ``core/node.py``): a
node refuses a pre-vote for a term it has voted in, and a node whose pre-vote
has its quorum gives way, once, to a higher-ranked peer whose pre-vote for the
same term it has itself just granted (``Node._yields_to_rival``).

Every cluster here has an election timeout of 60 s, so no timer fires by
itself: the tests expire the leader leases and run the timeouts by hand."""

import asyncio

import pytest

from tests.cluster import TestCluster
from tpuraft.conf import Configuration
from tpuraft.core.node import Node, State
from tpuraft.entity import PeerId
from tpuraft.rpc.messages import RequestVoteRequest


def _cluster(ports) -> TestCluster:
    c = TestCluster(len(ports), election_timeout_ms=60_000)
    c.peers = [PeerId.parse(f"127.0.0.1:{p}") for p in ports]
    c.conf = Configuration(list(c.peers))
    return c


class _Counts:
    """What the engine's control counts (``EngineControl``), on the timer
    control of a bare node: elections started, vote rounds that ended with
    no winner, pre-vote quorums given away."""

    def __init__(self, node: Node):
        self.started = self.lost = self.yielded = 0
        elect = node._elect_self

        async def counted():
            self.started += 1
            await elect()

        node._elect_self = counted
        node._ctrl.note_vote_round_lost = self._lost
        node._ctrl.note_election_yielded = self._yielded

    def _lost(self) -> None:
        self.lost += 1

    def _yielded(self) -> None:
        self.yielded += 1


async def _up(c: TestCluster, which) -> dict:
    """Start the peers at ``which`` with their leader leases run out."""
    counts = {}
    for i in which:
        node = await c.start(c.peers[i])
        node._last_leader_timestamp -= 120.0
        counts[c.peers[i]] = _Counts(node)
    return counts


def _pre_vote_from(candidate: Node, to: Node, term: int) -> RequestVoteRequest:
    last = candidate.log_manager.last_log_id()
    return RequestVoteRequest(
        group_id=candidate.group_id, server_id=str(candidate.server_id),
        peer_id=str(to.server_id), term=term, last_log_index=last.index,
        last_log_term=last.term, pre_vote=True)


async def _settle(c: TestCluster, rounds: int = 20) -> None:
    """Let the vote tasks run; a request to a peer that is down comes back
    after the fabric's 50 ms."""
    for _ in range(rounds):
        await asyncio.sleep(0.01)


# the dead leader's port first; ":10" ranks BELOW ":9" (the order is the
# strings'), and whichever way the pair lies exactly one of it gives way
@pytest.mark.parametrize("ports", [(5000, 5001, 5002), (8, 9, 10)])
async def test_two_survivors_whose_pre_votes_cross_elect_in_one_round(ports):
    c = _cluster(ports)
    counts = await _up(c, (1, 2))       # peer 0, the leader, is dead
    low, high = sorted((c.nodes[c.peers[1]], c.nodes[c.peers[2]]),
                       key=lambda n: str(n.server_id))
    term = low.current_term
    # both time out inside one round trip: each grants the other's
    # pre-vote before its own quorum comes back
    await asyncio.gather(low._handle_election_timeout(),
                         high._handle_election_timeout())
    leader = await c.wait_leader()
    await _settle(c)
    assert leader is high and high.current_term == term + 1
    assert low.state == State.FOLLOWER and low.current_term == term + 1
    assert low.leader_id == high.server_id
    # ONE election for the group, no round without a winner
    assert (counts[high.server_id].started, counts[low.server_id].started) \
        == (1, 0)
    assert (counts[high.server_id].yielded, counts[low.server_id].yielded) \
        == (0, 1)
    assert counts[high.server_id].lost == counts[low.server_id].lost == 0
    await c.stop_all()


async def test_a_lone_timeout_does_not_yield():
    """No crossing, no rival: the node that times out campaigns and wins."""
    c = _cluster((5000, 5001, 5002))
    counts = await _up(c, (1, 2))
    node = c.nodes[c.peers[1]]          # the lower-ranked of the two
    await node._handle_election_timeout()
    assert await c.wait_leader() is node
    assert (counts[node.server_id].started, counts[node.server_id].yielded) \
        == (1, 0)
    await c.stop_all()


async def test_a_node_that_yielded_campaigns_at_its_next_timeout_when_the_rival_is_gone():
    c = _cluster((5000, 5001, 5002))
    counts = await _up(c, (0, 1, 2))
    a, b, rival = (c.nodes[p] for p in c.peers)
    # b grants the rival's pre-vote; then the rival dies before it campaigns
    resp = await b.handle_request_vote(
        _pre_vote_from(rival, b, rival.current_term + 1))
    assert resp.granted
    await c.stop(c.peers[2])
    mine = counts[b.server_id]
    await b._handle_election_timeout()
    await _settle(c)
    # a's grant gave b its quorum, and b gave way to the rival it granted
    assert (mine.yielded, mine.started, b.state) == (1, 0, State.FOLLOWER)
    # nothing came: the next timeout finds the grant spent, though it is
    # younger than an election timeout and the term is the same
    await b._handle_election_timeout()
    assert await c.wait_leader() is b
    assert (mine.yielded, mine.started, mine.lost) == (1, 1, 0)
    assert a.leader_id == b.server_id
    await c.stop_all()


async def test_a_chain_of_five_voters_whose_top_misses_its_quorum_costs_one_timeout():
    """A < B < C: A granted B's pre-vote, B granted C's, and C's own round
    came to nothing.  A gives way to B and B to C, so the first round elects
    nobody; the second elects, because a grant is yielded to once."""
    c = _cluster((5000, 5001, 5002, 5003, 5004))
    counts = await _up(c, range(5))
    a, b, top = (c.nodes[p] for p in c.peers[:3])
    term = a.current_term
    assert (await a.handle_request_vote(
        _pre_vote_from(b, a, term + 1))).granted
    assert (await b.handle_request_vote(
        _pre_vote_from(top, b, term + 1))).granted
    await asyncio.gather(a._handle_election_timeout(),
                         b._handle_election_timeout())
    await _settle(c)
    # four grants each, one yield each: a later grant of the same round does
    # not make a candidate of a node that gave way
    assert [(counts[n.server_id].yielded, counts[n.server_id].started)
            for n in (a, b)] == [(1, 0), (1, 0)]
    assert all(n.state == State.FOLLOWER and n.current_term == term
               for n in c.nodes.values())
    await a._handle_election_timeout()
    assert await c.wait_leader() is a
    await _settle(c)
    assert sum(k.started for k in counts.values()) == 1
    assert sum(k.lost for k in counts.values()) == 0
    assert all(n.leader_id == a.server_id for n in c.nodes.values())
    await c.stop_all()


async def test_a_pre_vote_is_refused_for_a_term_the_node_has_voted_in():
    c = _cluster((5000, 5001, 5002))
    await c.start(c.peers[1])           # alone: its round cannot end
    node = c.nodes[c.peers[1]]
    other = PeerId.parse("127.0.0.1:5002")
    async with node._lock:
        await node._elect_self()
    term = node.current_term
    assert (node.state, node.voted_for) == (State.CANDIDATE, node.server_id)

    def ask(for_term: int, who: PeerId = other) -> RequestVoteRequest:
        return RequestVoteRequest(
            group_id=node.group_id, server_id=str(who),
            peer_id=str(node.server_id), term=for_term, last_log_index=0,
            last_log_term=0, pre_vote=True)

    # the real vote of this term is spent on itself: a second candidate of
    # the term could not win it, so its pre-vote is refused
    resp = await node.handle_request_vote(ask(term))
    assert (resp.granted, resp.term) == (False, term)
    assert node._prevote_granted is None
    # the next term is open
    resp = await node.handle_request_vote(ask(term + 1))
    assert resp.granted and node._prevote_granted[:2] == (other, term + 1)
    # and nothing of this moved the node
    assert (node.state, node.current_term, node.voted_for) \
        == (State.CANDIDATE, term, node.server_id)
    await c.stop_all()
