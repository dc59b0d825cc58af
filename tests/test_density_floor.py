"""The density floor at the benchmark's two densities: what 1,024 and 4,096
registered controls of 3 voters at 10,000 / 1,000 ms read on every controlled
row once a boot-order registration has settled, and what ``lane_stats`` says
of it.  Registration alone re-derives the floor at geometric counts and
re-applies it past 25 % growth, so a burst may stop a step short;
``settle_floor`` (``StoreEngine.start`` calls it after its boot batches) is
what leaves no row behind."""

from types import SimpleNamespace

import numpy as np
import pytest

from tpuraft.conf import Configuration
from tpuraft.core.engine import ROLE_LEADER, MultiRaftEngine
from tpuraft.entity import PeerId
from tpuraft.options import TickOptions

PEERS = [PeerId.parse(f"127.0.0.1:{6600 + i}") for i in range(3)]
ETO_MS, HB_MS, LEASE_MS = 10_000, 1_000, 9_000


class _Ctrl:
    """What the engine needs of an EngineControl to register it."""

    node = SimpleNamespace(is_leader=lambda: False)   # sends no beat

    def __init__(self, slot: int):
        self.slot = slot
        self.adopted = ETO_MS

    def _adopt_eto(self, eff_eto_ms: int) -> None:
        self.adopted = eff_eto_ms


def _boot(n: int, max_groups: int) -> tuple:
    """Register ``n`` controls the way a store's boot does: a slot, the
    group's conf, then the control, one after another."""
    eng = MultiRaftEngine(TickOptions(max_groups=max_groups, max_peers=4,
                                      backend="numpy"))
    conf = Configuration(list(PEERS))
    ctrls = []
    for _ in range(n):
        box = eng.ballot_box_factory()(lambda index: None)
        box.update_conf(conf, Configuration())
        ctrl = _Ctrl(box.slot)
        eff = eng.register_ctrl(ctrl, PEERS[0], eto_ms=ETO_MS, hb_ms=HB_MS,
                                lease_ms=LEASE_MS)
        if eff != ETO_MS:
            ctrl.adopted = eff
        ctrls.append(ctrl)
    return eng, ctrls


@pytest.mark.parametrize("controls, max_groups, eto_ms, hb_ms, lease_ms", [
    # 4 ms a control: n x 2 followers x factor 10 x 20 us / 10 % of a core
    (1024, 2048, 10_000, 1_000, 9_000),       # 4,096 ms: silent
    (4096, 8192, 16_384, 1_638, 14_745),      # in force
])
def test_every_controlled_row_carries_the_floor_of_the_registered_density(
        controls, max_groups, eto_ms, hb_ms, lease_ms):
    eng, ctrls = _boot(controls, max_groups)
    floor = 4 * controls
    assert eng.settle_floor() == floor == eng._density_floor_ms()
    hc = eng.has_ctrl
    assert int(hc.sum()) == controls
    for row, want in ((eng.eto_ms, eto_ms), (eng.hb_ms, hb_ms),
                      (eng.lease_ms, lease_ms)):
        assert np.unique(row[hc]).tolist() == [want]
    # the nodes' own options follow the rows (RPC budgets, follower lease)
    assert {c.adopted for c in ctrls} == {eto_ms}
    stats = eng.lane_stats()
    assert stats["eto_floor_ms"] == floor
    assert stats["eto_raised"] == (controls if eto_ms > ETO_MS else 0)
    # a slot that registers after the burst gets the floor in force
    box = eng.ballot_box_factory()(lambda index: None)
    box.update_conf(Configuration(list(PEERS)), Configuration())
    assert eng.register_ctrl(_Ctrl(box.slot), PEERS[0], eto_ms=ETO_MS,
                             hb_ms=HB_MS, lease_ms=LEASE_MS) == eto_ms


def test_registration_alone_stops_a_step_short_and_settling_never_lowers():
    """Why ``settle_floor`` exists: 4,096 registrations last re-derive the
    floor at 3,389 controls (13,556 ms).  And a floor in force stays when
    the density falls: leaders elected under it keep their timeouts."""
    eng, _ = _boot(4096, 8192)
    hc = eng.has_ctrl.copy()
    assert np.unique(eng.eto_ms[hc]).tolist() == [13_556]
    assert eng.settle_floor() == 16_384
    for slot in np.nonzero(hc)[0][:2048]:
        eng.unregister_ctrl(int(slot))
    assert eng._density_floor_ms() == 8_192
    assert eng.settle_floor() == 16_384
    assert np.unique(eng.eto_ms[eng.has_ctrl]).tolist() == [16_384]


class _Hub:
    """What ``_flush_heartbeats`` needs of a HeartbeatHub."""

    def __init__(self, max_fast_beats_per_rpc: int):
        self.max_fast_beats_per_rpc = max_fast_beats_per_rpc
        self.pulsed: list = []

    def pulse(self, reps) -> None:
        self.pulsed.append(len(reps))


def _lead_through(ctrls, hub) -> None:
    """Every control a leader whose two replicators beat through ``hub``."""
    node = SimpleNamespace(
        is_leader=lambda: True,
        replicators=SimpleNamespace(all=lambda: ["rep", "rep"]),
        node_manager=SimpleNamespace(heartbeat_hub=hub),
        options=SimpleNamespace(
            raft_options=SimpleNamespace(coalesce_heartbeats=True)))
    for c in ctrls:
        c.node = node
        c.maybe_quiesce = lambda now: None


def test_a_beat_round_is_one_beat_rpc_a_peer_store():
    """Groups that share a heartbeat interval beat together on its grid
    while their rows fit the one RPC the hub sends a destination
    (``HeartbeatHub.max_fast_beats_per_rpc``, 1,024: ``kv3x1024``'s one
    round).  Past that the interval has as many evenly spaced phases as a
    destination would get RPCs, a slot on phase ``slot % k``: 4,096 leaders
    beat in four rounds of 1,024 every 1,638 ms, each group once an
    interval and on its own phase from one beat to the next; a hub that
    carries twice as many rows an RPC halves the rounds."""
    from tpuraft.core.heartbeat_hub import HeartbeatHub

    eng, ctrls = _boot(4096, 8192)
    eng.settle_floor()
    hub = _Hub(HeartbeatHub().max_fast_beats_per_rpc)
    _lead_through(ctrls, hub)
    slots = np.nonzero(eng.has_ctrl)[0]
    hb, now = 1_638, 1_000_000
    eng.role[slots[:1024]] = ROLE_LEADER
    eng._flush_heartbeats(slots[:1024], now)
    assert hub.pulsed == [2048]
    assert np.unique(eng.hb_deadline[slots[:1024]]).tolist() == [
        (now // hb + 1) * hb]
    eng.role[slots] = ROLE_LEADER
    eng._flush_heartbeats(slots, now)
    due = eng.hb_deadline[slots]
    assert ((due > now) & (due <= now + hb)).all()
    rounds, groups = np.unique(due, return_counts=True)
    assert groups.tolist() == [1024] * 4
    assert sorted(rounds % hb) == [0, 409, 819, 1228]
    first = slots[due == rounds[0]]
    eng._flush_heartbeats(first, int(rounds[0]))
    assert np.unique(eng.hb_deadline[first]).tolist() == [rounds[0] + hb]
    assert hub.pulsed == [2048, 8192, 2048]
    assert eng.tick_hists["beat_rows"].count == 2048 + 8192 + 2048
    hub.max_fast_beats_per_rpc = 2048
    eng._flush_heartbeats(slots, now)
    assert np.unique(eng.hb_deadline[slots],
                     return_counts=True)[1].tolist() == [2048] * 2
