"""Fleet observability plane (ISSUE 13): per-region heat telemetry,
PD cluster view, and device-tick profiling.

Covers the tracker's seeded decay/convergence math, the noise gate, the
heartbeat wire extension BOTH directions (old client <-> new PD and
vice versa), the unified ClusterStatsManager intake (ONE region-stats
path for keys + heat), hot-region detection through the flight
recorder, the PD cluster view over the real RPC, the metrics_text TTL
render cache, and the engine's tick-phase histograms / [G]-lane
occupancy gauges / --profile-ticks perfetto export.
"""

from __future__ import annotations

import asyncio
import json
import time

import numpy as np
import pytest

from tpuraft.util.heat import (RegionHeatTracker, decode_heat_rows,
                               encode_heat_rows, heat_changed, heat_score)

pytestmark = pytest.mark.asyncio


# ---------------------------------------------------------------------------
# RegionHeatTracker units (seeded, injectable clock)
# ---------------------------------------------------------------------------


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self) -> float:
        return self.t


def test_tracker_converges_to_offered_rate():
    """Constant offered load at a fixed fold cadence converges the EWMA
    to the true rate; two identically-driven trackers are bit-equal
    (seeded determinism — the bench A/B contract)."""
    def drive() -> RegionHeatTracker:
        clk = _Clock()
        t = RegionHeatTracker(half_life_s=2.0, clock=clk)
        for _ in range(60):
            t.note_write(7, ops=50, bytes_in=800)
            t.note_read(7, ops=25, bytes_out=400)
            clk.t += 1.0
            t.fold()
        return t

    a, b = drive(), drive()
    h = a.heat(7)
    # 60 folds at half_life 2s: the EWMA has fully settled
    assert h.writes_s == pytest.approx(50.0, rel=0.01)
    assert h.reads_s == pytest.approx(25.0, rel=0.01)
    assert h.bytes_in_s == pytest.approx(800.0, rel=0.01)
    assert h.bytes_out_s == pytest.approx(400.0, rel=0.01)
    hb = b.heat(7)
    assert (h.writes_s, h.reads_s, h.bytes_in_s, h.bytes_out_s) == \
        (hb.writes_s, hb.reads_s, hb.bytes_in_s, hb.bytes_out_s)
    assert a.counters() == b.counters()


def test_tracker_decays_idle_region_and_forgets_it():
    clk = _Clock()
    t = RegionHeatTracker(half_life_s=1.0, clock=clk)
    t.note_write(3, ops=100)
    clk.t += 1.0
    t.fold()
    assert t.heat(3).writes_s > 0
    # silence: each 1s fold halves the rate (half_life=1); after ~20
    # half-lives the region is below noise and gets forgotten
    for _ in range(25):
        clk.t += 1.0
        t.fold()
    assert t.heat(3).writes_s == 0.0
    assert 3 not in t.snapshot()
    assert t.gauges()["heat_regions_tracked"] == 0


def test_tracker_top_coldest_and_drop():
    clk = _Clock()
    t = RegionHeatTracker(half_life_s=5.0, clock=clk)
    for rid, ops in ((1, 5), (2, 500), (3, 50)):
        t.note_write(rid, ops=ops)
    clk.t += 1.0
    t.fold()
    assert [rid for rid, _ in t.top(2)] == [2, 3]
    assert [rid for rid, _ in t.coldest(1)] == [1]
    t.drop(2)
    assert 2 not in t.snapshot()
    assert [rid for rid, _ in t.top(2)] == [3, 1]
    assert "RegionHeatTracker" in t.describe()


def test_tracker_applied_lane_keeps_region_alive_but_off_the_score():
    """Follower-side apply traffic is tracked (local visibility) but
    does NOT contribute to the serving score the PD ranks on."""
    clk = _Clock()
    t = RegionHeatTracker(half_life_s=1.0, clock=clk)
    t.note_applied(9, ops=100)
    clk.t += 1.0
    t.fold()
    h = t.heat(9)
    assert h.applied_s > 0
    assert h.score == 0.0


def test_fold_zero_dt_is_noop():
    clk = _Clock()
    t = RegionHeatTracker(clock=clk)
    t.note_write(1, ops=10)
    assert t.fold() == 0.0          # clock didn't advance
    assert t.heat(1).writes_s == 0.0


# ---------------------------------------------------------------------------
# noise gate + score
# ---------------------------------------------------------------------------


def test_heat_changed_noise_gate():
    # sub-absolute moves are noise regardless of ratio
    assert not heat_changed(0.4, 0.0)
    # >= min_abs AND >= ~12.5% relative: reportable
    assert heat_changed(10.0, 0.0)
    assert heat_changed(85.0, 100.0)   # 15% move: past the ~12.5% gate
    # steady heat (tiny relative move) stays gated — the delta plane
    # must not re-dirty every heartbeat round
    assert not heat_changed(101.0, 100.0)
    assert not heat_changed(99.0, 100.0)
    # decays to cold are reportable once big enough
    assert heat_changed(0.0, 8.0)


def test_heat_score_single_definition():
    # ops dominate; payload weighs in at one op per 4KiB
    assert heat_score(2.0, 3.0, 0.0, 0.0) == 5.0
    assert heat_score(0.0, 0.0, 4096.0, 4096.0) == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# wire codec + heartbeat wire-compat both directions
# ---------------------------------------------------------------------------


def test_heat_rows_codec_roundtrip_and_tolerance():
    rows = [(1, 10.0, 5.0, 100.0, 50.0), (77, 0.5, 0.25, 8.0, 4.0)]
    blob = encode_heat_rows(rows)
    got = decode_heat_rows(blob)
    assert [r[0] for r in got] == [1, 77]
    assert got[0][1] == pytest.approx(10.0)
    assert encode_heat_rows([]) == b""
    assert decode_heat_rows(b"") == []
    # a trailing partial row (torn frame) is dropped, not raised
    assert len(decode_heat_rows(blob[:-5])) == 1


def test_store_heartbeat_heat_wire_compat_both_directions():
    """StoreHeartbeatBatchRequest gained trailing heat/replicas fields.
    Old frames decode on new receivers with defaults; a new frame is a
    strict extension whose prefix an old decoder reads identically."""
    from tpuraft.rheakv.pd_messages import StoreHeartbeatBatchRequest
    from tpuraft.rpc.messages import decode_message, encode_message

    heat = encode_heat_rows([(4, 100.0, 10.0, 0.0, 0.0)])
    new = StoreHeartbeatBatchRequest(
        store_id=9, endpoint="127.0.0.1:1", deltas=[b"d0"], full=True,
        zone="z1", health="healthy", heat=heat,
        replicas=12, replicas_quiescent=5)
    wire = encode_message(new)
    got = decode_message(wire)
    assert got.heat == heat
    assert (got.replicas, got.replicas_quiescent) == (12, 5)
    assert decode_heat_rows(got.heat)[0][0] == 4
    # old sender -> new receiver: strip the trailing heat bytes field
    # (4-byte length prefix + payload) + two trailing i64s
    old_wire = wire[:-(4 + len(heat) + 8 + 8)]
    old_got = decode_message(old_wire)
    assert old_got.heat == b"" and old_got.replicas == 0
    assert old_got.deltas == [b"d0"] and old_got.health == "healthy"
    # new -> old receiver: the old-format prefix is byte-identical, so
    # an old decoder (which stops after health) reads the same values
    old_fmt = encode_message(StoreHeartbeatBatchRequest(
        store_id=9, endpoint="127.0.0.1:1", deltas=[b"d0"], full=True,
        zone="z1", health="healthy"))
    assert wire[:len(old_wire)] == old_fmt[:len(old_wire)]


def test_cluster_describe_messages_roundtrip():
    from tpuraft.rheakv.pd_messages import (ClusterDescribeRequest,
                                            ClusterDescribeResponse)
    from tpuraft.rpc.messages import decode_message, encode_message

    req = decode_message(encode_message(ClusterDescribeRequest(top_k=4)))
    assert req.top_k == 4
    resp = decode_message(encode_message(ClusterDescribeResponse(
        view_json='{"regions": 3}')))
    assert json.loads(resp.view_json) == {"regions": 3}


# ---------------------------------------------------------------------------
# ClusterStatsManager: ONE region-stats path (keys + heat)
# ---------------------------------------------------------------------------


def _stats(threshold=0):
    from tpuraft.rheakv.pd_server import ClusterStatsManager

    return ClusterStatsManager(split_threshold_keys=threshold)


def test_cluster_stats_unified_intake():
    s = _stats(threshold=100)
    s.record(1, 150)
    s.record_heat(1, 10.0, 5.0, 0.0, 0.0)
    # ONE record: the split policy reads keys, the view reads heat,
    # from the same entry
    ent = s.region_stats(1)
    assert ent.keys == 150 and ent.writes_s == 10.0
    assert s.last_keys(1) == 150
    assert s.should_split(1)
    s.mark_split_issued(1)
    # keys reset on split; the heat rates survive (load keeps landing
    # until clients re-route)
    assert s.last_keys(1) == 0
    assert s.region_stats(1).writes_s == 10.0


def test_cluster_stats_top_hot_and_cold():
    s = _stats()
    s.record_heat(1, 1.0, 0.0, 0.0, 0.0)
    s.record_heat(2, 50.0, 0.0, 0.0, 0.0)
    s.record(3, 10)  # keys only: zero heat
    assert [rid for rid, _ in s.top_hot(8)] == [2, 1]   # zero-score excluded
    assert [rid for rid, _ in s.top_cold(1)] == [3]


def test_hot_region_detection_fires_recorder_with_hysteresis():
    from tpuraft.util.trace import RECORDER

    s = _stats()
    s.hot_min_score = 5.0
    s.hot_factor = 2.0
    # background fleet: 20 cool regions
    for rid in range(10, 30):
        s.record_heat(rid, 0.5, 0.0, 0.0, 0.0)
    # one region goes hot past max(5.0, 2 x background p50)
    s._hot_recalc_at = 0.0  # sweep now sees the full population
    s.record_heat(1, 100.0, 0.0, 0.0, 0.0)
    assert 1 in s.hot_regions()
    assert s.hot_events == 1
    # recorder events are (ts, kind, group, detail) tuples
    evs = [e for e in RECORDER.events()
           if e[1] == "hot_region" and e[2] == "1"]
    assert evs and evs[-1][3]["score"] == pytest.approx(100.0)
    # staying hot does not re-fire
    s.record_heat(1, 110.0, 0.0, 0.0, 0.0)
    assert s.hot_events == 1
    # hysteresis: cools only below half the threshold
    s._hot_recalc_at = 0.0  # force a threshold refresh on next intake
    s.record_heat(1, s._hot_threshold * 0.75, 0.0, 0.0, 0.0)
    assert 1 in s.hot_regions()
    s.record_heat(1, 0.1, 0.0, 0.0, 0.0)
    assert 1 not in s.hot_regions()


def test_hot_detection_bootstrap_and_small_fleet_shape():
    """The two shapes the first-cut detector got wrong: a half-reported
    bootstrap fleet must not mass-flag off a floor threshold, and in a
    small fleet the hot set (which IS the score tail) must flag against
    the BACKGROUND median, not a tail percentile of itself."""
    s = _stats()
    # bootstrap: below hot_min_population heated regions, never flag
    for rid in range(4):
        s.record_heat(rid, 50.0, 0.0, 0.0, 0.0)
    assert s.hot_regions() == set()
    assert s.hot_events == 0
    # steady 3-hot-of-24 (the hotspot soak's shape): background at 10,
    # hot set at 300 — exactly the hot regions flag, none of the
    # background does, and a uniform fleet would flag nothing
    for rid in range(24):
        s.record_heat(rid, 10.0, 0.0, 0.0, 0.0)
    s._hot_recalc_at = 0.0
    for rid in (1, 5, 9):
        s.record_heat(rid, 300.0, 0.0, 0.0, 0.0)
    assert s.hot_regions() == {1, 5, 9}
    assert s.hot_events == 3


def test_hot_sweep_zeroes_stale_rates_and_cools_silent_regions():
    """A reporter that goes silent (leadership moved, region gone) must
    not leave standing rates in the view: the 1/s sweep zeroes rates
    older than heat_stale_s and re-judges flagged regions without
    waiting for an intake row the noise gate may never send."""
    import time as _time

    s = _stats()
    for rid in range(12):
        s.record_heat(rid, 10.0, 0.0, 0.0, 0.0)
    s._hot_recalc_at = 0.0
    s.record_heat(3, 500.0, 0.0, 0.0, 0.0)
    assert 3 in s.hot_regions()
    past = _time.monotonic() - (s.heat_stale_s + 1.0)
    for rid in range(12):
        s._stats[rid].heat_at = past
    s._hot_recalc_at = 0.0
    s.maybe_sweep()
    assert all(s.region_stats(r).writes_s == 0.0 for r in range(12))
    # the flagged region cooled via the sweep, not via an intake row
    assert s.hot_regions() == set()
    # keys survive staleness (matches the legacy keys-only intake)
    s.record(5, 77)
    s._stats[5].heat_at = past
    s._hot_recalc_at = 0.0
    s.maybe_sweep()
    assert s.last_keys(5) == 77


def test_hot_flags_survive_population_dip():
    """A brief reporter dropout (heated population below the gate)
    must neither erase live standing flags nor admit new ones — the
    hot_region signal must not flap on a population-count transient."""
    import time as _time

    s = _stats()
    for rid in range(12):
        s.record_heat(rid, 10.0, 0.0, 0.0, 0.0)
    s._hot_recalc_at = 0.0
    s.record_heat(3, 500.0, 0.0, 0.0, 0.0)
    assert 3 in s.hot_regions()
    events_before = s.hot_events
    # 9 of 12 reporters go stale -> heated dips below hot_min_population
    past = _time.monotonic() - (s.heat_stale_s + 1.0)
    for rid in range(12):
        if rid not in (1, 2, 3):
            s._stats[rid].heat_at = past
    s._hot_recalc_at = 0.0
    s.maybe_sweep()
    assert s._hot_threshold is None
    assert 3 in s.hot_regions()      # live flag survives the dip
    # intake during the dip neither flags nor cools
    s.record_heat(2, 400.0, 0.0, 0.0, 0.0)
    assert 2 not in s.hot_regions()
    s.record_heat(3, 450.0, 0.0, 0.0, 0.0)
    assert 3 in s.hot_regions()
    assert s.hot_events == events_before


async def test_heat_report_keepalive_re_reports_steady_heat(tmp_path):
    """Store side of the staleness pairing: the noise gate suppresses
    unchanged heat, so without the heat_refresh_s keepalive a steadily
    hot region would be expired by the PD's sweep and vanish from the
    view.  A row older than the refresh interval must re-report even
    with zero score movement."""
    import time as _time

    from tpuraft.rheakv.metadata import Region
    from tpuraft.rheakv.store_engine import StoreEngine, StoreEngineOptions
    from tpuraft.rpc.transport import InProcNetwork, InProcTransport, RpcServer

    net = InProcNetwork()
    ep = "127.0.0.1:6903"
    server = RpcServer(ep)
    net.bind(server)
    opts = StoreEngineOptions(
        server_id=ep,
        initial_regions=[Region(id=1, peers=[ep])],
        election_timeout_ms=200,
        data_path=str(tmp_path))
    store = StoreEngine(opts, server, InProcTransport(net, ep))
    await store.start()
    try:
        deadline = _time.monotonic() + 5.0
        while _time.monotonic() < deadline:
            if store.leader_region_ids() == [1]:
                break
            await asyncio.sleep(0.02)
        assert store.leader_region_ids() == [1]
        await asyncio.sleep(0.2)   # let a fold window accumulate time
        store.heat.note_write(1, ops=500, bytes_in=500)
        rows = store._heat_report(full=False)
        assert [r[0][0] for r in rows] == [1]   # first report: gate passes
        now = _time.monotonic()
        store._pd_heat_reported.update(
            {row[0]: (score, now) for row, score in rows})
        # steady heat: the very next round is noise-gated
        assert store._heat_report(full=False) == []
        # ...until the standing row ages past the keepalive interval
        score, _t = store._pd_heat_reported[1]
        store._pd_heat_reported[1] = (
            score, now - store.opts.heat_refresh_s - 1.0)
        rows = store._heat_report(full=False)
        assert [r[0][0] for r in rows] == [1]
    finally:
        await store.shutdown()


# ---------------------------------------------------------------------------
# PD intake + cluster view over the real RPC
# ---------------------------------------------------------------------------


async def test_pd_cluster_view_over_wire(tmp_path):
    """Heat rows + occupancy ride the heartbeat into the PD; the
    pd_cluster_describe RPC serves the folded view (top-K hot, zone
    rates, hibernation fraction, store roster)."""
    from tests.kv_cluster import PDTestCluster
    from tpuraft.rheakv.pd_messages import StoreHeartbeatBatchRequest
    from tpuraft.rheakv.pd_messages import encode_region_delta
    from tpuraft.rheakv.metadata import Region

    c = PDTestCluster(n_stores=0, n_pd=1, tmp_path=tmp_path)
    for ep in c.pd_endpoints:
        await c.start_pd(ep)
    try:
        await c.wait_pd_leader()
        pd_client = c.pd_client()
        r1 = Region(id=1, start_key=b"", end_key=b"m",
                    peers=["127.0.0.1:9001"])
        r2 = Region(id=2, start_key=b"m", end_key=b"",
                    peers=["127.0.0.1:9001"])
        req = StoreHeartbeatBatchRequest(
            store_id=1, endpoint="127.0.0.1:9001",
            deltas=[encode_region_delta(r.encode(), "127.0.0.1:9001", 10)
                    for r in (r1, r2)],
            full=True, zone="z-east", health="healthy",
            heat=encode_heat_rows([(1, 40.0, 10.0, 0.0, 0.0),
                                   (2, 1.0, 0.0, 0.0, 0.0)]),
            replicas=8, replicas_quiescent=6)
        resp = await pd_client._call("pd_store_heartbeat_batch", req)
        assert resp.success
        view = await pd_client.cluster_describe(top_k=2)
        assert view is not None
        assert view["regions"] == 2
        assert [r["region"] for r in view["hot"]] == [1, 2]
        assert view["hot"][0]["writes_s"] == pytest.approx(40.0)
        assert view["hot"][0]["keys"] == 10
        assert view["zone_rates"]["z-east"]["writes_s"] == \
            pytest.approx(41.0)
        assert view["hibernation"] == {
            "replicas": 8, "quiescent": 6, "fraction": 0.75}
        store_row = view["stores"][0]
        assert store_row["zone"] == "z-east"
        assert store_row["replicas_quiescent"] == 6
        # PD-side Prometheus text serves the same aggregates
        pd = await c.wait_pd_leader()
        text = pd.metrics_text()
        assert "tpuraft_pd_hb_heat_rows" in text
        assert "tpuraft_pd_hibernation_fraction" in text
        assert "tpuraft_pd_regions" in text
    finally:
        await c.stop_all()


async def test_cluster_describe_against_old_pd_returns_none():
    """A pre-observability PD has no pd_cluster_describe handler: the
    client's capability probe answers None instead of raising."""
    from tpuraft.rheakv.pd_client import RemotePlacementDriverClient
    from tpuraft.rpc.transport import InProcNetwork, InProcTransport, RpcServer

    net = InProcNetwork()
    ep = "127.0.0.1:7999"
    server = RpcServer(ep)   # no handlers registered at all
    net.bind(server)
    net.start_endpoint(ep)
    client = RemotePlacementDriverClient(
        InProcTransport(net, "probe:0"), [ep])
    assert await client.cluster_describe() is None


# ---------------------------------------------------------------------------
# metrics_text TTL render cache
# ---------------------------------------------------------------------------


async def test_metrics_text_ttl_cache(tmp_path):
    from tpuraft.rheakv.metadata import Region
    from tpuraft.rheakv.store_engine import StoreEngine, StoreEngineOptions
    from tpuraft.rpc.transport import InProcNetwork, InProcTransport, RpcServer

    net = InProcNetwork()
    ep = "127.0.0.1:6901"
    server = RpcServer(ep)
    net.bind(server)
    opts = StoreEngineOptions(
        server_id=ep,
        initial_regions=[Region(id=1, peers=[ep])],
        election_timeout_ms=200,
        data_path=str(tmp_path),
        metrics_cache_ttl_ms=10_000)
    store = StoreEngine(opts, server, InProcTransport(net, ep))
    await store.start()
    try:
        t1 = store.metrics_text()
        t2 = store.metrics_text()
        assert store.metrics_renders == 1
        assert store.metrics_cache_hits == 1
        # the cached render is served verbatim; only the age gauge moves
        base1 = t1.split("tpuraft_metrics_age_seconds")[0]
        base2 = t2.split("tpuraft_metrics_age_seconds")[0]
        assert base1 == base2
        assert "tpuraft_metrics_age_seconds" in t2
        # age stays bounded by the TTL
        age = float(t2.rsplit(" ", 1)[-1])
        assert 0.0 <= age <= 10.0
        # ttl=0 renders every call (tests/debugging knob)
        store.opts.metrics_cache_ttl_ms = 0
        store.metrics_text()
        store.metrics_text()
        assert store.metrics_renders == 3
        # the per-region aggregation the cache bounds is present
        assert "tpuraft_fsm_applied_entries" in t1
        assert "tpuraft_proposed_ops" in t1
        assert "tpuraft_heat_regions_tracked" in t1
    finally:
        await store.shutdown()


# ---------------------------------------------------------------------------
# device-tick profiling: phase histograms, lane gauges, perfetto export
# ---------------------------------------------------------------------------


def _numpy_engine(g: int = 8):
    from tpuraft.core.engine import MultiRaftEngine
    from tpuraft.options import TickOptions

    return MultiRaftEngine(TickOptions(max_groups=g, max_peers=3,
                                       backend="numpy"))


PER_TICK_HISTS = {"tick_total_ms", "tick_build_ms", "tick_device_ms",
                  "tick_apply_ms", "tick_state_ms", "tick_call_ms",
                  "tick_fetch_ms", "tick_heartbeat_ms"}


async def _engine(backend: str, g: int = 8, p: int = 4):
    """numpy: the twin, no loop needed.  jax: the jitted tick on CPU
    JAX, which ``start`` compiles (and then its loop is stopped)."""
    if backend == "numpy":
        return _numpy_engine(g)
    from tpuraft.core.engine import MultiRaftEngine
    from tpuraft.options import TickOptions

    e = MultiRaftEngine(TickOptions(max_groups=g, max_peers=p,
                                    backend="jax"))
    from tpuraft.util.metrics import Histogram

    await e.start()
    await e.shutdown()
    e.tick_hists = {k: Histogram() for k in e.tick_hists}   # past warm-up
    return e


@pytest.mark.parametrize("backend", ["numpy", "jax"])
async def test_tick_phase_histograms_count_ticks(backend):
    e = await _engine(backend)
    for _ in range(5):
        e.tick_once()
    hists = e.tick_histograms()
    events = {"elections_started", "leader_stepdowns", "beat_rows",
              "vote_rounds_lost", "elections_yielded", "leader_transfers"}
    overlap = {"tick_overlapped", "tick_ready", "tick_inflight_ms"}
    assert set(hists) == PER_TICK_HISTS | events | overlap | {
        "tick_late_ms", "tick_transfers", "fence_resolve_ms"}
    # tick_once is both halves in one go: nothing was collected a turn
    # after its enqueue (tests/test_tick_overlap.py drives tick())
    assert all(hists[k]["count"] == 0 for k in overlap)
    # one sample an event, not a tick: no node, no leader, none of them
    assert all(hists[k]["count"] == 0 for k in events)
    assert all(hists[k]["count"] == 5 for k in PER_TICK_HISTS)
    # the loop's lateness counts the loop's own ticks, a fence's wait
    # counts fences: tick_once by hand gives neither
    assert hists["tick_late_ms"]["count"] == 0
    assert hists["fence_resolve_ms"]["count"] == 0
    assert hists["tick_total_ms"]["p99"] >= 0.0
    assert "tick_p99_ms" in e.describe()
    # the device phase in its three parts, off the same clock reads
    h = e.tick_hists
    parts = h["tick_state_ms"].total + h["tick_call_ms"].total \
        + h["tick_fetch_ms"].total
    assert parts <= h["tick_device_ms"].total * (1 + 1e-9)
    assert parts == pytest.approx(h["tick_device_ms"].total)
    if backend == "numpy":
        assert h["tick_state_ms"].total == 0.0    # the twin builds none
    else:
        assert min(h[k].total for k in ("tick_state_ms", "tick_call_ms",
                                        "tick_fetch_ms")) > 0.0
    assert h["tick_heartbeat_ms"].total == 0.0     # no leader, no beat
    assert h["tick_heartbeat_ms"].total <= h["tick_apply_ms"].total
    # one array up and one down a tick on the device path, each a
    # sample of its bytes; the twin moves none
    per_tick = 0 if backend == "numpy" else 2
    assert h["tick_transfers"].count == 5 * per_tick
    assert h["tick_transfers"].total == 5 * per_tick * 4 * (
        (3 * e.P + 10 + 3) * e.G) / 2
    assert e.lane_stats()["tick_transfers"] == per_tick


def _assert_device_tick_is_the_twin(e, now: int) -> None:
    from tpuraft.core.engine import _NpOutputs

    rel, commit_rel = e._rel_views()
    dev = e._device_tick(rel, commit_rel, now)
    twin = e._np_tick(rel, commit_rel, now)
    for name in _NpOutputs.__slots__:
        a, b = getattr(dev, name), np.asarray(getattr(twin, name))
        assert isinstance(a, np.ndarray) and a.shape == b.shape, name
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("seed", [0, 1, 2])
async def test_packed_device_tick_equals_twin_across_a_grow(seed):
    """One buffer up, one down: the jitted tick on live mirrors is the
    numpy twin row for row, before and after ``_grow`` (buffer
    reallocated, one recompile for the new shape)."""
    from tests.test_ops_tick import randomize_mirrors
    from tpuraft.ops.tick import packed_state_shape, raft_tick_packed_jit

    rng = np.random.default_rng(seed)
    g, p = 24 + 8 * seed, 5              # shapes no other test compiles
    e = await _engine("jax", g=g, p=p)
    assert e._tick_fn is raft_tick_packed_jit
    buf = e._tick_buf
    assert buf.shape == packed_state_shape(g, p) and buf.dtype == np.int32
    for now in (700, 1100):
        randomize_mirrors(e, rng)
        _assert_device_tick_is_the_twin(e, now)
        assert e._tick_buf is buf                    # reused tick to tick
        assert e.lane_stats()["tick_transfers"] == 2
    compiled = raft_tick_packed_jit._cache_size()

    e._grow()
    assert e.G == 2 * g and e._tick_buf is None
    e.tick_once()
    assert e._tick_buf.shape == packed_state_shape(2 * g, p)
    for now in (900, 1300):
        randomize_mirrors(e, rng)
        _assert_device_tick_is_the_twin(e, now)
    h = e.tick_hists
    crossed = h["tick_transfers"].count
    e.tick_once()
    assert raft_tick_packed_jit._cache_size() == compiled + 1
    assert h["tick_transfers"].count == crossed + 2 == 2 * (4 + 2)
    assert h["tick_state_ms"].total + h["tick_call_ms"].total \
        + h["tick_fetch_ms"].total == pytest.approx(h["tick_device_ms"].total)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
async def test_tick_sections_nest_under_the_one_tracer(backend):
    from tpuraft.util.trace import TRACER

    e = await _engine(backend)
    TRACER.configure(enabled=True)
    try:
        w0 = time.perf_counter()
        for _ in range(4):
            e.tick_once()
        wall = time.perf_counter() - w0
        # switched on from a running loop the tracer frames the
        # collector too: a pass inside a tick is the tick's child
        table = {name: row for name, row in TRACER.section_table().items()
                 if not name.startswith("gc.")}
    finally:
        TRACER.configure(enabled=False)
        TRACER.reset()
    assert set(table) == {"tick.build", "tick.call", "tick.fetch",
                          "tick.apply"}
    assert all(calls == 4 for calls, _b, _s in table.values())
    # the sections tile the tick: they open at the clock read
    # tick_total_ms starts from and close after the one it ends at, one
    # at a time, so their seconds lie between that total and the wall
    # time of the four calls (no tolerance: a preempted test thread
    # stretches all three alike)
    tiled = sum(busy for _c, busy, _s in table.values())
    assert e.tick_hists["tick_total_ms"].total / 1e3 - 1e-9 <= tiled <= wall
    # tick.build holds the GroupState build, which tick_build_ms leaves
    # to the device phase
    assert table["tick.build"][1] >= \
        e.tick_hists["tick_build_ms"].total / 1e3


@pytest.mark.parametrize("wake", ["timed", "dirty"])
async def test_tick_late_ms_counts_both_wake_kinds(wake):
    """A wake is late by what the loop took past the time it was due:
    the timeout of a timed wait, the ``mark_dirty`` of a dirty one."""
    e = _numpy_engine()
    loop = asyncio.get_running_loop()

    def hog():                  # the loop thread, busy with other work
        if wake == "dirty":
            e.mark_dirty()
        time.sleep(0.05)

    loop.call_later(0.005, hog)
    await e._wait_dirty(0.010 if wake == "timed" else 5.0)
    # due 5 ms into a 50 ms hog (timed) or at its very start (dirty)
    assert 0.030 <= e._late_s <= 0.2
    # a mark that is already there wakes nothing and is late by nothing
    e._late_s = 0.0
    await e._wait_dirty(5.0 if wake == "dirty" else 0.0)
    if wake == "dirty":
        assert e._late_s == 0.0
    # the loop books the lateness once per tick it runs
    e.opts.tick_interval_ms = 5
    e._task = asyncio.ensure_future(e._loop())
    try:
        for _ in range(100):
            e.mark_dirty()
            await asyncio.sleep(0.005)
            if e.tick_hists["tick_late_ms"].count >= 3:
                break
    finally:
        await e.shutdown()
    late = e.tick_hists["tick_late_ms"]
    assert 3 <= late.count <= e.ticks
    assert late.total >= 0.0


class _Fence:
    done = False

    def __init__(self) -> None:
        self.confirmed = 0

    def note_quorum(self) -> None:
        self.confirmed += 1
        self.done = True


@pytest.mark.parametrize("tracing", [False, True])
def test_fence_resolve_ms_fills_only_while_tracing(tracing):
    from tpuraft.util.trace import TRACER

    e = _numpy_engine()
    waited = e.tick_hists["fence_resolve_ms"]
    TRACER.configure(enabled=tracing)
    try:
        fences = [_Fence(), _Fence()]
        for f in fences:
            e.arm_read_fence(2, f)
        e.tick_q_ack[2] = e.now_ms() + 1     # a quorum acked since
        e._resolve_fences(2)
    finally:
        TRACER.configure(enabled=False)
        TRACER.reset()
    assert [f.confirmed for f in fences] == [1, 1]
    assert e.fence_lane_resolves == 2
    assert waited.count == (2 if tracing else 0)
    assert waited.total >= 0.0


def test_lane_stats_matches_engine_arrays():
    from tpuraft.ops.tick import ROLE_CANDIDATE, ROLE_FOLLOWER, ROLE_LEADER

    e = _numpy_engine(g=16)
    e.has_ctrl[:8] = True
    e.role[:4] = ROLE_LEADER
    e.role[4:6] = ROLE_FOLLOWER
    e.role[6] = ROLE_CANDIDATE
    e.quiescent[:3] = True
    # an uncontrolled slot must not count, quiescent or not
    e.role[12] = ROLE_LEADER
    e.quiescent[12] = True
    ls = e.lane_stats()
    assert ls["groups"] == 8
    assert ls["leaders"] == 4
    assert ls["followers"] == 2
    assert ls["candidates"] == 1
    assert ls["quiescent"] == 3
    assert ls["hibernation_fraction"] == pytest.approx(3 / 8)
    assert ls["q_ack_age_ms_p99"] >= 0.0


async def test_tick_occupancy_matches_quiescent_count(tmp_path):
    """StoreEngine.tick_occupancy reports (controlled, quiescent) from
    the engine arrays — the pair the heartbeat ships to the PD."""
    from tpuraft.rheakv.metadata import Region
    from tpuraft.rheakv.store_engine import StoreEngine, StoreEngineOptions
    from tpuraft.rpc.transport import InProcNetwork, InProcTransport, RpcServer

    net = InProcNetwork()
    ep = "127.0.0.1:6902"
    server = RpcServer(ep)
    net.bind(server)
    opts = StoreEngineOptions(
        server_id=ep,
        initial_regions=[Region(id=1, peers=[ep])],
        election_timeout_ms=200,
        data_path=str(tmp_path))
    store = StoreEngine(opts, server, InProcTransport(net, ep))
    await store.start()
    try:
        # timer mode: every hosted region counts, none hibernate
        assert store.tick_occupancy() == (1, 0)
        e = _numpy_engine(g=8)
        e.has_ctrl[:5] = True
        e.quiescent[1:3] = True
        e.quiescent[7] = True      # uncontrolled: not counted
        store.multi_raft_engine = e
        assert store.tick_occupancy() == (5, 2)
    finally:
        store.multi_raft_engine = None
        await store.shutdown()
