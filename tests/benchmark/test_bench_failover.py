"""The failover deployment, ``kv3x1024-failover.ycsb_a_kill1``: a tiny copy (8
regions, election timeout 1 s, a window of a few seconds, the same two
faults) through ``driver.run_cell`` on the CPU: correct with one kill and one
restart, not correct with a fault planted or the restart left out; one seed,
one schedule of operations and faults; the manifest as committed."""

import asyncio
import json
import os
import time

import numpy as np
import pytest
from bench_helpers import (EVERY_CELL, REPO, add_files, extended_copy,
                           stands_together)

from benchmark import check_manifest, driver, plugins
from benchmark.driver import run_cell
from benchmark.loops import open as open_loop
from benchmark.reference import tick_mismatches, tick_reference
from benchmark.traffic import OpStream, Values

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2 ** 31 + 34
CELL = "kv3x1024-failover.ycsb_a_kill1"
TINY = "kv3x8-failover.ycsb_a_kill1_150"
NO_RESTART = "kv3x8-failover.ycsb_a_kill_only"
NEW = ("unavailable_s", "all_led_s", "elections_per_region", "election_ms",
       "client_bounces_per_op", "catch_up_s")


def _load(rel: str) -> dict:
    with open(os.path.join(REPO, rel)) as f:
        return json.load(f)


def failover_copy(tmp: str) -> str:
    """``extended_copy`` plus the committed deployment at 8 regions: the
    configuration's own cluster module and options, the mix's own loop and
    faults at 150 operations a second, the six metrics under other names,
    and a second mix with the restart left out (its loop module says it runs
    that schedule)."""
    root = extended_copy(tmp)
    cfg = _load("benchmark/configs/kv3x1024-failover.json")
    cfg.update(name="kv3x8-failover", regions=8, record_count=256,
               election_timeout_ms=1000)
    cfg["engine"] = dict(cfg["engine"], max_groups=16)
    mix = _load("benchmark/traffic/ycsb_a_kill1.json")
    mix.update(name="ycsb_a_kill1_150", warm_seconds=0.3,
               loop={"kind": "open_faults", "rate": 150})
    kill_only = dict(mix, name="ycsb_a_kill_only", faults=mix["faults"][:1],
                     loop={"kind": "kill_only", "rate": 150})
    files = {"benchmark/configs/kv3x8-failover.json": cfg,
             "benchmark/traffic/ycsb_a_kill1_150.json": mix,
             "benchmark/traffic/ycsb_a_kill_only.json": kill_only,
             "benchmark/loops/kill_only.py":
                 "from benchmark.loops.open_faults import IMPLEMENTS as _I\n"
                 "from benchmark.loops.open_faults import run_window\n"
                 "IMPLEMENTS = {'faults': [_I['faults'][0][:1]]}\n"}
    metrics = []
    for name in NEW:
        m = _load(f"benchmark/layer_metrics/{name}.json")
        m.update(name=name + ".kv3x8", workloads=[TINY])
        files[f"benchmark/layer_metrics/{name}.kv3x8.json"] = m
        metrics.append({k: v for k, v in m.items() if k != "reader"})
    add_files(
        root, files,
        configs=[{"name": "kv3x8-failover", "source": cfg["source"],
                  "file": "benchmark/configs/kv3x8-failover.json",
                  "reduced": ["record_count"], "why": "test size"}],
        workloads=[{"name": TINY, "config": "kv3x8-failover",
                    "traffic": "ycsb_a_kill1_150", "chips": 1,
                    "why": "test size"},
                   {"name": NO_RESTART, "config": "kv3x8-failover",
                    "traffic": "ycsb_a_kill_only", "chips": 1,
                    "why": "test size"}],
        per_layer=metrics)
    return root


def _run(tmp_path, cell=TINY, trace=False, fault=None, seconds=6.0):
    bm = check_manifest.check(failover_copy(str(tmp_path)))
    return asyncio.run(run_cell(
        bm, cell, SEED, seconds, trace, str(tmp_path / "work"), CPU,
        time.perf_counter(), fault=fault))


def test_the_tiny_cell_loses_its_leading_store_and_is_correct(
        tmp_path, monkeypatch):
    # a handful of elections: every one sampled, so ``election`` has a span
    monkeypatch.setattr(driver, "TRACE_SAMPLE_RATE", 1.0)
    result = _run(tmp_path, trace=True)
    summary = result["_summary"]
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0
    counters = summary["counters"]
    assert counters["loop.kills"] == 1 and counters["loop.restarts"] == 1
    faults = summary["loop"]["faults"]
    # every region the dead store led elected again, on the survivors
    assert counters["engine.elections_started.count"] \
        >= faults["regions_led"] >= 3
    assert 0 < faults["kill_s"] < faults["all_led_s"] < 6.0
    assert faults["restart_s"] < faults["booted_s"] <= faults["caught_up_s"]
    assert sum(faults["leaders_per_store"]) == 8
    assert faults["leaders_per_store"][faults["victim"]] == 0
    assert [p["phase"] for p in faults["phases"]][:2] == ["healthy", "outage"]
    # by phase, how late the generator sent what came due in it, and when
    # the backlog of the outage was gone: after all were led again
    assert all(p["ops"]["sent_late_ms"] >= 0.0 for p in faults["phases"]
               if p.get("ops"))
    assert faults["kill_s"] < faults["backlog_peak"]["at_s"]
    drained = faults["backlog_drained_s"]   # 2 % of this peak: two operations
    assert drained is None or faults["backlog_peak"]["at_s"] <= drained <= 6.0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    for name in NEW:
        assert name + ".kv3x8" in got, name
        assert name not in got      # the committed ones list their own cell
    # the election timeout is 1 s: no leader can answer before it has passed
    assert 0.9 < got["unavailable_s.kv3x8"] <= got["all_led_s.kv3x8"] + 0.2
    # only the regions the dead store led elect: their share of the eight
    assert got["elections_per_region.kv3x8"] >= faults["regions_led"] / 8
    assert got["election_ms.kv3x8"] > 0.0
    assert got["client_bounces_per_op.kv3x8"] > 0.0
    assert got["catch_up_s.kv3x8"] > 0.0
    spans = summary["spans"]
    assert spans["election"]["n"] >= 1
    assert "loop.raft.election" in spans
    # a counter never runs backwards over a store's death
    assert not {k: v for k, v in counters.items()
                if isinstance(v, (int, float)) and v < 0
                and not k.endswith(".leaders")}     # a state, not a count


@pytest.mark.parametrize("fault, fails", [
    ("stale_reads", "reads_stale"),         # the control
    ("drop_updates", "updates_lost"),
    ("skip_replica", "replica_divergent"),
])
def test_with_a_fault_planted_the_tiny_cell_is_not_correct(
        tmp_path, monkeypatch, fault, fails):
    monkeypatch.setattr(driver, "SETTLE_DEADLINE_S", 2.0)
    result = _run(tmp_path, fault=fault)
    assert result["correct"] is False
    c = result["checks"][fails]
    assert c["value"] > c["limit"]
    assert result["_summary"]["counters"]["loop.kills"] == 1


def test_with_the_restart_left_out_the_tiny_cell_is_not_correct(
        tmp_path, monkeypatch):
    monkeypatch.setattr(driver, "SETTLE_DEADLINE_S", 2.0)
    result = _run(tmp_path, cell=NO_RESTART)
    assert result["correct"] is False
    # the dead store's state machine holds nothing: every record diverges
    assert result["checks"]["replica_divergent"]["value"] >= 256
    counters = result["_summary"]["counters"]
    assert counters["loop.kills"] == 1 and counters["loop.restarts"] == 0
    assert result["failed"] == 0            # the survivors served all of it


class _Cluster:
    """What the loop asks of a cluster, answered by a clock."""

    regions = 4

    def __init__(self):
        self.calls: list = []
        self.t0 = time.perf_counter()
        self.down_until = None

    def _now(self):
        return time.perf_counter() - self.t0

    def counters(self):
        return {k: 0 for k in (
            "engine.elections_started.count", "engine.leader_stepdowns.count",
            "engine.vote_rounds_lost.count",
            "engine.elections_yielded.count", "client.batch_retries",
            "engine.ticks", "engine.tick_late_ms.count",
            "engine.tick_late_ms.total")}

    def most_leaders(self):
        return 0

    async def kill(self, i):
        self.calls.append(("kill", i, self._now()))
        self.down_until = time.perf_counter() + 0.3
        return {0, 1, 2, 3}

    async def restart(self, i):
        self.calls.append(("restart", i, self._now()))

    def leaders(self):
        return 4 if self.down_until is None \
            or time.perf_counter() > self.down_until else 0

    def leaders_per_store(self):
        return [0, 2, 2]

    def lagging(self, i):
        return set()

    def section_seconds(self):
        return []


class _Client:
    """A register a key; nothing is answered while the cluster is down."""

    def __init__(self):
        self.cluster, self.data = _Cluster(), {}

    async def _serve(self):
        while self.cluster.leaders() < 4:
            await asyncio.sleep(0.01)
        await asyncio.sleep(0.001)

    async def get(self, key):
        await self._serve()
        return self.data.get(key)

    async def put(self, key, value):
        await self._serve()
        self.data[key] = value
        return True


def _drive(seconds=1.5):
    bm = check_manifest.check(REPO)
    _, _, mix = check_manifest.cell(bm, CELL)
    loop = plugins.loop_of(bm, mix)
    mix = dict(mix, warm_seconds=0.1, loop=dict(mix["loop"], rate=400))
    stream = OpStream(mix, 64, SEED, n=1 << 16)
    values = Values(SEED, 100)
    keys = [b"k%04d" % i for i in range(64)]

    async def go():
        client = _Client()
        for i, k in enumerate(keys):
            client.data[k] = values.make(0xFFFFFFFF, i, i)
        return await loop.run_window(client, keys, stream, values, mix,
                                     seconds), client

    win, client = asyncio.run(go())
    return win, client, stream


def test_one_seed_gives_the_same_operations_due_times_and_fault_times():
    runs = [_drive() for _ in range(2)]
    rel, faults = [], []
    for win, client, stream in runs:
        order = np.argsort(win.due)
        due = np.array(win.due)[order]
        ops = [(win.ops[i][0], win.ops[i][1]) for i in order]
        assert ops == [(int(stream.kinds[i]), int(stream.records[i]))
                       for i in range(len(ops))]
        rel.append(due - due[0])
        # the faults at their shares of the window, from its start
        kinds = [(c[0], c[1]) for c in client.cluster.calls]
        assert kinds == [("kill", 0), ("restart", 0)]
        notes = win.notes["faults"]
        assert notes["kill_s"] == pytest.approx(0.2 * 1.5, abs=0.05)
        assert notes["restart_s"] == pytest.approx(0.65 * 1.5, abs=0.05)
        faults.append((notes["kill_s"], notes["restart_s"]))
        assert win.counters["loop.kills"] == 1
        assert win.counters["loop.restarts"] == 1
        # down for 0.3 s: what came due meanwhile waited, and counts
        assert 300.0 <= win.counters["loop.unavailable_ms"] < 450.0
        assert 300.0 <= win.counters["loop.all_led_ms"] < 550.0
        assert win.counters["cluster.regions"] == 4
        assert not [o for o in win.ops if not o[4]]
        held = [o[3] - t for o, t in zip(win.ops, win.due)
                if notes["kill_s"] + 0.02 < t - win.start
                < notes["kill_s"] + 0.1]
        assert held and min(held) > 0.15
    n = min(len(rel[0]), len(rel[1]))
    assert np.allclose(rel[0][:n], rel[1][:n], atol=1e-9)
    sched = open_loop.schedule(SEED, 400.0, 1 << 16)
    assert np.allclose(rel[0][:n], sched[:n] - sched[0], atol=1e-9)
    assert faults[0] == pytest.approx(faults[1], abs=0.05)


def test_the_committed_manifest_has_the_deployment(manifest_root):
    bm = check_manifest.check(manifest_root)
    # appended: what was there stays first and in order; more may follow
    assert [w["name"] for w in bm["workloads"]][:5] == [
        "kv3x1024.ycsb_a", "kv3x1024.ycsb_b", "kv3x4096.ycsb_a",
        "kv3x1024.ycsb_a_open", CELL]
    assert [c["name"] for c in bm["configs"]][:3] == [
        "kv3x1024", "kv3x4096", "kv3x1024-failover"]
    assert all(w["chips"] == 1 for w in bm["workloads"][:5])
    cell, cfg, mix = check_manifest.cell(bm, CELL)
    assert (cell["config"], cell["traffic"]) == ("kv3x1024-failover",
                                                 "ycsb_a_kill1")
    loop = plugins.loop_of(bm, mix)
    assert loop.IMPLEMENTS == {"faults": [mix["faults"]]}
    assert mix["faults"] == [
        {"kind": "kill_store", "store": "most_leaders", "at": 0.2},
        {"kind": "restart_store", "at": 0.65}]
    assert mix["loop"]["kind"] == "open_faults"
    cls = plugins.cluster_of(bm, cfg)
    assert cls.__module__.endswith("clusters.failover")
    # every field of kv3x1024 but the name, the source, the cluster module,
    # its options and the two guarantees more
    small = _load("benchmark/configs/kv3x1024.json")
    for key in set(small) - {"name", "source", "why", "guarantees",
                             "assumed"}:
        assert cfg[key] == small[key], key
    assert set(cfg) - set(small) == {"cluster", "options"}
    assert cfg["options"] == {"client_deadline_ms": 30000}
    assert set(cfg["guarantees"]) - set(small["guarantees"]) == {
        "availability", "recovery"}
    for k, v in small["guarantees"].items():
        assert cfg["guarantees"][k] == v
    # the same mix as the open cell but for the loop, the rate and the faults
    open_mix = _load("benchmark/traffic/ycsb_a_open.json")
    assert {k: v for k, v in mix.items()
            if k not in ("name", "why", "loop", "faults")} == {
        k: v for k, v in open_mix.items()
        if k not in ("name", "why", "loop", "faults")}
    names = [m["name"] for m in check_manifest.metrics_of(
        bm, CELL, "per_layer")]
    assert stands_together(names, NEW)
    assert set(EVERY_CELL) | set(NEW) <= set(names)
    for other in (w["name"] for w in bm["workloads"] if w["name"] != CELL):
        assert not set(NEW) & {m["name"] for m in check_manifest.metrics_of(
            bm, other, "per_layer")}
    assert {m["name"] for m in check_manifest.metrics_of(
        bm, CELL, "end_to_end")} == {"ops_per_s", "read_p95_ms",
                                     "update_p95_ms", "setup_s"}


def test_the_election_lanes_of_the_compiled_tick_equal_the_reference(tmp_path):
    """The lanes no healthy cell fires: a surviving engine's compiled tick on
    its live rows between the kill and the first election, and on every tick
    of the burst on which ``election_due`` or ``elected`` is set on some row,
    against ``reference.tick_reference`` row for row."""
    bm = check_manifest.check(failover_copy(str(tmp_path)))
    _, cfg, _ = check_manifest.cell(bm, TINY)

    async def go():
        cluster = plugins.cluster_of(bm, cfg)(cfg, str(tmp_path / "work"))
        await cluster.start()
        try:
            victim = cluster.most_leaders()
            survivors = [i for i in range(3) if i != victim]
            seen: list = []     # (lane that fired, rows that differ)

            def spy_on(i: int) -> None:
                engine = cluster.engines[i]
                tick_once = engine.tick_once

                def spy():
                    inputs, now, params, out = cluster.tick_probe(i)
                    fired = [lane for lane in ("election_due", "elected")
                             if out[lane].any()]
                    if fired:
                        seen.append((fired, tick_mismatches(
                            out, tick_reference(inputs, now, params))))
                    return tick_once()

                engine.tick_once = spy

            led = await cluster.kill(victim)
            assert led
            between = []
            for i in survivors:
                inputs, now, params, out = cluster.tick_probe(i)
                assert not out["election_due"].any()    # a timeout away
                between.append(tick_mismatches(
                    out, tick_reference(inputs, now, params)))
                spy_on(i)
            deadline = time.perf_counter() + 10.0
            while cluster.leaders() < cluster.regions:
                assert time.perf_counter() < deadline
                await asyncio.sleep(0.02)
            return between, seen
        finally:
            await cluster.shutdown()

    between, seen = asyncio.run(go())
    assert between == [0, 0]
    lanes = {lane for fired, _ in seen for lane in fired}
    assert "election_due" in lanes and "elected" in lanes, seen
    assert [bad for _, bad in seen] == [0] * len(seen)


def test_what_the_dense_cells_test_pins_still_holds(manifest_root):
    """What ``test_bench_dense.py``'s first test asserts of the dense cell,
    asserted once more beside the deployment that came after it."""
    dense_cell = "kv3x4096.ycsb_a"
    bm = check_manifest.check(manifest_root)
    cell, cfg, mix = check_manifest.cell(bm, dense_cell)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kv3x4096", "ycsb_a", 1)
    assert [w["name"] for w in bm["workloads"]][:4] == [
        "kv3x1024.ycsb_a", "kv3x1024.ycsb_b", "kv3x4096.ycsb_a",
        "kv3x1024.ycsb_a_open"]
    assert [c["name"] for c in bm["configs"]][:2] == ["kv3x1024", "kv3x4096"]
    entry = bm["configs"][1]
    assert entry["reduced"] == ["regions", "record_count"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert cfg == _load("benchmark/configs/kv3x4096.json")
    assert mix["name"] == "ycsb_a"
    assert mix["loop"] == {"kind": "closed", "clients": 256}
    layer = check_manifest.metrics_of(bm, dense_cell, "per_layer")
    assert [m["name"] for m in layer[:29]] == [
        m["name"] for m in bm["per_layer"][:29]]
    assert not any("workloads" in m for m in bm["per_layer"][:29])
    assert layer == [m for m in bm["per_layer"]
                     if dense_cell in m.get("workloads", [dense_cell])]
    assert set(EVERY_CELL) <= {m["name"] for m in layer}
