"""The balanced deployment, ``kv3x1024-balanced.ycsb_a``: a tiny copy (12
regions, election timeout 2 s, a window of a few seconds, at most 4 leaders a
store) through ``driver.run_cell`` on the CPU.  A cluster of 12 regions elects
its leaders wherever the timers fall, so the copy's cluster module first piles
them on the first store, which is where ``kv3x1024`` boots into, and the
committed module then spreads them: correct with leaders 4 / 4 / 4 from the
window's start to its close, not correct with a fault planted, an error and no
window where the transfers fail; each engine's compiled tick on rows that hold
leaders and followers at once; the manifest as committed."""

import asyncio
import json
import os
import time

import pytest
from bench_helpers import (EVERY_CELL, FROM_THE_DEVICE, REPO, add_files,
                           extended_copy, stands_together)

from benchmark import check_manifest, driver, plugins
from benchmark.driver import run_cell
from benchmark.reference import (FOLLOWER, LEADER, tick_mismatches,
                                 tick_reference)

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2 ** 31 + 36
CELL = "kv3x1024-balanced.ycsb_a"
TINY = "kv3x12-balanced.ycsb_a16"
STUCK = "kv3x12-stuck.ycsb_a16"
NEW = ("leader_share_max_pct", "commits_per_tick", "log_rounds_mixed_pct",
       "tick_late_ms.all")

# the committed module, after every leadership was moved to the first store
PILED = '''
import asyncio
import time

from benchmark.clusters.balanced import IMPLEMENTS, Cluster as _Balanced


class Cluster(_Balanced):
    async def spread_leaders(self):
        first = self.stores[0].server_id
        for store in self.stores[1:]:
            for re in store._regions.values():
                if re.is_leader():
                    st = await re.node.transfer_leadership_to(first)
                    assert st.is_ok(), st
        deadline = time.perf_counter() + 10.0
        while self.leaders_per_store() != [self.regions, 0, 0]:
            assert time.perf_counter() < deadline, self.leaders_per_store()
            await asyncio.sleep(0.02)
        await super().spread_leaders()
'''

# the same, with a CLI whose transfers every store refuses
STUCK_MODULE = PILED.replace("class Cluster(", "class _Piled(") + '''

class _Refusing:
    def __init__(self, transport):
        self._transport = transport

    async def call(self, endpoint, method, request, timeout_ms=None):
        from tpuraft.errors import RaftError
        from tpuraft.rpc.cli_messages import CliResponse

        if method == "cli_transfer_leader":
            return CliResponse(code=int(RaftError.EINVAL), msg="refused")
        return await self._transport.call(endpoint, method, request,
                                          timeout_ms)


class Cluster(_Piled):
    rebalance_deadline_s = 3.0

    def cli_transport(self):
        return _Refusing(super().cli_transport())
'''


def _load(rel: str) -> dict:
    with open(os.path.join(REPO, rel)) as f:
        return json.load(f)


def balanced_copy(tmp: str) -> str:
    """``extended_copy`` plus the committed deployment at 12 regions: the
    configuration's fields and options (at most 4 leaders a store), a cluster
    module that piles the leaders on the first store before the committed one
    spreads them, the four metrics under other names, and a second
    configuration whose cluster's transfers are refused."""
    root = extended_copy(tmp)
    cfg = _load("benchmark/configs/kv3x1024-balanced.json")
    cfg.update(name="kv3x12-balanced", cluster="balanced_piled", regions=12,
               record_count=240, election_timeout_ms=2000,
               options={"max_leaders_per_store": 4})
    cfg["engine"] = dict(cfg["engine"], max_groups=32)
    stuck = dict(cfg, name="kv3x12-stuck", cluster="balanced_stuck")
    files = {"benchmark/configs/kv3x12-balanced.json": cfg,
             "benchmark/configs/kv3x12-stuck.json": stuck,
             "benchmark/clusters/balanced_piled.py": PILED,
             "benchmark/clusters/balanced_stuck.py": STUCK_MODULE}
    metrics = []
    for name in NEW:
        m = _load(f"benchmark/layer_metrics/{name}.json")
        m.update(name=name + ".kv3x12", workloads=[TINY])
        files[f"benchmark/layer_metrics/{name}.kv3x12.json"] = m
        metrics.append({k: v for k, v in m.items() if k != "reader"})
    add_files(
        root, files,
        configs=[{"name": c["name"], "source": c["source"],
                  "file": f"benchmark/configs/{c['name']}.json",
                  "reduced": ["record_count"], "why": "test size"}
                 for c in (cfg, stuck)],
        workloads=[{"name": cell, "config": c["name"], "traffic": "ycsb_a16",
                    "chips": 1, "why": "test size"}
                   for cell, c in ((TINY, cfg), (STUCK, stuck))],
        per_layer=metrics)
    return root


def _run(tmp_path, cell=TINY, trace=False, fault=None, seconds=3.0):
    bm = check_manifest.check(balanced_copy(str(tmp_path)))
    return asyncio.run(run_cell(
        bm, cell, SEED, seconds, trace, str(tmp_path / "work"), CPU,
        time.perf_counter(), fault=fault))


def test_the_tiny_cell_spreads_its_leaders_and_is_correct(tmp_path):
    result = _run(tmp_path, trace=True)
    summary = result["_summary"]
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0
    counters, timings = summary["counters"], summary["timings"]
    # piled on the first store, then one rebalance call: eight leaderships
    # asked for, eight gained through TimeoutNow, before the load
    assert timings["rebalance_calls"] == 1
    assert timings["transfers_asked"] == 8
    assert timings["transfers_gained"] == 8
    assert 0.0 < timings["rebalance_s"] < 10.0
    # 4 / 4 / 4 when the window opens and when it closes, and nothing moved
    # in between
    assert summary["leaders_per_store"] == [4, 4, 4]
    assert [counters[f"engine{i}.leaders_now"] for i in range(3)] == [4, 4, 4]
    for name in ("elections_started", "leader_stepdowns", "leader_transfers"):
        assert counters[f"engine.{name}.count"] == 0, name
    # every store leads and follows: all three engines tick, every store's
    # log rounds and KV WAL rounds carry both of its roles at times
    for i in range(3):
        assert counters[f"engine{i}.ticks"] > 0
        assert 0 < counters[f"engine{i}.log_rounds_mixed.count"] \
            <= counters[f"engine{i}.log_rounds.count"]
        assert 0 < counters[f"engine{i}.kv_wal_syncs_mixed.count"] \
            <= counters[f"engine{i}.kv_wal_syncs.count"]
        assert counters[f"engine{i}.follower_rows.count"] > 0
    got = {k: v["value"] for k, v in result["metrics"].items()}
    for name in NEW:
        assert name + ".kv3x12" in got, name
        assert name not in got      # the committed ones list their own cell
    assert got["leader_share_max_pct.kv3x12"] == pytest.approx(100 / 3)
    assert got["commits_per_tick.kv3x12"] >= 0.0
    assert 0.0 < got["log_rounds_mixed_pct.kv3x12"] <= 100.0
    assert got["tick_late_ms.all.kv3x12"] >= 0.0
    # the metrics every cell reports are all there beside them, but the
    # three a device trace gives (none off the chip); + extended_copy's own
    assert set(EVERY_CELL) - set(FROM_THE_DEVICE) | {"srv_propose_ms"} \
        <= set(got)
    assert "leader_transfer" not in summary["spans"]    # none in the window


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
    assert result["_summary"]["leaders_per_store"] == [4, 4, 4]


def test_transfers_that_fail_raise_and_no_window_opens(tmp_path):
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError) as e:
        _run(tmp_path, cell=STUCK)
    # the counts, the ceiling and the deadline are in the message
    msg = str(e.value)
    assert "leaders [12, 0, 0] of 12 regions" in msg, msg
    assert "at most 4 a store" in msg and "within 3.0s" in msg
    assert "0 transfers" not in msg     # they were asked for, and refused
    assert time.perf_counter() - t0 < 30.0


def test_each_engines_compiled_tick_equals_the_reference_on_both_roles(
        tmp_path):
    """Rows no probe of another cell holds: while traffic runs, EACH of the
    three engines' live rows hold leaders and followers side by side, and
    its compiled tick equals ``reference.tick_reference`` row for row."""
    bm = check_manifest.check(balanced_copy(str(tmp_path)))
    _, cfg, mix = check_manifest.cell(bm, TINY)

    async def go():
        cluster = plugins.cluster_of(bm, cfg)(cfg, str(tmp_path / "work"))
        await cluster.start()
        stop = [False]

        async def caller(c: int) -> None:
            n = 0
            while not stop[0]:
                key = cluster.keys[(c * 37 + n) % len(cluster.keys)]
                assert await cluster.client.put(key, b"v%d" % n) is True
                assert await cluster.client.get(key) is not None
                n += 1

        callers = [asyncio.ensure_future(caller(c)) for c in range(8)]
        probes = []
        try:
            for _ in range(4):
                await asyncio.sleep(0.25)
                for e in range(3):
                    inputs, now, params, out = cluster.tick_probe(e)
                    probes.append((
                        e, int((inputs["role"] == LEADER).sum()),
                        int((inputs["role"] == FOLLOWER).sum()),
                        tick_mismatches(out, tick_reference(inputs, now,
                                                            params))))
            return probes
        finally:
            stop[0] = True
            await asyncio.gather(*callers)
            await cluster.shutdown()

    probes = asyncio.run(go())
    assert len(probes) == 12
    for engine, leaders, followers, differ in probes:
        assert (leaders, followers, differ) == (4, 8, 0), probes


def test_the_committed_manifest_has_the_deployment(manifest_root):
    bm = check_manifest.check(manifest_root)
    assert bm["workloads"][5]["name"] == CELL
    assert bm["configs"][3]["name"] == "kv3x1024-balanced"
    assert bm["configs"][3]["reduced"] == ["record_count"]
    cell, cfg, mix = check_manifest.cell(bm, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kv3x1024-balanced", "ycsb_a", 1)
    assert len(cell["why"]) <= 200 and len(cfg["source"]) <= 200
    cls = plugins.cluster_of(bm, cfg)
    assert cls.__module__.endswith("clusters.balanced")
    # every field of kv3x1024 but the name, the source, what it is for, where
    # the leaders lie, what was assumed, the cluster module and its options
    small = _load("benchmark/configs/kv3x1024.json")
    for key in set(small) - {"name", "source", "why", "layout", "assumed"}:
        assert cfg[key] == small[key], key
    assert set(cfg) - set(small) == {"cluster", "options"}
    assert cfg["options"] == {"max_leaders_per_store": 342}
    assert cfg["layout"].startswith(small["layout"])
    for k, v in small["assumed"].items():
        assert cfg["assumed"][k] == v
    # the mix is the file kv3x1024.ycsb_a runs, not a copy
    _, _, other = check_manifest.cell(bm, "kv3x1024.ycsb_a")
    assert mix == other == _load("benchmark/traffic/ycsb_a.json")
    names = [m["name"] for m in check_manifest.metrics_of(
        bm, CELL, "per_layer")]
    assert stands_together(names, NEW)
    assert set(EVERY_CELL) | set(NEW) <= set(names)
    for name in NEW:
        entry = next(m for m in bm["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
    for other_cell in (w["name"] for w in bm["workloads"]
                       if w["name"] != CELL):
        assert not set(NEW) & {m["name"] for m in check_manifest.metrics_of(
            bm, other_cell, "per_layer")}
    assert {m["name"] for m in check_manifest.metrics_of(
        bm, CELL, "end_to_end")} == {"ops_per_s", "read_p95_ms",
                                     "update_p95_ms", "setup_s"}


def test_what_the_failover_cells_test_pins_still_holds(manifest_root):
    """What ``test_bench_failover.py``'s manifest test asserts of the failover
    cell, asserted once more beside the deployment that came after it."""
    failover = "kv3x1024-failover.ycsb_a_kill1"
    bm = check_manifest.check(manifest_root)
    assert [w["name"] for w in bm["workloads"]][:5] == [
        "kv3x1024.ycsb_a", "kv3x1024.ycsb_b", "kv3x4096.ycsb_a",
        "kv3x1024.ycsb_a_open", failover]
    assert all(w["chips"] == 1 for w in bm["workloads"][:5])
    assert [c["name"] for c in bm["configs"]][:3] == [
        "kv3x1024", "kv3x4096", "kv3x1024-failover"]
    cell, cfg, mix = check_manifest.cell(bm, failover)
    assert (cell["config"], cell["traffic"]) == ("kv3x1024-failover",
                                                 "ycsb_a_kill1")
    assert plugins.loop_of(bm, mix).IMPLEMENTS == {"faults": [mix["faults"]]}
    assert plugins.cluster_of(bm, cfg).__module__.endswith(
        "clusters.failover")
    assert cfg == _load("benchmark/configs/kv3x1024-failover.json")
    names = [m["name"] for m in check_manifest.metrics_of(
        bm, failover, "per_layer")]
    own = ["unavailable_s", "all_led_s", "elections_per_region",
           "election_ms", "client_bounces_per_op", "catch_up_s"]
    assert set(EVERY_CELL) | set(own) <= set(names)
    assert stands_together(names, own)
