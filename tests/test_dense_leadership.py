"""Leadership holds under the write load where the density floor is in force.

The guarantee ``kv3x4096.ycsb_a`` holds the program to on the chip, at a size
the suite affords: the benchmark's own in-process 3-store cluster and closed
loop (``benchmark.driver.run_cell``, on the CPU) at 512 regions asking for a
1,000 ms election timeout, which the density floor raises to 2,048 / 204 ms
(4 ms a control), under 64 callers writing 1 KB records for a few seconds.
No operation fails, the history is clean against ``reference.check_history``,
no node starts an election and no leader steps down inside the window, and
every store leads at the close what it led at the load.
"""

import asyncio
import gc
import json
import os
import shutil
import time

from benchmark import check_manifest
from benchmark.driver import run_cell

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CELL = "kv3x512.write64"
TIME_LIMIT_S = 60.0


def _manifest_with_the_dense_test_cell(tmp: str) -> dict:
    """The benchmark with one more configuration, mix and cell, added as
    files and entries in a temporary copy."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.makedirs(os.path.join(tmp, "tests", "benchmark"))

    def load(rel):
        with open(os.path.join(tmp, rel)) as f:
            return json.load(f)

    cfg = load("benchmark/configs/kv3x4096.json")
    cfg.update(name="kv3x512", regions=512, record_count=8192,
               election_timeout_ms=1000,
               source=cfg["source"].replace("4096 regions", "512 regions"))
    cfg["engine"]["max_groups"] = 1024
    mix = load("benchmark/traffic/ycsb_a.json")
    mix.update(name="write64", read_share=0.0, update_share=1.0,
               loop={"kind": "closed", "clients": 64}, warm_seconds=0.5)
    bm = load("BENCHMARK.json")
    bm["configs"].append({"name": "kv3x512", "source": cfg["source"],
                          "file": "benchmark/configs/kv3x512.json",
                          "reduced": cfg["reduced"], "why": "test size"})
    bm["workloads"].append({"name": CELL, "config": "kv3x512",
                            "traffic": "write64", "chips": 1,
                            "why": "test size"})
    for rel, data in (("benchmark/configs/kv3x512.json", cfg),
                      ("benchmark/traffic/write64.json", mix),
                      ("BENCHMARK.json", bm)):
        with open(os.path.join(tmp, rel), "w") as f:
            json.dump(data, f)
    return check_manifest.check(tmp)


async def test_leaders_stay_where_they_were_elected_under_the_write_load(
        tmp_path):
    bm = _manifest_with_the_dense_test_cell(str(tmp_path))
    result = await asyncio.wait_for(
        run_cell(bm, CELL, 2 ** 31 + 29, 4.0, False, str(tmp_path / "work"),
                 CPU, time.perf_counter()), TIME_LIMIT_S)
    summary, counters = result["_summary"], result["_summary"]["counters"]
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 200
    assert result["checks"]["ops_failed"]["value"] == 0
    for name in ("reads_wrong", "reads_stale", "final_wrong", "updates_lost"):
        assert summary["check"][name] == 0
    # the floor was in force: the beats of the window came at its cadence
    # (512 leaders x 2 followers every 204 ms), not at the 100 ms asked for
    rows = sum(counters[f"engine{i}.beat_rows.count"] for i in range(3))
    assert 0 < rows <= 1024 * (summary["window_s"] / 0.204 + 2)
    at_load = summary["leaders_per_store"]
    assert sum(at_load) == 512
    assert [counters[f"engine{i}.leaders_now"] for i in range(3)] == at_load
    for i in range(3):
        assert counters[f"engine{i}.elections_started.count"] == 0
        assert counters[f"engine{i}.leader_stepdowns.count"] == 0
    assert counters["client.batch_retries"] == 0


async def test_a_booted_stores_objects_are_out_of_the_collectors_reach(
        monkeypatch):
    """What a full collection traverses grows with the replicas a process
    hosts (0.6 s every 3 s at 12,288 on the chip host), so a store freezes
    what its boot built, batch by batch, then what its elections built.
    The collector is the process's: the stores that serve are counted, and
    the heap stays frozen, and full collections braked, until the last of
    them has shut down."""
    from tests.kv_cluster import KVTestCluster
    from tpuraft.rheakv import store_engine as se

    monkeypatch.setattr(se, "_gc_stores", 0)    # whatever ran before
    monkeypatch.setattr(se, "_brake_full_collections",
                        lambda: gc.set_threshold(700, 10, 77))
    monkeypatch.setattr(se, "_release_full_collections",
                        lambda: gc.set_threshold(*before))
    before = gc.get_threshold()
    gc.unfreeze()
    c = KVTestCluster(n_stores=3)
    await c.start_all()
    try:
        assert se._gc_stores == 3
        booted = gc.get_freeze_count()
        assert booted > 1000                    # nodes, logs, machines
        leader = await c.wait_region_leader(1)
        # and once more when every region knows its leader: what the
        # elections built (replicators, their tasks) is as long-lived
        for _ in range(100):
            if gc.get_freeze_count() > booted:
                break
            await asyncio.sleep(0.1)
        assert gc.get_freeze_count() > booted
        assert await leader.raft_store.put(b"k", b"v")
        held = gc.get_freeze_count()
        gc.collect()                        # a full pass leaves them alone
        # (what the second freeze caught in flight dies by reference
        # count meanwhile: a message, a finished task)
        assert booted < gc.get_freeze_count() <= held
        # one store stops: the two that serve keep the freeze and the brake
        await c.stop_store(c.endpoints[0])
        assert se._gc_stores == 2
        assert gc.get_freeze_count() > 1000
        assert gc.get_threshold() == (700, 10, 77)
    finally:
        await c.stop_all()
    assert se._gc_stores == 0
    assert gc.get_freeze_count() == 0
    assert gc.get_threshold() == before


def test_full_collections_wait_for_a_quarter_of_the_frozen_heap(monkeypatch):
    """The freeze hides the frozen heap from CPython's own brake on full
    collections, so the store puts the quarter back, counted over what is
    frozen: silent at a few regions, 12 at 1,024 regions a store (358,552
    frozen), 52 at 4,096 (1,466,588), and given back at shutdown."""
    from tpuraft.rheakv import store_engine as se

    before = gc.get_threshold()
    young, middle, _ = before
    try:
        monkeypatch.setattr(se.gc, "get_freeze_count", lambda: 20_000)
        se._brake_full_collections()
        assert gc.get_threshold() == before
        monkeypatch.setattr(se.gc, "get_freeze_count", lambda: 1_466_588)
        se._brake_full_collections()
        oldest = 1_466_588 // (4 * young * middle)
        assert gc.get_threshold() == (young, middle, oldest)
        monkeypatch.setattr(se.gc, "get_freeze_count", lambda: 358_552)
        se._brake_full_collections()        # a smaller store never lowers it
        assert gc.get_threshold() == (young, middle, oldest)
    finally:
        se._release_full_collections()
    assert gc.get_threshold() == before
