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

import pytest

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
    the heap stays frozen and young passes at the serving threshold until
    the last of them has shut down."""
    from tests.kv_cluster import KVTestCluster
    from tpuraft.rheakv import store_engine as se

    monkeypatch.setattr(se, "_gc_stores", 0)    # whatever ran before
    monkeypatch.setattr(se, "_gc_thresholds_before", None)
    before = gc.get_threshold()
    serving = (max(before[0], se._SERVING_YOUNG_THRESHOLD), *before[1:])
    gc.unfreeze()
    c = KVTestCluster(n_stores=3)
    await c.start_all()
    try:
        assert se._gc_stores == 3
        assert gc.get_threshold() == serving
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
        # one store stops: the two that serve keep the freeze and the
        # serving threshold
        await c.stop_store(c.endpoints[0])
        assert se._gc_stores == 2
        assert gc.get_freeze_count() > 1000
        assert gc.get_threshold() == serving
    finally:
        await c.stop_all()
    assert se._gc_stores == 0
    assert gc.get_freeze_count() == 0
    assert gc.get_threshold() == before


def _thresholds_found(monkeypatch, found: tuple):
    """A copy of the collector's state as a process would have it before
    its first store: no store counted, ``found`` in force; gives the
    process's own thresholds back at the test's end."""
    from tpuraft.rheakv import store_engine as se

    monkeypatch.setattr(se, "_gc_stores", 0)
    monkeypatch.setattr(se, "_gc_thresholds_before", None)
    own = gc.get_threshold()
    gc.set_threshold(*found)
    return se, own


@pytest.mark.parametrize("found", [(700, 10, 10), (2_000, 10, 10),
                                   (700, 10, 100)])
def test_the_first_store_up_sets_the_serving_young_threshold(monkeypatch,
                                                              found):
    """An operation's objects live one batch round trip: while any store
    serves, a young pass waits for the serving threshold's allocations
    (the middle and oldest thresholds are the application's), and the
    last store down gives back exactly what the first one found."""
    se, own = _thresholds_found(monkeypatch, found)
    try:
        se._gc_store_up()
        serving = (se._SERVING_YOUNG_THRESHOLD, *found[1:])
        assert gc.get_threshold() == serving
        se._gc_store_up()                   # a second store changes nothing
        assert gc.get_threshold() == serving
        se._gc_store_down()                 # ... nor does its leaving
        assert gc.get_threshold() == serving
        se._gc_store_down()
        assert gc.get_threshold() == found
        assert se._gc_thresholds_before is None
    finally:
        gc.set_threshold(*own)


@pytest.mark.parametrize("found", [(1_000_000, 10, 10), (0, 10, 10)])
def test_a_threshold_the_application_set_is_never_lowered(monkeypatch, found):
    """An embedding application that raised the young threshold above the
    serving one keeps its own; one that switched the collector off
    (threshold 0) keeps it off."""
    se, own = _thresholds_found(monkeypatch, found)
    try:
        se._gc_store_up()
        assert gc.get_threshold() == found
        se._gc_store_down()
        assert gc.get_threshold() == found
    finally:
        gc.set_threshold(*own)


@pytest.mark.parametrize("leave", ["shutdown", "crash"])
async def test_the_last_store_down_restores_the_thresholds_it_found(
        monkeypatch, leave):
    """``shutdown()`` and ``crash()`` both count a store out, and the last
    one out restores the exact triple the first one found."""
    from tests.kv_cluster import KVTestCluster

    found = (1_234, 7, 9)
    se, own = _thresholds_found(monkeypatch, found)
    try:
        c = KVTestCluster(n_stores=3)
        await c.start_all()
        try:
            await c.wait_region_leader(1)
            serving = (se._SERVING_YOUNG_THRESHOLD, 7, 9)
            assert gc.get_threshold() == serving
            for ep in list(c.endpoints):
                if leave == "crash":
                    c.net.stop_endpoint(ep)
                    c.net.unbind(ep)
                    c.stores.pop(ep).crash()
                else:
                    await c.stop_store(ep)
                if c.stores:
                    assert gc.get_threshold() == serving
        finally:
            await c.stop_all()
        assert se._gc_stores == 0
        assert gc.get_threshold() == found
        assert gc.get_freeze_count() == 0
    finally:
        gc.set_threshold(*own)


def test_a_requests_objects_die_before_a_young_pass_sees_them(monkeypatch):
    """What the serving threshold is for: objects that live as long as a
    batch of requests in flight (here 1,000 at a time) are freed by
    reference count before a young pass ever examines them, where
    CPython's 700 examines nearly every batch."""
    def young_passes_over_batches() -> int:
        gc.collect()                        # the young count from 0
        first = gc.get_stats()[0]["collections"]
        for _ in range(200):
            batch = [[] for _ in range(1_000)]
            del batch
        return gc.get_stats()[0]["collections"] - first

    se, own = _thresholds_found(monkeypatch, (700, 10, 10))
    try:
        assert young_passes_over_batches() >= 100
        se._gc_store_up()
        assert young_passes_over_batches() == 0
        se._gc_store_down()
        assert young_passes_over_batches() >= 100
    finally:
        gc.set_threshold(*own)
