"""Shared by the benchmark's tests: temporary copies of the benchmark grown by
files and manifest entries only.  ``extended_copy`` adds the tiny cells that
the tests run on the CPU; ``grown_copy`` adds a deployment of each kind a
later PR may bring, so that a test of the committed manifest, run on it as
well (the ``manifest_root`` fixture of ``conftest.py``), fails in the PR that
writes it if it pins the manifest's whole list of cells, configurations or a
cell's per-layer metrics."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY_CELL = "kv3x8.ycsb_a16"
OPEN_CELL = "kv3x8.ycsb_a_open300"
# The per-layer metrics of the committed manifest with no ``workloads`` list,
# which every cell reports.  A test holds a cell to at least these and its
# own, by name: a metric appended later fails no test, one dropped fails.
EVERY_CELL = (
    "client_items_per_rpc", "reads_per_confirm_round", "device_fence_pct",
    "quorum_commit_ms", "tick_host_ms", "tick_dispatch_ms", "raft_tick_us",
    "raft_tick_roofline", "device_idle_pct", "log_flush_ms", "fsm_apply_ms",
    "update_p99_ms.client", "tick_state_ms", "tick_call_ms", "tick_fetch_ms",
    "tick_heartbeat_ms", "tick_late_ms", "fence_resolve_ms", "read_fence_ms",
    "log_fsync_ms", "log_wake_ms", "loop_cpu_pct", "loop_pct.client",
    "loop_pct.kv", "loop_pct.raft", "loop_pct.log", "loop_pct.fsm",
    "loop_pct.tick", "loop_pct.rpc", "kv_wal_entries_per_fsync",
    "kv_log_groups_per_fsync")
# what a traced run off the chip cannot read: there is no device trace
FROM_THE_DEVICE = ("raft_tick_us", "raft_tick_roofline", "device_idle_pct")


def stands_together(names: list, run) -> bool:
    """``run`` is in ``names`` as one stretch, in its own order: entries that
    were appended together, wherever later ones were appended after them."""
    run = list(run)
    return run[0] in names and \
        names[names.index(run[0]):][:len(run)] == run


def add_files(root: str, files: dict, **entries) -> None:
    """Write ``files`` (relative path -> JSON data or source text) under
    ``root``, none of which may be there, and append ``entries`` (manifest
    key -> list) to its ``BENCHMARK.json``."""
    for rel, data in files.items():
        path = os.path.join(root, rel)
        assert not os.path.exists(path), f"{rel} is there already"
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(data if isinstance(data, str) else json.dumps(data))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    for key, more in entries.items():
        bm[key].extend(more)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)


def _copy(tmp: str) -> None:
    """``BENCHMARK.json`` and ``benchmark/`` as committed, into ``tmp``."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.makedirs(os.path.join(tmp, "tests", "benchmark"))


def extended_copy(tmp: str) -> str:
    """Copy ``BENCHMARK.json`` and ``benchmark/`` into ``tmp`` and add, without
    touching a file that is there: configuration ``kv3x8`` (8 regions, 256
    records), mix ``ycsb_a16`` (16 clients), the metric ``srv_propose_ms``
    (a span no cell reads yet) and the cell ``kv3x8.ycsb_a16``; and for the
    open loop the mix ``ycsb_a_open300`` (300 operations a second), its cell
    ``kv3x8.ycsb_a_open300`` and that cell's ``arrival_late_ms.kv3x8``."""
    _copy(tmp)
    with open(os.path.join(tmp, "benchmark/configs/kv3x64.json")) as f:
        cfg = json.load(f)
    cfg.update(name="kv3x8", regions=8, record_count=256,
               election_timeout_ms=3000,
               source=cfg["source"].replace("64 regions", "8 regions"))
    cfg["engine"]["max_groups"] = 16
    with open(os.path.join(tmp, "benchmark/traffic/ycsb_a.json")) as f:
        mix = json.load(f)
    mix.update(name="ycsb_a16", loop={"kind": "closed", "clients": 16},
               warm_seconds=0.3)
    open_mix = dict(mix, name="ycsb_a_open300",
                    loop={"kind": "open", "rate": 300})
    metric = {"name": "srv_propose_ms", "unit": "ms", "better": "lower",
              "source": "program_span", "layer": "KV serving",
              "moves": "update_p95_ms"}
    with open(os.path.join(
            tmp, "benchmark/layer_metrics/arrival_late_ms.json")) as f:
        late = json.load(f)
    late.update(name="arrival_late_ms.kv3x8", workloads=[OPEN_CELL])
    add_files(
        tmp,
        {"benchmark/configs/kv3x8.json": cfg,
         "benchmark/traffic/ycsb_a16.json": mix,
         "benchmark/traffic/ycsb_a_open300.json": open_mix,
         "benchmark/layer_metrics/srv_propose_ms.json":
             dict(metric, reader={"kind": "span", "span": "srv_propose",
                                  "stat": "median", "scale": 1000.0}),
         "benchmark/layer_metrics/arrival_late_ms.kv3x8.json": late},
        configs=[{"name": "kv3x8", "source": cfg["source"],
                  "file": "benchmark/configs/kv3x8.json",
                  "reduced": ["record_count"], "why": "test size"}],
        workloads=[{"name": TINY_CELL, "config": "kv3x8",
                    "traffic": "ycsb_a16", "chips": 1, "why": "test size"},
                   {"name": OPEN_CELL, "config": "kv3x8",
                    "traffic": "ycsb_a_open300", "chips": 1,
                    "why": "test size"}],
        per_layer=[metric, {k: v for k, v in late.items() if k != "reader"}])
    return tmp


# What the grown copy adds.  Its names are the tests' own, so that no cell,
# mix or metric a later PR appends to the committed manifest meets one here.
PARTITION_FAULTS = [
    {"kind": "partition_store", "store": "most_leaders", "at": 0.2},
    {"kind": "heal_partition", "at": 0.65}]
GROWN_CONFIGS = ("grown-partition", "grown-mesh4")
GROWN_CELLS = ("grown-partition.grown_isolate1", "kv3x4096.grown_b",
               "grown-mesh4.grown_b")
GROWN_METRICS = ("grown_isolated_s", "grown_every_cell_ms")

GROWN_LOOP = '''"""A stub loop: a fault schedule that ``open_faults.py``
does not list, said to be run by a module of its own.  No cell of the copy is
run."""

IMPLEMENTS = {"faults": [%r]}


async def run_window(client, keys, stream, values, mix, seconds,
                     on_window_start=None, on_window_end=None):
    raise NotImplementedError("the grown copy's stub loop sends nothing")
''' % PARTITION_FAULTS


def grown_copy(tmp: str) -> str:
    """Copy ``BENCHMARK.json`` and ``benchmark/`` into ``tmp`` and add, as
    files and appended manifest entries only, what later PRs add: a partition
    deployment (configuration ``grown-partition`` on the failover cluster, a
    mix whose loop is the new module ``loops/grown_partition.py`` with a fault
    kind ``open_faults.py`` does not have, its cell and a per-layer metric
    that lists only that cell); the read-mostly mix at density on a
    configuration that is there (``kv3x4096.grown_b``); a four-chip
    configuration and its cell; and a per-layer metric with no ``workloads``
    list, which every cell then reports."""
    _copy(tmp)

    def load(rel: str) -> dict:
        with open(os.path.join(tmp, rel)) as f:
            return json.load(f)

    part = load("benchmark/configs/kv3x1024-failover.json")
    part.update(name=GROWN_CONFIGS[0],
                source="YCSB core workloada (Cooper et al., SoCC 2010), 1KB "
                       "records, zipfian; fault schedule = Jepsen nemesis "
                       "partition-random-node: one node of 3 cut off under "
                       "load, then healed")
    mesh = load("benchmark/configs/kv3x4096.json")
    mesh.update(name=GROWN_CONFIGS[1], chips=4)
    isolate = load("benchmark/traffic/ycsb_a_kill1.json")
    isolate.update(name="grown_isolate1", faults=PARTITION_FAULTS,
                   loop={"kind": "grown_partition", "rate": 720})
    mostly_reads = dict(load("benchmark/traffic/ycsb_b.json"), name="grown_b")
    one_cell = {"name": GROWN_METRICS[0], "unit": "s", "better": "lower",
                "source": "host_clock", "layer": "consensus host",
                "moves": "update_p95_ms", "workloads": [GROWN_CELLS[0]]}
    every_cell = {"name": GROWN_METRICS[1], "unit": "ms", "better": "lower",
                  "source": "program_span", "layer": "KV serving",
                  "moves": "read_p95_ms"}
    add_files(
        tmp,
        {"benchmark/configs/grown-partition.json": part,
         "benchmark/configs/grown-mesh4.json": mesh,
         "benchmark/traffic/grown_isolate1.json": isolate,
         "benchmark/traffic/grown_b.json": mostly_reads,
         "benchmark/loops/grown_partition.py": GROWN_LOOP,
         "benchmark/layer_metrics/grown_isolated_s.json": dict(
             one_cell, reader={"kind": "counter_ratio",
                               "numerator": "loop.isolated_ms",
                               "denominator": "loop.partitions",
                               "scale": 0.001}),
         "benchmark/layer_metrics/grown_every_cell_ms.json": dict(
             every_cell, reader={"kind": "span", "span": "srv_read",
                                 "stat": "median", "scale": 1000.0})},
        configs=[{"name": c["name"], "source": c["source"],
                  "file": f"benchmark/configs/{c['name']}.json",
                  "reduced": c["reduced"], "why": "the tests' grown copy"}
                 for c in (part, mesh)],
        workloads=[{"name": name, "config": name.split(".")[0],
                    "traffic": name.split(".")[1], "chips": chips,
                    "why": "the tests' grown copy"}
                   for name, chips in zip(GROWN_CELLS, (1, 1, 4))],
        per_layer=[one_cell, every_cell])
    return tmp
