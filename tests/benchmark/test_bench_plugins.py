"""A deployment arrives as added files: a cluster module, a loop module and a
per-layer metric go into a temporary copy, no file that is there is touched,
and the tiny cell runs through them.  A value no module says it runs is
refused by name."""

import asyncio
import hashlib
import json
import os
import time

import pytest
from bench_helpers import add_files, extended_copy

from benchmark import check_manifest, cluster, plugins
from benchmark.cluster import NotImplementedConfig
from benchmark.driver import run_cell

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CELL = "kv3x8slow.ycsb_a_paced"

SLOW_TICK = '''"""The deployment at another tick cadence, from its ``options``."""
from benchmark import cluster as base

IMPLEMENTS = dict(base.IMPLEMENTS)


class Cluster(base.Cluster):
    def tick_options(self, i):
        opts = super().tick_options(i)
        opts.tick_interval_ms = self.options["tick_interval_ms"]
        self.timings["tick_interval_ms"] = float(opts.tick_interval_ms)
        return opts
'''

PACED = '''"""One caller that thinks for ``gap_ms`` between its operations."""
import asyncio
import time

from benchmark.loops import Window
from benchmark.traffic import READ

IMPLEMENTS = {"faults": [[]]}


async def run_window(client, keys, stream, values, mix, seconds,
                     on_window_start=None, on_window_end=None):
    win, pc, sent = Window(), time.perf_counter, [0]

    async def send(until):
        while pc() < until:
            i = sent[0]
            sent[0] += 1
            kind, rec = int(stream.kinds[i]), int(stream.records[i])
            t0 = pc()
            if kind == READ:
                got, ok = values.parse(await client.get(keys[rec])), True
            else:
                got = (7, i, rec)
                ok = await client.put(keys[rec], values.make(7, i, rec))
            win.ops.append((kind, rec, t0, pc(), ok is True, got))
            await asyncio.sleep(mix["loop"]["gap_ms"] / 1e3)

    await send(pc() + mix["warm_seconds"])
    on_window_start()
    win.start = pc()
    await send(win.start + seconds)
    win.end = pc()
    on_window_end()
    in_window = sum(1 for o in win.ops if o[2] >= win.start)
    win.counters = {"loop.paced_ops": in_window, "loop.paced_s": seconds}
    win.notes = {"paced": in_window}
    return win
'''


def _digests(root: str) -> dict:
    out = {}
    for dirpath, _, files in os.walk(root):
        for fn in files:
            path = os.path.join(dirpath, fn)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def _add_the_deployment(root: str) -> None:
    with open(os.path.join(root, "benchmark/configs/kv3x8.json")) as f:
        cfg = json.load(f)
    cfg.update(name="kv3x8slow", cluster="slow_tick",
               options={"tick_interval_ms": 35},
               source=cfg["source"].replace("SOFAJRaft RheaKV", "RheaKV, tick 35 ms,"))
    with open(os.path.join(root, "benchmark/traffic/ycsb_a16.json")) as f:
        mix = json.load(f)
    mix.update(name="ycsb_a_paced", loop={"kind": "paced", "gap_ms": 2})
    metric = {"name": "paced_ops_per_s", "unit": "ops/s", "better": "higher",
              "source": "host_clock", "layer": "client", "moves": "ops_per_s",
              "workloads": [CELL]}
    add_files(
        root,
        {"benchmark/clusters/slow_tick.py": SLOW_TICK,
         "benchmark/loops/paced.py": PACED,
         "benchmark/configs/kv3x8slow.json": cfg,
         "benchmark/traffic/ycsb_a_paced.json": mix,
         "benchmark/layer_metrics/paced_ops_per_s.json": dict(
             metric, reader={"kind": "counter_ratio",
                             "numerator": "loop.paced_ops",
                             "denominator": "loop.paced_s"})},
        configs=[{"name": "kv3x8slow", "source": cfg["source"],
                  "file": "benchmark/configs/kv3x8slow.json",
                  "reduced": ["record_count"], "why": "test size"}],
        workloads=[{"name": CELL, "config": "kv3x8slow",
                    "traffic": "ycsb_a_paced", "chips": 1,
                    "why": "test size"}],
        per_layer=[metric])


def test_a_cluster_a_loop_and_a_metric_are_added_as_files_and_the_cell_runs(
        tmp_path):
    root = extended_copy(str(tmp_path))
    before = _digests(root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm_before = json.load(f)
    _add_the_deployment(root)
    after = _digests(root)
    # nothing that was there changed but the manifest, which only grew
    assert {k: v for k, v in after.items() if k in before
            and k != "BENCHMARK.json"} == {
        k: v for k, v in before.items() if k != "BENCHMARK.json"}
    assert set(after) - set(before) == {
        "benchmark/clusters/slow_tick.py", "benchmark/loops/paced.py",
        "benchmark/configs/kv3x8slow.json",
        "benchmark/traffic/ycsb_a_paced.json",
        "benchmark/layer_metrics/paced_ops_per_s.json"}
    bm = check_manifest.check(root)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        n = len(bm_before[key])
        assert [e["name"] for e in bm[key]][:n] == [
            e["name"] for e in bm_before[key]]
    _, cfg, mix = check_manifest.cell(bm, CELL)
    made = plugins.cluster_of(bm, cfg)
    assert issubclass(made, cluster.Cluster) and made is not cluster.Cluster
    assert plugins.loop_of(bm, mix).__file__ == os.path.join(
        root, "benchmark/loops/paced.py")

    result = asyncio.run(run_cell(
        bm, CELL, 2 ** 31 + 41, 1.2, True, str(tmp_path / "work"), CPU,
        time.perf_counter()))
    assert result["correct"] is True and result["failed"] == 0
    summary = result["_summary"]
    assert summary["timings"]["tick_interval_ms"] == 35.0    # the subclass
    assert summary["loop"]["paced"] == result["attempted"] > 20
    got = result["metrics"]
    assert got["paced_ops_per_s"]["value"] == pytest.approx(
        result["attempted"] / 1.2)
    # the metrics every cell reports are there beside it, the new two too
    # (counters and histograms: whether a span is sampled in a window this
    # short is chance)
    assert {"client_items_per_rpc", "tick_host_ms", "tick_dispatch_ms",
            "kv_wal_entries_per_fsync", "kv_log_groups_per_fsync"} <= set(got)
    assert "arrival_late_ms" not in got


@pytest.mark.parametrize("field, value", [
    ("stores", 5), ("replicas", 5), ("read_mode", "lease"),
    ("transport", "tcp"), ("log_scheme", "file"), ("kv_store", "memory"),
    ("engine.backend", "numpy"), ("engine.mesh_devices", 4)])
def test_a_value_the_named_module_does_not_run_is_refused_by_name(
        manifest_root, field, value):
    bm = check_manifest.check(manifest_root)
    _, cfg, _ = check_manifest.cell(bm, "kv3x1024.ycsb_a")
    assert plugins.cluster_of(bm, cfg) is cluster.Cluster
    cfg = json.loads(json.dumps(cfg))
    part = cfg
    *path, last = field.split(".")
    for p in path:
        part = part[p]
    part[last] = value
    with pytest.raises(NotImplementedConfig,
                       match=f"{field}={value!r} is not implemented"):
        plugins.cluster_of(bm, cfg)


def test_a_module_that_says_it_runs_a_value_is_handed_it(tmp_path):
    root = extended_copy(str(tmp_path))
    add_files(root, {"benchmark/clusters/five.py":
                     "from benchmark import cluster as base\n"
                     "IMPLEMENTS = dict(base.IMPLEMENTS, stores=[3, 5])\n"
                     "Cluster = base.Cluster\n",
                     "benchmark/loops/kill.py":
                     "IMPLEMENTS = {'faults': 'any'}\n"})
    bm = check_manifest.check(root)
    _, cfg, mix = check_manifest.cell(bm, "kv3x8.ycsb_a16")
    five = dict(cfg, stores=5, cluster="five")
    assert plugins.cluster_of(bm, five) is cluster.Cluster
    with pytest.raises(NotImplementedConfig, match="replicas=5"):
        plugins.cluster_of(bm, dict(five, replicas=5))
    with pytest.raises(NotImplementedConfig, match="stores=5"):
        plugins.cluster_of(bm, dict(cfg, stores=5))     # today's class
    faults = [{"kill_store": 1, "at": 0.33}]
    kill = dict(mix, loop={"kind": "kill"}, faults=faults)
    assert plugins.loop_of(bm, kill).IMPLEMENTS["faults"] == "any"
    # the configuration's options reach the class unread
    made = cluster.Cluster(dict(cfg, options={"anything": [1, 2]}), "/nowhere")
    assert made.options == {"anything": [1, 2]}
    assert cluster.Cluster(cfg, "/nowhere").options == {}
