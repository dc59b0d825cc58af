"""Shared by the benchmark's tests: a temporary copy of the benchmark with one
more configuration, two traffic mixes, two per-layer metrics and two cells,
added as files and manifest entries only."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY_CELL = "kv3x8.ycsb_a16"
OPEN_CELL = "kv3x8.ycsb_a_open300"


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


def extended_copy(tmp: str) -> str:
    """Copy ``BENCHMARK.json`` and ``benchmark/`` into ``tmp`` and add, without
    touching a file that is there: configuration ``kv3x8`` (8 regions, 256
    records), mix ``ycsb_a16`` (16 clients), the metric ``srv_propose_ms``
    (a span no cell reads yet) and the cell ``kv3x8.ycsb_a16``; and for the
    open loop the mix ``ycsb_a_open300`` (300 operations a second), its cell
    ``kv3x8.ycsb_a_open300`` and that cell's ``arrival_late_ms.kv3x8``."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.makedirs(os.path.join(tmp, "tests", "benchmark"))
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
