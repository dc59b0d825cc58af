"""Shared by the benchmark's tests: a temporary copy of the benchmark with one
more configuration, traffic mix, per-layer metric and cell, added as files and
manifest entries only."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY_CELL = "kv3x8.ycsb_a16"


def extended_copy(tmp: str) -> str:
    """Copy ``BENCHMARK.json`` and ``benchmark/`` into ``tmp`` and add, without
    touching a file that is there: configuration ``kv3x8`` (8 regions, 256
    records), mix ``ycsb_a16`` (16 clients), the metric ``srv_propose_ms``
    (a span no cell reads yet) and the cell ``kv3x8.ycsb_a16``."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.makedirs(os.path.join(tmp, "tests", "benchmark"))
    with open(os.path.join(tmp, "benchmark/configs/kv3x64.json")) as f:
        cfg = json.load(f)
    cfg.update(name="kv3x8", regions=8, record_count=256,
               election_timeout_ms=1000,
               source=cfg["source"].replace("64 regions", "8 regions"))
    cfg["engine"]["max_groups"] = 16
    with open(os.path.join(tmp, "benchmark/traffic/ycsb_a.json")) as f:
        mix = json.load(f)
    mix.update(name="ycsb_a16", loop={"kind": "closed", "clients": 16},
               warm_seconds=0.3)
    metric = {"name": "srv_propose_ms", "unit": "ms", "better": "lower",
              "source": "program_span", "layer": "KV serving",
              "moves": "update_p95_ms"}
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "kv3x8", "source": cfg["source"],
                          "file": "benchmark/configs/kv3x8.json",
                          "reduced": ["record_count"], "why": "test size"})
    bm["workloads"].append({"name": TINY_CELL, "config": "kv3x8",
                            "traffic": "ycsb_a16", "chips": 1,
                            "why": "test size"})
    bm["per_layer"].append(metric)
    for rel, data in (
            ("benchmark/configs/kv3x8.json", cfg),
            ("benchmark/traffic/ycsb_a16.json", mix),
            ("benchmark/layer_metrics/srv_propose_ms.json",
             dict(metric, reader={"kind": "span", "span": "srv_propose",
                                  "stat": "median", "scale": 1000.0})),
            ("BENCHMARK.json", bm)):
        with open(os.path.join(tmp, rel), "w") as f:
            json.dump(data, f)
    return tmp
