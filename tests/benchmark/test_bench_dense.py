"""The dense store, ``kv3x4096`` and its one cell ``kv3x4096.ycsb_a``: data
files and manifest entries only, the same deployment as ``kv3x1024`` at four
times the regions, and the program's compiled tick at the configuration's own
``[8192, 4]`` against the plain reference."""

import json
import os

import numpy as np
from bench_helpers import REPO
from test_bench_reference import _random_state

from benchmark import check_manifest, peaks
from benchmark.reference import TICK_OUTPUTS, tick_mismatches, tick_reference

CELL = "kv3x4096.ycsb_a"
# what the table of ISSUE 29 lets differ from kv3x1024.json
MAY_DIFFER = {"name", "source", "why", "regions", "engine", "record_count",
              "assumed", "reduced", "reduced_why"}


def _config(name: str) -> dict:
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_the_manifest_passes_and_the_cell_resolves(manifest_root):
    bm = check_manifest.check(manifest_root)
    cell, cfg, mix = check_manifest.cell(bm, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kv3x4096", "ycsb_a", 1)
    assert len(cell["why"]) <= 200
    # appended: what was there stays first and in order
    assert [w["name"] for w in bm["workloads"]][:2] == [
        "kv3x1024.ycsb_a", "kv3x1024.ycsb_b"]
    assert [c["name"] for c in bm["configs"]][:2] == ["kv3x1024", "kv3x4096"]
    entry = bm["configs"][1]
    assert entry["reduced"] == ["regions", "record_count"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert cfg == _config("kv3x4096")
    # the traffic is the file kv3x1024.ycsb_a runs, not a copy
    assert mix is not None and mix["name"] == "ycsb_a"
    assert (mix["read_share"], mix["update_share"], mix["zipfian_constant"],
            mix["scrambled"], mix["loop"]) == (
        0.5, 0.5, 0.99, True, {"kind": "closed", "clients": 256})
    # none of the 29 per-layer entries that were there lists its cells: the
    # cell reports every one of them (and whatever a later PR appended for
    # every cell), and all four end-to-end metrics
    layer = check_manifest.metrics_of(bm, CELL, "per_layer")
    assert [m["name"] for m in layer[:29]] == [
        m["name"] for m in bm["per_layer"][:29]]
    assert not any("workloads" in m for m in bm["per_layer"][:29])
    assert layer == [m for m in bm["per_layer"]
                     if CELL in m.get("workloads", [CELL])]
    assert {m["name"] for m in check_manifest.metrics_of(
        bm, CELL, "end_to_end")} == {"ops_per_s", "read_p95_ms",
                                     "update_p95_ms", "setup_s"}


def test_the_configuration_is_kv3x1024_at_four_times_the_regions():
    small, dense = _config("kv3x1024"), _config("kv3x4096")
    assert set(small) == set(dense)
    for key in set(small) - MAY_DIFFER:
        assert dense[key] == small[key], key
    assert dense["guarantees"] == small["guarantees"]
    assert dense["election_timeout_ms"] == small["election_timeout_ms"] == 10000
    assert (dense["regions"], dense["record_count"]) == (4096, 65536)
    assert dense["record_count"] // dense["regions"] == 16 \
        == small["record_count"] // small["regions"]
    # capacity twice the regions, as 2,048 is to 1,024; nothing else moved
    assert dense["engine"] == dict(small["engine"], max_groups=8192)
    assert dense["engine"]["max_groups"] == 2 * dense["regions"]
    assert dense["reduced"] == ["regions", "record_count"]
    assert set(dense["reduced_why"]) == set(dense["reduced"])
    # what the engine makes of the 10,000 ms asked for is stated
    assert set(small["assumed"]) < set(dense["assumed"])
    assert "16,384" in dense["assumed"]["effective_timeouts"]
    assert "quiesce_after_rounds 0" in dense["assumed"]["quiescence"]


def test_the_compiled_tick_at_the_configurations_shape_equals_the_reference():
    """Seeded random rows at ``[8192, 4]`` through the program's jitted
    packed tick (one array up, one down: the call the engine makes), on the
    CPU: 0 rows differ over the eleven outputs."""
    from tpuraft.ops.tick import (GroupState, TickParams, pack_state,
                                  packed_state_shape, raft_tick_packed_jit,
                                  unpack_outputs)

    eng = _config("kv3x4096")["engine"]
    g, p = eng["max_groups"], eng["max_peers"]
    assert (g, p) == (8192, 4)
    rng = np.random.default_rng(2 ** 31 + 29)
    s = _random_state(rng, g, p)
    # the timeouts the density floor puts in force at 4,096 controls
    params = {"election_timeout_ms": np.full(g, 16384),
              "heartbeat_ms": np.full(g, 1638),
              "lease_ms": np.full(g, 14745),
              "snapshot_ms": np.where(rng.random(g) < 0.5, 0, 1000)}
    # acks up to 2,000 ms old against a 16 s timeout would leave the
    # step_down and lease lanes empty: age a third of the rows past it
    old = rng.random(g) < 0.33
    s["last_ack"] = np.where(old[:, None] & (s["last_ack"] > -2 ** 31 + 1),
                             s["last_ack"] - 30000, s["last_ack"]
                             ).astype(np.int32)
    now = 2500
    buf = pack_state(GroupState(**s), now,
                     np.empty(packed_state_shape(g, p), np.int32))
    assert buf.shape == (3 * p + 10, g)
    out = raft_tick_packed_jit(buf, TickParams.make(
        params["election_timeout_ms"], params["heartbeat_ms"],
        params["lease_ms"], params["snapshot_ms"]))
    got = unpack_outputs(np.asarray(out))
    want = tick_reference(s, now, params)
    assert set(got) == set(TICK_OUTPUTS)
    assert tick_mismatches(got, want) == 0
    for name in ("commit_advanced", "elected", "fence_ok", "step_down",
                 "lease_valid", "hb_due", "stepdown_due", "election_due"):
        assert want[name].any() and not want[name].all(), name
    # the roofline's bytes at this shape: 114 B a group at P = 4
    assert peaks.raft_tick_min_bytes(g, p) == 114 * g
