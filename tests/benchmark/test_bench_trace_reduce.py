"""The reduction from a profiler trace to busy time, kernel time and gaps."""

import json
import os

import pytest

from benchmark import layers, trace_reduce
from benchmark.trace_reduce import (MODULES_LINE, OPS_LINE, WINDOW_EVENT,
                                    reduce_planes, union_seconds)

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_counts_overlap_once():
    seconds, merged = union_seconds([(0, 4e9), (2e9, 5e9), (7e9, 8e9),
                                     (7.5e9, 7.6e9)])
    assert seconds == 6.0 and merged == [[0, 5e9], [7e9, 8e9]]


def _planes():
    ms = 1_000_000
    return [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            [WINDOW_EVENT, 10 * ms, 100 * ms],
            ["tpuraft.raft_tick", 18 * ms, 6 * ms],
            ["tpuraft.raft_tick", 58 * ms, 4 * ms]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": MODULES_LINE, "events": [
                ["jit_raft_tick_outputs(1)", 5 * ms, 2 * ms],   # before
                ["jit_raft_tick_outputs(1)", 20 * ms, 3 * ms],
                ["jit_raft_tick_outputs(1)", 60 * ms, 1 * ms],
                ["jit_other(2)", 80 * ms, 10 * ms]]},
            {"name": OPS_LINE, "events": [
                ["fusion.1", 5 * ms, 2 * ms],
                ["fusion.1", 20 * ms, 2 * ms], ["sort.2", 21 * ms, 2 * ms],
                ["fusion.1", 60 * ms, 1 * ms],
                ["copy.3", 80 * ms, 10 * ms],
                ["fusion.1", 105 * ms, 10 * ms]]}]},  # half past the close
    ]


def test_busy_kernel_time_and_gaps_on_a_made_up_trace():
    prof = reduce_planes(_planes())
    assert prof["window_s"] == pytest.approx(0.100)
    # 20..23, 60..61, 80..90 and 105..110 of 10..110
    assert prof["busy_s"] == pytest.approx(0.019)
    assert trace_reduce.durations(prof, "raft_tick") == [0.003, 0.001]
    ops = dict(prof["breakdown"]["device_ops"])
    assert ops["copy.3"] == pytest.approx(0.010)
    assert ops["fusion.1"] == pytest.approx(0.008)
    gaps = dict(prof["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(0.081)
    # 10..20 lies mostly outside any annotation; no gap is mostly inside one
    assert list(gaps) == [trace_reduce.UNNAMED_GAP]
    ctx = layers.Context(counters={}, totals={}, spans=[], profile=prof, read_ms=[],
                         update_ms=[], engines=3, device_kind="TPU v5 lite",
                         max_groups=2048, max_peers=4)
    assert layers.read({"kind": "device_idle"}, ctx) == pytest.approx(81.0)
    assert layers.read({"kind": "trace_event", "event": "raft_tick",
                        "stat": "mean", "scale": 1e6}, ctx) \
        == pytest.approx(2000.0)
    share = layers.read({"kind": "roofline", "event": "raft_tick",
                         "bytes_fn": "raft_tick_min_bytes"}, ctx)
    assert share == pytest.approx(100 * (233472 / 819e9) / 0.002)
    # nothing to read: nothing said, never 0
    assert layers.read({"kind": "trace_event", "event": "nope",
                        "stat": "mean"}, ctx) is None
    assert reduce_planes(_planes()[:1]) is None


def test_the_recorded_chip_trace_reduces_to_what_was_read_off_it_by_hand():
    """Four tenths of a second cut from a traced run of kv3x64.ycsb_a on the TPU
    v5e (PR 26); the expectations were taken off it by a sweep of its own."""
    with open(os.path.join(HERE, "data", "trace_v5e_kv3x64.json")) as f:
        planes = json.load(f)
    with open(os.path.join(HERE, "data", "trace_v5e_kv3x64.expect.json")) \
            as f:
        want = json.load(f)
    prof = reduce_planes(planes)
    ticks = trace_reduce.durations(prof, "raft_tick")
    assert len(ticks) == want["raft_tick_events"]
    assert sum(ticks) == pytest.approx(want["raft_tick_seconds"])
    assert prof["busy_s"] == pytest.approx(want["busy_s"])
    assert prof["busy_s"] <= sum(
        s for _n, s in prof["breakdown"]["device_ops"]) + 1e-9 \
        or len(prof["breakdown"]["device_ops"]) == 10
    assert 0 < prof["busy_s"] < prof["window_s"]
