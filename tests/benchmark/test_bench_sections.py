"""The seventeen per-layer metrics that read the loop's sections, the tick's
phases and the two split envelopes: entries and files only, read by the
readers that were there; and a loop section on the window's line names the
idle gap it covers."""

import asyncio
import time

import pytest
from bench_helpers import TINY_CELL, extended_copy

from benchmark import check_manifest
from benchmark.driver import run_cell
from benchmark.trace_reduce import (MODULES_LINE, OPS_LINE, UNNAMED_GAP,
                                    WINDOW_EVENT, reduce_planes)

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}

# name -> (reader kind, what it reads, layer, the end-to-end metric it moves)
NEW_METRICS = {
    "tick_state_ms": ("histogram", "tick_state_ms", "tick", "read_p95_ms"),
    "tick_call_ms": ("histogram", "tick_call_ms", "tick", "read_p95_ms"),
    "tick_fetch_ms": ("histogram", "tick_fetch_ms", "tick", "read_p95_ms"),
    "tick_heartbeat_ms": ("histogram", "tick_heartbeat_ms", "tick",
                          "read_p95_ms"),
    "tick_late_ms": ("histogram", "tick_late_ms", "tick", "read_p95_ms"),
    "fence_resolve_ms": ("histogram", "fence_resolve_ms", "KV serving",
                         "read_p95_ms"),
    "read_fence_ms": ("span", "srv_read_fence", "KV serving", "read_p95_ms"),
    "log_fsync_ms": ("span", "log_fsync", "log and meta", "update_p95_ms"),
    "log_wake_ms": ("span", "log_wake", "log and meta", "update_p95_ms"),
    "loop_cpu_pct": ("span", "loop.cpu", "host loop", "ops_per_s"),
    "loop_pct.client": ("span", "loop.client", "client", "ops_per_s"),
    "loop_pct.kv": ("span", "loop.kv", "KV serving", "ops_per_s"),
    "loop_pct.raft": ("span", "loop.raft", "consensus host", "ops_per_s"),
    "loop_pct.log": ("span", "loop.log", "log and meta", "ops_per_s"),
    "loop_pct.fsm": ("span", "loop.fsm", "state machine and KV engine",
                     "ops_per_s"),
    "loop_pct.tick": ("span", "loop.tick", "tick", "ops_per_s"),
    "loop_pct.rpc": ("span", "loop.rpc", "transport", "ops_per_s"),
}
# A fsync the group commit takes inline, on the loop, has no wake to time:
# at 8 regions and 16 callers a quiet log often syncs that way, so a short
# run may sample no entry that went through an executor round.
MAY_BE_SILENT = {"log_wake_ms"}


def test_the_manifest_passes_with_the_seventeen_entries(manifest_root):
    bm = check_manifest.check(manifest_root)
    by_name = {m["name"]: m for m in bm["per_layer"]}
    assert len(NEW_METRICS) == 17 and set(NEW_METRICS) <= set(by_name)
    # appended after the twelve that were there, none of them moved; what
    # later PRs append comes after the seventeen and is free
    assert [m["name"] for m in bm["per_layer"]][12:29] == list(NEW_METRICS)
    assert "srv_propose_ms" not in by_name      # the tests' own extra metric


@pytest.mark.parametrize("name", list(NEW_METRICS))
def test_each_entry_is_a_data_file_for_a_reader_that_was_there(
        manifest_root, name):
    kind, reads, layer, moves = NEW_METRICS[name]
    m = {m["name"]: m for m in check_manifest.check(
        manifest_root)["per_layer"]}[name]
    assert "workloads" not in m                 # every cell, later ones too
    assert (m["layer"], m["moves"], m["better"]) == (layer, moves, "lower")
    reader = m["_reader"]
    assert reader["kind"] == kind
    if kind == "histogram":
        assert m["source"] == "host_clock" and m["unit"] == "ms"
        assert reader == {"kind": "histogram", "histograms": [reads],
                          "stat": "mean", "engine": "leader_heaviest"}
    else:
        assert m["source"] == "program_span" and reader["span"] == reads
        loop = reads.startswith("loop.")
        assert (m["unit"], reader["stat"], reader["scale"]) == (
            ("%", "mean", 100.0) if loop else ("ms", "median", 1000.0))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run of the tiny cell on the CPU, long enough for the
    tracer to roll two whole seconds."""
    tmp = tmp_path_factory.mktemp("sections")
    bm = check_manifest.check(extended_copy(str(tmp)))
    return asyncio.run(run_cell(
        bm, TINY_CELL, 2 ** 31 + 27, 2.6, True, str(tmp / "work"), CPU,
        time.perf_counter()))


@pytest.mark.parametrize("name", list(NEW_METRICS))
def test_the_traced_tiny_run_reports_each_new_metric(traced, name):
    assert traced["correct"] is True
    if name in MAY_BE_SILENT and name not in traced["metrics"]:
        # nothing to read, nothing said: every sampled fsync ran inline
        assert "log_wake" not in traced["_summary"]["spans"]
        return
    m = traced["metrics"][name]
    assert m["unit"] == ("%" if name.startswith("loop_") else "ms")
    assert m["value"] >= 0.0
    if name.startswith("loop_pct."):
        # a layer's share of the loop thread is within the thread's second
        assert m["value"] <= 100.0


def test_the_traced_tiny_run_adds_up(traced):
    got = {k: v["value"] for k, v in traced["metrics"].items()}
    # the old twelve, less those that need a device plane, are still there
    assert {"client_items_per_rpc", "reads_per_confirm_round",
            "device_fence_pct", "quorum_commit_ms", "tick_host_ms",
            "tick_dispatch_ms", "log_flush_ms", "fsm_apply_ms",
            "update_p99_ms.client"} <= set(got)
    # the device phase in its three parts
    assert got["tick_state_ms"] + got["tick_call_ms"] + got["tick_fetch_ms"] \
        == pytest.approx(got["tick_dispatch_ms"], rel=0.05)
    # the disk's part of a flush is inside the awaited envelope
    assert got["log_fsync_ms"] <= got["log_flush_ms"]
    # the engine-side part of the read fence is inside the server's span
    spans = traced["_summary"]["spans"]
    assert spans["srv_read_fence"]["n"] > 0
    # every section rolled up under its own name, per second
    sections = {n for n in spans if n.startswith("loop.") and n.count(".") == 2}
    assert {"loop.client.send", "loop.client.deliver", "loop.kv.batch",
            "loop.kv.read_round", "loop.raft.propose", "loop.raft.replicate",
            "loop.raft.follower", "loop.raft.ack", "loop.raft.heartbeat",
            "loop.log.stage", "loop.fsm.apply", "loop.rpc.inproc",
            "loop.tick.build", "loop.tick.call", "loop.tick.fetch",
            "loop.tick.apply"} <= sections      # a later PR may add its own
    layers = sum(got[n] for n in got if n.startswith("loop_pct."))
    assert 0.0 < layers <= 100.0 + 1e-6


def _planes(section_ms: int) -> list:
    """A 100 ms window with one 50 ms device gap (30..80) and a ``kv.batch``
    section that starts with the gap and covers ``section_ms`` of it."""
    ms = 1_000_000
    return [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            [WINDOW_EVENT, 10 * ms, 100 * ms],
            ["kv.batch", 30 * ms, section_ms * ms],
            ["tick.call", 80 * ms, 1 * ms]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": MODULES_LINE, "events": [
                ["jit_raft_tick_outputs(1)", 10 * ms, 20 * ms],
                ["jit_raft_tick_outputs(1)", 80 * ms, 30 * ms]]},
            {"name": OPS_LINE, "events": [
                ["fusion.1", 10 * ms, 20 * ms],
                ["fusion.1", 80 * ms, 30 * ms]]}]},
    ]


@pytest.mark.parametrize("covered_ms, named", [(30, "kv.batch"),
                                                (20, UNNAMED_GAP)])
def test_a_section_names_the_gap_it_covers_half_of(covered_ms, named):
    prof = reduce_planes(_planes(covered_ms))
    assert prof["window_s"] == pytest.approx(0.100)
    assert prof["busy_s"] == pytest.approx(0.050)
    gaps = dict(prof["breakdown"]["idle_gaps"])
    # 60 % of the gap under the section names it; 40 % leaves it unnamed
    assert gaps == {named: pytest.approx(0.050)}
