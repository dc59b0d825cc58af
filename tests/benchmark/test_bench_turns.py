"""The seven per-layer metrics that read the framed dispatch of the loop thread
(handles by kind, the selector, the collector, the turn) and the client's
queue: entries and data files for the ``span`` reader that was there, listed
for three cells; a traced run of the tiny cell on the CPU reports each under
a name of its own; and tracing off, nothing is patched."""

import asyncio
import gc
import json
import os
import time

import pytest
from bench_helpers import (EVERY_CELL, TINY_CELL, add_files, extended_copy,
                           stands_together)

from benchmark import check_manifest
from benchmark.driver import run_cell

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# the interpreter's own, as this module found it: nothing traces at import
_RUN = asyncio.events.Handle._run
CELLS = ["kv3x1024.ycsb_a", "kv3x1024.ycsb_b", "kv3x1024.ycsb_a_open"]

# name -> (the record it reads, statistic, scale, unit, layer, moves)
NEW = {
    "loop_turn_pct.step": ("loop.turn.step", "mean", 100.0, "%",
                           "host loop", "ops_per_s"),
    "loop_turn_pct.callback": ("loop.turn.callback", "mean", 100.0, "%",
                               "host loop", "ops_per_s"),
    "loop_turn_pct.timer": ("loop.turn.timer", "mean", 100.0, "%",
                            "host loop", "ops_per_s"),
    "loop_select_pct": ("loop.idle.select", "mean", 100.0, "%",
                        "host loop", "ops_per_s"),
    "loop_gc_pct": ("loop.gc", "mean", 100.0, "%", "host loop",
                    "update_p95_ms"),
    "loop_turn_p95_ms": ("turn", "p95", 1000.0, "ms", "host loop",
                         "read_p95_ms"),
    "client_queue_ms": ("client_queue", "median", 1000.0, "ms", "client",
                        "read_p95_ms"),
}
SHARES = [n for n, row in NEW.items() if row[3] == "%"]


def test_the_manifest_passes_with_the_seven_entries(manifest_root):
    bm = check_manifest.check(manifest_root)
    assert stands_together([m["name"] for m in bm["per_layer"]], NEW)
    for name, (span, stat, scale, unit, layer, moves) in NEW.items():
        m = bm["per_layer"][[e["name"] for e in bm["per_layer"]].index(name)]
        assert (m["unit"], m["layer"], m["moves"], m["better"], m["source"]) \
            == (unit, layer, moves, "lower", "program_span")
        # the three cells that list them
        assert m["workloads"] == CELLS
        assert m["_reader"] == {"kind": "span", "span": span, "stat": stat,
                                "scale": scale}
        # a data file for a reader that was there: the entry and the reader
        with open(os.path.join(
                manifest_root, "benchmark", "layer_metrics",
                name + ".json")) as f:
            assert set(json.load(f)) == set(m) - {"_reader"} | {"reader"}


def test_no_other_cells_list_changed(manifest_root):
    bm = check_manifest.check(manifest_root)
    # each cell's own per-layer metrics beside the ones every cell reports
    want = {"kv3x1024.ycsb_a": list(NEW), "kv3x1024.ycsb_b": list(NEW),
            "kv3x1024.ycsb_a_open": ["arrival_late_ms", *NEW],
            "kv3x4096.ycsb_a": [],
            "kv3x1024-failover.ycsb_a_kill1": [
                "unavailable_s", "all_led_s", "elections_per_region",
                "election_ms", "client_bounces_per_op", "catch_up_s"],
            "kv3x1024-balanced.ycsb_a": [
                "leader_share_max_pct", "commits_per_tick",
                "log_rounds_mixed_pct", "tick_late_ms.all"]}
    assert set(want) <= {w["name"] for w in bm["workloads"]}
    for cell, own in want.items():
        names = [m["name"] for m in check_manifest.metrics_of(
            bm, cell, "per_layer")]
        assert set(EVERY_CELL) | set(own) <= set(names), cell
        assert (set(NEW) <= set(names)) == (cell in CELLS)
        # every listed cell reports what each of the seven moves
        e2e = {m["name"] for m in check_manifest.metrics_of(
            bm, cell, "end_to_end")}
        assert {"ops_per_s", "read_p95_ms", "update_p95_ms"} <= e2e


def _copy_with_the_seven(tmp: str) -> dict:
    """The tests' copy of the benchmark with each of the seven files copied
    under a name of the tiny cell's own (``<name>.kv3x8``)."""
    extended_copy(tmp)
    files, entries = {}, []
    for name in NEW:
        with open(os.path.join(
                tmp, "benchmark", "layer_metrics", name + ".json")) as f:
            lm = json.load(f)
        lm.update(name=name + ".kv3x8", workloads=[TINY_CELL])
        files[f"benchmark/layer_metrics/{name}.kv3x8.json"] = lm
        entries.append({k: v for k, v in lm.items() if k != "reader"})
    add_files(tmp, files, per_layer=entries)
    return check_manifest.check(tmp)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run of the tiny cell on the CPU, long enough for the
    tracer to roll two whole seconds."""
    tmp = tmp_path_factory.mktemp("turns")
    bm = _copy_with_the_seven(str(tmp))
    result = asyncio.run(run_cell(
        bm, TINY_CELL, 2 ** 31 + 39, 2.6, True, str(tmp / "work"), CPU,
        time.perf_counter()))
    return result, _hooks()


def _hooks() -> tuple:
    from tpuraft.util.trace import TRACER

    return (asyncio.events.Handle._run is _RUN,
            TRACER._on_gc not in gc.callbacks, TRACER._turn_loop is None)


@pytest.mark.parametrize("name", list(NEW))
def test_the_traced_tiny_run_reports_each_new_metric(traced, name):
    result, _ = traced
    assert result["correct"] is True
    m = result["metrics"][name + ".kv3x8"]
    assert m["unit"] == NEW[name][3] and m["value"] >= 0.0
    if name in SHARES:
        assert m["value"] <= 100.0


def test_the_traced_tiny_run_adds_up(traced):
    result, hooks = traced
    got = {k: v["value"] for k, v in result["metrics"].items()}
    spans = result["_summary"]["spans"]
    # a frame's self seconds are what ran outside any section, so the
    # frames and the layers together stay within the thread's second
    layers = sum(v for k, v in got.items() if k.startswith("loop_pct."))
    frames = sum(got[n + ".kv3x8"] for n in SHARES)
    assert layers > 0.0 and frames > 0.0
    assert layers + frames <= 100.0 + 1e-6
    # work ran in task steps and callbacks, and the thread slept
    assert got["loop_turn_pct.step.kv3x8"] > 0.0
    assert got["loop_turn_pct.callback.kv3x8"] > 0.0
    assert got["loop_select_pct.kv3x8"] > 0.0
    # the client's queue is the span the summary line has carried all along
    assert got["client_queue_ms.kv3x8"] == pytest.approx(
        spans["client_queue"]["median_ms"], abs=1e-3)
    assert got["loop_turn_p95_ms.kv3x8"] >= 1.0    # a record from 1 ms up
    # every new name is on the summary line: what the three pinned cells
    # have in place of the metrics
    for name in ("loop.turn", "loop.turn.step", "loop.turn.callback",
                 "loop.turn.timer", "loop.idle.select", "loop.gc",
                 "loop.rest", "turn"):
        assert spans[name]["n"] >= 2, name
    owners = [n for n in spans if n.startswith("loop.turn.step.")]
    assert owners and "loop.turn.step.?" not in owners
    # each whole second: sections, frames and the rest are all of it
    assert spans["loop.rest"]["median_ms"] < 0.25 * 1e3
    # the flag was cleared at the window's close and handles ran since
    assert hooks == (True, True, True)


def test_an_untraced_run_patches_nothing(tmp_path, monkeypatch):
    from tpuraft.util.trace import TRACER

    bm = check_manifest.check(extended_copy(str(tmp_path)))
    before = (TRACER.turns, TRACER.turn_handles, TRACER.section_table())
    seen = []
    real = check_manifest.metrics_of

    def at_the_close(*args):
        # the driver asks for the cell's metrics once the window has closed
        seen.append(_hooks())
        return real(*args)

    monkeypatch.setattr(check_manifest, "metrics_of", at_the_close)
    result = asyncio.run(run_cell(
        bm, TINY_CELL, 2 ** 31 + 40, 1.0, False, str(tmp_path / "work"),
        CPU, time.perf_counter()))
    assert result["correct"] is True and "ops_per_s" in result["metrics"]
    assert seen and set(seen) == {(True, True, True)}
    # no handle was framed, no section opened: the loop was the parent's
    assert not TRACER.enabled
    assert (TRACER.turns, TRACER.turn_handles,
            TRACER.section_table()) == before
