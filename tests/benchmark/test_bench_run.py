"""The command end to end: no chip, no result; the last line's keys; and the
rest of a run, on the CPU at 8 regions, sound and with each fault planted."""

import asyncio
import io
import json
import os
import subprocess
import sys
import time
from contextlib import redirect_stderr, redirect_stdout

import pytest
from bench_helpers import REPO, TINY_CELL, extended_copy

from benchmark import check_manifest, driver, run
from benchmark.driver import decide, run_cell
from benchmark.faults import FAULTS

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def test_the_command_fails_for_want_of_a_chip_and_prints_no_result():
    """The whole command as the driver runs it, here where JAX is held to
    the CPU: another exit code than 0, nothing on standard output."""
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "kv3x1024.ycsb_b", "--seed", "1", "--seconds", "2", "--trace", "0"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_an_unknown_cell_is_refused_before_the_chip_is_asked_for():
    with pytest.raises(check_manifest.ManifestError, match="no cell"):
        run.main(["--workload", "kv3x1.ycsb_a", "--seed", "1",
                  "--seconds", "1"])


def _run(tmp_path, trace=False, fault=None):
    bm = check_manifest.check(extended_copy(str(tmp_path)))
    return asyncio.run(run_cell(
        bm, TINY_CELL, 2 ** 31 + 11, 1.5, trace, str(tmp_path / "work"), CPU,
        time.perf_counter(), fault=fault))


def _last_line(result) -> tuple:
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        run.print_result(result)
    return out.getvalue().splitlines(), err.getvalue().splitlines()


def test_a_sound_run_is_correct_and_its_last_line_has_the_contracts_keys(
        tmp_path):
    result = _run(tmp_path)
    assert result["correct"] is True
    assert result["attempted"] > 100 and result["failed"] == 0
    out, err = _last_line(result)
    assert out[0].startswith("summary: ")
    last = json.loads(out[-1])
    assert list(last) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]          # checks comes last
    assert set(last["metrics"]) == {"ops_per_s", "read_p95_ms",
                                    "update_p95_ms", "setup_s"}
    assert all(set(m) == {"value", "unit"} and m["value"] > 0
               for m in last["metrics"].values())
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # each number compared, beside its limit, ends standard error
    assert err[-1] == "correct: True"
    assert [ln.split(":")[0] for ln in err[:-1]] == [
        f"check {name}" for name in last["checks"]]
    assert last["checks"]["reads_stale"] == {"value": 0, "limit": 0,
                                             "op": "<="}


def test_a_traced_run_reports_per_layer_metrics_and_no_device_number_off_chip(
        tmp_path):
    result = _run(tmp_path, trace=True)
    assert result["correct"] is True
    got = set(result["metrics"])
    assert {"client_items_per_rpc", "reads_per_confirm_round",
            "device_fence_pct", "quorum_commit_ms", "tick_host_ms",
            "tick_dispatch_ms", "log_flush_ms", "fsm_apply_ms",
            "update_p99_ms.client", "srv_propose_ms"} <= got
    # the CPU has no device plane: those readers find nothing and say nothing
    assert not got & {"raft_tick_us", "raft_tick_roofline", "device_idle_pct"}
    assert "busy_s" not in result["device"]
    assert 0 < result["metrics"]["device_fence_pct"]["value"] <= 100.0


@pytest.mark.parametrize("fault, fails", [
    ("stale_reads", "reads_stale"),         # the control
    ("drop_updates", "updates_lost"),
    ("alter_answer", "reads_wrong"),
    ("skip_replica", "replica_divergent"),
])
def test_with_the_timed_path_broken_correct_comes_out_false(
        tmp_path, monkeypatch, fault, fails):
    assert fault in FAULTS
    # a replica that was skipped never converges: do not wait a minute for it
    monkeypatch.setattr(driver, "SETTLE_DEADLINE_S", 2.0)
    result = _run(tmp_path, fault=fault)
    assert result["correct"] is False
    c = result["checks"][fails]
    assert c["value"] > c["limit"]


def test_decide_holds_each_number_to_its_limit():
    ok = {"a": {"value": 0, "limit": 0, "op": "<="},
          "b": {"value": 3, "limit": 1, "op": ">="}}
    assert decide(ok)
    assert not decide(dict(ok, a={"value": 1, "limit": 0, "op": "<="}))
    assert not decide(dict(ok, b={"value": 0, "limit": 1, "op": ">="}))
