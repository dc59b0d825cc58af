"""The open loop: one seed, one schedule; latency from the due time, so a
stall shows in the requests that came due during it (and in no ``invoke``);
overload reads a growing backlog, not an error; and the tiny cell through it
is correct, and not correct with each fault planted, as in the closed loop."""

import asyncio
import os
import time

import numpy as np
import pytest
from bench_helpers import OPEN_CELL, REPO, TINY_CELL, extended_copy

from benchmark import check_manifest, driver, plugins
from benchmark.driver import end_to_end, run_cell
from benchmark.loops import Window, closed
from benchmark.loops import open as open_loop
from benchmark.traffic import READ, UPDATE, OpStream, Values

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2 ** 31 + 33


def _mix(rate, warm_s=0.1) -> dict:
    mix = dict(check_manifest.cell(
        check_manifest.check(REPO), "kv3x1024.ycsb_a_open")[2])
    mix.update(loop={"kind": "open", "rate": rate}, warm_seconds=warm_s)
    return mix


class FakeClient:
    """A register per key, served after ``service_s``; ``capacity`` callers
    at a time.  ``stall_at``: the first call after that many seconds blocks
    the event loop (clients and stores share it) for ``stall_s``."""

    def __init__(self, service_s=0.001, capacity=1 << 30, stall_at=None,
                 stall_s=0.0):
        self.data: dict = {}
        self.service_s, self.gate = service_s, asyncio.Semaphore(capacity)
        self.t0 = time.perf_counter()
        self.stall_at, self.stall_s, self.stalled = stall_at, stall_s, None

    async def _serve(self):
        if (self.stall_at is not None and self.stalled is None
                and time.perf_counter() - self.t0 >= self.stall_at):
            begin = time.perf_counter()
            time.sleep(self.stall_s)
            self.stalled = (begin, time.perf_counter())
        async with self.gate:
            await asyncio.sleep(self.service_s)

    async def get(self, key):
        await self._serve()
        return self.data.get(key)

    async def put(self, key, value):
        await self._serve()
        self.data[key] = value
        return True


def _drive(client_kw: dict, rate: float, seconds: float, seed=SEED):
    mix = _mix(rate)
    stream = OpStream(mix, 64, seed, n=1 << 16)
    values = Values(seed, 100)
    keys = [b"k%04d" % i for i in range(64)]

    async def go():
        client = FakeClient(**client_kw)
        for i, k in enumerate(keys):
            client.data[k] = values.make(0xFFFFFFFF, i, i)
        win = await open_loop.run_window(client, keys, stream, values, mix,
                                         seconds)
        return win, client

    win, client = asyncio.run(go())
    return win, client, stream


def test_one_seed_gives_one_schedule():
    a = open_loop.schedule(SEED, 2000.0, 1 << 14)
    assert np.array_equal(a, open_loop.schedule(SEED, 2000.0, 1 << 14))
    assert not np.array_equal(a, open_loop.schedule(SEED + 1, 2000.0, 1 << 14))
    # a longer schedule begins with the shorter one
    assert np.array_equal(a, open_loop.schedule(SEED, 2000.0, 1 << 15)[:1 << 14])
    gaps = np.diff(a)
    assert (gaps > 0).all()
    # independent users: exponential gaps, mean 1 / rate, deviation the same
    assert gaps.mean() == pytest.approx(1 / 2000.0, rel=0.03)
    assert gaps.std() == pytest.approx(1 / 2000.0, rel=0.05)


def test_two_runs_of_one_seed_send_the_same_operations_at_the_same_due_times():
    runs = [_drive({}, 400.0, 0.5) for _ in range(2)]
    rel = []
    for win, _, stream in runs:
        order = np.argsort(win.due)
        due = np.array(win.due)[order]
        ops = [(win.ops[i][0], win.ops[i][1]) for i in order]
        # operation i of the stream, in order, and nothing sent twice
        assert ops == [(int(stream.kinds[i]), int(stream.records[i]))
                       for i in range(len(ops))]
        assert len(win.ops) == len(win.due) > 150
        rel.append(due - due[0])
    n = min(len(rel[0]), len(rel[1]))      # the close falls where it falls
    assert abs(len(rel[0]) - len(rel[1])) <= 8
    assert np.allclose(rel[0][:n], rel[1][:n], atol=1e-9)
    sched = open_loop.schedule(SEED, 400.0, 1 << 16)
    assert np.allclose(rel[0][:n], sched[:n] - sched[0], atol=1e-9)


def test_a_stall_shows_in_the_latency_of_what_came_due_in_it_and_in_no_invoke():
    """The coordinated-omission case: the loop stalls for 200 ms, the
    requests that came due meanwhile are sent when it ends, and each one's
    latency holds the rest of the stall it sat out."""
    win, client, _ = _drive({"stall_at": 0.5, "stall_s": 0.2}, 300.0, 1.2)
    begin, end = client.stalled
    assert end - begin >= 0.2
    rows = list(zip(win.ops, win.due))
    held = [(o, t) for o, t in rows if begin + 0.005 < t < end]
    assert len(held) > 30                   # 300 a second for 0.2 s
    for o, t in held:
        assert o[2] >= end                  # sent only once the stall ended
        assert o[3] - t >= (end - t) - 1e-6      # counted from the due time
    # no call was made while the loop stood still
    assert not [o for o, _ in rows if begin + 0.005 < o[2] < end]
    # from the call, as a closed loop counts, those same requests look fast
    assert max(o[3] - o[2] for o, _ in held) < 0.1
    e2e = end_to_end(win)
    assert e2e["attempted"] == sum(1 for _, t in rows
                                   if win.start <= t < win.end)
    worst = max(e2e["_read_ms"] + e2e["_update_ms"])
    assert worst >= 180.0
    assert win.notes["late_ms"]["max"] >= 180.0
    assert win.notes["late_ms"]["p50"] < 20.0


def test_latency_counts_from_the_due_time_where_the_loop_gives_one():
    win = Window()
    win.start, win.end = 10.0, 20.0
    win.ops = [(READ, 1, 11.5, 11.6, True, None),       # due 11.0
               (UPDATE, 2, 12.0, 12.3, True, (0, 1, 2)),   # due 12.0
               (READ, 3, 20.5, 20.6, True, None)]       # due 19.9: in it
    e2e = end_to_end(win)                   # a closed loop: from the call
    assert e2e["attempted"] == 2
    assert e2e["_read_ms"] == [pytest.approx(100.0)]
    win.due = [11.0, 12.0, 19.9]
    e2e = end_to_end(win)
    assert e2e["attempted"] == 3 and e2e["failed"] == 0
    assert e2e["_read_ms"] == [pytest.approx(600.0), pytest.approx(700.0)]
    assert e2e["_update_ms"] == [pytest.approx(300.0)]
    assert e2e["ops_per_s"] == pytest.approx(0.2)   # answered in the window


def test_a_rate_the_system_cannot_keep_reads_a_growing_backlog_not_an_error():
    # one server, 1 ms a request: 1,000 a second at the most, offered 3,000
    win, client, _ = _drive({"service_s": 0.001, "capacity": 1}, 3000.0, 0.9)
    notes = win.notes
    assert not [o for o in win.ops if not o[4]]     # nothing shed or failed
    assert len(win.ops) == len(win.due) >= notes["due"] > 2000
    third, two_thirds, close = notes["backlog"].values()
    assert 0 < third < two_thirds < close
    assert close > 2 * third and close > 1000
    assert notes["answered_share"] < 0.6
    assert notes["in_flight"]["max"] >= close - 50
    assert notes["in_flight"]["mean"] > third
    # every request was answered in the end, long after it was due
    e2e = end_to_end(win)
    assert e2e["attempted"] == notes["due"] and e2e["failed"] == 0
    assert e2e["ops_per_s"] < 1100.0
    assert max(e2e["_read_ms"]) > 1000.0


def test_a_rate_it_keeps_reads_the_rate_and_a_flat_backlog():
    win, client, _ = _drive({"service_s": 0.002}, 500.0, 1.0)
    notes = win.notes
    assert notes["answered_share"] > 0.97
    assert max(notes["backlog"].values()) < 20
    assert end_to_end(win)["ops_per_s"] == pytest.approx(500.0, rel=0.15)
    assert win.counters["loop.arrivals"] == notes["due"]
    assert (win.counters["loop.arrival_late_ms"] / notes["due"]
            == pytest.approx(notes["late_ms"]["mean"]))
    # a value names its write by the operation's index
    writes = [o[5] for o in win.ops if o[0] == UPDATE]
    assert all(w[0] == open_loop.WRITER for w in writes)
    assert len({w[1] for w in writes}) == len(writes)


def test_both_loops_have_the_one_contract(manifest_root):
    import inspect

    bm = check_manifest.check(manifest_root)
    for mod in (closed, open_loop):
        sig = inspect.signature(mod.run_window)
        assert list(sig.parameters) == [
            "client", "keys", "stream", "values", "mix", "seconds",
            "on_window_start", "on_window_end"]
        assert mod.IMPLEMENTS == {"faults": [[]]}
    # looked up by the kind the mix names, from the tree that was checked
    _, _, mix = check_manifest.cell(bm, "kv3x1024.ycsb_a_open")
    loops = os.path.join(manifest_root, "benchmark", "loops")
    assert plugins.loop_of(bm, mix).__file__ == os.path.join(loops,
                                                             "open.py")
    assert mix["loop"] == {"kind": "open", "rate": mix["loop"]["rate"]}
    _, _, a = check_manifest.cell(bm, "kv3x1024.ycsb_a")
    assert plugins.loop_of(bm, a).__file__ == os.path.join(loops,
                                                           "closed.py")
    # the same mix but for how it arrives
    assert {k: v for k, v in mix.items() if k not in ("name", "why", "loop")} \
        == {k: v for k, v in a.items() if k not in ("name", "why", "loop")}


def _run(tmp_path, cell=OPEN_CELL, trace=False, fault=None, seconds=1.5):
    bm = check_manifest.check(extended_copy(str(tmp_path)))
    return asyncio.run(run_cell(
        bm, cell, SEED, seconds, trace, str(tmp_path / "work"), CPU,
        time.perf_counter(), fault=fault))


def test_the_tiny_cell_through_the_open_loop_is_correct(tmp_path):
    result = _run(tmp_path)
    assert result["correct"] is True
    assert result["failed"] == 0
    assert set(result["metrics"]) == {"ops_per_s", "read_p95_ms",
                                      "update_p95_ms", "setup_s"}
    notes = result["_summary"]["loop"]
    assert result["attempted"] == notes["due"] > 300     # 300 a second
    # how much of it is answered inside a window this short depends on the
    # machine (the fake client's tests hold the arithmetic); every request
    # is answered in the end, and none failed
    assert 0 < notes["answered_in_window"] <= notes["due"]
    assert set(notes["backlog"]) == {"0.5s", "1s", "close"}
    assert notes["in_flight"]["max"] >= 1 and notes["late_ms"]["max"] >= 0.0
    assert result["checks"]["ops_failed"] == {"value": 0, "limit": 0,
                                              "op": "<="}
    assert result["checks"]["read_device_fences"]["value"] > 0


@pytest.mark.parametrize("fault, fails", [
    ("stale_reads", "reads_stale"),         # the control
    ("drop_updates", "updates_lost"),
    ("alter_answer", "reads_wrong"),
    ("skip_replica", "replica_divergent"),
])
def test_with_the_timed_path_broken_the_open_loop_comes_out_not_correct(
        tmp_path, monkeypatch, fault, fails):
    monkeypatch.setattr(driver, "SETTLE_DEADLINE_S", 2.0)
    result = _run(tmp_path, fault=fault)
    assert result["correct"] is False
    c = result["checks"][fails]
    assert c["value"] > c["limit"]


def test_a_traced_open_run_reports_how_late_the_generator_ran(tmp_path):
    result = _run(tmp_path, trace=True)
    assert result["correct"] is True
    got = result["metrics"]
    assert got["arrival_late_ms.kv3x8"]["unit"] == "ms"
    assert got["arrival_late_ms.kv3x8"]["value"] == pytest.approx(
        result["_summary"]["loop"]["late_ms"]["mean"])
    # the committed cell's own metric lists that cell only
    assert "arrival_late_ms" not in got
    bm = check_manifest.check(REPO)
    for cell in (w["name"] for w in bm["workloads"]):
        names = {m["name"] for m in check_manifest.metrics_of(
            bm, cell, "per_layer")}
        assert ("arrival_late_ms" in names) == (
            cell == "kv3x1024.ycsb_a_open")
        assert {"kv_wal_entries_per_fsync", "kv_log_groups_per_fsync"} < names
    # the two fsync rounds: at least one entry and one group a round
    assert got["kv_wal_entries_per_fsync"]["value"] >= 1.0
    assert got["kv_log_groups_per_fsync"]["value"] >= 1.0
    assert TINY_CELL != OPEN_CELL
