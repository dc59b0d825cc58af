"""One run of one cell: build, load, warm, measure, settle, check.

``run_cell`` is everything of a run but the look for a chip and the printing
(``benchmark.run`` does those), so a test can drive it on the CPU with a fault
planted under it and see ``correct`` come out false.
"""

from __future__ import annotations

import asyncio
import contextlib
import shutil
import sys
import time

from benchmark import check_manifest, layers, plugins, trace_reduce
from benchmark.loops import SETTLE_DEADLINE_S, Window
from benchmark.reference import (INF, Read, Write, check_history,
                                 tick_mismatches, tick_reference)
from benchmark.stats import percentile, rate
from benchmark.traffic import LOADER, READ, OpStream, Values

FAILED_LATENCY_S = 60.0     # a failed operation misses every latency limit
TRACE_SAMPLE_RATE = 0.05


def end_to_end(win: Window, seconds: float | None = None) -> dict:
    """The window's end-to-end numbers, over every operation of it (or of its
    first ``seconds``: what a shorter run would have read).  An operation
    belongs to the window, and its latency counts, from when it was due: on a
    schedule that is ``win.due``, in a closed loop the call itself."""
    end = win.end if seconds is None else win.start + seconds
    since = win.due or [o[2] for o in win.ops]
    issued = [(o, t) for o, t in zip(win.ops, since) if win.start <= t < end]
    acked = sum(1 for o in win.ops if o[4] and win.start <= o[3] < end)

    def lat_ms(kind_is_read: bool) -> list:
        return [((o[3] - t) if o[4] else FAILED_LATENCY_S) * 1e3
                for o, t in issued if (o[0] == READ) == kind_is_read]

    reads, updates = lat_ms(True), lat_ms(False)
    out = {"ops_per_s": rate(acked, end - win.start),
           "attempted": len(issued),
           "failed": sum(1 for o, _ in issued if not o[4]),
           "reads": len(reads), "updates": len(updates),
           "_read_ms": reads, "_update_ms": updates}
    if reads:
        out["read_p95_ms"] = percentile(reads, 95)
        out["read_p50_ms"] = percentile(reads, 50)
    if updates:
        out["update_p95_ms"] = percentile(updates, 95)
        out["update_p50_ms"] = percentile(updates, 50)
    return out


def history_of(win: Window, load_records: int) -> tuple:
    """The window's rows as the reference's writes and reads; the load is
    each record's first write, acknowledged before anything else began."""
    writes = [Write(i, LOADER, i, -INF, -INF) for i in range(load_records)]
    reads = []
    for kind, rec, t0, t1, ok, got in win.ops:
        if kind == READ:
            if ok:
                reads.append(Read(rec, t0, t1, got))
        else:
            writes.append(Write(rec, got[0], got[1], t0, t1 if ok else INF))
    return writes, reads


async def settle_and_check(cluster, win: Window, values: Values) -> dict:
    """After the close: read every record linearizably, wait for the three
    state machines to hold that value, run each engine's compiled tick on its
    live rows against the reference, then hold the history to the model."""
    t0 = time.perf_counter()
    final_raw = await cluster.read_all(SETTLE_DEADLINE_S)
    final_time = time.perf_counter()
    final = {i: values.parse(v) for i, v in enumerate(final_raw)}
    records = range(len(final_raw))
    deadline = time.perf_counter() + SETTLE_DEADLINE_S
    while True:
        diverged = sum(
            1 for s in range(len(cluster.stores))
            for i, v in zip(records, cluster.replica_values(s, records))
            if v != final_raw[i])
        if not diverged or time.perf_counter() > deadline:
            break
        await asyncio.sleep(0.25)
    tick_bad = 0
    for e in range(len(cluster.engines)):
        inputs, now, params, outputs = cluster.tick_probe(e)
        tick_bad += tick_mismatches(outputs,
                                    tick_reference(inputs, now, params))
    writes, reads = history_of(win, len(final_raw))
    counts = check_history(writes, reads, final, final_time)
    counts["replica_divergent"] = diverged
    counts["tick_rows_differ"] = tick_bad
    counts["_check_s"] = time.perf_counter() - t0
    counts["_reads_checked"] = len(reads)
    counts["_writes_checked"] = len(writes)
    return counts


def decide(checks: dict) -> bool:
    ok = True
    for c in checks.values():
        v, lim = c["value"], c["limit"]
        ok &= (v <= lim) if c["op"] == "<=" else (v >= lim)
    return ok


def _check(value, limit, op="<=") -> dict:
    return {"value": value, "limit": limit, "op": op}


async def run_cell(bm: dict, cell_name: str, seed: int, seconds: float,
                   trace: bool, workdir: str, device: dict, t_process: float,
                   fault: str | None = None, say=lambda msg: None,
                   teardown: bool = True) -> dict:
    """The whole run but the chip check and the printing.  ``device`` is the
    device as JAX reports it; ``t_process`` the ``perf_counter`` reading at
    process start, from which ``setup_s`` counts.  With ``teardown`` false
    the cluster is left running for a caller that is about to exit: closing
    3,072 replicas takes seconds that every run of every check would pay."""
    from tpuraft.util.trace import TRACER

    from benchmark.faults import plant

    cell, cfg, traffic = check_manifest.cell(bm, cell_name)
    loop = plugins.loop_of(bm, traffic)
    values = Values(seed, cfg["field_count"] * cfg["field_bytes"])
    stream = OpStream(traffic, cfg["record_count"], seed)
    cluster = plugins.cluster_of(bm, cfg)(cfg, workdir)
    summary: dict = {"cell": cell_name, "seed": seed, "seconds": seconds,
                     "trace": trace, "fault": fault}
    profile_dir = f"{workdir}/profile"
    profiling = False
    try:
        if trace:
            # the profiler takes seconds to start and to stop, and a stalled
            # loop is a stalled cluster (elections at a 1 s timeout): it
            # starts before the cluster does and stops once the run has
            # settled; the window's marker says which part counts
            trace_reduce.start(profile_dir)
            profiling = True
        await cluster.start()
        say(f"booted in {cluster.timings['boot_s']:.1f}s, "
            f"{cluster.regions} leaders after "
            f"{cluster.timings['elect_s']:.1f}s more")
        await cluster.load(values)
        say(f"loaded {cfg['record_count']} records in "
            f"{cluster.timings['load_s']:.1f}s")
        summary["leaders_per_store"] = cluster.leaders_per_store()
        client = plant(fault, cluster, seed) if fault \
            else cluster.client

        before: dict = {}
        after: dict = {}
        setup: dict = {}
        window_note = contextlib.ExitStack()

        def on_window_start() -> None:
            before.update(cluster.counters())
            setup["s"] = time.perf_counter() - t_process
            if trace:
                # spans of the window's own operations only
                TRACER.configure(
                    enabled=True, sample_rate=TRACE_SAMPLE_RATE, seed=seed,
                    ring=1 << 18, slow_trigger=False)
                window_note.enter_context(
                    trace_reduce.annotation(trace_reduce.WINDOW_EVENT))

        def on_window_end() -> None:
            after.update(cluster.counters())
            window_note.close()

        if trace:
            TRACER.reset()
        try:
            win = await loop.run_window(
                client, cluster.keys, stream, values, traffic, seconds,
                on_window_start, on_window_end)
        finally:
            window_note.close()
            if trace:
                TRACER.enabled = False
        if fault:
            client.after_window()
        memory_peak = layers.memory_peak_bytes()
        say(f"window closed: {len(win.ops)} operations")

        counts = await settle_and_check(cluster, win, values)
        say(f"settled and checked in {counts['_check_s']:.1f}s")
        totals = cluster.counters()
        if profiling:
            trace_reduce.stop()
            profiling = False
        e2e = end_to_end(win)
        e2e["setup_s"] = setup["s"]
        delta = {k: after[k] - before[k] for k in after}
        delta.update(win.counters)      # what the loop itself counted
        n_eng = len(cluster.engines)
        for i in range(n_eng):     # a gauge, not a count
            delta[f"engine{i}.leaders_now"] = after[f"engine{i}.leaders"]

        checks = {
            # of every operation sent, the warm-up's too
            "ops_failed": _check(sum(1 for o in win.ops if not o[4]), 0),
            "reads_wrong": _check(counts["reads_wrong"], 0),
            "reads_stale": _check(counts["reads_stale"], 0),
            "final_wrong": _check(counts["final_wrong"], 0),
            "updates_lost": _check(counts["updates_lost"], 0),
            "replica_divergent": _check(counts["replica_divergent"], 0),
            "tick_rows_differ": _check(counts["tick_rows_differ"], 0),
            "tick_failures": _check(after["engine.tick_failures"], 0),
            "leaders": _check(cluster.leaders(), cluster.regions, ">="),
            # an engine that leads nothing ticks on events only, so the
            # count is over the three; each one's compiled tick also runs
            # in tick_rows_differ
            "device_ticks": _check(
                delta["engine.ticks"] if cluster.device_tick_runs() else 0,
                1, ">="),
            # SAFE reads resolved by the device fence lane (a mix with no
            # reads has none to resolve)
            "read_device_fences": _check(delta["kv.read_device_fences"],
                                         1 if e2e["reads"] else 0, ">="),
        }
        lag = win.loop_lag_ms or [0.0]
        summary.update({
            "timings": {k: round(v, 3) for k, v in cluster.timings.items()},
            "window_s": win.end - win.start,
            "ops": {k: v for k, v in e2e.items() if not k.startswith("_")},
            "latency_ms": {
                kind: {f"p{p}": round(percentile(sample, p), 3)
                       for p in (50, 75, 90, 95, 97, 99, 100)}
                for kind, sample in (("read", e2e["_read_ms"]),
                                     ("update", e2e["_update_ms"]))
                if sample},
            "generator_lag_ms": {"p50": percentile(lag, 50),
                                 "p95": percentile(lag, 95),
                                 "max": max(lag)},
            "loop": win.notes,
            "counters": {k: v for k, v in delta.items()
                         if not k.endswith(".total")},
            "check": counts,
            "shorter_windows": {
                str(n): {k: v for k, v in end_to_end(win, n).items()
                         if k in ("ops_per_s", "read_p95_ms", "update_p95_ms")}
                for n in (5, 10, 15, 20, 30) if n < seconds},
        })

        result = {"correct": decide(checks), "attempted": e2e["attempted"],
                  "failed": e2e["failed"], "metrics": {},
                  "device": dict(device, memory_peak_bytes=memory_peak)}
        if not trace:
            for m in check_manifest.metrics_of(bm, cell_name, "end_to_end"):
                if m["name"] in e2e:
                    result["metrics"][m["name"]] = {
                        "value": e2e[m["name"]], "unit": m["unit"]}
        else:
            spans = TRACER.spans()
            prof = trace_reduce.reduce_dir(profile_dir)
            ctx = layers.Context(
                counters=delta, totals=totals, spans=spans, profile=prof,
                read_ms=e2e["_read_ms"], update_ms=e2e["_update_ms"],
                engines=n_eng, device_kind=device["kind"],
                max_groups=cfg["engine"]["max_groups"],
                max_peers=cfg["engine"]["max_peers"])
            for m in check_manifest.metrics_of(bm, cell_name, "per_layer"):
                v = layers.read(m["_reader"], ctx)
                if v is not None:
                    result["metrics"][m["name"]] = {"value": v,
                                                    "unit": m["unit"]}
            if prof is not None:
                result["device"]["busy_s"] = prof["busy_s"]
                result["device"]["window_s"] = prof["window_s"]
                result["breakdown"] = prof["breakdown"]
                summary["profile"] = prof["note"]
            summary["spans"] = layers.span_table(spans)
        result["checks"] = checks
        result["_summary"] = summary
        return result
    finally:
        if profiling:
            trace_reduce.stop()
        if teardown or sys.exc_info()[0] is not None:
            await cluster.shutdown()
            shutil.rmtree(workdir, ignore_errors=True)
