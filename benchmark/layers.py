"""Per-layer metrics: one generic reader per kind of source.

A metric is a data file (``benchmark/layer_metrics/<name>.json``) whose
``reader`` names a kind below and its parameters, so a new span or counter of
the program needs a file, not code.  A reader that finds nothing to read
returns None and the harness leaves the metric out of the line; none returns
0 for a share of a roofline.
"""

from __future__ import annotations

from dataclasses import dataclass

from benchmark import peaks
from benchmark.trace_reduce import durations
from benchmark.stats import percentile, stat


@dataclass
class Context:
    """What a traced run has to read from."""
    counters: dict          # the program's counters, end minus start of window
    totals: dict            # the same counters since boot, read once settled
    spans: list             # Tracer spans of the window's sampled operations
    profile: dict | None    # trace_reduce.reduce_dir's result
    read_ms: list
    update_ms: list
    engines: int
    device_kind: str
    max_groups: int
    max_peers: int


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def _counter_ratio(r: dict, ctx: Context):
    """A ratio of two counters over the window, or (``"over": "run"``) since
    boot once everything in flight has settled: a share of events that are
    counted when they start and when they end is only whole over the run."""
    src = ctx.totals if r.get("over", "window") == "run" else ctx.counters
    den = src.get(r["denominator"], 0)
    if r["numerator"] not in src or den <= 0:
        return None
    return r.get("scale", 1.0) * src[r["numerator"]] / den


def _span(r: dict, ctx: Context):
    durs = [s["dur_s"] for s in ctx.spans if s["name"] == r["span"]]
    if not durs:
        return None
    return r.get("scale", 1.0) * stat(durs, r["stat"])


def _histogram(r: dict, ctx: Context):
    """Mean over the window of the named engine histograms, summed; the
    engine is the one that led most ticks' worth of work ("leader_heaviest":
    most leaders at the close) or all of them together."""
    if r["stat"] != "mean":
        raise ValueError("histogram readers give the window's mean only")
    if r["engine"] == "leader_heaviest":
        which = [max(range(ctx.engines),
                     key=lambda i: ctx.counters.get(f"engine{i}.leaders_now",
                                                    0))]
    elif r["engine"] == "all":
        which = list(range(ctx.engines))
    else:
        raise ValueError(f"unknown engine selector {r['engine']!r}")
    total = 0.0
    for h in r["histograms"]:
        n = sum(ctx.counters.get(f"engine{i}.{h}.count", 0) for i in which)
        if n <= 0:
            return None
        total += sum(ctx.counters[f"engine{i}.{h}.total"] for i in which) / n
    return total


def _trace_event(r: dict, ctx: Context):
    if ctx.profile is None:
        return None
    durs = durations(ctx.profile, r["event"])
    if not durs:
        return None
    return r.get("scale", 1.0) * stat(durs, r["stat"])


def _roofline(r: dict, ctx: Context):
    """The least time the chip could take to move the call's bytes, from its
    shapes, over the mean time the trace shows."""
    if ctx.profile is None:
        return None
    durs = durations(ctx.profile, r["event"])
    if not durs:
        return None
    n_bytes = peaks.WORK_FNS[r["bytes_fn"]](ctx.max_groups, ctx.max_peers)
    least = peaks.memory_bound_seconds(ctx.device_kind, n_bytes)
    return 100.0 * least / (sum(durs) / len(durs))


def _device_idle(r: dict, ctx: Context):
    if ctx.profile is None or ctx.profile["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.profile["busy_s"] / ctx.profile["window_s"])


def _latency(r: dict, ctx: Context):
    sample = ctx.read_ms if r["op"] == "read" else ctx.update_ms
    return percentile(sample, r["percentile"]) if sample else None


READERS = {"counter_ratio": _counter_ratio, "span": _span,
           "histogram": _histogram, "trace_event": _trace_event,
           "roofline": _roofline, "device_idle": _device_idle,
           "latency": _latency}


def read(reader: dict, ctx: Context):
    return READERS[reader["kind"]](reader, ctx)


def span_table(spans: list) -> dict:
    """Every span name with its count, median and p95 in ms: the summary
    line's view of what the tracer kept."""
    by_name: dict = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s["dur_s"] * 1e3)
    return {name: {"n": len(d), "median_ms": round(stat(d, "median"), 3),
                   "p95_ms": round(percentile(d, 95), 3)}
            for name, d in sorted(by_name.items())}
