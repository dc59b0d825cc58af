"""From the profiler's trace to device metrics.

The benchmark brackets its own ``jax.profiler`` session around the traced
run (three engines share the process, so ``TickOptions.profile_dir`` stays
empty) and marks the measured window with a ``TraceAnnotation``.  The trace is
read with nothing but JAX (``ProfileData``) into plain lists —
``[{"name", "lines": [{"name", "events": [[name, start_ns, dur_ns], ...]}]}]``
— and everything below works on those, so the same code reduces the small
recorded trace kept with the tests.

Device busy time is the union of the intervals in which an operation ran on
a device plane, clipped to the window and averaged over the chips that ran
anything; a kernel's time is the device duration of its program's events.
"""

from __future__ import annotations

import bisect
import glob
import os

WINDOW_EVENT = "benchmark.window"
DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
UNNAMED_GAP = "host python, no annotation (serve, tick build/apply, timers)"
GAPS_NAMED = 400


def start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # asyncio-heavy code: Python frames
    opts.host_tracer_level = 2       # would swamp the trace
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def annotation(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def load_planes(log_dir: str) -> list:
    import jax

    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return []
    data = jax.profiler.ProfileData.from_file(files[-1])
    return [{"name": pl.name,
             "lines": [{"name": ln.name,
                        "events": [[ev.name, ev.start_ns, ev.duration_ns]
                                   for ev in ln.events]}
                       for ln in pl.lines]}
            for pl in data.planes]


def union_seconds(intervals: list) -> tuple:
    """(seconds covered, merged intervals) of ``[(start_ns, end_ns), ...]``."""
    merged: list = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged) / 1e9, merged


def _window(planes: list) -> tuple:
    """(start_ns, end_ns, the host line that holds the marker)."""
    for pl in planes:
        if not pl["name"].startswith(HOST_PLANE):
            continue
        for ln in pl["lines"]:
            for name, t, d in ln["events"]:
                if name == WINDOW_EVENT:
                    return t, t + d, ln
    return None, None, None


def reduce_planes(planes: list) -> dict | None:
    """busy_s, window_s, each device program's event durations (seconds, by
    event name), and the breakdown: the device operations that took most
    time, and the idle gaps by what the host's main thread was doing."""
    w0, w1, host_line = _window(planes)
    devices = [pl for pl in planes if pl["name"].startswith(DEVICE_PLANE)]
    if not devices:
        return None
    if w0 is None:       # no marker: the whole traced span
        starts = [e[1] for pl in devices for ln in pl["lines"]
                  for e in ln["events"]]
        ends = [e[1] + e[2] for pl in devices for ln in pl["lines"]
                for e in ln["events"]]
        if not starts:
            return None
        w0, w1 = min(starts), max(ends)

    busy, merged_first = [], None
    op_seconds: dict = {}
    events: dict = {}
    for pl in devices:
        by_line = {ln["name"]: ln["events"] for ln in pl["lines"]}
        ops = by_line.get(OPS_LINE) or by_line.get(MODULES_LINE) or []
        spans = []
        for name, t, d in ops:
            a, b = max(t, w0), min(t + d, w1)
            if b > a:
                spans.append((a, b))
                short = name.split(" = ")[0][:80]
                op_seconds[short] = op_seconds.get(short, 0.0) + (b - a) / 1e9
        if not spans:
            continue
        seconds, merged = union_seconds(spans)
        busy.append(seconds)
        if merged_first is None:
            merged_first = merged
        for name, t, d in by_line.get(MODULES_LINE, []):
            if w0 <= t < w1:
                events.setdefault(name, []).append(d / 1e9)
    if not busy:
        return None

    # -- idle gaps of the first device, by what the host was doing ----------
    gaps = []
    edge = w0
    for a, b in merged_first:
        if a > edge:
            gaps.append((a - edge, edge, a))
        edge = max(edge, b)
    if w1 > edge:
        gaps.append((w1 - edge, edge, w1))
    gaps.sort(reverse=True)
    host = sorted((t, t + d, name) for name, t, d in
                  (host_line["events"] if host_line else [])
                  if name != WINDOW_EVENT and d > 0)
    host_starts = [h[0] for h in host]
    longest = max((h[1] - h[0] for h in host), default=0)
    by_host: dict = {}
    for length, a, b in gaps[:GAPS_NAMED]:
        best, best_overlap = UNNAMED_GAP, 0.0
        lo = bisect.bisect_left(host_starts, a - longest)
        hi = bisect.bisect_right(host_starts, b)
        for h0, h1, name in host[lo:hi]:
            overlap = min(h1, b) - max(h0, a)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        if best_overlap < 0.5 * length:
            best = UNNAMED_GAP
        by_host[best] = by_host.get(best, 0.0) + length / 1e9
    window_s = (w1 - w0) / 1e9
    busy_s = sum(busy) / len(busy)

    def top(d: dict) -> list:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:10]

    return {"busy_s": busy_s, "window_s": window_s, "events": events,
            "breakdown": {"device_ops": top(op_seconds),
                          "idle_gaps": top(by_host)},
            "note": {"devices": len(busy), "gaps": len(gaps),
                     "longest_gap_ms": gaps[0][0] / 1e6 if gaps else 0.0,
                     "programs": {k: len(v) for k, v in events.items()}}}


def durations(profile: dict, substring: str) -> list:
    """Device durations, in seconds, of every program whose name has
    ``substring`` in it."""
    return [d for name, durs in profile["events"].items()
            if substring in name for d in durs]


def reduce_dir(log_dir: str) -> dict | None:
    return reduce_planes(load_planes(log_dir))
