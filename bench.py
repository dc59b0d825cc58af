"""Benchmark: batched multi-raft commit throughput on the device plane.

Measures the north-star hot path (BASELINE.json config row 3/4): G raft
groups' quorum commit advancement as one [G, P] kernel per tick, with the
realistic per-tick host<->device traffic — upload the updated matchIndex
matrix, run the fused tick, download commit results.  commits/sec = total
log entries whose commit index advanced, summed over groups.

Dispatch is pipelined with a bounded in-flight window, matching how the
host runtime actually consumes the device plane: tick i+1's upload+launch
does not wait for tick i's commit download (commit acks are delivered to
waiting closures asynchronously), but no more than DEPTH ticks may be
outstanding so commit-ack latency stays bounded.  Acks are drained as
they arrive (non-blocking ``is_ready`` polling between submits), so the
reported latency is submit-to-arrival per tick — the commit-index ack
latency the host runtime observes — quantized by the submit interval,
with the device's completion round trip reported separately as its floor.

This is a synthetic loop around ``raft_tick`` (device-resident donated
state, one [G, P] upload per tick), NOT the program that serves —
``chip_smoke.py`` runs that.  Prints ONE JSON line, holding only what
this run measured, labelled with the device JAX reports:
  {"metric": ..., "value": N, "unit": "commits/s", "vs_baseline": N/1e6}
vs_baseline is against the BASELINE.md north-star target of 1M commits/s
(the reference repo publishes no benchmark numbers; see BASELINE.md).
"""

import json
import time
from collections import deque

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    from tpuraft.ops.tick import (
        ROLE_FOLLOWER,
        ROLE_LEADER,
        GroupState,
        TickParams,
        raft_tick,
    )
    from tpuraft.util.jax_cache import ensure_compile_cache

    ensure_compile_cache()

    G = 16384       # groups (north-star scale)
    P = 8           # peer slots
    VOTERS = 3      # 3-replica groups
    BATCH = 32      # entries acked per follower per tick (apply_batch)
    TICKS = 400
    WARMUP = 40

    rng = np.random.default_rng(0)
    state = GroupState.zeros(G, P)
    state.role = jnp.full((G,), ROLE_LEADER, jnp.int32)
    voter = np.zeros((G, P), bool)
    voter[:, :VOTERS] = True
    state.voter_mask = jnp.asarray(voter)
    state.pending_rel = jnp.ones((G,), jnp.int32)
    params = TickParams.make(1000, 100, 900)

    tick = jax.jit(raft_tick, donate_argnums=(0,))

    # host-side match bookkeeping: per tick, followers ack BATCH more
    # entries with realistic jitter (stragglers ack less).  Ack arrival is
    # workload generation, not framework work — precompute outside the
    # timed loop (int8: values fit; the cumulative matrix stays int32).
    host_match = np.zeros((G, P), np.int32)
    total = WARMUP + TICKS
    advances = rng.integers(BATCH // 2, BATCH + 1, (total, G, P)).astype(np.int8)
    advances[:, :, VOTERS:] = 0

    inflight = deque()   # (submit_time, tick_idx, device commit array)
    lat = []
    last_commit = None   # most recently materialized commit array
    DEPTH = 16           # provisional for warmup; re-sized to the link below

    def drain_one():
        nonlocal last_commit
        ts, idx, arr = inflight.popleft()
        last_commit = np.asarray(arr)        # materialize = commit ack
        lat.append(time.perf_counter() - ts)

    def submit(i):
        nonlocal state
        host_match[:, :] += advances[i]
        # the per-tick upload: one coalesced [G, P] transfer.  Copy: the
        # async transfer must not observe later in-place += mutations.
        state.match_rel = jax.device_put(host_match.copy())
        new_state, out = tick(state, jnp.int32(i), params)
        state = new_state
        commit = out.commit_rel
        commit.copy_to_host_async()
        inflight.append((time.perf_counter(), i, commit))
        # drain acks as they actually arrive (non-blocking), then enforce
        # the bound: at most DEPTH ticks outstanding.
        while inflight and inflight[0][2].is_ready():
            drain_one()
        while len(inflight) >= DEPTH:
            drain_one()

    for i in range(WARMUP):
        submit(i)
    while inflight:
        drain_one()

    # dispatch->completion latency floor of one tick on this device: the
    # minimum observable ack latency regardless of pipelining.
    rtts = []
    for _ in range(5):
        t1 = time.perf_counter()
        state2, out2 = tick(state, jnp.int32(0), params)
        out2.commit_rel.block_until_ready()
        rtts.append(time.perf_counter() - t1)
        state = state2
    completion_rtt_ms = round(min(rtts) * 1000, 2)

    # post-compile dispatch cost: a short unsynchronized burst
    burst = 8
    t_b = time.perf_counter()
    for i in range(WARMUP, WARMUP + burst):
        submit(i)
    dispatch_s = (time.perf_counter() - t_b) / burst
    while inflight:
        drain_one()

    # size the in-flight window to the device, not a constant: enough
    # outstanding ticks to cover the completion round trip at the
    # measured dispatch cost (plus margin)
    DEPTH = max(4, min(64, int(min(rtts) / max(dispatch_s, 1e-4)) + 4))

    # three measurement passes, report the MEDIAN: robust to one bad
    # window on a shared host without the upward bias of max
    passes = []
    half = (TICKS - burst) // 3
    start_i = WARMUP + burst
    for _ in range(3):
        lat.clear()
        base_commits = int(last_commit.sum())
        t0 = time.perf_counter()
        for i in range(start_i, start_i + half):
            submit(i)
        while inflight:
            drain_one()
        elapsed = time.perf_counter() - t0
        pass_commits = int(last_commit.sum()) - base_commits
        lat_ms = sorted(x * 1000 for x in lat)
        passes.append({
            "cps": pass_commits / elapsed,
            "tps": half / elapsed,
            "p50": lat_ms[len(lat_ms) // 2],
            "p99": lat_ms[int(len(lat_ms) * 0.99)],
        })
        start_i += half
    med = sorted(passes, key=lambda r: r["cps"])[len(passes) // 2]
    commits_per_sec = med["cps"]
    p50, p99 = med["p50"], med["p99"]

    dev = jax.devices()[0]
    print(json.dumps({
        "metric": "multiraft_batched_commits_per_sec_16k_groups",
        "value": round(commits_per_sec, 1),
        "unit": "commits/s",
        "vs_baseline": round(commits_per_sec / 1e6, 3),
        "extra": {
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())},
            "groups": G, "peer_slots": P, "voters": VOTERS,
            # the generator's mean ack advance (U[16,32]): commits/s
            # above is this x G x ticks/s, so ticks/s is the measurement
            "commits_per_tick_per_group": round(
                commits_per_sec / max(med["tps"], 1e-9) / G, 3),
            "pipeline_depth": DEPTH,
            "dispatch_ms": round(dispatch_s * 1000, 2),
            "ticks_per_sec": round(med["tps"], 1),
            # all raw passes reported so the aggregation is explicit
            "aggregation": "median_of_3_passes",
            "pass_commits_per_sec": [round(r["cps"], 1) for r in passes],
            "ack_p50_ms": round(p50, 3), "ack_p99_ms": round(p99, 3),
            "completion_rtt_ms": completion_rtt_ms,
            "baseline": "north-star 1e6 commits/s (BASELINE.md; reference publishes none)",
        },
    }))


if __name__ == "__main__":
    main()
