"""CPU host bench: scale ladder for the REAL protocol plane (VERDICT
r2 #1).  1K -> 4K -> 16K raft groups per process under sustained write
load — engine ticks + multilog fsync + RPC + FSM apply, NO synthetic
acks — recording commits/s, ack p50/p99, RSS, and asyncio task count
per G, plus the per-G overhead curve.

Topology per ladder rung: ONE process hosts all three replica
endpoints of every group (in-proc RPC; VERDICT: "in-proc or
loopback-TCP is fine"), each endpoint with its own MultiRaftEngine and
its own shared-journal multilog directory (real fsync on every append
round).  Leadership spreads by election priority.  The offered load is
paced per group so the ladder measures protocol capacity at scale, not
collapse behavior (the 3-process loopback-TCP variant lives in
bench_e2e.py and is recorded separately at its own G).

Each rung runs in a fresh subprocess (clean RSS accounting, no
cross-rung warm state), pinned by its parent with ``JAX_PLATFORMS=cpu``
— a CPU host bench until ROADMAP A1/C8 replaces it; no number here is
a device number.  Writes BENCH_SCALE.json.
"""

import argparse
import asyncio
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))


async def run_rung(args) -> dict:
    import random
    import resource

    from tpuraft.conf import Configuration
    from tpuraft.core.engine import MultiRaftEngine
    from tpuraft.core.node import Node
    from tpuraft.core.node_manager import NodeManager
    from tpuraft.core.state_machine import StateMachine
    from tpuraft.entity import PeerId, Task
    from tpuraft.options import NodeOptions, TickOptions
    from tpuraft.rpc.transport import (InProcNetwork, InProcTransport,
                                       RpcServer)

    G, R = args.groups, args.replicas
    net = InProcNetwork()
    eps = [PeerId.parse(f"127.0.0.1:{7800 + i}") for i in range(R)]

    class CountFSM(StateMachine):
        applied = 0

        async def on_apply(self, it):
            while it.valid():
                CountFSM.applied += 1
                it.next()

    cap = 1 << max(4, (G + 3).bit_length())
    engines, factories, managers, transports = [], [], [], []
    for i, ep in enumerate(eps):
        server = RpcServer(ep.endpoint)
        manager = NodeManager(server)
        net.bind(server)
        engine = MultiRaftEngine(TickOptions(
            max_groups=cap, max_peers=4, tick_interval_ms=20))
        await engine.start()
        engines.append(engine)
        factories.append(engine.ballot_box_factory())
        managers.append(manager)
        transports.append(InProcTransport(net, ep.endpoint))

    t_boot = time.monotonic()
    nodes: list[list[Node]] = [[] for _ in range(R)]

    async def boot_group(k: int) -> None:
        gid = f"g{k}"
        peers = [PeerId(ep.ip, ep.port, 0, 100 if k % R == i else 10)
                 for i, ep in enumerate(eps)]
        for i in range(R):
            opts = NodeOptions(
                election_timeout_ms=args.election_timeout_ms,
                initial_conf=Configuration(list(peers)),
                fsm=CountFSM(),
                log_uri=f"multilog://{args.dir}/store{i}/mlog#{gid}",
                raft_meta_uri=(
                    f"multimeta://{args.dir}/store{i}/meta#{gid}"
                    if args.meta == "multimeta" else "memory://"),
                enable_metrics=False)
            opts.raft_options.quiesce_after_rounds = args.quiesce
            node = Node(gid, peers[i], opts, transports[i],
                        ballot_box_factory=factories[i])
            node.node_manager = managers[i]
            managers[i].add(node)
            if not await node.init():
                raise RuntimeError(f"init failed {gid}@{i}")
            # defer this group's first election far past boot: at high G
            # the already-booted groups' elections + heartbeats otherwise
            # interfere superlinearly with the remaining inits (measured:
            # 16K-rung boot crawling at >45ms/node)
            eng = engines[i]
            eng.elect_deadline[node._ctrl.slot] = eng.now_ms() + 3_600_000
            nodes[i].append(node)

    # batched-concurrent boot (VERDICT r3 #7: 16Kx1 boot was 183s, 16Kx3
    # 1356s, serialized one node.init at a time): inits inside a batch
    # overlap their await points; batches stay bounded so the loop and
    # engine registration never see an unbounded task herd
    BOOT_BATCH = 256
    for k0 in range(0, G, BOOT_BATCH):
        await asyncio.gather(*(boot_group(k)
                               for k in range(k0, min(G, k0 + BOOT_BATCH))))
    # release elections en masse, jittered over ~4 timeouts: the
    # election_due mask fires them from the device tick (the mass
    # re-election path proven at 4K in test_engine_protocol)
    import numpy as np
    rng = np.random.default_rng(0)
    for i in range(R):
        eng = engines[i]
        now = eng.now_ms()
        spread_ms = (int(float(args.elect_spread_s) * 1000)
                     or 4 * args.election_timeout_ms)
        jit = rng.integers(0, spread_ms, eng.G)
        eng.elect_deadline[:] = now + args.election_timeout_ms // 4 + jit
        eng.mark_dirty()
    boot_s = time.monotonic() - t_boot

    # leadership: priority placement, converge to >= 98%
    deadline = time.monotonic() + 120 + G * 0.05
    led: list[Node] = []
    last_print = 0.0
    while time.monotonic() < deadline:
        led = [n for row in nodes for n in row if n.is_leader()]
        if len(led) >= int(G * 0.98):
            break
        if time.monotonic() - last_print > 15:
            last_print = time.monotonic()
            print(f"PROGRESS leaders={len(led)}/{G} "
                  f"t={time.monotonic() - t_boot - boot_s:.0f}s",
                  flush=True)
        await asyncio.sleep(0.5)
    elect_s = time.monotonic() - t_boot - boot_s

    if args.idle_window > 0:
        # -- idle beat-plane probe (ISSUE 4 acceptance): no write drive.
        # Seed one committed write per group so every group is provably
        # at a fully-matched tail, let quiescence (if enabled) take
        # hold, then measure the beat plane's RPC rate over a quiet
        # window from the hub + engine counters.
        async def seed(node: Node) -> None:
            fut = asyncio.get_running_loop().create_future()

            def done_cb(st, fut=fut):
                if not fut.done():
                    fut.set_result(st)

            await node.apply(Task(data=b"s", done=done_cb))
            await asyncio.wait_for(fut, 60)

        for k0 in range(0, len(led), 256):
            await asyncio.gather(*(seed(n) for n in led[k0:k0 + 256]))
        # settle: quiesce_after_rounds fully-acked beat rounds + the
        # handshake round, at the (possibly floor-raised) beat interval
        hb_s = max(float(e.hb_ms[e.has_ctrl].max()) for e in engines
                   if e.has_ctrl.any()) / 1000.0
        settle = min(120.0, (args.quiesce + 3) * hb_s + 2.0)
        print(f"PROGRESS idle-probe settling {settle:.0f}s "
              f"(hb={hb_s * 1000:.0f}ms)", flush=True)
        await asyncio.sleep(settle)
        hubs = [m.heartbeat_hub for m in managers]

        def beat_counters():
            return {
                "rpcs": sum(h.rpcs_sent for h in hubs),
                "beats": sum(h.beats_sent + h.fast_beats_sent
                             for h in hubs),
                "lease_rpcs": sum(h.lease_rpcs_sent for h in hubs),
            }

        c0 = beat_counters()
        await asyncio.sleep(args.idle_window)
        c1 = beat_counters()
        w = args.idle_window
        from tpuraft.ops.tick import ROLE_LEADER as _RL
        res = {
            "groups": G,
            "replicas": R,
            "leaders": len(led),
            "quiesce_after_rounds": args.quiesce,
            "idle_window_s": w,
            "beat_rpcs_per_s": round((c1["rpcs"] - c0["rpcs"]) / w, 2),
            "beats_per_s": round((c1["beats"] - c0["beats"]) / w, 2),
            "lease_rpcs_per_s": round(
                (c1["lease_rpcs"] - c0["lease_rpcs"]) / w, 2),
            "idle_rpcs_per_s": round(
                (c1["rpcs"] - c0["rpcs"]
                 + c1["lease_rpcs"] - c0["lease_rpcs"]) / w, 2),
            "quiescent_groups": sum(int(e.quiescent.sum())
                                    for e in engines),
            "quiescent_leaders": sum(
                int((e.quiescent & (e.role == _RL)).sum())
                for e in engines),
            "groups_quiesced": sum(h.groups_quiesced for h in hubs),
            "groups_woken": sum(h.groups_woken for h in hubs),
            "lease_expiries": sum(h.lease_expiries for h in hubs),
            # tick-plane gauges (fleet observability): the [G]-lane
            # reductions metrics_text serves — the per-engine
            # hibernation fractions here must agree with the raw
            # quiescent_groups count above (same arrays, one reduce)
            "lane_stats": [e.lane_stats() for e in engines],
            "tick_p99_ms": round(max(
                e.tick_hists["tick_total_ms"].percentile(99)
                for e in engines), 3),
            "eto_floor_ms": max(e._floor_applied_ms for e in engines),
            "eff_eto_ms": int(max(int(e.eto_ms[e.has_ctrl].max())
                                  for e in engines if e.has_ctrl.any())),
            "rss_mb": round(
                resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                / 1024, 1),
        }
        print("RESULT " + json.dumps(res), flush=True)
        os._exit(0)

    ok = [0]
    errs = [0]
    errs_by: dict[str, int] = {}  # error-class attribution (VERDICT r4 #7)
    lats: list[tuple[float, float]] = []  # (completion time, latency)
    t_drive0 = time.monotonic()
    stop_at = time.monotonic() + args.duration
    payload = b"x" * 16

    # replica rows per group, so the driver can follow leadership the
    # way a RouteTable client does: without this, a mid-window election
    # turns every later apply to the stale leader into EPERM noise
    # (r5 attribution: ALL residual 4Kx3 errors were EPERM/ENEWLEADER
    # from driving the boot-time leader list)
    by_group: dict[str, list[Node]] = {}
    for row in nodes:
        for n in row:
            by_group.setdefault(n.group_id, []).append(n)

    async def drive(node: Node) -> None:
        await asyncio.sleep(random.random() * args.pace_ms / 1e3)
        i = 0
        while time.monotonic() < stop_at:
            i += 1
            if not node.is_leader():
                cur = next((n for n in by_group[node.group_id]
                            if n.is_leader()), None)
                if cur is None:
                    await asyncio.sleep(args.pace_ms / 1e3)  # electing
                    continue
                node = cur
            fut = asyncio.get_running_loop().create_future()
            left = [args.batch]
            t0 = time.perf_counter()

            def cb(st, left=left, t0=t0, sample=True):
                if st.is_ok():
                    ok[0] += 1
                else:
                    errs[0] += 1
                    name = st.raft_error.name
                    errs_by[name] = errs_by.get(name, 0) + 1
                left[0] -= 1
                if left[0] == 0:
                    if sample:
                        lats.append((time.monotonic() - t_drive0,
                                     time.perf_counter() - t0))
                    if not fut.done():
                        fut.set_result(None)

            await node.apply_batch(
                [Task(data=payload, done=cb) for _ in range(args.batch)])
            try:
                await asyncio.wait_for(fut, 30)
            except asyncio.TimeoutError:
                pass
            await asyncio.sleep(args.pace_ms / 1e3)

    t0 = time.monotonic()
    await asyncio.gather(*(drive(n) for n in led))
    elapsed = time.monotonic() - t0
    # steady-state view: samples completing in the second half of the
    # window, after the boot-adjacent stragglers (late elections, cold
    # engine) have flushed — attributes how much of the overall p99 is
    # transient vs steady behavior
    half = elapsed / 2
    late = sorted(lt for (ts, lt) in lats if ts >= half)
    lats_v = sorted(lt for (_ts, lt) in lats)

    def pct(s, p):
        return round(s[min(len(s) - 1, int(p * len(s)))] * 1e3, 2) \
            if s else None

    res = {
        "groups": G,
        "replicas": R,
        "leaders": len(led),
        "boot_s": round(boot_s, 1),
        "elect_s": round(elect_s, 1),
        "commits_per_sec": round(ok[0] / elapsed, 1),
        "ok": ok[0],
        "errors": errs[0],
        "errors_by_class": dict(sorted(errs_by.items())),
        "ack_p50_ms": pct(lats_v, 0.50),
        "ack_p99_ms": pct(lats_v, 0.99),
        "ack_p50_ms_steady": pct(late, 0.50),
        "ack_p99_ms_steady": pct(late, 0.99),
        "rss_mb": round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024, 1),
        "asyncio_tasks": len(asyncio.all_tasks()),
        "applied_total": CountFSM.applied,
        "pace_ms": args.pace_ms,
        "batch": args.batch,
        "meta": args.meta,
        "engine_ticks": sum(e.ticks for e in engines),
        # density-aware floors (ISSUE 4): the effective operating point
        # the engine derived — no hand-tuned timeout in the command line
        "eto_floor_ms": max(e._floor_applied_ms for e in engines),
        "eff_eto_ms": int(max(int(e.eto_ms[e.has_ctrl].max())
                              for e in engines if e.has_ctrl.any())),
        "requested_eto_ms": args.election_timeout_ms,
    }
    print("RESULT " + json.dumps(res), flush=True)
    # skip graceful teardown of 3G nodes: the subprocess exits and the
    # measurement is done — teardown at 48K nodes costs minutes
    os._exit(0)


def _run_idle_probe(args) -> None:
    """A/B the idle beat plane at one (G, R): quiescence off vs on.
    Acceptance: idle beat-plane RPC rate drops >= 10x with quiescence
    (the hub's rpcs+lease counters are the measurement)."""
    import tempfile

    from tpuraft.storage.multilog import ensure_built

    ensure_built()
    g = int(args.rungs.split(",")[0])
    window = args.duration if args.duration > 0 else 30.0
    pair = {}
    for label, quiesce in (("quiesce_off", 0),
                           ("quiesce_on", args.quiesce or 8)):
        workdir = tempfile.mkdtemp(prefix=f"tpuraft_idle_{g}_")
        cmd = [sys.executable, os.path.join(REPO, "bench_scale.py"),
               "--rung", "--groups", str(g), "--dir", workdir,
               "--replicas", str(args.replicas),
               "--elect-spread-s", str(args.elect_spread_s),
               "--duration", "0", "--idle-window", str(window),
               "--quiesce", str(quiesce), "--meta", args.meta,
               "--election-timeout-ms", str(args.election_timeout_ms)]
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
        p = subprocess.Popen(cmd, stdout=subprocess.PIPE, env=env)
        row = None
        for line in p.stdout:
            line = line.decode().strip()
            if line.startswith("RESULT "):
                row = json.loads(line[len("RESULT "):])
            elif line.startswith("PROGRESS"):
                print(line, flush=True)
        p.wait()
        pair[label] = row or {"error": "rung produced no result"}
        print(label, json.dumps(pair[label]), flush=True)
        subprocess.run(["rm", "-rf", workdir])
    off = pair.get("quiesce_off") or {}
    on = pair.get("quiesce_on") or {}
    if "idle_rpcs_per_s" in off and "idle_rpcs_per_s" in on:
        denom = max(on["idle_rpcs_per_s"], 0.01)
        pair["rpc_reduction_x"] = round(off["idle_rpcs_per_s"] / denom, 1)
    path = os.path.join(REPO, args.json_out)
    out = {}
    if os.path.exists(path):
        with open(path) as f:
            out = json.load(f)
    out["idle_beat_plane"] = pair
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"idle_probe": "done",
                      "rpc_reduction_x": pair.get("rpc_reduction_x")}))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rungs", default="1024,4096,16384")
    ap.add_argument("--offered", default="3000",
                    help="offered entries/s; one value or comma list "
                         "matched to --rungs (capacity at high G is "
                         "1-core bound — over-offering measures queue "
                         "collapse, not protocol capacity)")
    # parent-side replicas passthrough (single-voter rungs measure the
    # engine+journal+FSM plane at G beyond the 3-replica election
    # capacity of one core)
    ap.add_argument("--duration", type=float, default=20.0)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--election-timeout-ms", type=int, default=10000)
    ap.add_argument("--json-out", default="BENCH_SCALE.json")
    ap.add_argument("--rung", action="store_true",
                    help="internal: run one rung in this process")
    ap.add_argument("--groups", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--pace-ms", type=float, default=0.0)
    ap.add_argument("--elect-spread-s", default="0",
                    help="window over which the boot-deferred elections "
                         "release (0 = 4x election timeout); one value "
                         "or comma list matched to --rungs; widen at "
                         "high GxR so the election herd stays under the "
                         "host's per-second election capacity")
    ap.add_argument("--meta", default="memory",
                    choices=["memory", "multimeta"],
                    help="raft meta storage: memory:// (volatile, the "
                         "r1-r4 ladder default) or multimeta:// (fsynced "
                         "{term, votedFor} via the shared group-commit "
                         "journal — the durable-meta election-herd "
                         "measurement, VERDICT r4 #3)")
    ap.add_argument("--dir", default="")
    ap.add_argument("--quiesce", type=int, default=0,
                    help="RaftOptions.quiesce_after_rounds: >0 lets "
                         "idle groups hibernate (store-level lease "
                         "liveness; ISSUE 4)")
    ap.add_argument("--idle-window", type=float, default=0.0,
                    help="rung-internal: measure the IDLE beat plane "
                         "over this window instead of driving writes")
    ap.add_argument("--idle-probe", action="store_true",
                    help="run the quiescence A/B idle probe at "
                         "--rungs[0] x --replicas (quiesce off vs on), "
                         "merge the pair into BENCH_SCALE.json as "
                         "'idle_beat_plane', and leave the drive rows "
                         "untouched")
    args = ap.parse_args()

    if args.rung:
        asyncio.run(run_rung(args))
        return

    if args.idle_probe:
        _run_idle_probe(args)
        return

    import tempfile

    from tpuraft.storage.multilog import ensure_built

    ensure_built()
    rows = []
    rung_list = [int(x) for x in args.rungs.split(",")]
    offered_list = [float(x) for x in args.offered.split(",")]
    if len(offered_list) == 1:
        offered_list *= len(rung_list)
    spread_list = [float(x) for x in str(args.elect_spread_s).split(",")]
    if len(spread_list) == 1:
        spread_list *= len(rung_list)
    if len(offered_list) != len(rung_list) or \
            len(spread_list) != len(rung_list):
        raise SystemExit("--offered/--elect-spread-s list lengths must "
                         "match --rungs (or be a single value)")
    for g, offered, spread in zip(rung_list, offered_list, spread_list):
        # offered load below the measured 1-core protocol capacity, so
        # ack latency reflects service time, not queue growth:
        # pace = G*batch/offered; the window stretches so every group
        # gets >= ~2 turns even when pace > duration
        pace_ms = max(200.0, g * args.batch / offered * 1000.0)
        rung_duration = max(args.duration, pace_ms * 2.0 / 1000.0)
        workdir = tempfile.mkdtemp(prefix=f"tpuraft_scale_{g}_")
        cmd = [sys.executable, os.path.join(REPO, "bench_scale.py"),
               "--rung", "--groups", str(g), "--dir", workdir,
               "--replicas", str(args.replicas),
               "--elect-spread-s", str(spread),
               "--duration", str(rung_duration), "--batch", str(args.batch),
               "--pace-ms", str(pace_ms), "--meta", args.meta,
               "--election-timeout-ms", str(args.election_timeout_ms)]
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
        t0 = time.monotonic()
        p = subprocess.Popen(cmd, stdout=subprocess.PIPE, env=env)
        row = None
        for line in p.stdout:
            line = line.decode().strip()
            if line.startswith("RESULT "):
                row = json.loads(line[len("RESULT "):])
            elif line.startswith("PROGRESS"):
                print(line, flush=True)
        p.wait()
        if row is None:
            row = {"groups": g, "error": "rung produced no result"}
        row["wall_s"] = round(time.monotonic() - t0, 1)
        if "error" not in row:
            row["offered_per_sec"] = round(
                g * args.batch / (pace_ms / 1000.0), 1)
        rows.append(row)
        print(json.dumps(row), flush=True)
        subprocess.run(["rm", "-rf", workdir])

    complete = [r for r in rows if "error" not in r]
    prev = {}
    prev_path = os.path.join(REPO, args.json_out)
    if os.path.exists(prev_path):
        with open(prev_path) as f:
            prev = json.load(f)
    out = {
        "metric": "protocol_plane_scale_ladder",
        "rows": rows,
        "per_g_overhead": {
            str(r["groups"]): {
                "rss_kb_per_group": round(r["rss_mb"] * 1024 / r["groups"], 1),
                "tasks_per_group": round(
                    r["asyncio_tasks"] / r["groups"], 2),
            } for r in complete
        },
        "stack": "in-proc RPC x3 replica endpoints, multilog shared-journal "
                 "fsync per store, engine protocol plane, priority "
                 "placement, paced offered load (~8K entries/s)",
        "note": "one PROCESS hosts all three replicas of every group; the "
                "3-process loopback-TCP variant is BENCH_E2E.json",
    }
    if "idle_beat_plane" in prev:   # the quiescence A/B rides along
        out["idle_beat_plane"] = prev["idle_beat_plane"]
    with open(os.path.join(REPO, args.json_out), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"rungs": len(rows), "ok": len(complete)}))


if __name__ == "__main__":
    main()
