"""CPU host bench: end-to-end multi-raft benchmark (VERDICT r1 #2).
Real store PROCESSES — C++ epoll transport between them, shared C++
multi-group journal log engine (one fsync per flush round across
groups), the MultiRaftEngine driving elections/commits — with
client-measured committed entries/s and commit-ack latency.

Topology: 3 store processes, each hosting one replica of every group;
leadership is spread by election priority (group k prefers endpoint
k % 3).  Appliers run in the leader's process (the reference's
benchmark drivers live in-JVM too); every op is one raft entry carried
through log fsync -> pipelined AppendEntries -> follower fsync ->
quorum reduce on the engine plane -> FSM apply -> ack.

Prints ONE JSON line and writes BENCH_E2E.json.

vs_baseline is against 1e5 ops/s — the (unverifiable, recollection-only)
upstream small-payload figure in BASELINE.md; the reference repo
publishes no benchmark numbers (mount empty).

Device note: a CPU host bench until ROADMAP A1/C8 replaces it.  The
parent pins every store child with ``JAX_PLATFORMS=cpu`` (three
processes cannot share one chip), where ``TickOptions.backend="auto"``
resolves to the numpy twin: no number here is a device number.
"""

import argparse
import asyncio
import json
import os
import signal
import socket
import struct
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))


# ===========================================================================
# store process
# ===========================================================================

async def run_store(args) -> None:
    from tpuraft.conf import Configuration
    from tpuraft.core.engine import MultiRaftEngine
    from tpuraft.core.node import Node
    from tpuraft.core.node_manager import NodeManager
    from tpuraft.core.state_machine import StateMachine
    from tpuraft.entity import PeerId, Task
    from tpuraft.options import NodeOptions, TickOptions
    from tpuraft.rpc.native_tcp import NativeTcpRpcServer, NativeTcpTransport

    me = args.index
    endpoints = args.peers.split(",")
    G = args.groups
    base = os.path.join(args.dir, f"store{me}")

    class CountFSM(StateMachine):
        applied = 0

        async def on_apply(self, it):
            while it.valid():
                CountFSM.applied += 1
                it.next()

    server = NativeTcpRpcServer(endpoints[me])
    await server.start()
    manager = NodeManager(server)
    transport = NativeTcpTransport(endpoint=endpoints[me])
    cap = 1 << max(4, (G + 3).bit_length())
    engine = MultiRaftEngine(TickOptions(
        max_groups=cap, max_peers=4, tick_interval_ms=10))
    await engine.start()
    factory = engine.ballot_box_factory()

    # store-wide SAFE read-confirmation amortizer: the batcher is
    # engine-agnostic (it only needs nodes + replicators), so the raw
    # protocol-plane bench exercises the same coalesced read fences the
    # RheaKV stack serves through
    from tpuraft.rheakv.store_engine import ReadConfirmBatcher

    read_batcher = ReadConfirmBatcher()

    nodes = []
    for k in range(G):
        gid = f"g{k}"
        # leader placement: endpoint (k % n) gets the high priority
        peers = [
            PeerId(ep.split(":")[0], int(ep.split(":")[1]), 0,
                   100 if k % len(endpoints) == i else 10)
            for i, ep in enumerate(endpoints)]
        conf = Configuration(peers)
        opts = NodeOptions(
            election_timeout_ms=args.election_timeout_ms,
            initial_conf=conf,
            fsm=CountFSM(),
            log_uri=f"multilog://{base}/mlog#{gid}",
            raft_meta_uri=(f"file://{base}/meta/{gid}"
                           if args.meta == "file" else "memory://"),
            enable_metrics=False)
        # one multi_heartbeat RPC per endpoint pair per beat interval
        opts.raft_options.coalesce_heartbeats = True
        node = Node(gid, peers[me], opts, transport,
                    ballot_box_factory=factory)
        node.node_manager = manager
        manager.add(node)
        ok = await node.init()
        assert ok
        node.read_only_service.attach_confirm_batcher(read_batcher)
        nodes.append(node)

    print("BOOTED", flush=True)

    # wait for local leadership of this process's share; converging
    # G elections across 3 time-sliced processes is O(G) work, so the
    # deadline scales with G (and 98% placement is good enough to
    # measure — the driver reports the real count)
    want = [n for i, n in enumerate(nodes) if i % len(endpoints) == me]
    deadline = time.monotonic() + 120 + G * 0.06
    while time.monotonic() < deadline:
        n_led = sum(1 for n in want if n.is_leader())
        if n_led >= max(1, int(len(want) * 0.98)):
            break
        await asyncio.sleep(0.5)
    led = [n for n in want if n.is_leader()]
    print(f"LEADING {len(led)}/{len(want)}", flush=True)

    loop = asyncio.get_running_loop()
    reader = asyncio.StreamReader()
    await loop.connect_read_pipe(
        lambda: asyncio.StreamReaderProtocol(reader), sys.stdin)

    async def measured_run(duration: float, window: int):
        """Windowed pipelined appliers on every locally-led group."""
        stop_at = time.monotonic() + duration
        ok = [0]
        errs = [0]
        lats: list[float] = []

        async def drive(node):
            # `window` batches of `batch` entries in flight per group —
            # apply_batch amortizes the lock/flush per batch, like the
            # reference's applyBatch=32 Disruptor drain
            batch = args.batch
            sem = asyncio.Semaphore(window)
            payload = b"x" * args.payload

            def batch_cb(t0, sample):
                left = [batch]

                def cb(st):
                    if st.is_ok():
                        ok[0] += 1
                    else:
                        errs[0] += 1
                    left[0] -= 1
                    if left[0] == 0:
                        sem.release()
                        if sample:
                            lats.append(time.perf_counter() - t0)
                return cb

            pending = set()
            i = 0
            if args.pace_ms:
                # paced mode (scale runs): spread each group's batch
                # cadence uniformly so offered load is shaped, not a
                # thundering herd on the shared core
                import random
                await asyncio.sleep(random.random() * args.pace_ms / 1e3)
            while time.monotonic() < stop_at:
                if not node.is_leader():
                    # leadership moved (possibly to another store
                    # process, whose own driver for this group takes
                    # over): idle instead of spraying not-leader
                    # rejections at the stale node — the RouteTable-
                    # client analog, ladder edition
                    await asyncio.sleep(
                        max(args.pace_ms / 1e3, 0.05) if args.pace_ms
                        else 0.05)
                    continue
                await sem.acquire()
                if args.pace_ms:
                    await asyncio.sleep(args.pace_ms / 1e3)
                if errs[0] > ok[0] + 1000:
                    # cluster unhealthy (election churn): back off
                    # instead of spinning failed applies at CPU speed
                    await asyncio.sleep(0.1)
                i += 1
                t0 = time.perf_counter()
                cb = batch_cb(t0, i % 8 == 0)
                tasks = [Task(data=payload, done=cb) for _ in range(batch)]
                fut = asyncio.ensure_future(node.apply_batch(tasks))
                pending.add(fut)
                fut.add_done_callback(pending.discard)
            if pending:
                await asyncio.gather(*pending, return_exceptions=True)
            # drain outstanding acks
            for _ in range(window):
                try:
                    await asyncio.wait_for(sem.acquire(), 5.0)
                except asyncio.TimeoutError:
                    break

        t_start = time.monotonic()
        # drive EVERY local node, gated on live leadership (not the
        # boot-time led list): a group whose leadership migrates to
        # this store mid-window gets driven here, and the stale node
        # stops being sprayed with not-leader applies
        await asyncio.gather(*(drive(n) for n in nodes))
        elapsed = time.monotonic() - t_start
        lats.sort()
        import resource

        return {
            "ok": ok[0], "errs": errs[0], "elapsed": elapsed,
            "applied": CountFSM.applied,
            "lat_p50_ms": round(lats[len(lats) // 2] * 1e3, 3) if lats else None,
            "lat_p99_ms": round(lats[int(len(lats) * 0.99)] * 1e3, 3)
            if lats else None,
            # scale accounting (VERDICT r2 #1): memory + event-loop task
            # population at this G, per store process
            "rss_mb": round(
                resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024, 1),
            "asyncio_tasks": len(asyncio.all_tasks()),
        }

    async def measured_read_mix(duration: float, frac: float):
        """Read/write-mix run: each in-flight slot is a read_index()
        fence (probability ``frac``) or an apply batch.  Reads count as
        ONE op each; the store-wide ReadConfirmBatcher coalesces every
        led group's fences into shared beat-plane rounds."""
        import random as _rnd

        stop_at = time.monotonic() + duration
        ok = [0]
        errs = [0]
        rlats: list[float] = []

        async def drive(node):
            batch = args.batch
            sem = asyncio.Semaphore(args.window)
            payload = b"x" * args.payload
            rng = _rnd.Random(id(node) & 0xffff)

            def batch_cb():
                left = [batch]

                def cb(st):
                    if st.is_ok():
                        ok[0] += 1
                    else:
                        errs[0] += 1
                    left[0] -= 1
                    if left[0] == 0:
                        sem.release()
                return cb

            async def one_read(sample: bool):
                t0 = time.perf_counter()
                try:
                    # bounded: a read wedged by churn must cost one slot
                    # for a few seconds, not hang the whole phase
                    await asyncio.wait_for(node.read_index(), 10.0)
                    ok[0] += 1
                    if sample:
                        rlats.append(time.perf_counter() - t0)
                except Exception:  # noqa: BLE001 — election churn etc.
                    errs[0] += 1
                finally:
                    sem.release()

            pending = set()
            i = 0
            while time.monotonic() < stop_at:
                if not node.is_leader():
                    await asyncio.sleep(0.05)
                    continue
                await sem.acquire()
                i += 1
                if rng.random() < frac:
                    fut = asyncio.ensure_future(one_read(i % 4 == 0))
                else:
                    cb = batch_cb()   # ONE shared countdown per batch
                    tasks = [Task(data=payload, done=cb)
                             for _ in range(batch)]
                    fut = asyncio.ensure_future(node.apply_batch(tasks))
                pending.add(fut)
                fut.add_done_callback(pending.discard)
            if pending:
                await asyncio.gather(*pending, return_exceptions=True)
            for _ in range(args.window):
                try:
                    await asyncio.wait_for(sem.acquire(), 5.0)
                except asyncio.TimeoutError:
                    break

        t_start = time.monotonic()
        await asyncio.gather(*(drive(n) for n in nodes))
        elapsed = time.monotonic() - t_start
        rlats.sort()
        svc_totals: dict[str, int] = {}
        for n in nodes:
            for k, v in n.read_only_service.counters().items():
                svc_totals[k] = svc_totals.get(k, 0) + v
        return {
            "ok": ok[0], "errs": errs[0], "elapsed": elapsed,
            "read_frac": frac,
            "read_p50_ms": round(rlats[len(rlats) // 2] * 1e3, 3)
            if rlats else None,
            "read_p99_ms": round(rlats[int(len(rlats) * 0.99)] * 1e3, 3)
            if rlats else None,
            "read_plane": dict(read_batcher.counters(), **svc_totals),
        }

    async def latency_probe(n_ops: int):
        """Low-load sequential acks on ONE group: the adaptive-tick
        commit-ack latency end-to-end."""
        if not led:
            return {"n": 0}
        node = led[0]
        lats = []
        for i in range(n_ops):
            fut = loop.create_future()
            t0 = time.perf_counter()
            await node.apply(Task(data=b"lat", done=fut.set_result))
            st = await fut
            if st.is_ok():
                lats.append(time.perf_counter() - t0)
            await asyncio.sleep(0.002)
        lats.sort()
        if not lats:
            return {"n": 0}
        return {
            "n": len(lats),
            "p50_ms": round(lats[len(lats) // 2] * 1e3, 3),
            "p99_ms": round(lats[int(len(lats) * 0.99)] * 1e3, 3),
            "min_ms": round(lats[0] * 1e3, 3),
        }

    async def latency_breakdown(n_ops: int):
        """Per-stage timestamps along ONE group's low-load commit-ack
        path (VERDICT r2 #3): apply -> stage -> leader fsync -> RPC
        (follower fsync inside) -> quorum tick -> commit advance -> FSM
        ack, via transient wrappers — production code stays clean."""
        if not led:
            return {"n": 0}
        node = led[0]
        lm = node.log_manager
        box = node.ballot_box
        marks: dict = {}

        orig_flush = lm.flush_staged

        async def flush_wrap(upto=None):
            marks.setdefault("flush_s", time.perf_counter())
            r = await orig_flush(upto)
            marks.setdefault("flush_e", time.perf_counter())
            return r

        orig_call = transport.call

        async def call_wrap(dst, method, req, timeout_ms=None):
            # r4: entry appends ride the send plane's multi_append
            # batches; heartbeats (multi_heartbeat) and probes are not
            # the measured path
            entrylike = method == "multi_append" or (
                method == "append_entries"
                and getattr(req, "entries", None))
            if entrylike:
                marks.setdefault("rpc_s", time.perf_counter())
            r = await orig_call(dst, method, req, timeout_ms=timeout_ms)
            if entrylike:
                marks.setdefault("rpc_e", time.perf_counter())
            return r

        orig_tick = engine.tick_once

        def tick_wrap():
            t = time.perf_counter()
            r = orig_tick()
            if "adv" in marks:
                marks.setdefault("tick_s", t)
                marks.setdefault("tick_e", time.perf_counter())
            return r

        orig_adv = box._advance

        def adv_wrap(idx):
            marks.setdefault("adv", time.perf_counter())
            return orig_adv(idx)

        lm.flush_staged = flush_wrap
        transport.call = call_wrap
        engine.tick_once = tick_wrap
        box._advance = adv_wrap
        stages: dict[str, list] = {}
        total = []
        try:
            for _ in range(n_ops):
                marks.clear()
                fut = loop.create_future()
                t0 = time.perf_counter()
                await node.apply(Task(data=b"brk", done=fut.set_result))
                st = await fut
                t_ack = time.perf_counter()
                if not st.is_ok():
                    continue
                rel = {k: (v - t0) * 1e3 for k, v in marks.items()}
                rel["ack"] = (t_ack - t0) * 1e3
                for k, v in rel.items():
                    stages.setdefault(k, []).append(v)
                total.append(rel["ack"])
                await asyncio.sleep(0.002)
        finally:
            lm.flush_staged = orig_flush
            transport.call = orig_call
            engine.tick_once = orig_tick
            box._advance = orig_adv

        def pct(xs, q):
            if not xs:
                return None
            s = sorted(xs)
            return round(s[min(len(s) - 1, int(len(s) * q))], 3)

        p99 = {k: pct(v, 0.99) for k, v in sorted(stages.items())}
        # name the tail's dominant *start-latency* stage from the data:
        # tick_s = commit-advancing tick scheduled late (loop
        # contention), rpc_s = batch RPC dispatch, flush_s = fsync start
        starts = {k: p99[k] for k in ("tick_s", "rpc_s", "flush_s")
                  if p99.get(k) is not None}
        dom = max(starts, key=starts.get) if starts else None
        return {
            "n": len(total),
            "note": "relative ms marks across ops; rpc includes "
                    "follower fsync (multi_append batch RPC); adv = "
                    "quorum commit advanced on the engine; tick = the "
                    "advancing tick's span",
            "stage_p50_ms": {k: pct(v, 0.5) for k, v in sorted(stages.items())},
            "stage_p99_ms": p99,
            "tail_attribution": (
                f"ack p99 {p99.get('ack')}ms: dominant start-latency "
                f"stage at p99 is {dom} ({starts.get(dom)}ms) of "
                + ", ".join(f"{k}={v}ms" for k, v in starts.items())),
        }

    while True:
        line = (await reader.readline()).decode().strip()
        if not line or line == "QUIT":
            break
        cmd = line.split()
        if cmd[0] == "GO":
            res = await measured_run(float(cmd[1]), args.window)
            print("RESULT " + json.dumps(res), flush=True)
        elif cmd[0] == "PROF":
            import cProfile
            import pstats

            prof = cProfile.Profile()
            prof.enable()
            res = await measured_run(float(cmd[1]), args.window)
            prof.disable()
            path = os.path.join(args.dir, f"prof_{me}.txt")
            with open(path, "w") as f:
                pstats.Stats(prof, stream=f).sort_stats("cumulative"
                                                        ).print_stats(50)
            res["prof"] = path
            print("RESULT " + json.dumps(res), flush=True)
        elif cmd[0] == "RMIX":
            res = await measured_read_mix(float(cmd[1]), float(cmd[2]))
            print("RESULT " + json.dumps(res), flush=True)
        elif cmd[0] == "LAT":
            res = await latency_probe(int(cmd[1]))
            print("RESULT " + json.dumps(res), flush=True)
        elif cmd[0] == "BRK":
            res = await latency_breakdown(int(cmd[1]))
            print("RESULT " + json.dumps(res), flush=True)

    for n in nodes:
        await n.shutdown()
    await engine.shutdown()
    await server.stop()
    await transport.close()


# ===========================================================================
# parent / driver
# ===========================================================================

def free_ports(n: int) -> list[int]:
    socks = []
    try:
        for _ in range(n):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", type=int, default=256)
    ap.add_argument("--stores", type=int, default=3)
    ap.add_argument("--duration", type=float, default=10.0)
    ap.add_argument("--warmup", type=float, default=3.0)
    ap.add_argument("--window", type=int, default=8,
                    help="outstanding apply BATCHES per led group")
    ap.add_argument("--batch", type=int, default=32,
                    help="entries per apply_batch (reference applyBatch)")
    ap.add_argument("--payload", type=int, default=16)
    ap.add_argument("--pace-ms", type=float, default=0.0,
                    help="per-group pause between batches (shapes offered "
                         "load for high-G scale runs; 0 = saturate)")
    ap.add_argument("--election-timeout-ms", type=int, default=1500)
    ap.add_argument("--json-out", default="BENCH_E2E.json",
                    help="result file (relative to the repo root)")
    ap.add_argument("--meta", default="file", choices=["file", "memory"],
                    help="raft meta storage; 'memory' speeds up boot at "
                         "high G (meta is not in the commit-ack path)")
    ap.add_argument("--read-mix", default="",
                    help="comma-separated read fractions (e.g. "
                         "'0.95,0.5'): after the write phase, run one "
                         "read/write-mix phase per fraction — reads are "
                         "read_index() fences amortized by the "
                         "store-wide ReadConfirmBatcher; rows land in "
                         "extra.read_mix of the JSON")
    ap.add_argument("--skip-brk", action="store_true",
                    help="skip the per-stage breakdown round")
    ap.add_argument("--dir", default="")
    ap.add_argument("--store", action="store_true",
                    help="internal: run as a store process")
    ap.add_argument("--index", type=int, default=0)
    ap.add_argument("--peers", default="")
    args = ap.parse_args()

    if args.store:
        asyncio.run(run_store(args))
        return

    import tempfile

    # build the native libs ONCE before spawning (stores would race it)
    from tpuraft.storage.multilog import ensure_built as build_multilog
    from tpuraft.rpc.native_tcp import ensure_built as build_transport

    build_multilog()
    build_transport()

    workdir = args.dir or tempfile.mkdtemp(prefix="tpuraft_e2e_")
    ports = free_ports(args.stores)
    peers = ",".join(f"127.0.0.1:{p}" for p in ports)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    procs = []
    try:
        for i in range(args.stores):
            procs.append(subprocess.Popen(
                [sys.executable, os.path.join(REPO, "bench_e2e.py"),
                 "--store", "--index", str(i), "--peers", peers,
                 "--groups", str(args.groups), "--dir", workdir,
                 "--window", str(args.window), "--batch", str(args.batch),
                 "--payload", str(args.payload),
                 "--pace-ms", str(args.pace_ms),
                 "--meta", args.meta,
                 "--election-timeout-ms", str(args.election_timeout_ms)],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env))

        def expect(p, prefix, timeout_s=180.0):
            import select

            t0 = time.monotonic()
            while True:
                left = timeout_s - (time.monotonic() - t0)
                if left <= 0:
                    raise TimeoutError(f"no {prefix!r} from store")
                # readline() alone would block past the deadline on a
                # silent-but-alive store; gate it on pipe readability
                ready, _, _ = select.select([p.stdout], [], [],
                                            min(left, 1.0))
                if not ready:
                    if p.poll() is not None:
                        raise RuntimeError("store process died")
                    continue
                line = p.stdout.readline().decode().strip()
                if line.startswith(prefix):
                    return line
                if not line and p.poll() is not None:
                    raise RuntimeError("store process died")

        for p in procs:
            # boot is O(G) node inits time-sliced on this host
            expect(p, "BOOTED", timeout_s=max(180.0, args.groups * 0.15))
        leading = [expect(p, "LEADING",
                          timeout_s=max(180.0, 150 + args.groups * 0.08))
                   for p in procs]
        n_led = sum(int(s.split()[1].split("/")[0]) for s in leading)

        def round_all(cmd):
            for p in procs:
                p.stdin.write((cmd + "\n").encode())
                p.stdin.flush()
            return [json.loads(expect(p, "RESULT")[len("RESULT "):])
                    for p in procs]

        def round_one(p, cmd):
            # low-load probes run on ONE store while the others idle —
            # probing all three concurrently triples the CPU in every
            # "low-load" sample on a 1-core host
            p.stdin.write((cmd + "\n").encode())
            p.stdin.flush()
            return json.loads(expect(p, "RESULT")[len("RESULT "):])

        round_all(f"GO {args.warmup}")          # warmup
        results = round_all(f"GO {args.duration}")
        read_rows = []
        for frac_s in [f for f in args.read_mix.split(",") if f]:
            frac = float(frac_s)
            rr = round_all(f"RMIX {args.duration} {frac}")
            r_ok = sum(r["ok"] for r in rr)
            r_el = max(r["elapsed"] for r in rr)
            plane: dict = {}
            for r in rr:
                for k, v in r.get("read_plane", {}).items():
                    plane[k] = plane.get(k, 0) + v
            read_rows.append({
                "read_frac": frac,
                "ops_per_sec": round(r_ok / r_el, 1),
                "errors": sum(r["errs"] for r in rr),
                "read_p50_ms": [r["read_p50_ms"] for r in rr],
                "read_p99_ms": [r["read_p99_ms"] for r in rr],
                "read_plane": plane,
            })
        lat = round_one(procs[0], "LAT 200")    # low-load single-group acks
        brk = (None if args.skip_brk
               else round_one(procs[0], "BRK 150"))  # per-stage breakdown
        for p in procs:
            p.stdin.write(b"QUIT\n")
            p.stdin.flush()

        total_ok = sum(r["ok"] for r in results)
        elapsed = max(r["elapsed"] for r in results)
        cps = total_ok / elapsed
        out = {
            "metric": "e2e_multiraft_commits_per_sec",
            "value": round(cps, 1),
            "unit": "commits/s",
            "topology": "single-process",
            "vs_baseline": round(cps / 1e5, 3),
            "extra": {
                "groups": args.groups, "stores": args.stores,
                "leaders_placed": n_led,
                "payload_bytes": args.payload,
                "window_per_group": args.window,
                "duration_s": args.duration,
                "errors": sum(r["errs"] for r in results),
                "per_store_cps": [round(r["ok"] / r["elapsed"], 1)
                                  for r in results],
                "underload_ack_p50_ms": [r["lat_p50_ms"] for r in results],
                "underload_ack_p99_ms": [r["lat_p99_ms"] for r in results],
                "lowload_single_group_ack": lat,
                "ack_breakdown": brk,
                "read_mix": read_rows,
                "rss_mb_per_store": [r.get("rss_mb") for r in results],
                "asyncio_tasks_per_store": [r.get("asyncio_tasks")
                                            for r in results],
                "host_cores": os.cpu_count(),
                "per_core_commits_per_sec": round(
                    cps / max(1, os.cpu_count()), 1),
                "stack": "native-tcp + multilog(shared fsync) + "
                         "engine plane + priority placement",
                "baseline": "1e5 ops/s (upstream recollection, "
                            "unverifiable — BASELINE.md; measured on a "
                            "multi-core Xeon ~ 3-6K ops/s/core — this "
                            "host is 1 vCPU, so compare per-core)",
            },
        }
        print(json.dumps(out))
        path = os.path.join(REPO, args.json_out)
        if os.path.exists(path):
            # a fresh full run must not drop the bench-gate calibration
            # keys (re-recorded separately via `bench_gate.py --record`)
            try:
                with open(path) as f:
                    prev = json.load(f).get("extra", {})
                for k, v in prev.items():
                    if k.startswith("gate_"):
                        out["extra"].setdefault(k, v)
            except Exception:  # noqa: BLE001 — corrupt old file
                pass
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGKILL)


if __name__ == "__main__":
    main()
