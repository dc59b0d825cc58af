"""chip_smoke.py — the served KV path, once, on the chip, checked.

    python chip_smoke.py [--seed N]

One process that owns the chip (it starts no other that needs JAX):

  kernels  the Pallas fused-quorum kernel compiled by Mosaic at the
           served shapes, bit-equal to the XLA path; ``raft_tick`` under
           both implementations
  lanes    one jax-backed MultiRaftEngine driven through every [G] lane
           (witness clamp, stepdown, read fences, elections, commits):
           65,536 groups x 4 sharded over four chips with G/4 rows on
           each, or — on one chip — 16,384 groups x 8 unsharded, with
           the mesh named as skipped
  replica  (four chips) ``replicated_tick`` over a (2, 2) mesh against
           the host oracle
  serve    the deployment: three StoreEngines over InProcNetwork, each
           with its own MultiRaftEngine(backend="jax"), 1,024 regions x 3
           replicas, multilog journals and the native KV engine with
           every durability option at its default (fsync on); all 1,024
           regions elect; 16,384 seeded 1 KB records loaded with
           put/put_list, every one read back with linearizable SAFE
           reads and compared; scans, compare_and_put and delete checked
           against a model; a seeded sample read from the state machine
           of each of the three stores; device-vs-numpy-twin parity of
           all eleven tick outputs on each engine; the disk guard OK.

Exits non-zero before any phase unless ``jax.devices()[0].platform`` is
"tpu".  Any failed or wrong operation raises: no phase is wrapped in a
catch.  Printed only when every phase passed: a ``summary: {...}`` line
(device, per-phase status and counts, compile seconds and cache hits,
elapsed), then as the last line of stdout the result object and nothing
more, ``{"ok": true, "device": {"platform", "kind", "count"}}``.  The
tier-1 suite runs the same phase functions at a tiny size on the CPU
(tests/test_chip_smoke.py).
"""

from __future__ import annotations

import argparse
import asyncio
import inspect
import json
import os
import shutil
import sys
import tempfile
import time

REGIONS = 1024
RECORDS_PER_REGION = 16
VALUE_BYTES = 1024
KERNEL_SHAPES = ((2048, 4), (16384, 8), (1000, 4))   # 1000: not a 128-multiple


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


_T0 = time.monotonic()


# ---------------------------------------------------------------------------
# device + compile cache
# ---------------------------------------------------------------------------

def require_chip() -> dict:
    """The device as JAX reports it; exits non-zero unless it is a TPU."""
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"platform: {dev.platform}  device_kind: {dev.device_kind}  "
          f"devices: {device['count']}  jax: {jax.__version__}", flush=True)
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU — JAX found platform "
                 f"{dev.platform!r}; this script only passes on the chip")
    return device


class CompileMeter:
    """Seconds JAX spent in backend compiles (or fetching them from the
    persistent cache), and the cache's hits and misses, from
    ``jax.monitoring`` — what tells a cold run from a warm one."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def report(self, cache_dir: str) -> dict:
        state = ("warm" if self.hits and not self.misses
                 else "cold" if self.misses and not self.hits else "mixed")
        return {"seconds": round(self.seconds, 3), "state": state,
                "cache_hits": self.hits, "cache_misses": self.misses,
                "cache_dir": cache_dir}


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------

def phase_kernels(seed: int, shapes=KERNEL_SHAPES,
                  interpret: bool = False) -> dict:
    """Mosaic compiles the fused-quorum kernel at the served shapes and
    it is bit-equal to XLA on seeded state with joint-consensus rows;
    ``raft_tick`` agrees under both implementations."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuraft.ops.quorum_pallas import (_fused_quorum_pallas,
                                           _fused_quorum_xla)
    from tpuraft.ops.tick import GroupState, TickParams, raft_tick

    rng = np.random.default_rng(seed)
    done = []
    for g, p in shapes:
        match = rng.integers(-1, 100, (g, p)).astype(np.int32)
        ack = rng.integers(0, 10_000, (g, p)).astype(np.int32)
        granted = rng.random((g, p)) < 0.5
        vm = rng.random((g, p)) < 0.6
        ovm = (rng.random((g, p)) < 0.4) & (rng.random((g, 1)) < 0.3)
        check(ovm.any(axis=1).sum() > 0, "no joint-consensus rows drawn")
        args = tuple(jnp.asarray(a) for a in (match, granted, ack, vm, ovm))
        ref = jax.block_until_ready(_fused_quorum_xla(*args))
        out = jax.block_until_ready(
            _fused_quorum_pallas(*args, interpret=interpret))
        for name, a, b in zip(("quorum_idx", "elected", "q_ack"), ref, out):
            check(np.array_equal(np.asarray(a), np.asarray(b)),
                  f"pallas {name} differs from xla at G={g} P={p}")
        done.append([g, p])

    # one raft_tick under each implementation
    g, p = shapes[0]
    state = GroupState.zeros(g, p)
    state.role = jnp.asarray(rng.integers(0, 3, (g,)).astype(np.int32))
    state.match_rel = jnp.asarray(rng.integers(0, 50, (g, p)).astype(np.int32))
    state.granted = jnp.asarray(rng.random((g, p)) < 0.6)
    voter = np.zeros((g, p), bool)
    voter[:, :3] = True
    state.voter_mask = jnp.asarray(voter)
    state.last_ack = jnp.asarray(
        rng.integers(0, 2_000, (g, p)).astype(np.int32))
    params = TickParams.make(1000, 100, 900)
    tick = jax.jit(raft_tick, static_argnames=("quorum_impl",))
    _, o_xla = tick(state, jnp.int32(1500), params, quorum_impl="xla")
    _, o_pal = tick(state, jnp.int32(1500), params,
                    quorum_impl="pallas_interpret" if interpret else "pallas")
    for name in o_xla.__dataclass_fields__:
        check(np.array_equal(np.asarray(getattr(o_xla, name)),
                             np.asarray(getattr(o_pal, name))),
              f"raft_tick output {name} differs between xla and pallas")
    return {"ok": True, "shapes": done, "interpret": interpret,
            "tick_outputs_equal": len(o_xla.__dataclass_fields__)}


# ---------------------------------------------------------------------------
# phase: lanes (mesh on four chips, one device otherwise) + replica plane
#
# A synthetic harness around the REAL engine: stub controls stand in for
# nodes and count the handler deliveries the tick schedules, while the
# tensors, the sharded tick, the clamp, the fence lane and the apply
# loops are the production code.  The full-protocol proofs (elections,
# transfers, linearizability) live in pytest and examples/soak.py.
# ---------------------------------------------------------------------------

class _StubReplicators:
    def all(self):
        return []


class _StubNode:
    replicators = _StubReplicators()

    def is_leader(self):
        return True

    # handler objects the tick schedules by reference; the stub ctrl
    # counts deliveries instead of running them (real handlers re-verify
    # under the node lock — there is no node here)
    def _check_dead_nodes(self):
        pass

    def _on_election_due(self):
        pass

    def _on_engine_elected(self):
        pass

    def _on_engine_quorum_dead(self):
        pass

    def _on_snapshot_due(self):
        pass


class _StubCtrl:
    """EngineControl stand-in: the exact surface _apply_protocol and
    _flush_heartbeats touch, with shared delivery counters."""

    def __init__(self, engine, slot: int, counts: dict):
        self.engine = engine
        self.slot = slot
        self.node = _StubNode()
        self.counts = counts

    def _adopt_eto(self, eff_eto_ms: int) -> None:
        pass

    def push_election_deadline(self, now_ms=None) -> None:
        e = self.engine
        now = e.now_ms() if now_ms is None else now_ms
        e.elect_deadline[self.slot] = now + int(e.eto_ms[self.slot])

    def note_election_due(self) -> None:
        pass    # no tracer here: nothing to begin a span on

    def schedule(self, name: str, handler) -> None:
        self.counts[name] = self.counts.get(name, 0) + 1

    def priority_rounds_accrue(self) -> bool:
        # every stepdown_due fire reaches the handler, fresh row or not:
        # the lane's deliveries are what drive_lanes counts
        return True

    def maybe_quiesce(self, now: int) -> None:
        pass

    def wake_from_quiescence(self, reason: str = "activity",
                             *a, **kw) -> None:
        pass


class _StubFence:
    __slots__ = ("done",)
    resolved = 0

    def __init__(self):
        self.done = False

    def note_quorum(self):
        self.done = True
        _StubFence.resolved += 1


async def drive_lanes(groups: int, devices: int, duration_s: float,
                      seed: int, peers: int = 4) -> dict:
    """Drive every [G] lane of one jax-backed engine and prove each
    engaged.  ``devices`` > 1 shards the group axis over that many
    devices (mesh mode); 1 runs the same driver on one device."""
    import resource

    import jax
    import numpy as np

    from tpuraft.conf import Configuration
    from tpuraft.core.engine import (ROLE_FOLLOWER, ROLE_LEADER,
                                     MultiRaftEngine)
    from tpuraft.options import TickOptions

    rng = np.random.default_rng(seed)
    eng = MultiRaftEngine(TickOptions(
        max_groups=groups, max_peers=peers, backend="jax",
        mesh_devices=devices if devices > 1 else 0,
        tick_interval_ms=20, eager_commit=False,
        density_aware_timeouts=False))
    t_boot = time.monotonic()
    await eng.start()
    assert eng._tick_fn is not None, "jax tick did not engage"
    assert (eng._deadline_fold is not None) == (devices > 1), \
        "mesh mode did not follow the device count"

    G = eng.G
    factory = eng.ballot_box_factory()
    counts: dict = {}
    commits = [0]
    confs = {
        # 3 data voters — the witness-free steady state
        "data": Configuration.parse(
            "10.0.0.1:80,10.0.0.2:80,10.0.0.3:80"),
        # 2 data + 1 witness: the valid geo shape (quorum 2, one copy +
        # one metadata ack commits)
        "witness": Configuration.parse(
            "10.0.0.1:80,10.0.0.2:80,10.0.0.3:80/witness"),
        # witness-MAJORITY rows: invalid as a conf (is_valid refuses it
        # node-side) but exactly the degenerate tensor state the commit
        # clamp is the third safety layer against — the probe slots
        # prove the device clamp pins commit to the best data match
        "probe": Configuration.parse(
            "10.0.0.1:80,10.0.0.2:80/witness,10.0.0.3:80/witness"),
    }
    self_peer = confs["data"].peers[0]
    empty = Configuration()

    boxes = []
    kinds = np.zeros(G, dtype=np.int8)   # 0=data 1=witness 2=probe
    for s in range(G):
        box = factory(lambda idx, _c=commits: _c.__setitem__(
            0, _c[0] + 1))
        # probe stride lands on EVEN slots — the leader half, so the
        # clamp assertion actually measures committing groups
        kind = "probe" if s % 64 == 62 else (
            "witness" if s % 4 == 3 else "data")
        kinds[s] = {"data": 0, "witness": 1, "probe": 2}[kind]
        box.update_conf(confs[kind], empty)
        eng.register_ctrl(_StubCtrl(eng, s, counts), self_peer,
                          eto_ms=500, hb_ms=100, lease_ms=450)
        boxes.append(box)

    now = eng.now_ms()
    leaders = np.arange(G) % 2 == 0
    L = np.nonzero(leaders)[0]
    for s in L:
        boxes[s].reset_pending_index(1)
    eng.role[~leaders] = ROLE_FOLLOWER
    # election lane: a seeded sample of followers falls due during the
    # window; everyone else schedules far out (the election protocol
    # itself is proven in pytest/soak — here we prove lane delivery
    # without a 32K-slot python storm per eto)
    eng.elect_deadline[:] = now + 3_600_000
    sample = rng.choice(np.nonzero(~leaders)[0],
                        size=min(64, int((~leaders).sum())), replace=False)
    eng.elect_deadline[sample] = now + 50
    # beat fan-out needs real replicators; the stub has none to flush,
    # so park the hb lane out of the window
    eng.hb_deadline[:] = now + 3_600_000
    # stepdown/priority lane: stagger first fire over one eto/2 period
    eng.stepdown_deadline[:] = now + rng.integers(1, 250, G)
    boot_s = time.monotonic() - t_boot

    # standing match rows.  Probe slots: data col 0 at 3, witness cols
    # at 9 — the unclamped quorum stat says 9, the clamp must pin 3.
    probe = kinds == 2
    Pn = np.nonzero(probe)[0]
    lead_probe = probe & leaders
    eng.match_abs[np.ix_(Pn, [1, 2])] = 9
    eng.match_abs[Pn, 0] = 3

    t0 = time.monotonic()
    ticks = 0
    rounds = 0
    fences: list = []
    drive = L[~probe[L]]
    while time.monotonic() - t0 < duration_s:
        rounds += 1
        now = eng.now_ms()
        # fresh voter acks for every leader (cols 0..2 are the voters)
        eng.last_ack[np.ix_(L, [0, 1, 2])] = now
        # advance the replicated tail: self + one follower move, the
        # second follower lags a round — quorum = the moving pair
        eng.match_abs[np.ix_(drive, [0, 1])] = rounds
        eng.match_abs[drive, 2] = max(0, rounds - 1)
        # arm a read-fence wave on a rotating slice of leaders
        wave = L[(rounds % 8)::16]
        for s in wave[:256]:
            f = _StubFence()
            fences.append((int(s), f))
            eng.arm_read_fence(int(s), f)
        eng.tick_once()
        ticks += 1
    elapsed = time.monotonic() - t0
    # one settle tick so the last fence wave sees a covering q_ack
    eng.last_ack[np.ix_(L, [0, 1, 2])] = eng.now_ms()
    eng.tick_once()
    ticks += 1

    # -- lane proofs --------------------------------------------------------
    # witness clamp: every probe LEADER's commit sits at the best data
    # match (3), never the unclamped quorum stat (9)
    probe_commits = eng.commit_abs[lead_probe]
    clamp_ok = bool((probe_commits <= 3).all())
    clamp_engaged = bool((probe_commits == 3).all())
    # plain witness groups commit normally through the clamp lane
    wit_lead = (kinds == 1) & leaders
    wit_commit_ok = bool((eng.commit_abs[wit_lead] >= rounds - 1).all())
    # where the rows live: one direct call of the compiled tick on the
    # same mirrors, outputs left on the device — every device must hold
    # its G/devices rows, not everything on device 0
    # (G is the last axis of the one packed array a single device
    # returns, and the only one of a mesh's [G] rows)
    out = eng._call_tick(eng._group_state(*eng._rel_views()), eng.now_ms())
    shards = jax.tree_util.tree_leaves(out)[0].addressable_shards
    rows_per_shard = [int(sh.data.shape[-1]) for sh in shards]
    shard_devices = sorted(sh.device.id for sh in shards)
    n_dev = max(devices, 1)
    stats = eng.lane_stats()
    res = {
        "groups": G,
        "peers": peers,
        "mesh_devices": devices,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
        "rows_per_shard": rows_per_shard,
        "shard_devices": shard_devices,
        "boot_s": round(boot_s, 1),
        "duration_s": round(elapsed, 2),
        "ticks": ticks,
        "ticks_per_sec": round(ticks / elapsed, 1),
        "drive_rounds": rounds,
        "commits": commits[0],
        "commits_per_sec": round(commits[0] / elapsed, 1),
        "witness_groups": stats["witness_groups"],
        "witness_commit_ok": wit_commit_ok,
        "clamp_probe_groups": int(lead_probe.sum()),
        "clamp_held": clamp_ok,
        "clamp_engaged": clamp_engaged,
        "stepdown_ticks": stats["stepdown_ticks"],
        "stepdown_handler_calls": counts.get("stepdown_tick", 0),
        "election_due_handled": counts.get("election_due", 0),
        "fence_armed": stats["fence_lane_armed"],
        "fence_resolved": stats["fence_lane_resolves"],
        "fences_pending": stats["fences_pending"],
        "rss_mb": round(resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss / 1024, 1),
    }
    failures = []
    if not int(lead_probe.sum()):
        failures.append("no clamp probe groups on the leader half")
    if not clamp_ok:
        failures.append(
            f"witness clamp BREACHED: probe commits {probe_commits[:8]}")
    if not clamp_engaged:
        failures.append("witness clamp never engaged on probe rows")
    if not wit_commit_ok:
        failures.append("witness-conf groups failed to commit")
    if res["stepdown_ticks"] <= 0 \
            or res["stepdown_handler_calls"] != res["stepdown_ticks"]:
        failures.append("stepdown/priority lane: "
                        f"{res['stepdown_handler_calls']} deliveries for "
                        f"{res['stepdown_ticks']} fires")
    if res["fence_resolved"] <= 0 \
            or res["fence_resolved"] != res["fence_armed"]:
        failures.append(f"device fence lane resolved "
                        f"{res['fence_resolved']} of {res['fence_armed']} "
                        f"armed rounds")
    if res["election_due_handled"] <= 0:
        failures.append("election lane never delivered")
    if commits[0] <= 0:
        failures.append("no commits advanced through the device tick")
    if rows_per_shard != [G // n_dev] * n_dev \
            or len(set(shard_devices)) != n_dev:
        failures.append(f"group axis not spread: {rows_per_shard} rows "
                        f"on devices {shard_devices}")
    if stats["tick_failures"]:
        failures.append(f"{stats['tick_failures']} ticks raised")
    res["ok"] = not failures
    res["failures"] = failures
    await eng.shutdown()
    return res


def phase_lanes(seed: int, n_devices: int, mesh_groups: int = 65536,
                single_groups: int = 16384, duration_s: float = 6.0) -> dict:
    if n_devices >= 4:
        res = asyncio.run(drive_lanes(mesh_groups, 4, duration_s, seed,
                                      peers=4))
        res["mesh"] = "ran"
    else:
        print(f"mesh: skipped: {n_devices} device", flush=True)
        res = asyncio.run(drive_lanes(single_groups, 1, duration_s, seed,
                                      peers=8))
        res["mesh"] = f"skipped: {n_devices} device"
    check(res["ok"], "lane driver: " + "; ".join(res["failures"]))
    return res


def phase_replica(seed: int, groups: int = 16384) -> dict:
    """``replicated_tick`` over a (2, 2) mesh of four devices against the
    host oracle (what ``__graft_entry__.dryrun_multichip`` does on CPU
    devices)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from tpuraft.parallel.collective import replicated_tick

    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs).reshape(2, 2), ("replica", "groups"))
    r = 4
    rng = np.random.default_rng(seed)
    match = rng.integers(0, 1 << 20, (r, groups)).astype(np.int32)
    granted = rng.random((r, groups)) < 0.5
    commit, votes = replicated_tick(mesh, n_replicas=r)(
        jnp.asarray(match), jnp.asarray(granted))
    commit, votes = np.asarray(commit), np.asarray(votes)
    check(np.array_equal(commit, np.sort(match, axis=0)[::-1][r // 2]),
          "replicated_tick commit point differs from the host oracle")
    check(np.array_equal(votes, granted.sum(axis=0)),
          "replicated_tick vote count differs from the host oracle")
    return {"ok": True, "mesh": [2, 2], "replicas": r, "groups": groups,
            "devices": [d.id for d in devs]}


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------

def _bkey(k: int) -> bytes:
    return b"%06x" % k


def _record_key(region: int, j: int) -> bytes:
    return _bkey(region) + b"/%04d" % j


def engine_parity(engine) -> int:
    """The same mirrors through the device tick and through its numpy
    twin: every output row must be bit-equal.  Returns the row count."""
    import numpy as np

    from tpuraft.core.engine import _NpOutputs

    now = engine.now_ms()
    rel, commit_rel = engine._rel_views()
    dev = engine._device_tick(rel, commit_rel, now)
    twin = engine._np_tick(rel, commit_rel, now)
    for name in _NpOutputs.__slots__:
        a, b = np.asarray(getattr(dev, name)), np.asarray(getattr(twin, name))
        check(a.shape == b.shape and np.array_equal(a, b),
              f"device tick and numpy twin differ on {name}")
    return len(_NpOutputs.__slots__)


async def _gather_limited(coros, limit: int) -> list:
    sem = asyncio.Semaphore(limit)

    async def run(c):
        async with sem:
            return await c

    return await asyncio.gather(*(run(c) for c in coros))


async def serve(workdir: str, seed: int, regions: int,
                per_region: int, election_timeout_ms: int,
                elect_deadline_s: float) -> dict:
    import numpy as np

    from tpuraft.core.engine import MultiRaftEngine
    from tpuraft.options import RaftOptions, TickOptions
    from tpuraft.rheakv.client import BatchingOptions, RheaKVStore
    from tpuraft.rheakv.metadata import Region
    from tpuraft.rheakv.native_store import NativeRawKVStore
    from tpuraft.rheakv.pd_client import FakePlacementDriverClient
    from tpuraft.rheakv.store_engine import StoreEngine, StoreEngineOptions
    from tpuraft.rpc.transport import (InProcNetwork, InProcTransport,
                                       RpcServer)
    from tpuraft.util.health import PRESSURE_OK

    R, S = regions, 3
    check(RaftOptions().sync and RaftOptions().sync_meta
          and inspect.signature(NativeRawKVStore).parameters["sync"].default
          is True, "a durability default is no longer fsync-on")
    net = InProcNetwork()
    endpoints = [f"127.0.0.1:{6600 + i}" for i in range(S)]
    region_list = [Region(id=k + 1, start_key=_bkey(k) if k else b"",
                          end_key=_bkey(k + 1) if k + 1 < R else b"",
                          peers=list(endpoints)) for k in range(R)]
    cap = 1 << max(4, (R + 3).bit_length())   # 1,024 regions -> 2,048 slots

    engines, stores = [], []
    res: dict = {"regions": R, "stores": S, "engine_capacity": [cap, 4]}
    t0 = time.monotonic()
    try:
        for i, ep in enumerate(endpoints):
            os.makedirs(f"{workdir}/store{i}", exist_ok=True)
            server = RpcServer(ep)
            net.bind(server)
            engine = MultiRaftEngine(TickOptions(
                max_groups=cap, max_peers=4, tick_interval_ms=20,
                backend="jax"))
            engines.append(engine)
            store = StoreEngine(
                StoreEngineOptions(
                    server_id=ep,
                    initial_regions=[r.copy() for r in region_list],
                    data_path=f"{workdir}/store{i}",
                    election_timeout_ms=election_timeout_ms,
                    log_scheme="multilog",
                    # opened as `rheakv_server --store native` opens it
                    raw_store_factory=lambda i=i: NativeRawKVStore(
                        f"{workdir}/store{i}/kv")),
                server, InProcTransport(net, ep),
                multi_raft_engine=engine)
            stores.append(store)
            await store.start()
        res["boot_s"] = round(time.monotonic() - t0, 1)
        say(f"serve: {S} stores x {R} regions booted in {res['boot_s']}s")
        some_node = next(iter(stores[0]._regions.values())).node
        check(some_node.options.raft_options.sync
              and some_node.options.raft_options.sync_meta,
              "region nodes run with fsync off")

        # -- every region elects, from the device's election_due lane ----
        t1 = time.monotonic()
        led = 0
        while time.monotonic() - t1 < elect_deadline_s:
            led = sum(1 for s in stores for re in s._regions.values()
                      if re.is_leader())
            if led >= R:
                break
            await asyncio.sleep(0.5)
        res["leaders"] = led
        res["elect_s"] = round(time.monotonic() - t1, 1)
        check(led == R, f"only {led} of {R} regions elected a leader "
                        f"within {elect_deadline_s}s")
        say(f"serve: {led}/{R} leaders in {res['elect_s']}s")

        client = RheaKVStore(
            FakePlacementDriverClient([r.copy() for r in region_list]),
            InProcTransport(net, "kvclient:0"),
            batching=BatchingOptions(enabled=True), timeout_ms=20000)
        await client.start()

        # -- load: seeded 1 KB records, first half of each region's by
        # put, second half by put_list ----------------------------------
        rng = np.random.default_rng(seed)
        blob = rng.bytes(R * per_region * VALUE_BYTES)
        model: dict = {}   # key -> value, None once deleted
        for k in range(R):
            for j in range(per_region):
                off = (k * per_region + j) * VALUE_BYTES
                model[_record_key(k, j)] = blob[off:off + VALUE_BYTES]
        half = per_region // 2
        singles = [_record_key(k, j) for k in range(R) for j in range(half)]
        listed = [_record_key(k, j) for k in range(R)
                  for j in range(half, per_region)]
        t2 = time.monotonic()
        acks = await _gather_limited(
            (client.put(key, model[key]) for key in singles), 512)
        check(all(a is True for a in acks), "a put was not acknowledged")
        chunk = 256
        acks = await _gather_limited(
            (client.put_list([(key, model[key])
                              for key in listed[i:i + chunk]])
             for i in range(0, len(listed), chunk)), 8)
        check(all(a is True for a in acks), "a put_list was not acknowledged")
        res["loaded"] = len(model)
        res["load_s"] = round(time.monotonic() - t2, 1)
        say(f"serve: loaded {len(model)} x {VALUE_BYTES} B in "
            f"{res['load_s']}s")

        # -- read every acknowledged record back (linearizable SAFE) ----
        t3 = time.monotonic()
        keys = list(model)
        got = await _gather_limited((client.get(key) for key in keys), 512)
        wrong = sum(1 for key, v in zip(keys, got) if v != model[key])
        check(wrong == 0, f"{wrong} of {len(keys)} records read back wrong")
        res["read_back"] = len(keys)
        res["read_s"] = round(time.monotonic() - t3, 1)
        say(f"serve: read back {len(keys)} equal in {res['read_s']}s")

        # -- scan / compare_and_put / delete against the model ----------
        ops = 0
        for a in sorted({0, R // 3, max(0, R - 3)}):
            b = min(a + 3, R)
            rows = [(key, v) for key, v in await client.scan(
                _bkey(a), _bkey(b) if b < R else b"")]
            want = sorted((key, v) for key, v in model.items()
                          if _bkey(a) <= key and (b >= R or key < _bkey(b)))
            check(rows == want, f"scan of regions [{a}, {b}) wrong: "
                                f"{len(rows)} rows, want {len(want)}")
            ops += 1
        picks = [keys[int(i)] for i in rng.choice(len(keys), 12,
                                                  replace=False)]
        for key in picks[:4]:
            new = b"cas:" + key
            check(await client.compare_and_put(key, model[key], new) is True,
                  "compare_and_put with the right expectation refused")
            model[key] = new
            ops += 1
        for key in picks[4:8]:
            check(await client.compare_and_put(key, b"not-it", b"x") is False,
                  "compare_and_put with a wrong expectation applied")
            ops += 1
        for key in picks[8:]:
            check(await client.delete(key) is True, "delete not acknowledged")
            model[key] = None
            ops += 1
        after = await client.multi_get(picks)
        check(all(after[key] == model[key] for key in picks),
              "reads after compare_and_put/delete disagree with the model")
        res["other_ops"] = ops + 1

        # -- the promise is three replicas: a seeded sample, in the state
        # machine of EACH store (followers apply behind the commit) -----
        sample = picks + [keys[int(i)] for i in rng.choice(len(keys), 244,
                                                           replace=False)]
        deadline = time.monotonic() + 60
        bad = {}
        while True:
            bad = {i: sum(1 for key in sample
                          if s.raw_store.get(key) != model[key])
                   for i, s in enumerate(stores)}
            if not any(bad.values()) or time.monotonic() > deadline:
                break
            await asyncio.sleep(0.5)
        check(not any(bad.values()),
              f"replica state machines disagree with the model: {bad}")
        res["replica_sample"] = {"keys": len(sample), "stores": S}
        say(f"serve: {len(sample)} sampled records equal on all {S} stores")

        # -- the device did the work ------------------------------------
        stats = [e.lane_stats() for e in engines]
        res["ticks"] = [e.ticks for e in engines]
        res["tick_failures"] = sum(st["tick_failures"] for st in stats)
        res["device_leaders"] = sum(st["leaders"] for st in stats)
        res["fence_lane_resolves"] = sum(st["fence_lane_resolves"]
                                         for st in stats)
        res["read_device_fences"] = sum(
            s.read_batcher.counters()["read_device_fences"] for s in stores)
        check(all(e._tick_fn is not None and e.ticks > 0 for e in engines),
              "an engine is not running the jitted device tick")
        check(res["tick_failures"] == 0,
              f"{res['tick_failures']} engine ticks raised")
        check(res["device_leaders"] == R,
              f"engine rows hold {res['device_leaders']} leaders, want {R}")
        check(res["read_device_fences"] > 0
              and res["fence_lane_resolves"] > 0,
              "SAFE reads were not resolved by the device fence lane")
        res["parity_rows"] = [engine_parity(e) for e in engines]
        say(f"serve: device-vs-twin parity bit-equal on {S} engines "
            f"({res['parity_rows'][0]} rows each); "
            f"{res['read_device_fences']} device fences")

        # -- the disk guard, as every store sees it ---------------------
        levels = {}
        for ep, s in zip(endpoints, stores):
            check(s.disk_budget is not None and s.disk_budget.reconciles > 0,
                  f"{ep}: the disk guard never measured the disk")
            levels[ep] = s.disk_budget.pressure()
            print(f"disk guard {ep}: {s.disk_budget.describe()}", flush=True)
        res["disk_guard"] = levels
        check(all(lv == PRESSURE_OK for lv in levels.values()),
              f"disk guard not OK: {levels}")
        res["durability"] = "defaults: raft log, raft meta and KV WAL fsync"
        await client.shutdown()
    finally:
        for s in stores:
            await s.shutdown()
    res["ok"] = True
    res["elapsed_s"] = round(time.monotonic() - t0, 1)
    return res


def phase_serve(seed: int, regions: int = REGIONS,
                per_region: int = RECORDS_PER_REGION,
                election_timeout_ms: int = 10000,
                elect_deadline_s: float = 300.0) -> dict:
    workdir = tempfile.mkdtemp(prefix="tpuraft_smoke_")
    try:
        return asyncio.run(serve(workdir, seed, regions, per_region,
                                 election_timeout_ms, elect_deadline_s))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


# ---------------------------------------------------------------------------

def report(device: dict, detail: dict) -> bool:
    """The ``summary:`` line, then the result line: an object with exactly
    the keys ``ok`` and ``device``, the last thing on stdout."""
    ok = all(p["ok"] for p in detail["phases"].values())
    print("summary: " + json.dumps({"ok": ok, "device": device, **detail}),
          flush=True)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return ok


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    device = require_chip()

    import jax

    from tpuraft.util.jax_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    print(f"compile cache: {cache_dir}", flush=True)
    meter = CompileMeter()

    phases = {}
    phases["kernels"] = phase_kernels(args.seed)
    say(f"kernels ok: {phases['kernels']['shapes']}")
    phases["lanes"] = phase_lanes(args.seed, device["count"])
    say(f"lanes ok: {phases['lanes']['groups']} groups x "
        f"{phases['lanes']['peers']}, rows/shard "
        f"{phases['lanes']['rows_per_shard']}, mesh {phases['lanes']['mesh']}")
    if device["count"] >= 4:
        phases["replica"] = phase_replica(args.seed)
        say("replica ok: (2, 2) mesh agrees with the host oracle")
    else:
        phases["replica"] = {"ok": True,
                             "skipped": f"{device['count']} device"}
    phases["serve"] = phase_serve(args.seed)

    if not report(device, {
        "jax": jax.__version__,
        "seed": args.seed,
        "phases": phases,
        "compile": meter.report(cache_dir),
        "elapsed_s": round(time.monotonic() - _T0, 1),
        "claim": None,
    }):
        sys.exit(1)


if __name__ == "__main__":
    main()
