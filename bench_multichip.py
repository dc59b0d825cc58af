"""Mesh-mode engine ladder: ONE MultiRaftEngine spanning N devices
drives 64K+ raft groups with every [G] protocol lane active (ISSUE 19).

Three modes:

``--smoke``
    CPU dryrun on 8 virtual host devices: boots a mesh-mode engine at a
    small G and PROVES each lane engaged — witness commit clamp (device
    commit pinned to the best data-replica match on adversarial rows),
    stepdown/priority tick delivery, device read-fence quorum tallies,
    election-due scheduling.  Wired into ``make multichip-smoke`` /
    ``make check``.  The only mode that pins JAX to the CPU.

``--scale``
    The acceptance rung: G=65536 groups sharded over the devices JAX
    reports (``--devices`` to use fewer), same lane assertions,
    sustained tick-rate + commit-rate measurement.  The RESULT line is
    labelled from ``jax.devices()``; ``chip_smoke.py`` runs the same
    driver on the chip.

``--engine-shape``
    Single-device calibration shape for bench_gate.py: G leader-heavy
    groups on the no-jax numpy tick path, tick_once in a tight loop,
    RESULT line with best-of-N ticks/s.  Pre/post-PR comparable — the
    committed calibration pins the single-device engine against
    regressions from the mesh work.

The scale/smoke driver is a synthetic harness around the REAL engine:
stub controls stand in for nodes (counting the handler deliveries the
tick schedules), while the tensors, the sharded tick, the clamp, the
fence lane and the apply loops are the production code paths.  The
full-protocol proofs (elections, transfers, linearizability) live in
pytest and examples/soak.py; this bench proves the mesh plane carries
the lanes at a G no single-process node population can reach.
"""

import argparse
import json
import sys
import time


# ---------------------------------------------------------------------------
# stub control plane: counts what the tick delivers, owns nothing else
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


# ---------------------------------------------------------------------------
# lane driver (smoke + scale + chip_smoke.py)
# ---------------------------------------------------------------------------

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
    # beat fan-out is bench_scale's measurement (real replicators); the
    # stub has none to flush, so park the hb lane out of the window
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


def _run_mesh(args) -> int:
    import asyncio

    import jax

    from tpuraft.util.jax_cache import ensure_compile_cache

    if args.smoke:
        # the one mode pinned to the CPU: virtual host devices, set
        # before the backend initialises
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.devices or 8)
    ensure_compile_cache()
    devices = args.devices or len(jax.devices())
    groups = args.groups or (1024 if args.smoke else 65536)
    duration = args.duration or (1.5 if args.smoke else 6.0)
    res = asyncio.run(drive_lanes(groups, devices, duration, args.seed))
    print("RESULT " + json.dumps(res), flush=True)
    if not res["ok"]:
        print("FAIL: " + "; ".join(res["failures"]), file=sys.stderr)
        return 1
    print(f"multichip {'smoke' if args.smoke else 'scale'} OK: "
          f"{res['groups']} groups / {res['mesh_devices']} "
          f"{res['platform']} devices, "
          f"{res['ticks_per_sec']} ticks/s", flush=True)
    return 0


# ---------------------------------------------------------------------------
# --engine-shape: single-device calibration for bench_gate.py
# ---------------------------------------------------------------------------

def _engine_shape_once(groups: int, peers: int, duration_s: float,
                       seed: int) -> float:
    import numpy as np

    from tpuraft.core.engine import (ROLE_FOLLOWER, ROLE_LEADER,
                                     MultiRaftEngine)
    from tpuraft.options import TickOptions

    rng = np.random.default_rng(seed)
    # never start()ed: _tick_fn stays None, so this measures the numpy
    # tick path — identical pre/post mesh work, which is the point of
    # the gate (the single-device shape must not regress)
    eng = MultiRaftEngine(TickOptions(max_groups=groups, max_peers=peers,
                                      tick_interval_ms=20))
    g = eng.G
    now = eng.now_ms()
    # leader-heavy standing state: half leaders, half followers, 3 voters
    eng.role[:] = np.where(np.arange(g) % 2 == 0, ROLE_LEADER,
                           ROLE_FOLLOWER)
    eng.voter_mask[:, :3] = True
    eng.self_col[:] = 0
    eng.has_ctrl[:] = False      # no ctrls: measure the tick plane only
    eng.last_ack[:, :3] = now    # fresh quorum: no step_down churn
    eng.elect_deadline[:] = now + 3_600_000
    eng.hb_deadline[:] = now + 3_600_000
    eng.stepdown_deadline[:] = now + 3_600_000
    eng.match_abs[:, :3] = rng.integers(1, 50, size=(g, 3))
    eng.pending_rel[:] = 1
    t0 = time.perf_counter()
    ticks = 0
    while time.perf_counter() - t0 < duration_s:
        eng.tick_once()
        ticks += 1
    return ticks / (time.perf_counter() - t0)


def _run_engine_shape(args) -> int:
    best = max(_engine_shape_once(args.groups or 1024, 4,
                                  args.duration or 2.0, args.seed)
               for _ in range(3))
    print("RESULT " + json.dumps(
        {"engine_ticks_per_sec": round(best, 1),
         "groups": args.groups or 1024}), flush=True)
    return 0


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--smoke", action="store_true",
                      help="fast CPU 8-device lane-parity dryrun")
    mode.add_argument("--scale", action="store_true",
                      help="64K-group acceptance rung on the devices "
                           "JAX reports")
    mode.add_argument("--engine-shape", action="store_true",
                      help="single-device tick-rate calibration shape "
                           "(bench_gate.py row)")
    ap.add_argument("--groups", type=int, default=0,
                    help="override G (default: 1024 smoke / 65536 scale)")
    ap.add_argument("--devices", type=int, default=0,
                    help="mesh size (default: 8 virtual CPU devices "
                         "for --smoke, every device JAX reports for "
                         "--scale)")
    ap.add_argument("--duration", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    if args.engine_shape:
        sys.exit(_run_engine_shape(args))
    sys.exit(_run_mesh(args))


if __name__ == "__main__":
    main()
