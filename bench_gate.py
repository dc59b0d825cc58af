"""CPU host bench: the pre-merge perf gate (`make bench-gate`).  Short
bench runs at the committed configurations must not regress by more
than the threshold (default 20%).  Every child is pinned with
``JAX_PLATFORMS=cpu``; no number here is a device number (ROADMAP A1
replaces this gate with per-cell bounds measured on the chip).

Rows:
  e2e_commits_per_sec — a short `bench_e2e.py` run vs BENCH_E2E.json
  engine_ticks_per_sec — the single-device engine tick rate at the
                        committed leader-heavy shape (bench_multichip
                        --engine-shape) vs BENCH_E2E.json
                        extra.gate_engine_ticks_per_sec, so the mesh-
                        mode work (ISSUE 19: witness clamp, stepdown
                        lane, fence tallies in every tick) can't tax
                        the single-device engine unnoticed.
  kv_ops_per_sec      — a short `bench_region_density.py` run (the full
                        RheaKV serving stack: batching client →
                        kv_command_batch → propose fan-out → coalesced
                        FSM apply) vs BENCH_REGIONS.json, so the
                        KV-vs-protocol throughput gap (ROADMAP item 1)
                        can't silently reopen.
  kv_read_ops_per_sec — the 95/5 read-mix shape vs its calibration.
  kv_write_ops_per_sec — the saturated pure-write shape (w256 @128
                        regions) vs its calibration, so write-plane
                        regressions (ISSUE 15's append rounds + eager
                        commits + ack-at-commit) gate like the rest.
  kv_mp_write_ops_per_sec — the SAME saturated pure-write shape with
                        each store a real OS process (bench_multiproc:
                        examples.proc_supervisor children over real
                        sockets) vs its calibration, so the process
                        fabric (ISSUE 16: READY probes, drain contract,
                        per-process CPU attribution) gates alongside
                        the in-process rows.  Calibration is same-host:
                        on a 1-CPU container the mp shape pays socket +
                        context-switch cost with no parallelism to buy,
                        and the floor reflects that honestly.
  kv_ops_traced       — tracing-overhead gate: the untraced rows above
                        run with the trace plane DISABLED (the
                        zero-cost claim — any always-on cost regresses
                        them vs calibration), and this row re-runs the
                        kv shape with 5%-sampled tracing, which must
                        stay within BENCH_GATE_TRACE_THRESHOLD
                        (default 5%) of the same-session untraced
                        measurement.
  kv_ops_heat_overhead — heat-accounting gate: per-region heat
                        tracking defaults ON, so the kv row already
                        pays for it; this row runs the same shape with
                        --no-heat and the heat-ON measurement must
                        stay within BENCH_GATE_HEAT_THRESHOLD
                        (default 3%) of the heat-OFF comparator.
  kv_ops_disk_guard   — disk-budget gate (ISSUE 17): the DiskBudget
                        accounting + admission check default ON, so
                        the kv row already pays for them; this row
                        runs the same shape with --no-disk-guard and
                        the guard-ON measurement must stay within
                        BENCH_GATE_DISK_THRESHOLD (default 2%) of the
                        guard-OFF comparator — the hot-path cost of
                        the pressure plane is a couple of integer adds
                        and one dict lookup, and this row keeps it so.
  kv_ops_clocked      — injected-clock gate (ISSUE 18): the default
                        rows run on the zero-indirection SYSTEM clock
                        (module-level staticmethods bound to the C
                        time functions), and this row re-runs the kv
                        shape with --chaos-clock (a per-store
                        ChaosClock at rate 1.0 — the full virtual-
                        clock arithmetic with no behavior change),
                        which must stay within
                        BENCH_GATE_CLOCK_THRESHOLD (default 2%) of
                        the same-session uninjected measurement.
  kv_ops_lifecycle_overhead — region-lifecycle gate (ISSUE 20): the kv
                        row runs against a counting fake PD; this row
                        re-runs the shape against a REAL placement
                        driver with the lifecycle policy loop on and
                        every actuator held idle, and must stay within
                        BENCH_GATE_LIFECYCLE_THRESHOLD (default 3%) of
                        the same-session fake-PD measurement — policy
                        evaluation at 128 regions is pure PD-side scan
                        work and must never tax the serving path.

The committed JSONs are the contract, but gate runs are SHORT (boot +
elections amortize worse over a 6 s window than over a full bench), so
each floor is derived from a same-shape calibration value stored as
``extra.gate_commits_per_sec`` / ``extra.gate_kv_ops_per_sec`` in the
respective JSON — record both with ``python bench_gate.py --record`` on
the host that runs the gate.  Without a calibration the e2e row falls
back to the full-run ``value`` (conservative); the KV row cannot (its
full run uses a different duration/region shape) and reads as broken.

A run below its floor is retried (best-of-N, default 2 extra runs)
before the gate fails: a real regression makes EVERY run slow, while a
noisy-neighbour phase on a shared host does not survive three samples.
Exit 0 = within threshold, 1 = regression, 2 = the gate itself could
not run (missing baseline, bench crash) — a broken gate must read as
failure, not as a pass.

    python bench_gate.py                 # both rows, 20%
    python bench_gate.py --record        # (re)calibrate both baselines
    BENCH_GATE_THRESHOLD=0.3 python bench_gate.py   # looser (noisy CI)
    BENCH_GATE_RETRIES=0 python bench_gate.py       # strict single run
"""

import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.abspath(__file__))


def _run_e2e_once(extra: dict, duration: float) -> float:
    """One short bench_e2e run at the committed shape; returns commits/s
    or raises RuntimeError when the bench itself fails."""
    out_path = os.path.join(tempfile.mkdtemp(prefix="tpuraft_gate_"),
                            "gate.json")
    cmd = [sys.executable, os.path.join(REPO, "bench_e2e.py"),
           "--groups", str(extra.get("groups", 64)),
           "--stores", str(extra.get("stores", 3)),
           "--window", str(extra.get("window_per_group", 8)),
           "--payload", str(extra.get("payload_bytes", 16)),
           "--duration", str(duration), "--warmup", "2",
           "--skip-brk", "--json-out", out_path]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    print("bench-gate:", " ".join(cmd), flush=True)
    rc = subprocess.call(cmd, env=env)
    if rc != 0 or not os.path.exists(out_path):
        raise RuntimeError(f"bench run failed (rc={rc})")
    with open(out_path) as f:
        return float(json.load(f)["value"])


def _run_kv_once(extra: dict, duration: float,
                 read_frac: float = -1.0,
                 trace_sample: float = 0.0,
                 heat_off: bool = False,
                 disk_guard_off: bool = False,
                 chaos_clock: bool = False,
                 lifecycle_pd: bool = False,
                 workers: int = 0) -> float:
    """One short bench_region_density run at the gate shape; returns
    KV ops/s through the full serving stack.  ``read_frac >= 0`` runs
    the read-mix shape (the amortized read plane's regression row);
    ``trace_sample > 0`` runs with product tracing sampling at that
    rate (the tracing-overhead row); ``heat_off`` disables per-region
    heat tracking (the heat-overhead row's A/B comparator);
    ``disk_guard_off`` disables the disk budget / pressure plane (the
    disk-guard-overhead row's A/B comparator); ``chaos_clock`` routes
    every store's timing reads through an injected ChaosClock at rate
    1.0 (the clock-overhead row's A/B comparator); ``lifecycle_pd``
    replaces the counting fake PD with a real placement driver whose
    lifecycle policy loop runs with every actuator held idle (the
    lifecycle-overhead row's A/B comparator)."""
    regions = int(extra.get("gate_regions", 128))
    out_path = os.path.join(tempfile.mkdtemp(prefix="tpuraft_gate_kv_"),
                            "gate_regions.json")
    cmd = [sys.executable, os.path.join(REPO, "bench_region_density.py"),
           "--regions", str(regions),
           "--duration", str(duration),
           "--election-timeout-ms", str(extra.get("gate_eto_ms", 1000)),
           "--json-out", out_path]
    key = "row" if regions == 1024 else f"row_{regions}"
    if workers > 0:
        cmd += ["--workers", str(workers)]
        if workers != 24:
            key += f"_w{workers}"
    if read_frac >= 0:
        cmd += ["--read-frac", str(read_frac)]
        key += f"_r{int(round(read_frac * 100))}"
    if trace_sample > 0:
        cmd += ["--trace-sample", str(trace_sample)]
    if heat_off:
        cmd.append("--no-heat")
        key += "_noheat"
    if disk_guard_off:
        cmd.append("--no-disk-guard")
        key += "_nodg"
    if chaos_clock:
        cmd.append("--chaos-clock")
        key += "_ck"
    if lifecycle_pd:
        cmd.append("--lifecycle-pd")
        key += "_lcpd"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    print("bench-gate:", " ".join(cmd), flush=True)
    rc = subprocess.call(cmd, env=env)
    if rc != 0 or not os.path.exists(out_path):
        raise RuntimeError(f"kv bench run failed (rc={rc})")
    with open(out_path) as f:
        data = json.load(f)
    row = data.get(key, {})
    if "ops_per_sec" not in row:
        raise RuntimeError(f"kv bench produced no {key}.ops_per_sec")
    return float(row["ops_per_sec"])


def _run_mp_once(extra: dict, duration: float) -> float:
    """One short bench_multiproc run at the gate shape: real OS-process
    stores (examples.proc_supervisor) serving the saturated pure-write
    workload over real sockets; returns cross-process KV ops/s."""
    regions = int(extra.get("gate_mp_regions", 128))
    out_path = os.path.join(tempfile.mkdtemp(prefix="tpuraft_gate_mp_"),
                            "gate_mp.json")
    cmd = [sys.executable, os.path.join(REPO, "bench_multiproc.py"),
           "--regions", str(regions),
           "--duration", str(duration),
           "--workers", "256",
           # calibration shape: long eto keeps timer-mode standing load
           # flat so the short window measures serving, not elections
           "--election-timeout-ms",
           str(extra.get("gate_mp_eto_ms", 10000)),
           "--json-out", out_path]
    key = ("row_mp" if regions == 1024 else f"row_mp_{regions}") \
        + "_w256_r0"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    print("bench-gate:", " ".join(cmd), flush=True)
    rc = subprocess.call(cmd, env=env)
    if rc != 0 or not os.path.exists(out_path):
        raise RuntimeError(f"mp bench run failed (rc={rc})")
    with open(out_path) as f:
        data = json.load(f)
    row = data.get(key, {})
    if "ops_per_sec" not in row:
        raise RuntimeError(f"mp bench produced no {key}.ops_per_sec")
    return float(row["ops_per_sec"])


def _run_engine_once(extra: dict) -> float:
    """One bench_multichip --engine-shape run: the single-device engine
    tick rate at the committed leader-heavy shape (numpy tick path, no
    mesh).  The row pins the per-tick host cost of the [G] lanes — the
    group-axis sharding work must not tax the single-device engine."""
    cmd = [sys.executable, os.path.join(REPO, "bench_multichip.py"),
           "--engine-shape",
           "--groups", str(extra.get("gate_engine_groups", 1024)),
           "--duration", str(extra.get("gate_engine_duration_s", 2.0))]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    print("bench-gate:", " ".join(cmd), flush=True)
    out = subprocess.run(cmd, env=env, capture_output=True, text=True)
    if out.returncode != 0:
        raise RuntimeError(f"engine shape bench failed "
                           f"(rc={out.returncode}): {out.stderr[-300:]}")
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            return float(json.loads(
                line[len("RESULT "):])["engine_ticks_per_sec"])
    raise RuntimeError("engine shape bench produced no RESULT line")


def _gate(name: str, committed: float, run_once, threshold: float,
          retries: int) -> tuple[int, dict]:
    floor = committed * (1.0 - threshold)
    best, runs = 0.0, 0
    try:
        for attempt in range(1 + max(0, retries)):
            best = max(best, run_once())
            runs = attempt + 1
            if best >= floor:
                break
            if attempt < retries:
                print(f"bench-gate[{name}]: {best:.1f} < floor {floor:.1f}, "
                      f"retrying ({attempt + 1}/{retries})", flush=True)
    except RuntimeError as exc:
        print(f"bench-gate[{name}]: {exc}")
        return 2, {"gate": name, "verdict": "BROKEN", "error": str(exc)}
    verdict = "OK" if best >= floor else "REGRESSION"
    report = {
        "gate": name,
        "committed": committed,
        "measured": round(best, 1),
        "floor": round(floor, 1),
        "threshold": threshold,
        "runs": runs,
        "verdict": verdict,
    }
    return (0 if verdict == "OK" else 1), report


def main() -> int:
    e2e_path = os.path.join(REPO, "BENCH_E2E.json")
    kv_path = os.path.join(REPO, "BENCH_REGIONS.json")
    if not os.path.exists(e2e_path):
        print("bench-gate: no committed BENCH_E2E.json baseline")
        return 2
    with open(e2e_path) as f:
        e2e_base = json.load(f)
    kv_base = {}
    if os.path.exists(kv_path):
        with open(kv_path) as f:
            kv_base = json.load(f)
    e2e_extra = e2e_base.get("extra", {})
    kv_extra = kv_base.setdefault("extra", {})
    threshold = float(os.environ.get("BENCH_GATE_THRESHOLD", "0.20"))
    duration = float(os.environ.get("BENCH_GATE_DURATION", "6"))
    retries = int(os.environ.get("BENCH_GATE_RETRIES", "2"))

    if "--record" in sys.argv[1:]:
        # calibrate: best-of-2 short runs per row
        try:
            e2e_best = max(_run_e2e_once(e2e_extra, duration)
                           for _ in range(2))
            kv_best = max(_run_kv_once(kv_extra, duration)
                          for _ in range(2))
            read_best = max(_run_kv_once(kv_extra, duration, read_frac=0.95)
                            for _ in range(2))
            write_best = max(_run_kv_once(kv_extra, duration,
                                          read_frac=0.0, workers=256)
                             for _ in range(2))
            mp_best = max(_run_mp_once(kv_extra, duration)
                          for _ in range(2))
            engine_best = max(_run_engine_once(e2e_extra)
                              for _ in range(2))
        except RuntimeError as exc:
            print(f"bench-gate: {exc}")
            return 2
        e2e_extra["gate_commits_per_sec"] = round(e2e_best, 1)
        e2e_extra["gate_engine_ticks_per_sec"] = round(engine_best, 1)
        e2e_extra["gate_duration_s"] = duration
        e2e_base["extra"] = e2e_extra
        with open(e2e_path, "w") as f:
            json.dump(e2e_base, f, indent=1)
            f.write("\n")
        kv_extra["gate_kv_ops_per_sec"] = round(kv_best, 1)
        kv_extra["gate_read_ops_per_sec"] = round(read_best, 1)
        kv_extra["gate_write_ops_per_sec"] = round(write_best, 1)
        kv_extra["gate_mp_write_ops_per_sec"] = round(mp_best, 1)
        kv_extra["gate_duration_s"] = duration
        kv_extra.setdefault("gate_regions", 128)
        kv_extra.setdefault("gate_eto_ms", 1000)
        with open(kv_path, "w") as f:
            json.dump(kv_base, f, indent=1)
            f.write("\n")
        print(json.dumps({"gate": "recorded",
                          "gate_commits_per_sec":
                              e2e_extra["gate_commits_per_sec"],
                          "gate_engine_ticks_per_sec":
                              e2e_extra["gate_engine_ticks_per_sec"],
                          "gate_kv_ops_per_sec":
                              kv_extra["gate_kv_ops_per_sec"],
                          "gate_read_ops_per_sec":
                              kv_extra["gate_read_ops_per_sec"],
                          "gate_write_ops_per_sec":
                              kv_extra["gate_write_ops_per_sec"],
                          "gate_mp_write_ops_per_sec":
                              kv_extra["gate_mp_write_ops_per_sec"],
                          "duration_s": duration}))
        return 0

    worst = 0
    reports = []
    rc, rep = _gate("e2e_commits_per_sec",
                    float(e2e_extra.get("gate_commits_per_sec",
                                        e2e_base["value"])),
                    lambda: _run_e2e_once(e2e_extra, duration),
                    threshold, retries)
    worst = max(worst, rc)
    reports.append(rep)
    if "gate_engine_ticks_per_sec" not in e2e_extra:
        # the single-device engine shape (ISSUE 19) needs its own row:
        # the mesh-mode sharding work lands new [G] lanes in every tick
        # and this is the floor that keeps them honest on one device
        print("bench-gate[engine_ticks_per_sec]: no calibration "
              "(run `python bench_gate.py --record`)")
        worst = max(worst, 2)
        reports.append({"gate": "engine_ticks_per_sec",
                        "verdict": "BROKEN",
                        "error": "no gate_engine_ticks_per_sec "
                                 "calibration"})
    else:
        rc, rep = _gate("engine_ticks_per_sec",
                        float(e2e_extra["gate_engine_ticks_per_sec"]),
                        lambda: _run_engine_once(e2e_extra),
                        threshold, retries)
        worst = max(worst, rc)
        reports.append(rep)
    if "gate_kv_ops_per_sec" not in kv_extra:
        # no same-shape calibration — a silent pass would defeat the row
        print("bench-gate[kv_ops_per_sec]: no calibration "
              "(run `python bench_gate.py --record`)")
        worst = max(worst, 2)
        reports.append({"gate": "kv_ops_per_sec", "verdict": "BROKEN",
                        "error": "no gate_kv_ops_per_sec calibration"})
    else:
        rc, rep = _gate("kv_ops_per_sec",
                        float(kv_extra["gate_kv_ops_per_sec"]),
                        lambda: _run_kv_once(kv_extra, duration),
                        threshold, retries)
        worst = max(worst, rc)
        reports.append(rep)
        # tracing-overhead row (observability plane): the untraced kv
        # rows above ARE the zero-cost claim (tracing defaults off, so
        # any always-on cost would regress them vs calibration); this
        # row additionally bounds SAMPLED tracing at 5% of the same-
        # session untraced measurement — same host phase, so shared-
        # host noise largely cancels (retries absorb the rest)
        if rep.get("verdict") == "OK":
            trace_threshold = float(os.environ.get(
                "BENCH_GATE_TRACE_THRESHOLD", "0.05"))
            rc, trep = _gate(
                "kv_ops_traced",
                float(rep["measured"]),
                lambda: _run_kv_once(kv_extra, duration,
                                     trace_sample=0.05),
                trace_threshold, retries)
            worst = max(worst, rc)
            trep["untraced"] = rep["measured"]
            reports.append(trep)
            # heat-overhead row (fleet observability): heat tracking
            # defaults ON, so the kv row above already PAYS for heat —
            # gate it against a same-session heat-OFF run at 3%.  The
            # committed floor is the heat-off measurement (the faster
            # comparator); retries re-run the heat-ON side.
            heat_threshold = float(os.environ.get(
                "BENCH_GATE_HEAT_THRESHOLD", "0.03"))
            try:
                heat_off = _run_kv_once(kv_extra, duration,
                                        heat_off=True)
                rc, hrep = _gate(
                    "kv_ops_heat_overhead", heat_off,
                    lambda: _run_kv_once(kv_extra, duration),
                    heat_threshold, retries)
                hrep["heat_off"] = round(heat_off, 1)
            except RuntimeError as exc:
                print(f"bench-gate[kv_ops_heat_overhead]: {exc}")
                rc, hrep = 2, {"gate": "kv_ops_heat_overhead",
                               "verdict": "BROKEN", "error": str(exc)}
            worst = max(worst, rc)
            reports.append(hrep)
            # disk-guard-overhead row (ISSUE 17): the DiskBudget is
            # fed from the hot path (a couple of integer adds per
            # append/snapshot) and the shed check is one state read at
            # admission — gate the guard-ON run against a same-session
            # guard-OFF comparator at 2% so the pressure plane can
            # never grow a per-op statvfs or lock without tripping CI.
            disk_threshold = float(os.environ.get(
                "BENCH_GATE_DISK_THRESHOLD", "0.02"))
            try:
                guard_off = _run_kv_once(kv_extra, duration,
                                         disk_guard_off=True)
                rc, drep = _gate(
                    "kv_ops_disk_guard", guard_off,
                    lambda: _run_kv_once(kv_extra, duration),
                    disk_threshold, retries)
                drep["disk_guard_off"] = round(guard_off, 1)
            except RuntimeError as exc:
                print(f"bench-gate[kv_ops_disk_guard]: {exc}")
                rc, drep = 2, {"gate": "kv_ops_disk_guard",
                               "verdict": "BROKEN", "error": str(exc)}
            worst = max(worst, rc)
            reports.append(drep)
            # injected-clock-overhead row (ISSUE 18): the kv row above
            # runs on the zero-indirection SYSTEM clock; this row runs
            # the SAME shape through a per-store ChaosClock at rate
            # 1.0 (full virtual-clock arithmetic, no behavior change)
            # and must stay within 2% of the same-session uninjected
            # measurement — the clock fabric can never grow a lock or
            # a syscall per read without tripping CI.
            clock_threshold = float(os.environ.get(
                "BENCH_GATE_CLOCK_THRESHOLD", "0.02"))
            rc, crep = _gate(
                "kv_ops_clocked",
                float(rep["measured"]),
                lambda: _run_kv_once(kv_extra, duration,
                                     chaos_clock=True),
                clock_threshold, retries)
            worst = max(worst, rc)
            crep["uninjected"] = rep["measured"]
            reports.append(crep)
            # lifecycle-overhead row (ISSUE 20): the kv row above runs
            # against a counting FAKE PD; this row re-runs the SAME
            # shape against a real placement driver whose lifecycle
            # policy loop evaluates every heartbeat round with every
            # actuator held idle (split/merge/move thresholds no run
            # can cross), and must stay within 3% of the same-session
            # fake-PD measurement — the policy scan over 128 regions'
            # heat/stats can never grow per-op cost on the serving
            # path without tripping CI.
            lifecycle_threshold = float(os.environ.get(
                "BENCH_GATE_LIFECYCLE_THRESHOLD", "0.03"))
            rc, lrep = _gate(
                "kv_ops_lifecycle_overhead",
                float(rep["measured"]),
                lambda: _run_kv_once(kv_extra, duration,
                                     lifecycle_pd=True),
                lifecycle_threshold, retries)
            worst = max(worst, rc)
            lrep["fake_pd"] = rep["measured"]
            reports.append(lrep)
    if "gate_read_ops_per_sec" not in kv_extra:
        # the amortized read plane (ISSUE 10) needs its own regression
        # row — a silent pass without a calibration would defeat it
        print("bench-gate[kv_read_ops_per_sec]: no calibration "
              "(run `python bench_gate.py --record`)")
        worst = max(worst, 2)
        reports.append({"gate": "kv_read_ops_per_sec", "verdict": "BROKEN",
                        "error": "no gate_read_ops_per_sec calibration"})
    else:
        rc, rep = _gate("kv_read_ops_per_sec",
                        float(kv_extra["gate_read_ops_per_sec"]),
                        lambda: _run_kv_once(kv_extra, duration,
                                             read_frac=0.95),
                        threshold, retries)
        worst = max(worst, rc)
        reports.append(rep)
    if "gate_write_ops_per_sec" not in kv_extra:
        # the batched write plane (ISSUE 15) needs its own regression
        # row: the saturated pure-write shape (w256) exercises the
        # append rounds + eager commits + ack-at-commit pipeline the
        # default 24-worker mixed row barely touches
        print("bench-gate[kv_write_ops_per_sec]: no calibration "
              "(run `python bench_gate.py --record`)")
        worst = max(worst, 2)
        reports.append({"gate": "kv_write_ops_per_sec",
                        "verdict": "BROKEN",
                        "error": "no gate_write_ops_per_sec calibration"})
    else:
        rc, rep = _gate("kv_write_ops_per_sec",
                        float(kv_extra["gate_write_ops_per_sec"]),
                        lambda: _run_kv_once(kv_extra, duration,
                                             read_frac=0.0, workers=256),
                        threshold, retries)
        worst = max(worst, rc)
        reports.append(rep)
    if "gate_mp_write_ops_per_sec" not in kv_extra:
        # the process fabric (ISSUE 16) needs its own regression row:
        # the cross-process topology exercises READY probes, framed
        # sockets, and the drain contract that no in-process row touches
        print("bench-gate[kv_mp_write_ops_per_sec]: no calibration "
              "(run `python bench_gate.py --record`)")
        worst = max(worst, 2)
        reports.append({"gate": "kv_mp_write_ops_per_sec",
                        "verdict": "BROKEN",
                        "error": "no gate_mp_write_ops_per_sec "
                                 "calibration"})
    else:
        rc, rep = _gate("kv_mp_write_ops_per_sec",
                        float(kv_extra["gate_mp_write_ops_per_sec"]),
                        lambda: _run_mp_once(kv_extra, duration),
                        threshold, retries)
        worst = max(worst, rc)
        reports.append(rep)
    for rep in reports:
        print(json.dumps(rep))
    return worst


if __name__ == "__main__":
    sys.exit(main())
