"""The benchmark's command: one run of one cell on the chip.

    python -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process per run.  Checks the manifest, requires the TPU (and as many
chips as the cell asks for; it fails, it never falls back), builds the
cluster, loads, warms, measures ``--seconds``, settles and checks, and prints
the result object as the last line of standard output.  Detail goes on an
earlier ``summary: {...}`` line.  Each number compared for ``correct`` is
printed beside its limit as the last lines of standard error and under
``checks``, the result's last key.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORK = os.path.join(ROOT, ".benchmark_work")


def require_chip(chips: int) -> dict:
    """The device as JAX reports it; exits non-zero, with no result, unless
    it is a TPU with at least ``chips`` chips."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: {device}  jax: {jax.__version__}", file=sys.stderr,
          flush=True)
    if device["platform"] != "tpu":
        sys.exit(f"benchmark.run: no TPU: JAX found platform "
                 f"{device['platform']!r}; nothing is measured off the chip")
    if device["count"] < chips:
        sys.exit(f"benchmark.run: the cell asks for {chips} chips, JAX "
                 f"found {device['count']}")
    return device


def print_result(result: dict) -> None:
    """``summary:`` line, then the result (``checks`` last) on standard
    output; the compared numbers beside their limits, last on standard
    error."""
    summary = result.pop("_summary", None)
    checks = result.pop("checks")
    if summary is not None:
        print("summary: " + json.dumps(summary), flush=True)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} {c['op']} {c['limit']}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    result["checks"] = checks
    print(json.dumps(result), flush=True)


def main(argv=None, fault: str | None = None) -> int:
    """``fault`` is ``benchmark.control``'s: the command has no such option."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import check_manifest

    bm = check_manifest.check(ROOT)
    cell, _cfg, _traffic = check_manifest.cell(bm, args.workload)
    device = require_chip(cell["chips"])

    from tpuraft.util.jax_cache import ensure_compile_cache

    from benchmark.driver import run_cell

    cache_dir = ensure_compile_cache()
    workdir = os.path.join(WORK, f"{args.workload}-{os.getpid()}")
    os.makedirs(workdir, exist_ok=True)

    def say(msg: str) -> None:
        print(f"[+{time.perf_counter() - _T_PROCESS:6.1f}s] {msg}",
              file=sys.stderr, flush=True)

    say(f"compile cache: {cache_dir}")

    async def run_and_exit() -> None:
        result = await run_cell(
            bm, args.workload, args.seed, args.seconds, bool(args.trace),
            workdir, device, _T_PROCESS, fault=fault, say=say,
            teardown=False)
        say("done")
        print_result(result)
        # the result is out and nothing of the cluster outlives the process:
        # leave without closing 3,072 replicas one by one
        sys.stdout.flush()
        sys.stderr.flush()
        shutil.rmtree(workdir, ignore_errors=True)
        os._exit(0)

    asyncio.run(run_and_exit())
    return 0


if __name__ == "__main__":
    sys.exit(main())
