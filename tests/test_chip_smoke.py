"""Bring-up guards (ISSUE 21): nothing on the served route may hide the
device, take the chip by accident, or move the compile cache — and the
chip smoke's own phases must keep running between chip runs."""

import asyncio
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _py(code: str, cwd: str = REPO, unset: str = "",
        **env) -> subprocess.CompletedProcess:
    full = dict(os.environ, PYTHONPATH=REPO, **env)
    full.pop(unset, None)
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          cwd=cwd, env=full, capture_output=True, text=True,
                          timeout=120)


def test_importing_the_engine_initialises_no_backend():
    """A process that only imports the package (a numpy-backend child, a
    launcher parent) must not open the accelerator: the chip belongs to
    the one process that asks for it."""
    r = _py("""
        import tpuraft.core.engine, tpuraft.ops, tpuraft.parallel
        from jax._src import xla_bridge
        assert not xla_bridge.backends_are_initialized()
    """)
    assert r.returncode == 0, r.stderr


def test_compile_cache_dir_env_left_alone_else_checkout(tmp_path):
    code = """
        import jax
        from tpuraft.util.jax_cache import ensure_compile_cache
        print(ensure_compile_cache())
        print(jax.config.jax_compilation_cache_dir)
    """
    r = _py(code, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(tmp_path), str(tmp_path)]
    # found from the package's own path, not from the working directory
    r = _py(code, cwd=str(tmp_path), unset="JAX_COMPILATION_CACHE_DIR")
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [os.path.join(REPO, ".jax_cache")] * 2


@pytest.mark.parametrize("backend", ["jax", "auto"])
def test_unobtainable_platform_raises_not_numpy(backend):
    """A backend that cannot initialise is an error — never a quiet
    landing on the numpy twin."""
    r = _py(f"""
        import asyncio
        from tpuraft.core.engine import MultiRaftEngine
        from tpuraft.options import TickOptions

        async def main():
            eng = MultiRaftEngine(TickOptions(max_groups=16, max_peers=4,
                                              backend={backend!r}))
            try:
                await eng.start()
            except RuntimeError as e:
                print("RAISED", e)
                return
            print("STARTED", eng._tick_fn)

        asyncio.run(main())
    """, JAX_PLATFORMS="no_such_platform")
    assert "RAISED" in r.stdout and "STARTED" not in r.stdout, \
        r.stdout + r.stderr


def test_chip_smoke_refuses_to_run_without_the_chip():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "'cpu'" in r.stderr
    assert "platform: cpu" in r.stdout
    assert '"ok"' not in r.stdout and "[smoke" not in r.stdout  # no phase ran


def test_chip_smoke_last_line_is_the_result_object_and_nothing_more(capsys):
    """The driver reads the last stdout line: exactly ``ok`` and ``device``
    (``platform``, ``kind``, ``count``); the detail goes on the line
    before it."""
    import json

    import chip_smoke

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert chip_smoke.report(device, {"phases": {"a": {"ok": True, "n": 3}},
                                      "claim": None})
    summary, last = capsys.readouterr().out.splitlines()
    assert json.loads(last) == {"ok": True, "device": device}
    assert list(json.loads(last)) == ["ok", "device"]
    assert summary.startswith("summary: ")
    detail = json.loads(summary[len("summary: "):])
    assert detail["phases"]["a"]["n"] == 3 and detail["claim"] is None
    assert not chip_smoke.report(device, {"phases": {"a": {"ok": False}}})
    assert json.loads(capsys.readouterr().out.splitlines()[-1])["ok"] is False


def test_native_build_failure_raises_over_a_stale_library(tmp_path):
    from tpuraft.util.native_build import ensure_built

    (tmp_path / "libx.so").write_bytes(b"stale")
    os.utime(tmp_path / "libx.so", (1, 1))
    (tmp_path / "Makefile").write_text("all:\n\t@echo broken >&2; exit 1\n")
    with pytest.raises(RuntimeError, match="native build failed"):
        ensure_built(str(tmp_path), str(tmp_path / "libx.so"))


def test_chip_smoke_phases_at_tiny_size():
    """The smoke's phase functions, on the CPU: 64 regions, Pallas in
    interpret mode, the mesh and replica phases over four of the eight
    virtual devices."""
    import chip_smoke
    from chip_smoke import drive_lanes

    k = chip_smoke.phase_kernels(0, shapes=((256, 4), (200, 8)),
                                 interpret=True)
    assert k["ok"] and k["tick_outputs_equal"] == 11
    lanes = asyncio.run(drive_lanes(512, 4, 0.5, 0))
    assert lanes["ok"], lanes["failures"]
    assert lanes["rows_per_shard"] == [128] * 4
    one = asyncio.run(drive_lanes(256, 1, 0.3, 0, peers=8))
    assert one["ok"], one["failures"]
    assert chip_smoke.phase_replica(0, groups=64)["ok"]
    s = chip_smoke.phase_serve(0, regions=64, election_timeout_ms=1000,
                               elect_deadline_s=60)
    assert s["ok"] and s["leaders"] == 64
    assert s["loaded"] == s["read_back"] == 64 * 16
    assert s["parity_rows"] == [11, 11, 11]
    assert s["read_device_fences"] > 0 and s["tick_failures"] == 0
