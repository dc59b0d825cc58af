"""Test env: JAX on the CPU with 8 virtual host devices, so the sharding
tests exercise a realistic mesh without TPU hardware (SURVEY.md §5
lesson: N real nodes, one process).  ``JAX_PLATFORMS`` is set for the
subprocesses tests spawn; the compile cache goes where
``tpuraft.util.jax_cache`` puts it for every other entry point."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")  # for subprocesses

import jax  # noqa: E402

from tpuraft.util.jax_cache import ensure_compile_cache  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
ensure_compile_cache()

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: run test via asyncio.run")


# Hard cap per async test so a protocol deadlock fails the one test loudly
# instead of wedging the whole suite (first JAX compiles can take ~40s;
# integration tests poll with 5s deadlines — 120s is comfortably above both).
ASYNC_TEST_TIMEOUT_S = 120


def pytest_pyfunc_call(pyfuncitem):
    """Minimal asyncio test support (pytest-asyncio is not in the image)."""
    func = pyfuncitem.obj
    if inspect.iscoroutinefunction(func):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }

        async def capped():
            await asyncio.wait_for(func(**kwargs), ASYNC_TEST_TIMEOUT_S)

        asyncio.run(capped())
        return True
    return None
