"""Test fixtures.

Tests are CPU runs by definition: XLA's CPU backend with 8 virtual devices,
so compiles are fast, float64 is true float64 (the bit-exactness oracle),
the multi-chip sharding paths execute, and six xdist workers never reach
for a chip.  Differential fixtures mirror the reference's
with_cpu_session/with_gpu_session oracle (reference:
integration_tests/src/main/python/spark_session.py:145-158) and the
@inject_oom fault-injection marker (conftest.py:177).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The persistent compile cache stays off for the suite, in this process and
# in every executor/worker process a test spawns (they inherit the
# variable): jaxlib 0.9's cache write has crashed natively on the CPU
# backend under the engine's thread pool, and a test run must not fill
# <checkout>/.jax_cache.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

# Force the platform and the device count before the first backend use,
# whatever the environment says (backends initialise lazily, so config
# updates made here still apply).
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_compilation_cache", False)
jax.config.update("jax_enable_x64", True)

import faulthandler  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

#: Every test's own time limit, module fixtures included: some three times
#: the slowest test under the driver's command (test_tpcds_gauntlet's
#: test_q7, 202 s beside five busy workers).  Past it the worker prints
#: every thread's stack into the log and dies; xdist reports `node down`,
#: fails that one test and hands the rest of its queue to a new worker.
TEST_LIMIT_S = 600

#: the real stderr, duplicated before capture takes fd 2 for each test
_STDERR_FD = None


def pytest_configure(config):
    global _STDERR_FD
    _STDERR_FD = os.dup(2)
    config.addinivalue_line(
        "markers",
        "inject_oom: inject synthetic retry/split OOMs into the device arena "
        "mid-query; the differential oracle then proves retry correctness "
        "(reference: spark.rapids.sql.test.injectRetryOOM).",
    )
    config.addinivalue_line(
        "markers",
        "allow_non_gpu(*names): permit the listed execs/exprs to fall back "
        "to CPU in the plan-shape assertion.",
    )


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item, nextitem):
    faulthandler.dump_traceback_later(TEST_LIMIT_S, exit=True,
                                      file=_STDERR_FD)
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()


@pytest.fixture(autouse=True)
def _device_permits_whole(request):
    """After every test the device semaphore is whole and the test's own
    thread holds nothing: a permit that leaks fails the test that leaked
    it, and no longer hangs whichever two-task query comes a hundred tests
    later in the same process.  A query still running in the background
    gets a few seconds to give its permits back; a leak never does."""
    yield
    from spark_rapids_tpu.memory.semaphore import tpu_semaphore
    sem = tpu_semaphore()
    total = sem.occupancy()["semaphore_slots_total"]
    deadline = time.monotonic() + 5.0
    while sem._sem.available() != total and time.monotonic() < deadline:
        time.sleep(0.05)
    free, mine = sem._sem.available(), sem.held_count()
    # made whole again, so that only the test that leaked is blamed
    sem._tls.held = 0
    sem._sem.release(total - sem._sem.available())
    assert free == total and mine == 0, (
        f"{request.node.nodeid} leaves the device semaphore with {free} of "
        f"{total} permits free and {mine} held by the test's own thread")


@pytest.fixture(autouse=True)
def _seeded_rng():
    np.random.seed(0)


@pytest.fixture(autouse=True)
def _inject_oom_marker(request):
    """Activate OOM injection for tests marked @pytest.mark.inject_oom."""
    marker = request.node.get_closest_marker("inject_oom")
    if marker is None:
        yield
        return
    from spark_rapids_tpu.memory import retry as retry_mod

    retry_mod.enable_oom_injection(num_ooms=1, skip=0, kind="retry")
    try:
        yield
    finally:
        retry_mod.disable_oom_injection()
