"""Test fixtures.

Mirrors the reference's fixture strategy (ref: python/ray/tests/conftest.py:410
ray_start_regular; cluster fixtures building real multi-raylet clusters
in-process). JAX tests run on a virtual 8-device CPU mesh
(--xla_force_host_platform_device_count), the reference-recommended way to
exercise 256-chip sharding logic in CI.
"""
import faulthandler
import os
import signal
import sys
import threading
import time

# Tests run on the CPU: a virtual 8-device mesh. Set before jax is imported
# anywhere in the test process (workers inherit the environment).
os.environ["JAX_PLATFORMS"] = "cpu"
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

# Every test phase (set-up, call, tear-down) runs under a limit of its own,
# so one stuck test fails by name instead of running the whole suite into
# its clock. `@pytest.mark.time_limit(seconds)` overrides it; 0 switches it
# off (for a test that needs SIGALRM itself).
DEFAULT_TIME_LIMIT_S = 180


def _under_time_limit(item):
    marker = item.get_closest_marker("time_limit")
    limit = marker.args[0] if marker else DEFAULT_TIME_LIMIT_S
    # SIGALRM is delivered to the main thread only
    if not limit or threading.current_thread() is not threading.main_thread():
        return (yield)

    def on_alarm(signum, frame):
        faulthandler.dump_traceback(file=sys.__stderr__, all_threads=True)
        pytest.fail(f"time limit {limit} s")

    previous = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    return (yield from _under_time_limit(item))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    return (yield from _under_time_limit(item))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    return (yield from _under_time_limit(item))


@pytest.fixture
def machine_load():
    """Runnable processes per core over the last minute (1.0: every core
    busy). A speed-up measured between two runs on this machine shrinks
    when both fight other work for cores, so a floor found on an idle box
    takes its scale from this, not from the core count alone."""
    try:
        return os.getloadavg()[0] / (os.cpu_count() or 1)
    except OSError:
        return 0.0


@pytest.fixture
def ray_start_regular():
    import ray_tpu

    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True, head_resources={"CPU": 2.0})
    yield cluster
    cluster.shutdown()


@pytest.fixture
def wait_engine_aborted():
    """Wait until a pipeline engine's abort after a kill has run its course:
    the engine is torn down and no abort thread is left. The state a test
    needs before `recover()`: an abort thread that is still writing its
    post-mortem tears down whatever graph it finds afterwards, the
    recovered one included (ROADMAP C11). The engine has no public
    "closed" state yet, so this reads `_torn` and the abort thread's name;
    `test_recover_without_checkpoint_restarts_from_step_zero[at-once]`
    calls `recover()` without it, as a user's loop does."""
    def wait(eng, timeout=60.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if eng._torn and not any(t.name.startswith("pipeline-abort-")
                                     for t in threading.enumerate()):
                return True
            time.sleep(0.05)
        return False
    return wait
