"""Test configuration: JAX on a virtual 8-device CPU mesh, and a time limit
on every test.

The tests run with ``JAX_PLATFORMS=cpu``; setting the ``jax_platforms``
config here as well makes a bare ``pytest`` do the same. Sharding is
exercised on 8 virtual CPU devices. The chip is reached only through
``chip_smoke.py`` (README "Run").

``@pytest.mark.timeout(seconds)`` is implemented below with SIGALRM — the
pytest-timeout plugin is not installed, and a mark nothing implements
bounds nothing. Every other test gets ``DEFAULT_TIMEOUT_S``, so one hang
costs one test, not the run.
"""

import os
import signal
import threading

import pytest

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

DEFAULT_TIMEOUT_S = 300.0


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: minutes-long test, deselected by tier-1 (-m 'not slow')")
    config.addinivalue_line(
        "markers", "timeout(seconds): fail the test when its call phase runs "
        "longer (SIGALRM, tests/conftest.py; default "
        f"{DEFAULT_TIMEOUT_S:.0f}s)")


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    mark = item.get_closest_marker("timeout")
    limit = float(mark.args[0]) if mark and mark.args else DEFAULT_TIMEOUT_S
    if threading.current_thread() is not threading.main_thread():
        return (yield)  # signals are delivered to the main thread only

    def on_alarm(_sig, _frame):
        raise TimeoutError(f"{item.nodeid} exceeded its {limit:.0f}s limit")

    old = signal.signal(signal.SIGALRM, on_alarm)
    # re-fires every 5 s past the limit: one alarm landing inside a task
    # step is swallowed into that task's result and the hang goes on
    signal.setitimer(signal.ITIMER_REAL, limit, 5.0)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)
