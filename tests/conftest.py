"""Test harness config: run everything on a virtual 8-device CPU mesh.

Tests never touch an accelerator: sharding correctness is validated on 8
virtual CPU devices (the same mechanism `__graft_entry__.dryrun_multichip`
uses), and the chip is reached only through `chip_smoke.py`. The platform
is pinned both in the environment and in jax.config (the config outranks
the environment should anything have imported jax first), before any
backend is initialized by a test.
"""

import faulthandler
import os
import signal

import pytest

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# One test's own time limit. The slowest test (a bench `--smoke` subprocess,
# with its own 420 s timeout) takes about a minute, so a test still running
# past this is stuck: its process writes every thread's stack to the stderr
# the run started with, and the test fails there. A test stuck where no
# Python runs (a deadlock in native code) is ended GRACE_S later: the
# process exits, and under pytest-xdist the run goes on in a new worker.
TEST_TIME_LIMIT_S = 480.0
GRACE_S = 60.0
_WATCHDOG: dict = {}


def pytest_configure(config):
    # no pytest.ini/setup.cfg in this repo: register the marker here so
    # `-m 'not slow'` (the tier-1 selection) runs warning-free
    config.addinivalue_line(
        "markers",
        "slow: multi-second drills (chaos convergence); excluded from the "
        "tier-1 `-m 'not slow'` run")
    # a copy of stderr taken before any test's output capture
    _WATCHDOG["file"] = os.fdopen(os.dup(2), "w")
    # pytest's own faulthandler_timeout, when given, owns faulthandler's
    # one timer
    _WATCHDOG["backstop"] = not float(
        config.getini("faulthandler_timeout") or 0)


def pytest_unconfigure(config):
    f = _WATCHDOG.pop("file", None)
    if f is not None:
        f.close()


def _time_limit_reached(signum, frame):
    f = _WATCHDOG["file"]
    f.write(f"\n{_WATCHDOG['nodeid']} still running after "
            f"{TEST_TIME_LIMIT_S:.0f} s; every thread's stack:\n")
    f.flush()
    faulthandler.dump_traceback(file=f)
    pytest.fail(f"still running after {TEST_TIME_LIMIT_S:.0f} s, "
                "the time limit of one test")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    _WATCHDOG["nodeid"] = item.nodeid
    previous = signal.signal(signal.SIGALRM, _time_limit_reached)
    signal.setitimer(signal.ITIMER_REAL, TEST_TIME_LIMIT_S)
    if _WATCHDOG["backstop"]:
        faulthandler.dump_traceback_later(
            TEST_TIME_LIMIT_S + GRACE_S, file=_WATCHDOG["file"], exit=True)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)
        if _WATCHDOG["backstop"]:
            faulthandler.cancel_dump_traceback_later()
