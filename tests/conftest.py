"""Test harness config: run everything on a virtual 8-device CPU mesh.

Tests never touch an accelerator: sharding correctness is validated on 8
virtual CPU devices (the same mechanism `__graft_entry__.dryrun_multichip`
uses), and the chip is reached only through `chip_smoke.py`. The platform
is pinned both in the environment and in jax.config (the config outranks
the environment should anything have imported jax first), before any
backend is initialized by a test.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    # no pytest.ini/setup.cfg in this repo: register the marker here so
    # `-m 'not slow'` (the tier-1 selection) runs warning-free
    config.addinivalue_line(
        "markers",
        "slow: multi-second drills (chaos convergence); excluded from the "
        "tier-1 `-m 'not slow'` run")
