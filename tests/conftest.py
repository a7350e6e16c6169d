"""Test configuration: the tests run on the CPU, on a virtual 8-device mesh,
so multi-chip sharding paths compile and execute without TPU hardware.

Both are forced here and not left to the environment: XLA_FLAGS gets the
device count before the CPU client is created (at the first jax.devices()
call), and the platform is set through jax.config so a shell without
JAX_PLATFORMS=cpu still cannot reach for an accelerator. The asserts below
fail the run at collection if either did not take. To drive the chip, do not
run pytest: run `python chip_smoke.py` through the chip tool."""

import os
import sys

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "").replace(
        "--xla_force_host_platform_device_count=8", ""
    )
).strip()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

assert jax.devices()[0].platform == "cpu", jax.devices()
assert len(jax.devices()) == 8, jax.devices()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 gate (-m 'not slow'); "
        "ci/tier1-check still runs these standalone",
    )
