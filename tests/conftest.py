import os
import sys

# Tier-1 runs on a forced 8-device CPU mesh so shard_map mixer paths
# (repro.dist.sync) execute as genuine multi-device programs instead of
# collapsing to 1 device.  Must happen before the first jax import —
# conftest loads before every test module.  Subprocess probes
# (tests/test_dist.py-style) pop the parent's XLA_FLAGS and force their
# own count, so they are unaffected; launch/dryrun.py still forces 512
# in its own process per the dry-run contract.
_flags = os.environ.get("XLA_FLAGS", "")
if ("xla_force_host_platform_device_count" not in _flags
        and "jax" not in sys.modules):
    # If jax is already imported (exotic plugin, sitecustomize) the flag
    # cannot take effect; leave it unset and let the multi_device
    # fixture skip rather than aborting the whole suite.
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from hypothesis import settings  # noqa: E402

settings.register_profile("ci", deadline=None, max_examples=25)
settings.load_profile("ci")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "multi_device: exercises real multi-device shard_map programs "
        "(needs the forced 8-device CPU mesh)")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection storms (repro.faults) — seeded chaos "
        "traces over the NDMP engines and the slot loop")


@pytest.fixture
def multi_device():
    """The 8-device CPU mesh tier-1 runs on.  Returns the device count;
    skips if the XLA force flag did not take (e.g. jax was pre-imported
    by an exotic plugin)."""
    import jax
    n = jax.device_count()
    if n < 8:
        pytest.skip(f"needs >= 8 host devices, have {n}")
    return n
