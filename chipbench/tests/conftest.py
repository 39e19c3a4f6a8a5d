import os
import sys

# Same forced 8-device CPU mesh as the repository's own tests: this
# directory may be collected first, and the flag only works before the
# first jax import.
_flags = os.environ.get("XLA_FLAGS", "")
if ("xla_force_host_platform_device_count" not in _flags
        and "jax" not in sys.modules):
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)
