"""Test configuration.

Forces JAX onto a virtual 8-device CPU mesh BEFORE jax is imported anywhere,
so multi-chip sharding paths (dp/tp) are exercised without TPU hardware.
Bench (`bench.py`) and the driver's entry checks run outside pytest and see
the real device topology.
"""

import os
import sys

# Force-override whatever the ambient environment says: tests run on the
# virtual 8-device CPU mesh, on a chip host too. Both must be in place
# before the backend initializes (first device use).
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
