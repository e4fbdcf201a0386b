"""Test configuration: force an 8-device virtual CPU mesh.

All tests run hardware-free: JAX is pinned to the CPU platform with 8
virtual devices so sharding/collective code paths (tp/dp/sp meshes) are
exercised exactly as they would be on an 8-chip TPU slice.

Must run before jax is imported anywhere in the test process.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
