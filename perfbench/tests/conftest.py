"""Four virtual CPU devices for the tests that build a configuration's mesh
in this process (set before jax starts; the dry runs set their own)."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
