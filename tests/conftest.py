import os

# Tests run on the REAL device topology (1 CPU device). Tests that need a
# mesh start a subprocess with more virtual devices.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
