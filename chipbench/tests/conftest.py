"""The rehearsal runs on the CPU, on four virtual devices for the data=4
mesh. Both variables are read when jax's backend starts, so they are set
here, before the first test imports jax."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS",
                                                                ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()
