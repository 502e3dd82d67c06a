"""Test configuration: run everything on a virtual 8-device CPU mesh so
multi-chip sharding paths are exercised without TPU hardware.  The
environment is set before jax is imported, and children the tests spawn
(CLI runs, workers, replicas) inherit it.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") +
    " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
