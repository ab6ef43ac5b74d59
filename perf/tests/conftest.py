"""The benchmark's own tests run off the chip: hold jax to the CPU before
anything imports it, give it four devices, and put perf/ on the path."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# four virtual devices, so that a four-chip cell rehearses its mesh
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
