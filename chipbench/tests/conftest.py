"""CPU rehearsal settings, before jax loads: four virtual devices, the Pallas
kernels through the interpreter."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
os.environ["SRML_DISTANCE_KERNEL"] = "interpret"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
