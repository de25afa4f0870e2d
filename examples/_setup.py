"""Shared example bootstrap: run on whatever platform JAX finds (the
reference needs ``mpiexec -n 8``; here one process drives the mesh of
every device). The CPU is a request, never a fallback: with
``JAX_PLATFORMS=cpu`` (or ``PYLOPS_MPI_TPU_PLATFORM=cpu``) the examples
run on a simulated 8-device CPU mesh with float64 oracles."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

if "cpu" in (os.environ.get("JAX_PLATFORMS", ""),
             os.environ.get("PYLOPS_MPI_TPU_PLATFORM", "")):
    os.environ.setdefault(
        "XLA_FLAGS",
        (os.environ.get("XLA_FLAGS", "")
         + " --xla_force_host_platform_device_count=8").strip())
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)
else:
    import jax  # noqa: F401
