"""Test configuration: force an 8-device virtual CPU mesh.

Tests exercise multi-chip sharding logic without TPU hardware; the driver's
``dryrun_multichip`` uses the same mechanism.  ``JAX_PLATFORMS`` is set in
the environment too, so every subprocess a test launches (servers, bench,
workers) lands on the CPU the same way.

The persistent compile cache is switched off for the suite (JAX's own
``JAX_ENABLE_COMPILATION_CACHE``): a test run must not depend on, or leave
behind, executables on disk.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_NUM_CPU_DEVICES"] = "8"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
