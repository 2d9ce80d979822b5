"""Test configuration: force an 8-device virtual CPU mesh.

Tests never require real TPU hardware; sharding invariants run on
jax's CPU backend with xla_force_host_platform_device_count=8 (the
driver separately dry-run-compiles the multi-chip path via
__graft_entry__.dryrun_multichip).
"""

import os
import sys

# Force CPU (the env var for child processes, jax.config for this one).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
