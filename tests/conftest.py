"""Test configuration.

Tests run on a virtual 8-device CPU platform so that multi-chip sharding code
paths (jax.sharding.Mesh over 8 devices) are exercised without TPU hardware,
mirroring how the driver dry-runs the multichip path.

A pytest plugin pre-imports jax before this file runs, so setting
JAX_PLATFORMS in os.environ is not enough — the jax config must be updated
directly (safe because no backend is initialized yet at collection time).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tier-1 never touches an accelerator
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
