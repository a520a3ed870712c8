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


# tests/chipbench/conftest.py lists the tests of earlier PRs that pin
# `BENCHMARK.json`'s lists as their PR left them; its file belongs to the
# benchmark, which a PR that appends a metric may add to and not edit. PR 36
# appended `label_sets_made_per_event`, which outdates one more of them: PR
# 35's own "the last four per-layer entries are mine". It is expected to
# fail, strictly, until a `benchmark` PR repairs the file and takes this out
# (PERF.md §7); tests/chipbench/test_benchmark_lists.py and
# test_label_sets_made.py hold the same entries by their index.
PINNED_TO_PR_35S_BENCHMARK_JSON = {
    "test_wan.py::test_every_new_metric_file_is_named_by_benchmark_json",
}


def pytest_collection_modifyitems(config, items):
    import pytest

    for item in items:
        if (
            item.nodeid.split("tests/chipbench/")[-1]
            in PINNED_TO_PR_35S_BENCHMARK_JSON
        ):
            item.add_marker(pytest.mark.xfail(
                strict=True,
                reason="pins BENCHMARK.json's per_layer as PR 35 left it; "
                "PR 36 appended to it (tests/conftest.py)",
            ))
