"""Test harness config: force JAX onto a virtual 8-device CPU mesh.

Mirrors the reference's multi-node-without-a-cluster CT pattern
(SURVEY.md §4): correctness/sharding tests run on
``--xla_force_host_platform_device_count=8`` CPU devices; real-TPU perf is
exercised only by ``bench.py``.

Must run before any test module imports jax, hence env mutation at
conftest import time.
"""

import gc
import os
import re

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = re.sub(
    r"--xla_force_host_platform_device_count=\d+",
    "",
    os.environ.get("XLA_FLAGS", ""),
).strip()
os.environ["XLA_FLAGS"] = (
    _flags + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_ENABLE_X64"] = "0"

# The in-process TPU match service defaults ON for production nodes; in
# the unit suite it would add a kernel jit compile to every node start.
# Tests that exercise it opt in with an explicit `tpu.enable = true`.
os.environ.setdefault("EMQX_TPU__ENABLE", "false")
# Likewise the persistent compilation cache every tpu.enable start turns
# on: JAX's cache writes are not atomic, and six xdist workers would
# share one <repo>/.jax_cache.  Tests of the cache wiring opt in.
os.environ.setdefault("EMQX_MATCH__SEGMENTS__XLA_CACHE", "false")

def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running (bench smoke, multihost) — excluded from "
        "tier-1 via -m 'not slow'",
    )

# The unit suite runs on the CPU whatever the machine holds: pin the
# config too (it wins over anything that set jax_platforms earlier)
# before any backend is initialized.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert jax.devices()[0].platform == "cpu", jax.devices()
assert len(jax.devices()) == 8, jax.devices()


@pytest.fixture(autouse=True)
def _thaw_heap():
    """A node that uploads a table, or grows one by ``heap.GROWTH_STEP``
    routes, freezes the heap and scales the collector's third threshold
    (``observe/heap.py``).  Both belong to the process: put them back
    after each test, so that the suite's memory and every other test's
    collections are as they were.  (Unfreezing nothing is a list splice
    of nothing.)"""
    thresholds = gc.get_threshold()
    yield
    gc.unfreeze()
    gc.set_threshold(*thresholds)
