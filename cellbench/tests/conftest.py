"""Four CPU devices for every run these tests start, in this process or
in a child (both read ``XLA_FLAGS`` when JAX is first imported): a cell
with ``"chips": 4`` refuses to run on fewer, and ``test_run_rehearse.py``
parametrises over every cell of ``BENCHMARK.json``.  A count the caller
set stands."""

import os

_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + f" {_FLAG}=4").strip()
