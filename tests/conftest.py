"""Test configuration.

Tests run on the CPU backend with a virtual 8-device platform so multi-chip
sharding paths compile+execute without TPU hardware (SURVEY.md §4 implication
(c): single-process simulation of a pod), mirroring how the reference
simulates clusters in one JVM (local-mode Spark, embedded Aeron).

x64 is enabled for gradient-check precision (the reference forces double
precision in GradientCheckUtil).

Tiering (pytest.ini): the default run skips tests marked `slow` /
`multiprocess` — the r3 full suite grew past a 9-minute wall and timed out
the reviewer the same way the unbuffered bench timed out the driver.
`--full-tier` (or DL4J_TPU_FULL_TESTS=1) runs everything.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: tests never take the chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
# 0: the suite is death-by-a-thousand sub-second compiles; store them all.
# In the environment (read by jax at import) so that the replica and worker
# processes the tests spawn cache theirs too.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

import jax

# the config update holds even if a pytest plugin imported jax before the
# environment variable above was set
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

# The suite's wall clock is dominated by XLA compiles of thousands of tiny
# programs, many of them the same program built by different tests. The
# persistent cache (common/compile_cache.py: the environment's directory if
# JAX_COMPILATION_CACHE_DIR is set, else <checkout>/.jax_cache) serves the
# repeats within a cold run and everything on a warm one.
from deeplearning4j_tpu.common.compile_cache import (  # noqa: E402
    enable_compile_cache)

enable_compile_cache()

import pytest


def pytest_addoption(parser):
    parser.addoption(
        "--full-tier", action="store_true", default=False,
        help="run the full suite including slow/multiprocess tests")


# `test_laguna_cell.py` (PR 32) asserts that Laguna's cell and its seven
# metrics are the LAST entries of BENCHMARK.json's lists. The driver takes a
# new entry anywhere but at the end as a move of what was there, and a file
# under the benchmark's `paths` is a `benchmark` PR's to edit (PERF §7 row
# 17 (c), ROADMAP M11): until that PR relaxes the pin the test is expected to
# fail at it. Strict, so that the entry here goes with the pin.
# `test_joyai_cell.py` holds everything else that test asserts.
PINNED_LAST = {
    "tests/benchmark/test_laguna_cell.py::test_the_cells_declaration":
        "pins Laguna's entries as the last of BENCHMARK.json's lists; "
        "PR 34's stand after them, as the driver requires",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.nodeid in PINNED_LAST:
            item.add_marker(pytest.mark.xfail(
                reason=PINNED_LAST[item.nodeid], strict=True,
                raises=AssertionError))
    if (config.getoption("--full-tier")
            or os.environ.get("DL4J_TPU_FULL_TESTS", "").lower()
            in ("1", "true", "yes", "on")):
        return
    skip = pytest.mark.skip(
        reason="full tier only (pass --full-tier or DL4J_TPU_FULL_TESTS=1)")
    for item in items:
        if "slow" in item.keywords or "multiprocess" in item.keywords:
            item.add_marker(skip)
