"""Shared example setup.

Examples default to the CPU backend with a virtual 8-device mesh so every
script runs anywhere (several demonstrate multi-device parallelism). Set
DL4J_EXAMPLES_HW=1 to use whatever accelerator the environment configures
instead (single-accelerator hosts can't run the mesh examples).
"""
import os

if not os.environ.get("DL4J_EXAMPLES_HW"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from deeplearning4j_tpu.common.compile_cache import (  # noqa: E402
    enable_compile_cache)

enable_compile_cache()
