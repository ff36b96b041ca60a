"""Test configuration.

Mirrors the reference's test strategy (SURVEY.md §4): the suite runs on CPU
with a *virtual 8-device mesh* so all multi-device/sharding machinery is
exercised without TPU hardware — the TPU analogue of the reference running
multi-device tests on cpu(0)/cpu(1) (tests/python/unittest/
test_multi_device_exec.py) and its localhost "fake cluster" pattern.

Must set XLA flags before jax initializes.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# XLA:CPU logs two ~3 KB error lines for every executable it reads from
# the persistent compile cache ("Target machine feature +prefer-no-scatter
# is not supported on the host machine": LLVM tuning hints taken for ISA
# features — the same machine wrote the entry). Only FATAL is left on, so
# a failing test's captured stderr holds the test's own output.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The suite runs on the CPU whatever the environment says: the config
# override also covers a JAX that was imported before this file.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# The persistent compile cache is placed by mxnet_tpu.config at package
# import (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache) — jit
# programs survive across pytest runs and are shared with every
# subprocess the suite spawns.

import numpy as _np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed_rngs():
    """Deterministic RNG per test regardless of execution order (the
    reference seeds per-module; a shared global key made
    test_module_fit_converges order-dependent)."""
    _np.random.seed(0)
    import mxnet_tpu as mx
    mx.random.seed(0)
    yield
