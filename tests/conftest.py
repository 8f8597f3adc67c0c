"""Test config: force an 8-device virtual CPU mesh before JAX initializes.

Mirrors the reference's in-process mini-cluster testing approach (SURVEY §4):
multi-"worker" behavior is exercised on one host by faking 8 devices.
"""

import os

# FORCE (not setdefault): worker subprocesses spawned by tests inherit
# os.environ and must come up on the virtual CPU mesh too, never on an
# accelerator the host happens to have
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
# skip the LLVM -O2 backend pass on test kernels: results are
# bit-identical (no fast-math; reduction order is fixed at the HLO
# level), but compile time — which dominates the tier-1 wall clock on
# a 1-core container — drops ~35% per kernel. Benches ignore this
# (bench.py runs outside pytest), so measured numbers stay honest.
if "xla_backend_optimization_level" not in flags:
    flags = (flags + " --xla_backend_optimization_level=0").strip()
os.environ["XLA_FLAGS"] = flags
# The persistent compilation cache stays OFF in tests and in the worker
# processes they start, even when the environment sets
# JAX_COMPILATION_CACHE_DIR: on this CPU jaxlib, executables deserialized
# from the cache mid-suite produce wrong results and segfault under
# donation (reproduced in tests/test_checkpoint.py). Compile-time savings
# must come from smaller test dims instead.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(42)
