"""Test harness: force an 8-device virtual CPU platform BEFORE jax imports.

This is the scale-sim strategy from SURVEY.md §7: multi-chip sharding is
validated on a faked 8-device CPU mesh (``xla_force_host_platform_device_count``);
the suite never takes a chip, so it also runs on a machine that has one
(tests/test_flash_tpu.py is the exception and is run with --noconftest).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the suite is dominated by XLA:CPU compiles
# of the jit round programs, and most tests re-request programs an earlier
# run already built.  The multi-process tests (CLI federation, DCN
# children) spawn fresh interpreters that inherit the directory through
# the environment instead of recompiling every program from scratch.
from colearn_federated_learning_tpu.utils.compile_cache import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()
# Most of the suite's programs compile in well under the second below
# which jax does not persist an executable; with the default threshold a
# warm run is no faster than a cold one.  (Test harness only: on the chip
# compiles take tens of seconds and the entry points keep jax's default.)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"

import sys

# Repo root on sys.path regardless of how pytest was launched: test modules
# import both `tests.*` helpers and `scripts.*` protocol builders, and
# pytest's prepend import mode only adds tests/ itself.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_devices():
    devices = jax.devices()
    assert len(devices) >= 8, f"expected 8 virtual CPU devices, got {devices}"
    return devices


@pytest.fixture(scope="session")
def mesh8(cpu_devices):
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(cpu_devices[:8]), ("clients",))
