"""Test harness: force an 8-device virtual CPU platform.

The reference's universal test trick is multi-process-on-localhost under
``mpirun -np 2`` (SURVEY §4).  The TPU-native analogue: a virtual 8-device
CPU mesh via ``--xla_force_host_platform_device_count=8`` so every in-mesh
collective, sharding and shard_map path runs exactly as it would on an
8-chip slice — no TPU hardware needed for the core suite.
"""

import faulthandler
import os
import tempfile

# Hang diagnosability: tier-1 runs under an outer `timeout -k` that
# SIGKILLs the run with no stacks.  Dump every thread's traceback to
# stderr shortly before that budget expires (and on SIGSEGV & friends
# via enable()), so a future hang names its wedged thread in the tier-1
# log instead of dying silently.  The margin is configurable for local
# runs with tighter budgets; exit=False — the dump is diagnostic, the
# outer timeout stays in charge of killing.
faulthandler.enable()
faulthandler.dump_traceback_later(
    int(os.environ.get("HVD_TEST_DUMP_TRACEBACK_AFTER_S", "800")),
    exit=False)

# must run before jax initializes its backends
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("HOROVOD_TPU_MESH_SHAPE", "2,4")
# hermetic warm-start cache: every compile goes through JAX's
# persistent cache where hvd.init() placed it (runtime/compile_cache.py);
# a per-session root — not the fixed in-checkout default, which the chip
# runs use — keeps a suite run from inheriting a stale entry or leaving
# one behind
os.environ.setdefault("HOROVOD_COMPILE_CACHE_DIR",
                      tempfile.mkdtemp(prefix="hvd_tpu_test_cache_"))

import jax  # noqa: E402

# the test suite runs on the virtual 8-device CPU platform whatever the
# machine holds
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=False)
def hvd_runtime():
    """Initialized runtime with a fresh 2x4 (dcn, ici) mesh per test."""
    import horovod_tpu as hvd

    hvd.init()
    yield hvd
    hvd.shutdown()


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    """A freshly-initialized runtime whose compile cache is an empty
    directory of the test's own, which keeps every compile: JAX stores
    only compiles of a second or more, and a test's step takes less."""
    d = str(tmp_path / "cc")
    monkeypatch.setenv("HOROVOD_COMPILE_CACHE_DIR", d)
    kept = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import horovod_tpu as hvd

    hvd.shutdown()
    hvd.init()
    yield d
    hvd.shutdown()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", kept)
