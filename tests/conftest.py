"""Test harness: run JAX on a virtual 8-device CPU mesh so sharding/collective code
paths are exercised without TPU hardware (multi-chip dry-run model)."""

import os

# JAX_PLATFORMS=cpu in the environment is the whole pin: this process and
# every child a test starts inherit it, and no jax.config call is needed.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# The package turns JAX's persistent compilation cache on at first use
# (observability/compile_cache.py, default <checkout>/.jax_cache).  The
# suite turns it off, for this process and for the children it starts:
# every run then compiles what it tests, so a compile regression cannot
# hide behind an entry cached by an earlier tree, and the described-TPU
# compiles of test_chip_compile.py would only write entries that no
# process without a chip can read back.  The two tests that exercise the
# cache itself (test_async_io.py, test_multichip_smoke.py) re-enable it
# in their own child processes, with a directory of their own.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, excluded from tier-1 "
        "(-m 'not slow')")
