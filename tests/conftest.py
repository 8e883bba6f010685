"""Test harness config.

Tests run on CPU with 8 virtual XLA devices so mesh/sharding paths
(data-parallel learner, sharded replay) are exercised without TPU hardware —
the strategy SURVEY.md §4 prescribes for the missing reference test layer.
Must set env vars before jax initialises a backend.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Belt and braces for a shell that exported another platform list (the
# chip invocation is JAX_PLATFORMS=tpu,cpu): backends init lazily, so
# pinning the live config before the first jax.devices() call lands on CPU.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# NO persistent XLA compile cache on the CPU backend, on purpose: XLA's
# CPU AOT loader warns that cached executables were compiled with
# pseudo-features (+prefer-no-gather/-scatter) its host-feature check
# can't match, and for the suite's collective-dense multi-device
# programs (the pp pipeline step above all) the warning is REAL — with
# the cache enabled the AOT-loaded executable nondeterministically
# SIGABRTs the whole pytest process (~25% of runs, reproduced 2026-07-31
# with an 8-run A/B: 3/8 aborts with cache, 0/22 without).  The suite
# pays fresh compiles instead; utils/helpers.enable_compile_cache keeps
# the cache for TPU-platform processes, whose entries are TPU
# executables that never cross the CPU AOT loader.  Enforced, not just
# unset: an ambient env var (e.g. exported by a TPU drive's shell)
# would otherwise silently re-enable it here and in every spawn child.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
jax.config.update("jax_compilation_cache_dir", None)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import signal  # noqa: E402

import pytest  # noqa: E402

# Per-test wall-clock backstop (SIGALRM; pytest-timeout is not in this
# image).  Unmarked tests get DEFAULT_TIMEOUT; long end-to-end tests carry
# @pytest.mark.slow plus an explicit @pytest.mark.timeout(n).  The fast
# tier is `pytest -m "not slow"`.  Note the alarm can only interrupt the
# main thread between bytecodes: a test stuck inside one long C call
# (e.g. an XLA compile) overshoots until that call returns.
DEFAULT_TIMEOUT = 300


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: end-to-end/learning test excluded from the fast "
        "tier (run with -m slow or no -m filter)")
    config.addinivalue_line(
        "markers", "timeout(seconds): per-test wall-clock limit "
        "(default %d)" % DEFAULT_TIMEOUT)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    marker = item.get_closest_marker("timeout")
    limit = int(marker.args[0]) if marker else DEFAULT_TIMEOUT

    def _on_alarm(signum, frame):
        raise TimeoutError(
            f"{item.nodeid} exceeded the {limit}s wall-clock limit")

    old_handler = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(limit)
    try:
        return (yield)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old_handler)
