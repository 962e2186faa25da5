"""Test configuration: force an 8-device CPU platform.

The analogue of the reference's run-distributed-tests-on-CPU-CI trick
(``test_utils.py:227-265`` launches gloo ranks): a virtual 8-device CPU mesh
lets sharded/replicated/resharding paths run anywhere. Multi-process elastic
tests additionally spawn real processes (see ``torchsnapshot_tpu/test_utils.py``).

Note: the env vars must be set before jax is imported.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

# Dedup digests default to `auto` (resolved from the usable core count). The
# incremental-dedup feature tests must behave identically on any CI box —
# including one whose ambient environment exports this knob — so pin them on
# unconditionally; the auto gate itself is covered explicitly in
# test_knobs.py.
os.environ["TORCHSNAPSHOT_TPU_DEDUP_DIGESTS"] = "1"

# --- Global hang guard -------------------------------------------------------
# The reference pins a 300 s per-test timeout for every run (pytest.ini:1-7).
# pyproject.toml's `timeout = 300` covers CI (pytest-timeout installed there);
# this SIGALRM fallback makes a hang fail in bare local runs too, where the
# plugin is not available. No-op when pytest-timeout is active.

import signal

import pytest

try:
    import pytest_timeout  # noqa: F401

    _HAVE_PYTEST_TIMEOUT = True
except ImportError:
    _HAVE_PYTEST_TIMEOUT = False

_FALLBACK_TIMEOUT_S = 300


def pytest_addoption(parser):
    if not _HAVE_PYTEST_TIMEOUT:
        # Register the ini key pytest-timeout would own, so pyproject.toml's
        # `timeout = 300` doesn't raise "unknown config option" warnings.
        parser.addini("timeout", "per-test timeout in seconds (fallback)")


def _alarm_guard(item, phase):
    # One alarm per protocol phase (setup/call/teardown), so a deadlocking
    # fixture is caught too — pytest-timeout guards all three phases and the
    # fallback must match that contract.
    if _HAVE_PYTEST_TIMEOUT or not hasattr(signal, "SIGALRM"):
        return None
    try:
        timeout = int(float(item.config.getini("timeout") or _FALLBACK_TIMEOUT_S))
    except (ValueError, KeyError):
        timeout = _FALLBACK_TIMEOUT_S

    def _on_alarm(signum, frame):
        raise TimeoutError(
            f"test {phase} exceeded the global {timeout}s timeout "
            "(conftest SIGALRM fallback)"
        )

    previous = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(timeout)
    return previous


def _alarm_clear(previous):
    signal.alarm(0)
    signal.signal(signal.SIGALRM, previous)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    previous = _alarm_guard(item, "setup")
    try:
        yield
    finally:
        if previous is not None:
            _alarm_clear(previous)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    previous = _alarm_guard(item, "call")
    try:
        yield
    finally:
        if previous is not None:
            _alarm_clear(previous)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    previous = _alarm_guard(item, "teardown")
    try:
        yield
    finally:
        if previous is not None:
            _alarm_clear(previous)


# --- A compile cache of the test's own ---------------------------------------
# A measuring entry point keeps jax's persistent compile cache where
# JAX_COMPILATION_CACHE_DIR says and otherwise at <checkout>/.jax_cache
# (chip_smoke.py, perfbench/run.py). test_chip_smoke.py holds the dry
# run to leaving that directory alone, so a test that starts such a child, or
# drives a harness or an architecture that may, asks for this fixture: the
# variable points under its own tmp_path for the test's length, and a child
# whose environment is built from scratch is handed os.environ's value.


@pytest.fixture
def compile_cache_dir(tmp_path, monkeypatch):
    path = str(tmp_path / "jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", path)
    return path
