"""Test env: force a virtual 8-device CPU platform BEFORE jax imports.

Mirrors the reference's CI posture (GPU tests runnable on CPU,
ref: SURVEY §4 implication) — all sharding/collective tests run on an
8-device CPU mesh; real-TPU runs use the same code with the env unset.
"""

import os

# Force CPU: unit tests never touch a chip (a chip belongs to one
# process, and chip_smoke.py is the program that runs there). Both
# variables are read when the backend starts, which is lazy — set here,
# before the first jax.devices() call, they give a pure 8-device
# virtual-CPU platform. jax.config.update covers a jax that a pytest
# plugin imported before this file ran.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Per-test wall-clock guard (pytest-timeout's signal method, inlined:
# the image has no pytest-timeout wheel and tier-1 cannot pip install).
# One hung test must not eat the whole 870s tier-1 budget — the guard
# raises inside the test at the limit so the rest of the suite still
# runs. Override per test with @pytest.mark.timeout(seconds) (0 =
# unlimited), or globally with PT_TEST_TIMEOUT. SIGALRM only fires on
# the main thread; worker-thread tests are unaffected, and anything
# hung in non-interruptible C code is out of reach (same limitation as
# pytest-timeout's signal mode — the launcher-level `timeout -k` in the
# tier-1 command stays the backstop).
_DEFAULT_TEST_TIMEOUT = float(os.environ.get("PT_TEST_TIMEOUT", "300"))


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    import signal
    import threading

    limit = _DEFAULT_TEST_TIMEOUT
    m = item.get_closest_marker("timeout")
    if m and m.args:
        limit = float(m.args[0])
    if (limit <= 0 or not hasattr(signal, "SIGALRM")
            or threading.current_thread() is not threading.main_thread()):
        yield
        return

    def on_alarm(sig, frame):
        raise TimeoutError(
            f"{item.nodeid} exceeded the {limit:.0f}s per-test guard "
            f"(tests/conftest.py; override with "
            f"@pytest.mark.timeout(seconds) or PT_TEST_TIMEOUT)")

    prev = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, prev)


@pytest.fixture(autouse=True, scope="module")
def _static_mode_ends_with_its_file():
    """``pt.enable_static()`` is ambient state, and a score of test files
    switch it on and leave it on. Under ``--dist loadfile`` the next file on
    that worker then builds static Variables in its eager tests (which file
    that is follows the workers' timing: ``test_book`` after
    ``test_pallas_registry``, ``test_contrib_r3::TestTrainingDecoder`` in
    the driver's runs). A file ends in the mode it began in."""
    from paddle_tpu.static import program
    was = program.in_static_mode()
    yield
    (program.enable_static if was else program.disable_static)()


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(0)
    import paddle_tpu
    from paddle_tpu.core import random as ptrandom
    ptrandom.seed(0)
    yield


@pytest.fixture
def fresh_programs():
    """Fresh default main/startup programs + scope for static tests."""
    import paddle_tpu as pt
    from paddle_tpu.static.executor import Scope, scope_guard
    from paddle_tpu.framework import unique_name
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup), unique_name.guard(), \
            scope_guard(Scope()) as scope:
        yield main, startup, scope
