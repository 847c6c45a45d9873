"""Test configuration: force an 8-device virtual CPU platform so multi-chip
sharding paths (mesh, pjit, shard_map, collectives) run without TPU hardware
— in the environment (children inherit it) and through jax.config (should a
plugin have imported jax first).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# The suite's time is the CPU compiler's: LLVM without its optimisation
# passes compiles a test's programs a fifth sooner and runs them at these
# sizes as fast. What the tests hold is the programs' mathematics, float32
# against references, not the CPU backend's code (a described TPU's
# compiler, tests/test_tpu_compile*.py, does not read the flag).
if "xla_backend_optimization_level" not in flags:
    os.environ["XLA_FLAGS"] += " --xla_backend_optimization_level=0"
os.environ["JAX_PLATFORMS"] = "cpu"

# The timeout-engine watchdog os._exit(1)s a process whose asyncio
# timeout loop is starved past this budget (futures.py:_watchdog_loop).
# In PRODUCTION trainers that suicide is the last line of defense; in
# the PYTEST process — which builds in-process Managers, arming the
# watchdog — a 30s budget is lethal under suite load: the r5 stamp-1
# run died with a truncated report (rc=1, no summary) when the resnet
# integ test's two compiling children starved the loop thread past 30s
# on the 1-core box.  300s still catches a genuinely wedged loop in
# long integ tests without turning box load into suite suicide.
os.environ.setdefault("TORCHFT_WATCHDOG_TIMEOUT_SEC", "300")

# The runner's best-effort pdeathsig preexec hook forces fork() in the
# jax-threaded pytest process (a small deadlock risk Python 3.12 warns
# about) and this container doesn't deliver pdeathsig anyway; the
# suite's orphan defense is the SIGTERM unwind below + explicit
# runner.stop() calls in the integ tests' finally blocks.
os.environ.setdefault("TORCHFT_RUNNER_PDEATHSIG", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# The persistent XLA compile cache stays off for the suite: tests must not
# depend on what an earlier run left on disk.

# ---------------------------------------------------------------------------
# Per-test timeouts (reference discipline: its pyproject enforces a global
# 60s via pytest-timeout).  pytest-timeout isn't in this image, so we
# implement the same "signal" method inline: SIGALRM in the main thread
# raises through whatever the test is blocked on.  On this 1-core box one
# hung test otherwise wedges the whole 12-minute suite — and hang-wedges
# are exactly this framework's failure domain.
#
# Defaults: 120s per test, 600s for @pytest.mark.slow; override per-test
# with @pytest.mark.timeout(N).
# ---------------------------------------------------------------------------

import signal  # noqa: E402

import pytest  # noqa: E402


# A SIGTERM (outer `timeout`, driver deadline) must unwind fixtures and
# test finally-blocks — the integ tests' spawned trainer processes are
# only reaped by runner.stop() calls in those blocks (pdeathsig is not
# delivered in this container; orphaned trainers spin on quorum retries
# and degrade every later run — observed r5).  KeyboardInterrupt is the
# exception pytest already unwinds cleanly on.
def _sigterm_to_interrupt(_signum, _frame):
    raise KeyboardInterrupt("SIGTERM")


signal.signal(signal.SIGTERM, _sigterm_to_interrupt)

_DEFAULT_TIMEOUT_S = 120
_SLOW_TIMEOUT_S = 600


class _TestTimeout(Exception):
    pass


def _item_timeout(item) -> float:
    marker = item.get_closest_marker("timeout")
    if marker is not None and marker.args:
        return float(marker.args[0])
    if item.get_closest_marker("slow") is not None:
        return _SLOW_TIMEOUT_S
    return _DEFAULT_TIMEOUT_S


def _alarmed(item, phase):
    """Hookwrapper body shared by setup/call/teardown: hangs in fixture
    setup or teardown wedge the suite just as surely as hangs in the test
    body (pytest-timeout's signal method arms all three phases too)."""
    seconds = _item_timeout(item)

    def _on_alarm(signum, frame):
        raise _TestTimeout(
            f"{item.nodeid} exceeded its {seconds:.0f}s timeout ({phase})"
        )

    old_handler = signal.signal(signal.SIGALRM, _on_alarm)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old_handler)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    yield from _alarmed(item, "setup")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    yield from _alarmed(item, "call")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    yield from _alarmed(item, "teardown")
