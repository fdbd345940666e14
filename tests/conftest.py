"""Test configuration.

Tests run on CPU with 8 virtual devices so multi-chip sharding
(jax.sharding.Mesh) is exercised without TPU hardware, mirroring how the
reference tests spin up an in-process multi-node cluster without a real
cluster (reference cluster/cluster.go:123-189). Runs on a TPU happen via
chip_smoke.py and benchmarks/run.py, not pytest. JAX_PLATFORMS is set here, before
anything imports jax, so subprocesses a test starts inherit the pin too.
"""

import os

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# Lock-order sanitizer ON for the whole suite (must be set before any
# gubernator_tpu module creates its locks): every named internal lock
# tracks held-sets and the global acquisition-order graph, so the
# engine/peer/gateway concurrency tests double as deadlock-order
# probes. The autouse fixture below fails the offending test on any
# cycle or double-acquire. See gubernator_tpu/utils/lockorder.py.
os.environ.setdefault("GUBER_LOCK_SANITIZER", "1")
# Guarded-by race sanitizer ON too (requires the lock sanitizer's held
# stacks; must be set before the annotated modules import — guarded_by
# reads the gate when it runs). Every declared field access is checked
# against its lock, and the autouse fixture below fails the test that
# recorded a violation. See gubernator_tpu/utils/raceguard.py.
os.environ.setdefault("GUBER_RACE_SANITIZER", "1")

import asyncio  # noqa: E402
import signal  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection suite (fast deterministic subset runs "
        "in tier-1; soak variants are also marked slow)",
    )
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 run (-m 'not slow')"
    )
    config.addinivalue_line(
        "markers",
        "flaky: quarantined known-flaky test (also marked slow so "
        "tier-1 never pays for a hang; run explicitly with -m flaky)",
    )
    config.addinivalue_line(
        "markers",
        "deadline(seconds): hard per-test SIGALRM watchdog covering "
        "setup+call+teardown — a hang fails with TimeoutError instead "
        "of eating the suite budget (no pytest-timeout in this env)",
    )


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    """Hand-rolled per-test watchdog for @pytest.mark.deadline(s).

    Wraps the whole protocol (fixture setup, call, teardown) because
    the known hangs live in module-scoped cluster fixtures, not the
    test body. SIGALRM only delivers to the main thread — exactly
    where pytest runs tests — and interrupts the blocking
    Future.result()/Condition.wait() calls the in-process cluster
    plumbing parks on."""
    m = item.get_closest_marker("deadline")
    if (
        m is None
        or not hasattr(signal, "SIGALRM")
        or threading.current_thread() is not threading.main_thread()
    ):
        yield
        return
    seconds = int(m.args[0]) if m.args else 120

    def _abort(signum, frame):
        raise TimeoutError(
            f"{item.nodeid} exceeded its {seconds}s deadline marker"
        )

    old = signal.signal(signal.SIGALRM, _abort)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(autouse=True)
def _lock_order_clean():
    """Fail the test that introduced a lock-order violation. Deliberate
    inversion tests (test_lockorder.py) use their own LockOrderGraph, so
    the session-default graph must stay violation-free."""
    from gubernator_tpu.utils import lockorder

    before = len(lockorder.DEFAULT_GRAPH.report())
    yield
    after = lockorder.DEFAULT_GRAPH.report()
    if len(after) > before:
        raise AssertionError(
            "lock-order violation(s) recorded during this test:\n"
            + lockorder.DEFAULT_GRAPH.format_report()
        )


@pytest.fixture(autouse=True)
def _race_guard_clean():
    """Fail the test that introduced a guarded-by violation. Deliberate
    violation tests (test_raceguard.py) use their own RaceGraph, so the
    session-default graph must stay empty."""
    from gubernator_tpu.utils import raceguard

    before = len(raceguard.DEFAULT_GRAPH.report())
    yield
    after = raceguard.DEFAULT_GRAPH.report()
    if len(after) > before:
        report = raceguard.DEFAULT_GRAPH.format_report()
        raceguard.DEFAULT_GRAPH.clear()
        raise AssertionError(
            "guarded-by race violation(s) recorded during this test:\n"
            + report
        )


@pytest.fixture(autouse=True)
def _clear_fault_rules():
    """The fault injector is process-global (one instance partitions a
    whole in-process cluster); rules must never leak across tests."""
    yield
    from gubernator_tpu.utils import faults

    faults.INJECTOR.clear()


@pytest.fixture
def frozen_clock():
    from gubernator_tpu.utils import clock

    with clock.freeze() as clk:
        yield clk


class LoopThread:
    """A dedicated asyncio event loop running on a background thread, so
    long-lived async fixtures (the in-process cluster) span many tests
    without pytest-asyncio."""

    def __init__(self):
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _run(self):
        asyncio.set_event_loop(self.loop)
        self.loop.run_forever()

    def run(self, coro, timeout=30):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(timeout)

    def stop(self):
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(timeout=5)


@pytest.fixture(scope="module")
def loop_thread():
    lt = LoopThread()
    yield lt
    lt.stop()
