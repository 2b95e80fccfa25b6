"""Test configuration: force an 8-virtual-device CPU platform BEFORE jax
import (the gloo/fake-device analog — SURVEY.md §4 test strategy)."""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_X64", "0")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reseed():
    import paddle_tpu
    paddle_tpu.seed(2024)
    yield


@pytest.fixture(autouse=True)
def _no_page_refcount_leak():
    """Every ServingEngine's page-refcount bookkeeping must exactly match
    its live page tables + prefix cache when the test ends — a drifted
    refcount (leak, double-count, page simultaneously free and referenced)
    fails the test that caused it, not a later one."""
    yield
    import sys
    paged = sys.modules.get("paddle_tpu.inference.paged")
    if paged is None:
        return
    for eng in list(paged._LIVE_ENGINES):
        eng.check_invariants()


@pytest.fixture(autouse=True)
def _no_worker_process_leak():
    """The refcount leak guard, extended across the process boundary
    (ISSUE 17): every worker process a test spawned must have filed a
    final PagePool/page-table/cache invariants report over the RPC wire
    — directly at teardown (stop/retire/shutdown), or, for workers
    killed mid-drill, through their replacement's post-restore check —
    and every report must hold.  A fleet left running is itself a leak:
    it is force-killed here and the test fails."""
    yield
    import sys
    procfleet = sys.modules.get("paddle_tpu.serving.procfleet")
    if procfleet is None:
        return
    problems = []
    for fl in list(procfleet._LIVE_FLEETS):
        # each fleet is judged exactly once (by the test that made it)
        procfleet._LIVE_FLEETS.discard(fl)
        if not fl.closed:
            fl.shutdown(drain=False, force=True)
            problems.append("test leaked a running ProcessFleet "
                            "(never shut down; workers force-killed)")
            continue
        try:
            fl.assert_worker_invariants()
        except AssertionError as e:
            problems.append(str(e))
    assert not problems, "; ".join(problems)


@pytest.fixture(autouse=True)
def _thread_sanitize_lane():
    """`make race-check` lane: GRAFT_THREAD_SANITIZE=1 wraps every test in
    the lock-order/ownership sanitizer, so the fleet failover, frontend and
    proc-smoke drills run with instrumented threading.Lock/RLock — a
    lock-order inversion anywhere in the drill fails that test with both
    stacks instead of deadlocking CI.  Off (the default) this fixture is
    free: no patching, timed perf windows see raw stdlib locks."""
    if os.environ.get("GRAFT_THREAD_SANITIZE") != "1":
        yield
        return
    from paddle_tpu.analysis.thread_sanitize import thread_sanitize
    with thread_sanitize():
        yield


@pytest.fixture(autouse=True)
def _no_fault_plan_leak():
    """A test that exits with a live FaultPlan (inject() scope not closed)
    would silently corrupt every later test's behavior — fail it here,
    after clearing the leak so only the culprit fails."""
    yield
    from paddle_tpu.resilience import faults
    leaked = faults.active_plan() is not None
    faults._ACTIVE.clear()
    assert not leaked, (
        "test leaked a live FaultPlan into the next test — close the "
        "resilience.inject() scope")
