"""Test harness: 8 fake CPU devices (SURVEY.md §4).

All tests run on the CPU backend with
``--xla_force_host_platform_device_count=8`` so mesh/sharding/collective
logic (psum, all_gather, ppermute ring attention, TP shard_map) is
exercised multi-device without TPU hardware. Must be set before jax
initializes — hence here, at conftest import time.
"""

import os
import time

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402

# If a pytest plugin imported jax before this conftest ran, the env var
# above came too late; the config update still works as long as no
# backend has been initialized.
jax.config.update("jax_platforms", "cpu")


# ------------------------------------------------------------- timeouts
#
# ``@pytest.mark.timeout(N)`` is ENFORCED here (pytest-timeout is not in
# the image and the environment is pip-install-free): a SIGALRM fires
# after N seconds and fails the test with a TimeoutError — same
# mechanism as pytest-timeout's default "signal" method. Limitation
# (shared with pytest-timeout): the alarm interrupts Python bytecode,
# not a wedged C call that never re-enters the interpreter; the
# distributed tests therefore ALSO bound their subprocesses with
# ``communicate(timeout=...)`` as a second line of defense.


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    import signal

    marker = item.get_closest_marker("timeout")
    seconds = int(marker.args[0]) if marker and marker.args else 0
    if seconds <= 0 or not hasattr(signal, "SIGALRM"):
        return (yield)

    def on_alarm(signum, frame):
        raise TimeoutError(
            f"test exceeded its {seconds}s timeout marker (frame: "
            f"{frame.f_code.co_filename}:{frame.f_lineno})"
        )

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(seconds)
    try:
        return (yield)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def slot_pool(num_slots: int, max_len: int, registry):
    """The pool of a FAKE engine (test_serving, test_chaos,
    test_overload, test_router, test_slo): the real ``PagedKVPool`` at
    one layer of one 2-wide head, which those engines use as a slot
    bookkeeper (alloc/free, lengths, occupancy, the stats and digest
    the batcher and ``/health`` read) and never step on a device."""
    from tensorflow_examples_tpu.serving.paged_kv import PagedKVPool

    return PagedKVPool(
        num_layers=1, num_slots=num_slots, num_heads=1, max_len=max_len,
        head_dim=2, block_size=min(16, max_len & -max_len),
        registry=registry,
    )


@pytest.fixture
def faults():
    """Arm a deterministic fault plan for the duration of one test.

    Usage::

        def test_x(faults):
            engine = faults("sigterm@5,ioerr@2")
            ...

    The plan is torn down afterwards even if the test dies mid-fault.
    Spec grammar: tensorflow_examples_tpu/utils/faults.py (sigterm@N,
    nan@N[:M], slow@N[:S], ioerr@K, badbatch@N).
    """
    from tensorflow_examples_tpu.utils import faults as faults_mod

    def arm(spec: str):
        return faults_mod.install(spec)

    yield arm
    faults_mod.clear()


@pytest.fixture
def serve_faults():
    """Arm a deterministic SERVING fault plan for one test (ISSUE 10).

    Usage::

        def test_x(serve_faults):
            engine = serve_faults("crash@1:4,badhealth@0:3")
            ...

    Spec grammar: tensorflow_examples_tpu/utils/faults.py serve side
    (crash@R:N, slowrep@R:S, transport@R:K, kvexhaust@R:N,
    badhealth@R:K). Torn down afterwards even if the test dies
    mid-fault.
    """
    from tensorflow_examples_tpu.utils import faults as faults_mod

    def arm(spec: str):
        return faults_mod.serve_install(spec)

    yield arm
    faults_mod.serve_clear()


@pytest.fixture(scope="session")
def devices():
    d = jax.devices()
    assert len(d) == 8, f"expected 8 fake CPU devices, got {len(d)}"
    return d


@pytest.fixture
def mesh8():
    from tensorflow_examples_tpu.core.mesh import MeshConfig, create_mesh

    return create_mesh(MeshConfig(data=8))


# ----------------------------------------------- ISSUE 14: race guards
#
# Two autouse guards arm the serving-fleet test modules (the tiers
# with real thread traffic — chaos, router, overload, serving):
#
# * lock-order cycle detector (analysis/lockorder.py): every
#   package-allocated threading.Lock/RLock is wrapped while the test
#   runs; acquisitions build a held-before graph and a cycle is a
#   failure AT ORDERING-ESTABLISHMENT time — no actual deadlock (or
#   lucky interleaving) needed. This is the runtime complement of
#   graftlint's static lock pass (docs/static_analysis.md).
# * thread-leak guard: a serving/router/chaos/overload test that
#   leaves a batcher/probe/supervisor/autoscaler/worker loop thread
#   behind fails loudly instead of slowing every later test.

_LOCKORDER_MODULES = (
    "test_chaos.py",
    "test_router.py",
    "test_overload.py",
    "test_journal.py",
    "test_slo.py",
)
_THREAD_GUARD_MODULES = _LOCKORDER_MODULES + ("test_serving.py",)

# Loop/pool threads repo code owns; anything with these names still
# alive after a test (plus a grace period for joins in teardown
# paths) is an orphan. Transient per-request threads (router-dispatch/
# router-hedge, http.server handler threads) are excluded: an
# abandoned hedge loser may legally outlive its request by design.
_OWNED_THREAD_NAMES = (
    "serving-batcher",
    "serving-frontend",
    "router-probe",
    "router-frontend",
    "router-standby",
    "canary-prober",
    "replica-supervisor",
    "fleet-autoscaler",
    "telemetry-metrics-server",
    "train-watchdog",
    "input_worker",
)


def _owned(thread) -> bool:
    name = thread.name or ""
    return any(name.startswith(p) for p in _OWNED_THREAD_NAMES)


@pytest.fixture(autouse=True)
def _serving_thread_leak_guard(request):
    if request.node.fspath.basename not in _THREAD_GUARD_MODULES:
        yield
        return
    import threading as _threading

    before = set(_threading.enumerate())
    yield
    deadline = time.monotonic() + 5.0
    leaked = []
    while True:
        leaked = [
            t for t in _threading.enumerate()
            if t not in before and t.is_alive() and _owned(t)
        ]
        if not leaked or time.monotonic() > deadline:
            break
        time.sleep(0.05)
    assert not leaked, (
        "test leaked serving loop thread(s): "
        f"{[t.name for t in leaked]} — close() the batcher/router/"
        "supervisor/pool it started (ISSUE 14 thread-leak guard)"
    )


@pytest.fixture(autouse=True)
def _lock_order_guard(request):
    if request.node.fspath.basename not in _LOCKORDER_MODULES:
        yield
        return
    from tensorflow_examples_tpu.analysis import lockorder

    mon = lockorder.arm()
    try:
        yield
    finally:
        lockorder.disarm()
    assert not mon.violations, (
        "lock-order cycle(s) established during this test (deadlock "
        "hazard even if this run did not interleave into it):\n  "
        + "\n  ".join(mon.violations)
    )
