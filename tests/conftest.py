"""Test fixtures.

Reference analogue: python/ray/tests/conftest.py (ray_start_regular:245,
ray_start_cluster:326). JAX tests run on a virtual 8-device CPU mesh
(xla_force_host_platform_device_count) so multi-chip sharding logic is
exercised without TPU hardware (SURVEY.md environment notes).
"""

import os

# Must be set before jax initializes a backend anywhere in the test process.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("RTPU_PRESTART_WORKERS", "0")
# Every inbound RPC in every test process is validated against the
# declared wire schema (_private/schema.py) — handler/schema drift
# fails loudly here instead of silently skewing the protocol.
os.environ.setdefault("RTPU_VALIDATE_WIRE", "1")
# Full head-sampling in tests: production defaults to 10% (Dapper
# stance, bounds serve overhead — see _private/tracing.py), but tests
# assert on complete span trees for specific request ids.
os.environ.setdefault("RTPU_TRACE_SAMPLE", "1.0")

# Tune writes experiment dirs (loggers + resumable state) to this root by
# default; keep test runs out of $HOME.
import tempfile  # noqa: E402
os.environ.setdefault(
    "RTPU_RESULTS_DIR", tempfile.mkdtemp(prefix="rtpu_results_"))

# Pin the platform through the live config too (safe as long as no backend
# has been initialized yet).
try:
    import jax
    jax.config.update("jax_platforms", "cpu")
    # tests compare kernel numerics against XLA references: keep f32 matmuls
    jax.config.update("jax_default_matmul_precision", "highest")
except Exception:
    pass

import contextlib  # noqa: E402
import faulthandler  # noqa: E402
import signal  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402

# Every phase of every test (setup, call, teardown; a fixture's teardown
# runs in the teardown of the last test that used it) may take this long:
# three times the longest test of a whole run (101 s).  One wait without
# end then costs one test and names it, not the run (ROADMAP D9).
TEST_LIMIT_S = 300.0

_real_stderr_fd = None


def pytest_configure(config):
    # capture is suspended while plugins are configured, so fd 2 is still
    # the process's own stderr (an xdist worker's reaches the terminal):
    # the backstop below must not write into a capture file that dies
    # with the process
    global _real_stderr_fd
    if _real_stderr_fd is None:
        _real_stderr_fd = os.dup(2)


@contextlib.contextmanager
def _limited(item, phase):
    """Fail `phase` of `item` with every thread's stack once it has run
    for TEST_LIMIT_S.  SIGALRM interrupts the main thread's lock, future
    and socket waits; for a wait no signal breaks (a C call that holds
    the GIL, a blocked signal) the process exits at twice the limit with
    its stacks on stderr, so at worst one xdist worker is lost."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def on_alarm(signum, frame):
        with tempfile.TemporaryFile() as f:
            faulthandler.dump_traceback(f, all_threads=True)
            f.seek(0)
            stacks = f.read().decode(errors="replace")
        pytest.fail(
            f"{item.nodeid}: {phase} still running after {TEST_LIMIT_S:g} s "
            f"(TEST_LIMIT_S, tests/conftest.py); every thread's stack:\n"
            f"{stacks}", pytrace=False)

    faulthandler.dump_traceback_later(
        2 * TEST_LIMIT_S, exit=True,
        file=_real_stderr_fd if _real_stderr_fd is not None else 2)
    previous = signal.signal(signal.SIGALRM, on_alarm)
    # fires again every tenth of the limit: a test's own `finally` that
    # waits on the same dead peer is interrupted too
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S, TEST_LIMIT_S / 10)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)
        faulthandler.cancel_dump_traceback_later()


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    with _limited(item, "setup"):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    with _limited(item, "call"):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    with _limited(item, "teardown"):
        return (yield)


def pytest_sessionstart(session):
    """Pin the heavyweight integration deps as REQUIRED: the
    torch/transformers-gated tests (test_llama, test_transformers_*,
    lightning/gbdt adapters) importorskip — on a leaner image the
    breadth they prove would silently evaporate as skips.  Set
    RTPU_ALLOW_MISSING_DEPS=1 to opt back into skipping."""
    if os.environ.get("RTPU_ALLOW_MISSING_DEPS"):
        return
    import importlib.util
    missing = []
    # the deps this image ships and the breadth tests rely on
    # (xgboost/lightgbm are NOT in the image — their trainers gate on
    # them by design and fall back to sklearn GBDT)
    for dep in ("torch", "transformers", "sklearn"):
        if importlib.util.find_spec(dep) is None:
            missing.append(dep)
    if missing:
        raise pytest.UsageError(
            f"required integration deps missing: {missing} — the gated "
            "tests would silently skip; install them or set "
            "RTPU_ALLOW_MISSING_DEPS=1 to accept reduced coverage")


@pytest.fixture(scope="session", autouse=True)
def _no_asyncio_teardown_leaks():
    """Regression gate for shutdown hygiene: a Connection/EventLoopThread
    that abandons pending tasks surfaces here as "Task was destroyed but
    it is pending!" (Task.__del__ -> asyncio logger) or "Event loop is
    closed" callbacks.  Zero tolerance — these mask real errors in every
    long-lived process log."""
    import gc
    import logging

    leaked = []

    class _Trap(logging.Handler):
        def emit(self, record):
            msg = record.getMessage()
            # "Event loop is closed" rides in exc_info (the default
            # asyncio exception handler logs "Exception in callback ..."
            # with the RuntimeError attached), not the message text.
            if record.exc_info and record.exc_info[1] is not None:
                msg += f" | {record.exc_info[1]!r}"
            if ("Task was destroyed but it is pending" in msg
                    or "Event loop is closed" in msg):
                leaked.append(msg)

    trap = _Trap()
    logging.getLogger("asyncio").addHandler(trap)
    yield
    gc.collect()  # force pending Task.__del__ before we assert
    logging.getLogger("asyncio").removeHandler(trap)
    assert not leaked, (
        f"{len(leaked)} asyncio teardown leak(s); first 5: {leaked[:5]}")


@pytest.fixture(scope="session")
def stop_driver():
    """How a fixture ends a driver process it started (one that called
    ``ray_tpu.init()``): SIGINT, so that the driver's exit handler takes
    its GCS, raylet and workers down with it; killed outright, it left
    them running after every run."""
    import subprocess

    def stop(proc, timeout=30):
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=timeout)
    return stop


@pytest.fixture(scope="function")
def ray_start_regular():
    import ray_tpu
    ctx = ray_tpu.init(num_cpus=4, ignore_reinit_error=True,
                       object_store_memory=256 * 1024 * 1024)
    yield ctx
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def ray_start_shared():
    """Module-scoped cluster for cheap tests (worker startup is ~1s/proc on
    the 1-core CI box, so most tests share one cluster)."""
    import ray_tpu
    ctx = ray_tpu.init(num_cpus=4, ignore_reinit_error=True,
                       object_store_memory=256 * 1024 * 1024)
    yield ctx
    ray_tpu.shutdown()


@pytest.fixture(scope="function")
def ray_start_cluster():
    from ray_tpu._private.cluster_utils import Cluster
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 2})
    yield cluster
    cluster.shutdown()


@pytest.fixture
def cpu_mesh8():
    import jax
    import numpy as np
    from jax.sharding import Mesh
    devices = jax.devices("cpu")
    assert len(devices) >= 8, "conftest must force 8 host devices"
    return Mesh(np.array(devices[:8]).reshape(2, 4), ("dp", "tp"))
