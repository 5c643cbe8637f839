"""Test harness config: force an 8-device CPU mesh so sharding/collective
paths are exercised without TPU hardware (driver benches separately on TPU).

Must run before jax is first imported anywhere in the test process.
"""

import os
import sys

# Tests must never claim the chip (one process per chip, and tier-1 runs
# where there is none): the env var covers child processes, the config
# update below covers a jax something already imported.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import threading  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


# ---------------------------------------------------------------------------
# Leak guard (zero-downtime operations contract): drain/swap/rolling-restart
# must not strand worker threads or sockets.  The lifecycle/e2e test modules
# autouse this module-scoped fixture, so the check runs inside tier-1
# alongside the lint gates.
# ---------------------------------------------------------------------------
#: thread-name prefixes outside our control (library pools, pytest
#: internals).  Framework threads are all explicitly named (segment
#: workers by element, "-watchdog", "tcpq-*", "-model-stage", pumps), so
#: anonymous "Thread-N" / executor workers are not our leak signal.
_LEAK_IGNORE = (
    "MainThread", "Thread-", "ThreadPool", "Dummy", "asyncio",
    "pydevd", "raylet",
)


def _live_framework_threads() -> set:
    return {
        t.name for t in threading.enumerate()
        if t.is_alive() and not t.name.startswith(_LEAK_IGNORE)
    }


def _socket_fd_count() -> int:
    """Open socket fds of this process (-1 = unsupported platform)."""
    fd_dir = "/proc/self/fd"
    try:
        fds = os.listdir(fd_dir)
    except OSError:
        return -1
    n = 0
    for fd in fds:
        try:
            if os.readlink(os.path.join(fd_dir, fd)).startswith("socket:"):
                n += 1
        except OSError:
            continue
    return n


def _live_metrics_servers() -> int:
    """Open telemetry exposition servers (each owns a listener socket +
    a '<name>-metrics' thread).  Lazy import: modules that never touch
    telemetry must not pay for it."""
    mod = sys.modules.get("nnstreamer_tpu.core.telemetry")
    if mod is None:
        return 0
    return mod.live_server_count()


@pytest.fixture(scope="module")
def module_leak_check():
    """Assert the module left no framework threads, no net-new socket
    fds, and no open metrics-exposition server behind (bounded
    convergence wait — teardown is asynchronous).

    The metrics endpoint is covered twice: its serve thread is named
    ``<owner>-metrics`` (visible to the thread census — never a
    ``Thread-N`` the ignore list skips) and its listener socket counts
    in the fd census; the explicit server count makes the failure
    message say WHAT leaked instead of just 'a socket'."""
    threads_before = _live_framework_threads()
    sockets_before = _socket_fd_count()
    metrics_before = _live_metrics_servers()
    yield
    deadline = time.monotonic() + 8.0
    leaked_threads: set = set()
    sockets_now = sockets_before
    metrics_now = metrics_before
    while time.monotonic() < deadline:
        leaked_threads = _live_framework_threads() - threads_before
        sockets_now = _socket_fd_count()
        metrics_now = _live_metrics_servers()
        if not leaked_threads and metrics_now <= metrics_before and (
                sockets_before < 0 or sockets_now <= sockets_before):
            break
        time.sleep(0.05)
    assert metrics_now <= metrics_before, (
        f"leaked metrics exposition server(s) after module: "
        f"{metrics_before} -> {metrics_now} (Pipeline.stop() must close "
        "the endpoint)"
    )
    assert not leaked_threads, (
        f"leaked framework threads after module: {sorted(leaked_threads)}"
    )
    if sockets_before >= 0:
        assert sockets_now <= sockets_before, (
            f"leaked sockets after module: {sockets_before} -> {sockets_now}"
        )
