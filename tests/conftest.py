"""Test configuration.

Tests run on the CPU jax backend with 8 virtual devices so multi-chip
sharding logic is exercised without TPU hardware (the driver separately
dry-runs the multichip path; see __graft_entry__.py).
"""

import os

# Tests always run on the CPU backend with 8 virtual devices, whatever
# the machine has. The persistent compilation cache stays off here: it is
# for the process that owns a chip (backend_probe.enable_compile_cache).
os.environ["JAX_PLATFORMS"] = "cpu"

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# jax may already be imported by a pytest plugin before this conftest runs;
# config.update still applies as long as no backend has been initialized.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# Concurrency net (VERDICT r4 item 10): every runtime the suite starts
# carries a blocked-event-loop watchdog; a callback stalling the loop
# >5s dumps all thread stacks to stderr. (Full asyncio debug mode is
# enabled per-module where its overhead is acceptable —
# test_concurrency_net.py — not suite-wide.)
os.environ.setdefault("RT_LOOP_WATCHDOG_S", "5")

# Runtime-env pip tests either install a LOCAL wheel (--no-index) or
# assert a typed failure on a bogus requirement. Point pip at a dead
# index by default so the failure tests fail fast (connection refused,
# no retries) and the suite never waits on real network resolution.
os.environ.setdefault("PIP_INDEX_URL", "http://127.0.0.1:1/simple")
os.environ.setdefault("PIP_RETRIES", "0")
os.environ.setdefault("PIP_DEFAULT_TIMEOUT", "1")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "pyarrow: test exercises the Arrow block path; auto-skipped "
        "when pyarrow is not installed")


def _have_pyarrow() -> bool:
    try:
        import pyarrow  # noqa: F401

        return True
    except ImportError:
        return False


def pytest_collection_modifyitems(config, items):
    """Arrow-path tests skip cleanly without pyarrow (the block format
    degrades to object ndarrays, but these tests assert Arrow-specific
    behavior)."""
    if not _have_pyarrow():
        skip = pytest.mark.skip(reason="pyarrow not installed")
        for it in items:
            if "pyarrow" in it.keywords:
                it.add_marker(skip)


@pytest.fixture
def fixed_port():
    """A port that a test may bind, release and bind AGAIN (a head
    restarted on its old port). It lies below the kernel's ephemeral
    range (32768 up), so no other process's outgoing connection can be
    standing on it: a port made from the pid alone, in that range, met
    "address already in use" in one whole run of three under six xdist
    workers. The first from a pid-derived start that binds now."""
    import socket

    start = 20000 + os.getpid() % 10000
    for port in range(start, start + 200):
        with socket.socket() as s:
            try:
                s.bind(("127.0.0.1", port))
            except OSError:
                continue
        return port
    raise RuntimeError(f"no free port in {start}..{start + 200}")


@pytest.fixture
def rt():
    """A fresh runtime per test."""
    import ray_tpu

    ray_tpu.shutdown()
    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


@pytest.fixture
def traced(rt):
    """Task-plane tracing on for one test, undone with the symmetric
    API (enable/disable + register/unregister) instead of hand-popping
    RT_TRACING and poking tracing._enabled."""
    from ray_tpu.util import tracing

    exported = []
    tracing.enable_tracing()
    tracing.register_exporter(exported.append)
    tracing.drain_local_spans()
    yield rt
    tracing.unregister_exporter(exported.append)
    tracing.disable_tracing()
    tracing.drain_local_spans()
    tracing.drain_request_spans()


@pytest.fixture
def watch_device_get(monkeypatch):
    """``watch()`` starts recording the element count of every array
    ``jax.device_get`` fetches from then on (the LLM engine's only way
    of bringing a device array to the host) and returns the list."""
    import jax
    import numpy as np

    def watch():
        fetched = []
        real = jax.device_get

        def device_get(x):
            fetched.extend(int(np.size(leaf))
                           for leaf in jax.tree_util.tree_leaves(x))
            return real(x)

        monkeypatch.setattr(jax, "device_get", device_get)
        return fetched

    return watch


@pytest.fixture(scope="session")
def shared_rt():
    """A session-scoped runtime for cheap read-only tests."""
    import ray_tpu

    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()
