"""Concurrency net (VERDICT r4 item 10): runtime nets for the bug
classes that chaos tests only catch by luck.

1. FUZZ: a reply-path interleaving storm — task bursts racing forced
   gc.collect() from another thread, under full asyncio debug mode —
   the exact conditions that made r4's lost-reply bug visible.
2. WATCHDOG: the blocked-event-loop watchdog (conftest arms it for the
   whole suite) names the culprit when a callback stalls the loop.

The STRUCTURAL nets that used to live here — the weak-spawn lint, the
transition-event/gauge emission lints, the trace-propagation and
step-accounting lints — are now checkers I401..I405 in
``ray_tpu.analysis`` (declarative site tables, same coverage), gated
by ``tests/test_lint.py`` and exercised against known-bad fixtures in
``tests/test_analysis.py``. New invariant lints go through
``ray_tpu/analysis/invariants.py``, not this file.
"""

import gc
import os
import queue
import threading
import time

import pytest

import ray_tpu


@pytest.fixture(autouse=True)
def async_debug(monkeypatch):
    """Full asyncio debug for this module: never-retrieved exceptions,
    slow-callback warnings, cross-thread misuse checks."""
    monkeypatch.setenv("RT_ASYNC_DEBUG", "1")
    monkeypatch.setenv("RT_LOOP_WATCHDOG_S", "2")
    yield


# ---------------------------------------------------------------------------
# 1. Reply-path GC fuzz
# ---------------------------------------------------------------------------
def test_reply_path_survives_gc_storm(rt):
    """Bursts of tasks on both lanes while another thread forces full
    collections: every reply must arrive (r4's bug: GC'd pending handler
    tasks silently dropped replies, hanging get()).

    The storm is COUNTED, not free-running: each burst releases
    ``PER_BURST`` full collections on the storm's thread, the first
    begun before the burst is submitted, so they fall on pending
    replies. A thread that collects as fast as it can holds the
    interpreter lock for as long as the process's heap is large, and
    late in an xdist worker's life that starved the event loop past
    any timeout: what was under test was the box."""
    PER_BURST = 4
    bursts = queue.Queue()      # a burst's collections; None ends
    collections = []

    def gc_storm():
        for n in iter(bursts.get, None):
            for _ in range(n):
                gc.collect()
                collections.append(1)

    t = threading.Thread(target=gc_storm, daemon=True)
    t.start()
    try:
        @ray_tpu.remote(scheduling_strategy="device")
        def dev(i):
            return i

        @ray_tpu.remote
        def cpu(i):
            return i * 2

        for round_ in range(3):
            n = 60
            bursts.put(PER_BURST)
            refs = [dev.remote(i) for i in range(n)]
            assert ray_tpu.get(refs, timeout=60) == list(range(n))
            bursts.put(PER_BURST)
            refs = [cpu.remote(i) for i in range(20)]
            assert ray_tpu.get(refs, timeout=120) == [
                i * 2 for i in range(20)]
    finally:
        bursts.put(None)
        t.join(timeout=120)
    assert not t.is_alive()
    assert len(collections) == 6 * PER_BURST


# ---------------------------------------------------------------------------
# 2. Blocked-loop watchdog
# ---------------------------------------------------------------------------
def test_watchdog_red_flags_blocked_loop(capfd):
    """A callback that stalls the event loop gets NAMED: the watchdog
    dumps thread stacks to stderr within its period."""
    ray_tpu.shutdown()
    os.environ["RT_LOOP_WATCHDOG_S"] = "0.5"
    try:
        rt = ray_tpu.init(num_cpus=1)
        rt.loop.call_soon_threadsafe(lambda: time.sleep(1.6))
        time.sleep(2.5)
        err = capfd.readouterr().err
        assert "EVENT LOOP BLOCKED" in err, err[-500:]
    finally:
        ray_tpu.shutdown()
        os.environ["RT_LOOP_WATCHDOG_S"] = "5"
