"""tests/callcount.py counts what the block does, not what the box does:
the same number twice, with the collector provoked, and with another
thread spinning beside it."""

import gc
import os
import sys
import threading

from callcount import calls_of, python_calls


def _leaf(x):
    return x + 1


def _block():
    """1 + 100 + 1 Python-level calls, and garbage for the collector."""
    total = 0
    for i in range(100):
        total = _leaf(total)
        cycle = [i]
        cycle.append(cycle)
    return sorted([total], key=_leaf)


def _count():
    with python_calls() as c:
        _block()
    return c.n


def test_the_count_of_a_block_is_the_block():
    assert _count() == 102
    with python_calls() as c:
        pass
    assert c.n == 0


def test_the_count_is_equal_in_two_runs_and_beside_a_busy_thread():
    alone = [_count(), _count()]
    hooked = []
    gc.callbacks.append(lambda phase, info: hooked.append(phase))
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            _leaf(0)

    busy = threading.Thread(target=spin, daemon=True)
    busy.start()
    try:
        beside = [_count() for _ in range(20)]
    finally:
        stop.set()
        busy.join(timeout=30)
        gc.callbacks.pop()
    assert not busy.is_alive()
    assert alone + beside == [102] * 22
    # The collector is back on afterwards, and nothing is left profiling.
    assert gc.isenabled()
    assert sys.getprofile() is None


def test_calls_of_counts_one_function_and_puts_it_back():
    real = os.getpid
    with calls_of(os, "getpid") as pids, python_calls() as c:
        assert os.getpid() == real()
        os.getpid()
    assert pids.n == 2
    assert c.n == 2         # the counting stand-in is Python code
    assert os.getpid is real
