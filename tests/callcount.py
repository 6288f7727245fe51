"""Counts in place of clocks: what a hot path costs, stated as something
the box's load cannot move.

A duration measured beside five other xdist workers says how busy the box
is. The number of Python-level calls a block makes, or how often it
reaches a function that must stay off the hot path, says what the code
does, and reads the same alone and under load.
"""

import contextlib
import gc
import sys
import threading
from unittest import mock


class Count:
    """What a block counted, readable once the block has ended."""

    n = 0


class python_calls:
    """``with python_calls() as c:`` counts the Python-level function
    calls THIS thread makes inside the block (``sys.setprofile`` is per
    thread, so another thread's work is not counted). The collector is
    held off inside the block: a ``gc.callbacks`` hook is Python code too,
    and when a collection falls is not the block's doing."""

    def __enter__(self):
        self._count = Count()
        self._gc_was_on = gc.isenabled()
        gc.disable()
        sys.setprofile(self._event)
        return self._count

    def _event(self, frame, event, arg):
        if event == "call" and frame.f_code is not _EXIT:
            self._count.n += 1

    def __exit__(self, *exc):
        sys.setprofile(None)
        if self._gc_was_on:
            gc.enable()


_EXIT = python_calls.__exit__.__code__


@contextlib.contextmanager
def calls_of(owner, name, thread=None):
    """Count the calls of ``owner.name`` inside the block, by whatever
    thread, or by the one thread whose ``threading.get_ident()`` is
    ``thread`` (a block that must not count what a loop left running by
    an earlier test of the same process does meanwhile); the function
    still runs."""
    real = getattr(owner, name)
    count = Count()

    def counted(*args, **kwargs):
        if thread is None or threading.get_ident() == thread:
            count.n += 1
        return real(*args, **kwargs)

    with mock.patch.object(owner, name, counted):
        yield count
