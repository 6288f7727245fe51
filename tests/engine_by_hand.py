"""An LLM deployment's engine driven step by step from the test.

``LLMServer`` starts its engine's loop thread as it is built, and what
then overlaps with what is the box's business: three streams staggered
150 ms apart met on a tiny pool on a busy box and missed each other on an
idle one. A test that needs a particular meeting (a late join, an
over-admission that preempts) builds the deployment inside ``held()``,
which keeps the engine's loop from starting, sends its requests through
Serve as any client would, waits for the engine's word that they ARRIVED,
and calls ``step()`` itself. In local mode the device-lane replica lives
in the test's process, so the engine is at hand.
"""

import contextlib
import time
from unittest import mock


@contextlib.contextmanager
def held(expect: int = 1, timeout: float = 120.0):
    """Engines built inside the block are appended to the list this
    yields, their loops not started. ``serve.run`` returns before the
    replica is constructed, so the block ends by waiting for ``expect``
    engines."""
    from ray_tpu.llm.engine import LLMEngine

    engines = []
    with mock.patch.object(LLMEngine, "start",
                           lambda self: engines.append(self)):
        yield engines
        deadline = time.monotonic() + timeout
        while len(engines) < expect:
            assert time.monotonic() < deadline, "no engine was built"
            time.sleep(0.01)


def arrived(eng, n: int, timeout: float = 120.0):
    """Block until the engine holds ``n`` requests, waiting or active."""
    deadline = time.monotonic() + timeout
    while len(eng._waiting) + len(eng._active) < n:
        assert time.monotonic() < deadline, (
            f"{len(eng._waiting) + len(eng._active)} of {n} requests "
            f"reached the engine")
        time.sleep(0.01)


def drive(eng, steps=None):
    """``steps`` steps, or (None) steps until nothing waits or runs."""
    if steps is not None:
        for _ in range(steps):
            eng.step()
        return
    taken = 0
    while eng._waiting or eng._active:
        eng.step()
        taken += 1
        assert taken < 10_000, "the engine does not drain"
