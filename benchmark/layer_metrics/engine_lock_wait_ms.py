"""Median time the engine's loop spent acquiring its own lock between
two steps (``add_request`` callers hold it): ``lock_wait_ms`` of the
window's ``llm.step`` ring entries, leaving out the steps before which
the loop slept on an empty engine (``idle_wait``). A part of
``engine_between_ms``."""

from benchmark import timeline


def read(c):
    return timeline.median_or_none(
        [e["lock_wait_ms"] for e in timeline.entries(c, "lock_wait_ms")
         if not e.get("idle_wait")])
