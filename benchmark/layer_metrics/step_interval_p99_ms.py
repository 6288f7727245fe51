"""Nearest-rank 99th percentile of the engine's step interval, one
``finish()`` to the next (``interval_ms`` = ``between_ms`` +
``step_ms`` of the window's ``llm.step`` ring entries), leaving out
the steps before which the loop slept on an empty engine
(``idle_wait``). Since PR 35 every gap between two token frames is a
step's length, so this is the server's side of the client's
``itl_p99_ms``: pooled over steps, where the client pools over gaps."""

from benchmark import timeline, traffic


def read(c):
    intervals = [e["interval_ms"] for e in timeline.entries(c, "interval_ms")
                 if not e.get("idle_wait")]
    return traffic.percentile(intervals, 99) if intervals else None
