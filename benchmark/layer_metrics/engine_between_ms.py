"""Median time from one engine step's end to the next one's start
while there was work: ``between_ms`` of the window's ``llm.step`` ring
entries (the lock's hand-over to ``add_request`` callers, the loop's
condition), leaving out the steps before which the loop slept on an
empty engine (``idle_wait``)."""

from benchmark import timeline


def read(c):
    return timeline.median_or_none(
        [e["between_ms"] for e in timeline.entries(c, "between_ms")
         if not e.get("idle_wait")])
