"""Mean share of the decode program's lanes that held a sequence:
``lanes`` over ``max_batch`` of the window's ``llm.step`` ring entries,
the scheduler's own count (``batch_occupancy_pct`` rebuilds the same
from client frames)."""

from benchmark import timeline


def read(c):
    steps = timeline.entries(c, "lanes")
    if not steps:
        return None
    return 100.0 * sum(e["lanes"] / e["max_batch"] for e in steps) \
        / len(steps)
