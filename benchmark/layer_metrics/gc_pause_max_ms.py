"""The longest single pass of the interpreter's collector that ended
inside the window: the largest ``gc_max_ms`` among the window's
``llm.step`` ring entries (a pass is put down to the step interval it
ended in, whichever thread ran it)."""

from benchmark import timeline


def read(c):
    pauses = [e["gc_max_ms"] for e in timeline.entries(c, "gc_max_ms")]
    return max(pauses) if pauses else None
