"""Share of the interpreter probe's samples inside the window that
found the interpreter held: 100 x the sum of ``interp_held_n`` over the
sum of ``interp_n`` of the window's ``llm.step`` ring entries. A sample
is held when the probe ran again later than
``perfmodel.INTERP_HELD_FLOOR_S`` past its period, a floor set from an
idle process's lateness on the benchmark's host (PERF.md section 6, PR
60): the share of instants at which a serving thread that woke with
work to do would have waited for another thread to give the
interpreter up. A program without the probe gives nothing to read."""

from benchmark import timeline


def read(c):
    entries = timeline.entries(c, "interp_n")
    n = sum(e["interp_n"] for e in entries)
    return 100.0 * sum(e["interp_held_n"] for e in entries) / n if n else None
