"""Mean time a step interval's host side had work and was not running:
``stall_ms`` of the window's ``llm.step`` ring entries, the interval
less the loop's sleep and the waits for the device (a device span less
its dispatch half), less the engine thread's CPU time. In this process
that is the interpreter lock held by another thread, a collection
another thread ran, the engine's own lock, or the OS. A MEAN, not a
median: the thread's CPU clock ticks at 10 ms on the benchmark's host,
a quarter of a chat step, so one entry is good to a tick, while the
ticks cancel in a window's sum."""

import statistics

from benchmark import timeline


def read(c):
    stalls = [e["stall_ms"] for e in timeline.entries(c, "stall_ms")]
    return statistics.fmean(stalls) if stalls else None
