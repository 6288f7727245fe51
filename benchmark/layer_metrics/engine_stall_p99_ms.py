"""Nearest-rank 99th percentile of ``stall_ms`` over the window's
``llm.step`` ring entries (``engine_stall_ms`` is their mean): how long
the engine's thread was kept from running in the window's worst
intervals, to a tick of the thread's CPU clock (10 ms on the
benchmark's host)."""

from benchmark import timeline, traffic


def read(c):
    stalls = [e["stall_ms"] for e in timeline.entries(c, "stall_ms")]
    return traffic.percentile(stalls, 99) if stalls else None
