"""Mean lateness of the interpreter probe's samples inside the window:
the sum of ``interp_late_ms`` over the sum of ``interp_n`` of the
window's ``llm.step`` ring entries. The probe (``util/perfmodel.py``
``_InterpreterProbe``) is a thread of the served process that sleeps a
fixed period and reads how much later than asked it runs again: asleep
it holds nothing, and to run it needs the interpreter, so its lateness
is what a poll with a reply to carry, a request's executor hand-off or
the engine after the device waits at that instant. A program without
the probe, as every commit before PR 60, gives nothing to read."""

from benchmark import timeline
from benchmark.harness import log


def read(c):
    entries = timeline.entries(c, "interp_n")
    n = sum(e["interp_n"] for e in entries)
    if not n:
        return None
    late = sum(e["interp_late_ms"] for e in entries)
    stats = c.get("engine_stats") or ({}, {})
    period = (stats[1].get("interp") or {}).get("period_s")
    if period and not c.get("rehearse"):
        # The probe's own check: a sample a period, less its lateness.
        log(f"interp_wait_ms: {n} samples in the window's entries, longest "
            f"{max(e['interp_late_max_ms'] for e in entries):.1f} ms late; "
            f"the window over the period is {c['window_s'] / period:.0f}; "
            f"held long {sum(e['held_long_ms'] for e in entries):.1f} ms")
    return late / n
