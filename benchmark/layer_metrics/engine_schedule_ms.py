"""Median host time an engine step spends deciding and laying out what
runs: the ``llm.admit`` (admission, prefix lookup), ``llm.slots`` (KV
slots, copy-on-write, preemption) and ``llm.decode.build`` (the decode
program's input arrays) phases of the window's ``llm.step`` ring
entries. On a chip run it also logs the whole host gap by phase."""

from benchmark import timeline
from benchmark.harness import log


def read(c):
    steps = timeline.entries(c, "phases_ms")
    if steps and not c["rehearse"]:     # times: never from a CPU run
        names = sorted({n for e in steps for n in e["phases_ms"]})
        log("engine host gap by phase, median ms a step: " + ", ".join(
            f"{n} {timeline.phases_ms(c, (n,)):.3f}" for n in names)
            + f"; other_ms "
            f"{timeline.median_or_none([e['other_ms'] for e in steps]):.3f}"
            f" of host_gap_ms "
            f"{timeline.median_or_none([e['host_gap_ms'] for e in steps]):.3f}")
    return timeline.phases_ms(c, ("llm.admit", "llm.slots",
                                  "llm.decode.build"))
