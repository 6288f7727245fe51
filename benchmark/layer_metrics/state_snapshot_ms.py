"""What taking one state snapshot costs the step that takes it: the
``llm.state_snapshot`` host phase of the window's ``llm.step`` ring
entries (the prefix index's entry and the dispatch of the slot's copy,
``jit_state_copy_slot``, behind the span that left the state there),
median over the steps that took one. The copy itself runs on the device
behind the step's programs and is in ``decode_device_ms`` /
``device_idle_pct``'s busy time."""

from benchmark import timeline

PHASE = "llm.state_snapshot"


def read(c):
    return timeline.median_or_none(
        [e["phases_ms"][PHASE] for e in timeline.entries(c, "phases_ms")
         if PHASE in e["phases_ms"]])
