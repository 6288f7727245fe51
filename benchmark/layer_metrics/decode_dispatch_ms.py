"""Median host time of the decode span's dispatch: from the span's
start until the jitted call has returned its futures (argument
hand-over and enqueue), ``dispatch_ms_by["decode"]`` of the window's
``llm.step`` ring entries. The rest of ``decode_device_ms`` is the wait
for the ids and the wake-up after it."""

from benchmark import timeline


def read(c):
    return timeline.median_or_none(
        [e["dispatch_ms_by"]["decode"]
         for e in timeline.entries(c, "dispatch_ms_by")
         if "decode" in e["dispatch_ms_by"]])
