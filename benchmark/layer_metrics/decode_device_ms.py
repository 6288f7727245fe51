"""Median device span of the decode program alone, host clock from
dispatch to ``block_until_ready``: ``device_ms_by["decode"]`` of the
window's ``llm.step`` ring entries (``decode_step_ms`` is the same
span with the step's prefill chunks added)."""

from benchmark import timeline


def read(c):
    return timeline.median_or_none(
        [e["device_ms_by"]["decode"]
         for e in timeline.entries(c, "device_ms_by")
         if "decode" in e["device_ms_by"]])
