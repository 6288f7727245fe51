"""Median device span of an engine step, host clock from dispatch to
``block_until_ready``: ``device_ms`` of the window's ``llm.step`` ring
entries (a step's prefill chunks and its decode together)."""

import statistics


def read(c):
    spans = [e["device_ms"] for e in c.get("engine_steps", [])]
    return statistics.median(spans) if spans else None
