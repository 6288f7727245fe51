"""Median host time of an engine step around its device spans:
``host_gap_ms`` of the ``llm.step`` entries the engine put into the
process's device-step ring during the window."""

import statistics


def read(c):
    gaps = [e["host_gap_ms"] for e in c.get("engine_steps", [])]
    return statistics.median(gaps) if gaps else None
