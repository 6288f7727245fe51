"""What the readers of the program's own step timeline share (PR 24).

The program writes one entry a step into its device-step ring
(``ray_tpu/util/perfmodel.py``): the drivers pass the window's entries
through whole (``engine_steps``; a training step's reach the reports as
``train_*`` keys), so a field the program adds reaches its reader with
no edit to a driver. ``engine_stats()["phase_hist"]`` is cumulative
``sum`` / ``count`` a phase, read at both edges of the window. A
program that lacks a field, as every commit before PR 24 does, gives
its reader nothing to read: None, and the metric is left out.
"""

from __future__ import annotations

import statistics


def entries(c, key: str) -> list:
    """The window's ring entries that carry ``key``."""
    return [e for e in c.get("engine_steps") or [] if key in e]


def median_or_none(values: list):
    return statistics.median(values) if values else None


def phases_ms(c, names: tuple):
    """Median over the window's steps of the summed host phases
    ``names`` (a phase that did not run in a step counts 0 there)."""
    return median_or_none([sum(e["phases_ms"].get(n, 0.0) for n in names)
                           for e in entries(c, "phases_ms")])


def hist_mean_ms(c, phase: str):
    """Mean of a serve/slo phase over the window: the difference of its
    cumulative sum between the window's edges over that of its count."""
    stats = c.get("engine_stats")
    if not stats:
        return None
    a, b = (s.get("phase_hist", {}).get(phase) for s in stats)
    if not b:
        return None
    a = a or {"sum": 0.0, "count": 0}
    n = b["count"] - a["count"]
    return (b["sum"] - a["sum"]) / n * 1e3 if n > 0 else None


def module_ms(c, needle: str):
    """Device time of one execution of the compiled program whose name
    on the trace's ``XLA Modules`` line holds ``needle`` (the program's
    explicit jit name): seconds over executions."""
    trace = c.get("trace")
    if not trace:
        return None
    calls = secs = 0.0
    for name, (n, s) in trace["modules"].items():
        if needle in name:
            calls += n
            secs += s
    return secs / calls * 1e3 if calls else None


def reports(c, key: str):
    """Median of a ``train_*`` key over the window's reports."""
    return median_or_none([r[key] for r in c.get("reports") or []
                           if key in r])
