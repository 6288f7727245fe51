"""Milliseconds the interpreter's collector ran a second of the window,
all generations, whichever thread ran it: ``engine_stats()["gc"]``
({generation: [passes, seconds]}, cumulative) at the window's two
edges over the window's seconds."""


def _seconds(stats):
    totals = stats.get("gc")
    if totals is None:
        return None
    return sum(seconds for _, seconds in totals.values())


def read(c):
    stats = c.get("engine_stats")
    if not stats:
        return None
    a, b = (_seconds(s) for s in stats)
    if a is None or b is None or c["window_s"] <= 0:
        return None
    return (b - a) * 1e3 / c["window_s"]
