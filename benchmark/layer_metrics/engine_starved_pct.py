"""Share of the window the engine's loop slept on an empty engine (no
request waiting, none in flight): ``engine_stats()["idle_s"]``
(cumulative; a sleep is counted when it ends, at most 0.5 s late) at
the window's two edges over the window's seconds."""


def read(c):
    stats = c.get("engine_stats")
    if not stats:
        return None
    a, b = (s.get("idle_s") for s in stats)
    if a is None or b is None or c["window_s"] <= 0:
        return None
    return 100.0 * (b - a) / c["window_s"]
